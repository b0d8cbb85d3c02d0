"""The output projection that keeps the loss's statistics: ``logits = x W``
and, from the same visit of every vocabulary tile, each row's log-sum-exp.

XLA computes a log-sum-exp over a product's output in two visits: the row
maximum rides the projection's fusion, and a pass of its own re-reads every
logit to sum the exponentials (at Transformer-base's head, 24,576 x 37,000
float32 logits: 3.64 GB read in 4.8 ms, at the HBM roofline, for 24,576
sums).  A kernel that walks the vocabulary a tile at a time can carry both —
the running maximum ``m`` and the sum rescaled to it, ``s <- s * exp(m_old -
m) + sum(exp(tile - m))``, a flash forward's — and needs no second visit.

- :func:`proj_xent_fwd` — the Pallas kernel ``proj_xent_fwd``.  ``x [N, D]``
  (bf16, or float32 rounded here), ``w [D, V]`` bf16, the rows ``N / seq``
  sequences of ``seq`` positions → ``logits [N / seq, seq, V]`` in the dtype
  asked for (``jnp.matmul``'s of the unrounded rows), ``lse [N / seq, seq,
  1]`` float32.  Grid ``(N / tm, ceil(V / tn))``, rows parallel, vocabulary
  tiles in order; ``D`` whole.  A row block is rounded to bf16 and
  transposed ONCE, at its first vocabulary tile, into scratch — the rounding
  XLA's default-precision product makes — and each tile is one ``bf16 x bf16
  -> float32`` product ``w[:, tile].T @ x[block].T``, classes down and
  positions along, written out (in the logits' dtype, and the statistics are
  of what was written), then folded into ``m`` and ``s``; ``lse = m + log s``
  at the last tile.  A last tile that hangs over ``V`` gives its classes
  ``>= V`` to neither statistic and does not write them.
- :func:`proj_xent_xla` — the same mathematics as XLA has it, ``jnp.matmul``
  and ``jax.nn.logsumexp`` over float32: what the tests and the chip's smoke
  hold the kernel to.

Which of the two a program takes is the op's to choose
(``ops/nn_ops.py fc_softmax_with_cross_entropy``), from the back end, the
shapes and the mesh.

The kernel alone on one v5e, ms a call (PR 60, ``chip_smoke.py
phase_proj_xent``; 96 sequences of 256 float32 rows x ``[512, 37000]`` bf16,
931 GFLOP and 3.64 GB of float32 logits a call; 24 calls less 4 in ONE
program, whose loop also rounds the rows and perturbs them — XLA's product
alone reads 6.66 there and 5.59 in the train step's trace, this kernel 6.09
and 5.25; logits equal to XLA's to the bit, log-sum-exp within 8.7e-7)::

    XLA: matmul + logsumexp   11.48      XLA: matmul alone    6.66
    row block x vocabulary tile
    2048 x  512    6.09  (as run)        1024 x 1024    6.12
    4096 x  512    6.04                  1024 x  512    6.20
    2048 x 1024    6.10                  1024 x 2048    6.20
    2048 x 2048    6.58                   512 x 1024    6.30

The tile hardly matters alone; in the step it decides what the call takes
from XLA (below): 2,048 x 512 asks for 27 MB and the step read 199,173
tokens/s, 1,024 x 1,024 (24 MB) 199,117, 2,048 x 1,024 (45 MB) 197,078 — the
input-gradient product lost its weight's place in VMEM.  The first form —
logits ``[N, V]`` row-major, float32 rows, 100 MB — read 7.02 at 1,024 x
2,048, 6.27-6.30 at 2,048 rows, 8.51 at 512 and 11.31 at 256 (``w`` is read
``N / tm`` times), and LOST 1.3% in the step.

**What the kernel owes the program round it** (PR 60; each found by compiling
the step for a described v5e, then read on the chip).  The two gradient
products that read the logits stay XLA's, and their time is decided by what
the forward leaves them:

- *The logits lie a sequence at a time with the positions along the lanes*,
  ``[N / seq, V, seq]``, and are handed on as the transposed VIEW: it is the
  layout XLA itself gives ``f32[B, T, V]`` logits between its own projection
  and the gradient products (``{1,2,0}``), so the view is a bitcast.
- *The call asks for the VMEM its tile needs* (:func:`_vmem_bytes`), not for
  the chip's: XLA keeps the output projection's weight (38 MB, transposed)
  and the decoder's last residual sum (50 MB) in VMEM across the head for the
  two gradient products, and a call that reserved 100 MB pushed both out to
  HBM.
- *The rows arrive as bf16* where the program's are float32 (the op rounds
  them, as the kernel's first act would): a float32 operand made XLA write
  the last layer norm's output to HBM for the call and read it THERE in the
  weight-gradient product, where it recomputes it from VMEM otherwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import pallas_interpret

LANE = 128
ROW_BLOCK = 2048    # tm: rows of x a grid row holds
VOCAB_TILE = 512    # tn: classes a grid step multiplies


def _vmem_bytes(tm: int, tn: int, d: int, itemsize: int) -> int:
    """What a grid step holds, as Mosaic counted it for a described v5e over
    nine tile shapes: 4.4 output tiles (both buffers, the product, the
    exponentials and what stands between them), the row block in both
    buffers with its transposed bf16 copy and that copy's scratch, the weight
    tile in both buffers.  The call asks for THIS and not for the 100 MB a
    v5e would give: what a Mosaic call reserves XLA cannot fill with the
    operands it keeps in VMEM across the step, and the two gradient products
    that follow this call ran 1.4x as long when theirs (the rows, the weight)
    had been pushed out to HBM (compiled for a described v5e, then on the
    chip)."""
    return int(4.4 * tm * tn * 4 + 2 * tm * d * (itemsize + 2)
               + 2 * d * tn * 2)


def _kernel(x_ref, w_ref, logits_ref, lse_ref, xt_ref, m_ref, s_ref, *,
            V, tn, tiles, seq):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        xt_ref[...] = x_ref[...].astype(jnp.float32).T.astype(jnp.bfloat16)
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        s_ref[...] = jnp.zeros(s_ref.shape, jnp.float32)

    # [tn, tm] = w.T[tile] @ x[block].T: classes down, positions along
    out = jnp.dot(w_ref[...], xt_ref[...],
                  preferred_element_type=jnp.float32).astype(logits_ref.dtype)
    for g in range(out.shape[1] // seq):
        logits_ref[g] = out[:, g * seq:(g + 1) * seq]
    t = out.astype(jnp.float32)

    def fold(t):
        m_old = m_ref[...]
        m = jnp.maximum(m_old, jnp.max(t, axis=0, keepdims=True))
        s_ref[...] = s_ref[...] * jnp.exp(m_old - m) + jnp.sum(
            jnp.exp(t - m), axis=0, keepdims=True)
        m_ref[...] = m

    if V % tn:
        # only the last tile hangs over: the others pay no compare
        pl.when(j < tiles - 1)(lambda: fold(t))

        @pl.when(j == tiles - 1)
        def _():
            cls = j * tn + lax.broadcasted_iota(jnp.int32, t.shape, 0)
            fold(jnp.where(cls < V, t, -jnp.inf))
    else:
        fold(t)

    @pl.when(j == tiles - 1)
    def _():
        lse = m_ref[...] + jnp.log(s_ref[...])
        for g in range(lse.shape[1] // seq):
            lse_ref[g] = lse[:, g * seq:(g + 1) * seq]


def fits(n_rows: int, d: int, seq: int, tm: int = ROW_BLOCK) -> bool:
    """Whole row blocks of whole sequences, sequences and the contraction
    of whole lane tiles."""
    return (seq > 0 and seq % LANE == 0 and tm % seq == 0
            and n_rows % tm == 0 and d % LANE == 0)


@functools.partial(jax.jit, static_argnames=("seq", "logits_dtype", "tm", "tn",
                                             "interpret"))
def proj_xent_fwd(x, w, *, seq: int, logits_dtype=None, tm: int = ROW_BLOCK,
                  tn: int = VOCAB_TILE, interpret=None):
    """``x [N, D] @ w [D, V]`` for rows that are ``N / seq`` sequences of
    ``seq`` positions -> ``(logits [N / seq, seq, V], lse [N / seq, seq, 1]
    float32)``, both as views of what the kernel wrote with the positions
    along the lanes (``[N / seq, V, seq]``); ``fits(N, D, seq, tm)`` must
    hold.  ``logits_dtype``: the product's, where the rows were float32 and
    are handed over rounded to bf16 already (the kernel's first act)."""
    (N, D), V = x.shape, w.shape[1]
    logits_dtype = logits_dtype or jnp.result_type(x, w)
    if not fits(N, D, seq, tm) or w.dtype != jnp.bfloat16:
        raise ValueError(f"proj_xent_fwd does not fit x {x.shape} {x.dtype}, "
                         f"w {w.shape} {w.dtype} at sequences of {seq} and a "
                         f"row block of {tm}")
    if interpret is None:
        interpret = pallas_interpret()
    tn = min(tn, pl.cdiv(V, LANE) * LANE)
    tiles = pl.cdiv(V, tn)
    logits, lse = pl.pallas_call(
        functools.partial(_kernel, V=V, tn=tn, tiles=tiles, seq=seq),
        name="proj_xent_fwd",
        grid=(N // tm, tiles),
        in_specs=[pl.BlockSpec((tm, D), lambda i, j: (i, 0)),
                  pl.BlockSpec((tn, D), lambda i, j: (j, 0))],
        out_specs=[pl.BlockSpec((tm // seq, tn, seq), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((tm // seq, 1, seq), lambda i, j: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((N // seq, V, seq), logits_dtype),
                   jax.ShapeDtypeStruct((N // seq, 1, seq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((D, tm), jnp.bfloat16),
                        pltpu.VMEM((1, tm), jnp.float32),
                        pltpu.VMEM((1, tm), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_bytes(tm, tn, D, x.dtype.itemsize),
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, w.T)
    return logits.transpose(0, 2, 1), lse.transpose(0, 2, 1)


def proj_xent_xla(x, w):
    """The pair as XLA has it: the product, and the log-sum-exp of what it
    wrote, in float32 at least."""
    logits = jnp.matmul(x, w)
    stats = logits.astype(jnp.promote_types(logits.dtype, jnp.float32))
    return logits, jax.nn.logsumexp(stats, axis=-1, keepdims=True)
