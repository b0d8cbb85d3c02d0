"""Short-sequence fused attention: one forward and ONE backward Pallas kernel
that never put a ``[Tq, Tk]`` tensor in HBM.

Where the whole key sequence of a head fits in VMEM there is nothing for the
flash algorithm to do: no key loop, no online softmax, no running max to
rescale by.  What cost ``flash_attention`` the in-model comparison at
sequence 256 was the SHAPE of its work, not its rate: a grid of
``(B*H, Tq/128, Tk/128)`` steps of one head's 128x128 tile each, three
kernels a training step (forward, dq, dk/dv), ~9,200 grid steps an attention
at ~0.35 us a step.  Here a grid step takes one batch row and a GROUP of
heads (all of them, where VMEM allows), a head's scores are one
``[q_chunk, Tk]`` f32 tile in VMEM, softmax is one pass, and because every
key of the head is resident the backward writes dq, dk and dv from ONE
recomputation of scores, probabilities and dropout bits.  Saved for the
backward: ``(o, lse)`` only.

Shared with the other paths of ``kernels/attention.py``: the key mask's and
the causal mask's meaning, ``_scaled_q`` (softmax in exp2 units),
``_finalize_dropout``.  Dropout drops ``mha_xla``'s elements: the hash is
``_hash_dropout``'s, over the seed and the element's GLOBAL ``(b, h, q, k)``
(a shard passes its batch offset), so the two paths compute the same
function up to the order of sums and can be compared with dropout on.

Precision is ``mha_xla``'s: bf16 operands on the chip (a float32 q, k, v
or dO is rounded as XLA's default-precision dots round it: ``_mxu``), f32
accumulation, f32 scores / softmax / dropout, probabilities cast to the
operands' dtype for the context product, outputs in the caller's dtype.  One difference in a degenerate case: a
query row with no visible key gives zeros and zero gradients (the flash
kernel's convention; ``mha_xla`` attends uniformly to the masked keys).

Sizes are reckoned from the shapes (``plan``): the heads of a grid step and
the query rows of a score tile from what VMEM holds; lengths are padded to
the lane width and padded keys masked.  A causal tile's keys end at its last
row's diagonal, so the masked upper part is never computed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import pallas_interpret
from . import attention as A

LANE = 128
# what one grid step's blocks (double-buffered) and score tiles may take of
# a v5e core's 128 MiB of VMEM, and the limit handed to Mosaic
VMEM_BUDGET = 40 << 20
VMEM_LIMIT = 64 << 20
# f32 score-sized temporaries alive at once in a head's body (scores,
# probabilities, hash, multiplier, dP, dS and their casts), counted generously
_TILES_FWD, _TILES_BWD = 6, 10
# query rows of one score tile, and the (head, tile) bodies a grid step
# unrolls: measured on the v5e (ops/attention_ops.py has the table)
Q_ROWS = 256
BODIES = 8


def _round_up(x, m):
    return -(-x // m) * m


def lane_group(H, D):
    """Heads side by side in one lane group: two of 64 fill 128 lanes."""
    g = max(1, LANE // D)
    while H % g:
        g -= 1
    return g


def plan(H, Tq, Tk, D, itemsize, backward=False):
    """``(heads a grid step, query rows a score tile)`` for these shapes, or
    None where even one head's blocks and tiles do not fit the budget.  As
    many heads as keep the step's unrolled bodies at ``BODIES`` and its
    blocks in VMEM: one batch row's eight heads at 256, four at 512, two at
    1,024."""
    Tq_p, Tk_p = _round_up(Tq, LANE), _round_up(Tk, LANE)
    q_chunk = min(Tq_p, Q_ROWS)
    n_tiles = -(-Tq_p // q_chunk)
    tiles = (_TILES_BWD if backward else _TILES_FWD) * q_chunk * Tk_p * 4
    # q, o (and dO, dq) by Tq; k, v (and dk, dv) by Tk; two buffers each
    n = 4 if backward else 2
    head = 2 * n * (Tq_p + Tk_p) * D * itemsize
    if backward:
        tiles += 2 * Tk_p * D * 4  # the f32 dk / dv accumulators
    g = lane_group(H, D)
    for hg in range(H, 0, -g):
        if (H % hg == 0 and (hg * n_tiles <= BODIES or hg == g)
                and hg * head + tiles <= VMEM_BUDGET):
            return hg, q_chunk
    return None


def _chunks(Tq, Tk, q_chunk, causal):
    """Static ``(first row, rows, keys)`` of each score tile, the widest
    first (the backward's accumulators are initialised by a tile that covers
    every key).  A causal tile's keys end at its last row's diagonal."""
    out = []
    for q0 in range(0, Tq, q_chunk):
        rows = min(q_chunk, Tq - q0)
        keys = min(Tk, _round_up(q0 + rows, LANE)) if causal else Tk
        out.append((q0, rows, keys))
    return out[::-1]


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


_NT = ((1,), (1,))  # a @ b.T
_TN = ((0,), (0,))  # a.T @ b
_NN = ((1,), (0,))


class _Tile:
    """What the forward and the backward recompute alike for one score
    tile of one head: masked scores in exp2 units and the dropout
    multiplier of ``mha_xla``'s ``_hash_dropout``.

    A head's body is traced ONCE a score tile (``jax.jit``; Mosaic inlines
    it) and bound once a head: a kernel's cost to trace is its equations,
    a process traces two forwards and two backwards in its set-up, and the
    eight heads of a step differ in one scalar of the hash and in which
    lanes of their group are theirs.  What is left a head outside the body
    is bound as ``lax`` primitives."""

    def __init__(self, seed_ref, mask_ref, *, causal, rate, has_mask, heads,
                 head_dim, lanes):
        self.causal, self.rate = causal, rate
        # additive key mask, one row for the step's batch row: -1e30 + s
        # is -1e30 in f32, which is what mha_xla's where() puts there
        self.bias = (jnp.where(mask_ref[:] > 0, 0.0, A.NEG_INF)
                     .astype(jnp.float32) if has_mask
                     else jnp.zeros((1, 1), jnp.float32))
        self.has_mask = has_mask
        # which lanes of a lane group are its i-th head's
        self.alone = lanes == head_dim
        head_of_lane = lax.broadcasted_iota(
            jnp.int32, (1, lanes), 1) // head_dim
        self.mine = [head_of_lane == i for i in range(lanes // head_dim)]
        if rate > 0.0:
            b = (pl.program_id(0) + seed_ref[1]).astype(jnp.uint32)
            h0 = (pl.program_id(1) * heads).astype(jnp.uint32)
            self._scalar = (b * jnp.uint32(0xC2B2AE3D)
                            + h0 * jnp.uint32(0x27D4EB2F),
                            seed_ref[0].astype(jnp.uint32))

    def only(self, mine, x):
        """``x`` of a lane group with the lanes of every head but one
        zeroed: contracted over the whole group it gives that head's
        product, with no lane moved — and a head's result, valid in its own
        lanes, ready to be summed with the others'."""
        return x if self.alone else jnp.where(mine, x,
                                              jnp.zeros((), x.dtype))

    def scores(self, qs_i, k, bias, q0):
        rows, keys = qs_i.shape[0], k.shape[0]
        s = _dot(qs_i, k, _NT)
        if self.has_mask:
            s = s + bias[:, :keys]
        if self.causal:
            qi = q0 + lax.broadcasted_iota(jnp.int32, (rows, keys), 0)
            ki = lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
            s = jnp.where(qi >= ki, s, A.NEG_INF)
        return s

    def salt(self, h):
        """The hash's scalar term for head ``h`` of the step, folded on the
        scalar core (xor is associative)."""
        if self.rate <= 0.0:
            return np.uint32(0)
        bh, seed = self._scalar
        return lax.bitwise_xor(
            lax.add(bh, np.uint32(h * 0x27D4EB2F & 0xFFFFFFFF)), seed)

    def dropout(self, salt, q0, rows, keys):
        """``_hash_dropout`` at (this step's batch row, the head ``salt``
        was made for, rows ``q0..``, keys ``0..``)."""
        qi = q0 + lax.broadcasted_iota(jnp.uint32, (rows, 1), 0)
        ki = lax.broadcasted_iota(jnp.uint32, (1, keys), 1)
        x = (qi * jnp.uint32(0x9E3779B1)) ^ (ki * jnp.uint32(0x85EBCA77))
        return A._finalize_dropout(x ^ salt, self.rate)

    def forward(self, q0, rows, keys):
        """One head over one score tile: ``(o, lse)``, ``o`` zero outside
        the head's lanes."""
        @jax.jit
        def head(qs, k, v, bias, mine, salt):
            s = self.scores(self.only(mine, qs), k, bias, q0)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp2(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            if self.rate > 0.0:
                p = p * self.dropout(salt, q0, rows, keys)
            acc = _dot(p.astype(v.dtype), v, _NN)
            # a row that sees no key: zeros out, and an lse that makes the
            # backward's probabilities zero (the flash kernel's convention)
            seen = m > 0.5 * A.NEG_INF
            return (self.only(mine, acc * jnp.where(seen, 1.0 / l, 0.0)),
                    jnp.where(seen, m + jnp.log2(l), -A.NEG_INF)[:, 0])
        return head

    def backward(self, q0, rows, keys):
        """One head over one score tile: ``(dq, dk, dv)`` before
        ``sm_scale``, each zero outside the head's lanes."""
        @jax.jit
        def head(qs, q, k, v, do, do_o, lse, bias, mine, salt):
            s = self.scores(self.only(mine, qs), k, bias, q0)
            p = jnp.exp2(s - lse[:, None])
            dp = _dot(self.only(mine, do), v, _NT)
            delta = jnp.sum(self.only(mine, do_o), axis=-1, keepdims=True)
            if self.rate > 0.0:
                drop = self.dropout(salt, q0, rows, keys)
                dv = _dot((p * drop).astype(do.dtype), do, _TN)
                dp = dp * drop
            else:
                dv = _dot(p.astype(do.dtype), do, _TN)
            # the log2(e) folded into the scores and the ln 2 of exp2's
            # derivative cancel: plain sm_scale scales dq and dk
            ds = (p * (dp - delta)).astype(q.dtype)
            return (self.only(mine, _dot(ds, k, _NN)),
                    self.only(mine, _dot(ds, q, _TN)), self.only(mine, dv))
        return head


def _sum(per_head):
    """One lane group from its heads' results, each zero outside its own
    lanes."""
    return functools.reduce(lax.add, per_head)


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, *,
                sm_scale, causal, rate, has_mask, heads, head_dim, group,
                chunks):
    """One batch row, ``heads`` heads: q, k, v and o as ``[T, heads * D]``,
    lane-dense, a lane group (``group`` heads, 128 lanes at D = 64) at a
    time.  Unrolled throughout: the scheduler overlaps one head's products
    with another's vector work (a loop over heads measured 17-35% slower)."""
    lanes = group * head_dim
    tile = _Tile(seed_ref, mask_ref, causal=causal, rate=rate,
                 has_mask=has_mask, heads=heads, head_dim=head_dim,
                 lanes=lanes)
    bodies = {c: tile.forward(*c) for c in chunks}
    for g in range(heads // group):
        at_g = pl.ds(g * lanes, lanes)
        for q0, rows, keys in chunks:
            at = pl.ds(q0, rows)
            qs = A._scaled_q(q_ref[at, at_g], sm_scale)
            k, v = k_ref[:keys, at_g], v_ref[:keys, at_g]
            outs = []
            for i in range(group):
                h = g * group + i
                o, lse = bodies[q0, rows, keys](
                    qs, k, v, tile.bias, tile.mine[i], tile.salt(h))
                outs.append(o)
                lse_ref[h, 0, at] = lse
            o_ref[at, at_g] = _sum(outs).astype(o_ref.dtype)


def _bwd_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, do_ref,
                lse_ref, dq_ref, dk_ref, dv_ref, *scratch,
                sm_scale, causal, rate, has_mask, heads, head_dim, group,
                chunks):
    """The forward's layout; scores, probabilities and dropout bits are
    recomputed ONCE a tile and dq, dk, dv written together."""
    lanes = group * head_dim
    tile = _Tile(seed_ref, mask_ref, causal=causal, rate=rate,
                 has_mask=has_mask, heads=heads, head_dim=head_dim,
                 lanes=lanes)
    bodies = {c: tile.backward(*c) for c in chunks}
    for g in range(heads // group):
        at_g = pl.ds(g * lanes, lanes)
        for n, (q0, rows, keys) in enumerate(chunks):
            at = pl.ds(q0, rows)
            q, do = q_ref[at, at_g], do_ref[at, at_g]
            qs = A._scaled_q(q, sm_scale)
            k, v = k_ref[:keys, at_g], v_ref[:keys, at_g]
            do_o = do.astype(jnp.float32) * o_ref[at, at_g].astype(
                jnp.float32)
            dqs, dks, dvs = [], [], []
            for i in range(group):
                h = g * group + i
                dq, dk, dv = bodies[q0, rows, keys](
                    qs, q, k, v, do, do_o, lse_ref[h, 0, at], tile.bias,
                    tile.mine[i], tile.salt(h))
                dqs.append(dq)
                dks.append(dk)
                dvs.append(dv)
            dq_ref[at, at_g] = (_sum(dqs) * sm_scale).astype(dq_ref.dtype)
            dk, dv = _sum(dks) * sm_scale, _sum(dvs)
            if len(chunks) == 1:
                dk_ref[:, at_g] = dk.astype(dk_ref.dtype)
                dv_ref[:, at_g] = dv.astype(dv_ref.dtype)
            elif n == 0:  # the widest tile: every key
                scratch[0][:] = dk
                scratch[1][:] = dv
            else:
                scratch[0][:keys] += dk
                scratch[1][:keys] += dv
        if len(chunks) > 1:
            dk_ref[:, at_g] = scratch[0][:].astype(dk_ref.dtype)
            dv_ref[:, at_g] = scratch[1][:].astype(dv_ref.dtype)


def _merged(x):
    """[B, H, T, D] -> [B, T, H * D]: the heads side by side in the lanes,
    as the projection that made them left them — XLA folds this transpose
    with the model's own split into nothing, and a 64-wide head no longer
    pads every row to 128 lanes in HBM and in VMEM."""
    B, H, T, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, T, H * D)


def _split(x, H):
    B, T, HD = x.shape
    return x.reshape(B, T, H, HD // H).transpose(0, 2, 1, 3)


def _prepare(q, k, v, kv_mask, H, backward, head_group, q_chunk):
    """Pad the lengths to the lane width, settle the mask, plan the grid."""
    B, Tq, D = q.shape[0], q.shape[1], q.shape[2] // H
    Tk = k.shape[1]
    planned = plan(H, Tq, Tk, D, q.dtype.itemsize, backward)
    if planned is None and not (head_group and q_chunk):
        raise ValueError(f"short_attention: [{Tq}, {Tk}] x {D} does not fit "
                         "a grid step's VMEM")
    hg = head_group or planned[0]
    cq = q_chunk or planned[1]
    assert hg % lane_group(H, D) == 0 and H % hg == 0, (H, D, hg)
    q, _ = A._pad_to(q, LANE, 1)
    k, pad_k = A._pad_to(k, LANE, 1)
    v, _ = A._pad_to(v, LANE, 1)
    has_mask = kv_mask is not None or pad_k > 0
    if not has_mask:
        mask = jnp.zeros((B, 1, LANE), jnp.float32)  # never read
    else:
        if kv_mask is None:
            kv_mask = jnp.ones((B, Tk), jnp.float32)
        mask, _ = A._pad_to(kv_mask.astype(jnp.float32), LANE, 1)
        mask = mask[:, None, :]
    return q, k, v, mask, has_mask, hg, cq


def _specs(hg, T, D):
    return pl.BlockSpec((None, T, hg * D), lambda b, g: (b, 0, g))


def _static(causal, sm_scale, rate, has_mask, hg, cq, Tq_p, Tk_p, H, D):
    return dict(sm_scale=sm_scale, causal=causal, rate=float(rate),
                has_mask=has_mask, heads=hg, head_dim=D,
                group=lane_group(H, D),
                chunks=tuple(_chunks(Tq_p, Tk_p, cq, causal)))


def _params(interpret):
    return {} if interpret else {
        "compiler_params": pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)}


@functools.partial(jax.jit, static_argnames=(
    "heads", "causal", "sm_scale", "rate", "interpret", "head_group",
    "q_chunk", "out_dtype"))
def _forward(seeds, q, k, v, kv_mask, *, heads, causal, sm_scale, rate,
             interpret, head_group=None, q_chunk=None, out_dtype=None):
    """q [B,Tq,H*D], k, v [B,Tk,H*D] (``_merged``) ->
    ``(o [B,Tq,H*D], lse [B,H,1,Tq_padded])``.  One ``jit`` a variant: the
    eighteen attentions of a Transformer-base step trace and lower two
    forwards (causal, not causal), not eighteen."""
    B, Tq, H, D = q.shape[0], q.shape[1], heads, q.shape[2] // heads
    q_p, k_p, v_p, mask, has_mask, hg, cq = _prepare(
        q, k, v, kv_mask, H, False, head_group, q_chunk)
    Tq_p, Tk_p = q_p.shape[1], k_p.shape[1]
    kernel = functools.partial(_fwd_kernel, **_static(
        causal, sm_scale, rate, has_mask, hg, cq, Tq_p, Tk_p, H, D))
    o, lse = pl.pallas_call(
        kernel,
        name="short_attn_fwd",
        out_shape=[A._sds((B, Tq_p, H * D), out_dtype or q.dtype, q_p),
                   A._sds((B, H, 1, Tq_p), jnp.float32, q_p)],
        grid=(B, H // hg),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # seed, batch offset
            _specs(hg, Tq_p, D), _specs(hg, Tk_p, D), _specs(hg, Tk_p, D),
            pl.BlockSpec((None, 1, mask.shape[2]), lambda b, g: (b, 0, 0)),
        ],
        out_specs=[
            _specs(hg, Tq_p, D),
            pl.BlockSpec((None, hg, 1, Tq_p), lambda b, g: (b, g, 0, 0)),
        ],
        interpret=interpret, **_params(interpret),
    )(seeds, q_p, k_p, v_p, mask)
    return o[:, :Tq], lse


@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "rate", "interpret", "head_group", "q_chunk",
    "out_dtype"))
def _backward(seeds, q, k, v, kv_mask, o, lse, do, *, causal, sm_scale, rate,
              interpret, head_group=None, q_chunk=None, out_dtype=None):
    """``(dq, dk, dv)``, merged as q, k, v, o and dO are, from one kernel:
    scores, probabilities and dropout bits recomputed once a tile, the three
    gradients written together."""
    B, Tq, H = q.shape[0], q.shape[1], lse.shape[1]
    D, Tk = q.shape[2] // H, k.shape[1]
    q_p, k_p, v_p, mask, has_mask, hg, cq = _prepare(
        q, k, v, kv_mask, H, True, head_group, q_chunk)
    o_p, _ = A._pad_to(o, LANE, 1)
    do_p, _ = A._pad_to(do, LANE, 1)  # zero rows: nothing reaches dk, dv
    Tq_p, Tk_p = q_p.shape[1], k_p.shape[1]
    static = _static(causal, sm_scale, rate, has_mask, hg, cq, Tq_p, Tk_p,
                     H, D)
    kernel = functools.partial(_bwd_kernel, **static)
    by_q, by_k = _specs(hg, Tq_p, D), _specs(hg, Tk_p, D)
    scratch = ([pltpu.VMEM((Tk_p, static["group"] * D), jnp.float32)] * 2
               if len(static["chunks"]) > 1 else [])
    dtype = out_dtype or q.dtype
    dq, dk, dv = pl.pallas_call(
        kernel,
        name="short_attn_bwd",
        out_shape=[A._sds((B, Tq_p, H * D), dtype, q_p),
                   A._sds((B, Tk_p, H * D), dtype, q_p),
                   A._sds((B, Tk_p, H * D), dtype, q_p)],
        grid=(B, H // hg),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            by_q, by_k, by_k,
            pl.BlockSpec((None, 1, mask.shape[2]), lambda b, g: (b, 0, 0)),
            by_q, by_q,
            pl.BlockSpec((None, hg, 1, Tq_p), lambda b, g: (b, g, 0, 0)),
        ],
        out_specs=[by_q, by_k, by_k],
        scratch_shapes=scratch,
        interpret=interpret, **_params(interpret),
    )(seeds, q_p, k_p, v_p, mask, o_p, do_p, lse)
    return dq[:, :Tq], dk[:, :Tk], dv[:, :Tk]


def _seeds(dropout_seed, batch_offset):
    """int32[2] for SMEM: the dropout seed, and the global index of this
    shard's first batch row (the hash counts rows as ``mha_xla`` does)."""
    seed = (jnp.zeros((), jnp.int32) if dropout_seed is None
            else jnp.asarray(dropout_seed, jnp.int32).reshape(()))
    off = (jnp.zeros((), jnp.int32) if batch_offset is None
           else jnp.asarray(batch_offset, jnp.int32).reshape(()))
    return jnp.stack([seed, off])


def _scale(q, sm_scale):
    return float(1.0 / np.sqrt(q.shape[-1])) if sm_scale is None \
        else float(sm_scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def short_attention(q, k, v, kv_mask, dropout_seed, batch_offset,
                    causal=False, sm_scale=None, dropout_rate=0.0):
    """q [B,H,Tq,D], k, v [B,H,Tk,D], kv_mask [B,Tk] 1/0 or None ->
    [B,H,Tq,D].  ``batch_offset``: the global index of row 0 when the batch
    is a shard (``lax.axis_index * B`` under ``shard_map``), else None."""
    return _sa_fwd(q, k, v, kv_mask, dropout_seed, batch_offset, causal,
                   sm_scale, dropout_rate)[0]


def _mxu(x, interpret):
    """The operand the MXU sees.  A float32 operand takes the back end's
    default precision, as XLA's own dots do (``mha_xla`` on a TPU: ONE bf16
    pass, f32 accumulation — ROADMAP, closed by measurement): compiled for
    the TPU it is rounded to bf16 here, outside the kernel, where XLA folds
    the cast into whatever produced it and the kernel fetches half the bytes;
    interpreted it stays float32, as XLA's dots on the CPU do.  Outputs and
    gradients keep the caller's dtype either way."""
    if x.dtype == jnp.float32 and not interpret:
        return x.astype(jnp.bfloat16)
    return x


def _sa_fwd(q, k, v, kv_mask, dropout_seed, batch_offset, causal, sm_scale,
            dropout_rate):
    seeds = _seeds(dropout_seed, batch_offset)
    interpret = pallas_interpret()
    H = q.shape[1]
    # merged, THEN rounded: the two transposes meet and fold, and the cast
    # joins the projection that made the operand
    q2, k2, v2 = (_mxu(_merged(x), interpret) for x in (q, k, v))
    o2, lse = _forward(seeds, q2, k2, v2, kv_mask, heads=H,
                       causal=bool(causal), sm_scale=_scale(q, sm_scale),
                       rate=float(dropout_rate), interpret=interpret,
                       out_dtype=q.dtype.name)
    return _split(o2, H), (q2, k2, v2, kv_mask, seeds, o2, lse)


def _sa_bwd(causal, sm_scale, dropout_rate, res, do):
    q2, k2, v2, kv_mask, seeds, o2, lse = res
    interpret = pallas_interpret()
    H = lse.shape[1]
    grads = _backward(seeds, q2, k2, v2, kv_mask, o2, lse,
                      _mxu(_merged(do), interpret), causal=bool(causal),
                      sm_scale=_scale(do, sm_scale), rate=float(dropout_rate),
                      interpret=interpret, out_dtype=o2.dtype.name)
    return tuple(_split(g, H) for g in grads) + (None, None, None)


short_attention.defvjp(_sa_fwd, _sa_bwd)
