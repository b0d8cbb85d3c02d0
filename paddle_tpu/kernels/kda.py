"""Kimi Delta Attention (KDA): a gated delta rule with a decay a CHANNEL, for
the decode plane.

A head keeps a state ``S`` [K, V] float32 (``K`` the key's channels, ``V`` the
value's) and, with a log-decay ``a_t ≤ 0`` a key channel, a step size ``b_t``
in (0, 1), a unit key ``k_t`` and a query ``q_t``,

    S' = Diag(exp a_t) S_{t−1}
    S_t = S' + b_t k_t (v_t − S'ᵀ k_t)ᵀ ,      o_t = S_tᵀ q_t

(``kernels/ssd.py`` is Mamba-2's: a scalar decay a head and no delta
correction, so it cannot compute this.)  The cache keeps a stream's rows
**value-major**, ``Sᵀ`` [V, K]: the decay is then a row over the lanes, which
broadcasts down the sublanes for nothing, and ``S'ᵀ k`` a sum along the lanes.

- :func:`kda_scan` — a prompt's scan from a zero state.  The Pallas kernel
  (``kda_chunk_prefill``) takes a chunk of :data:`CHUNK` positions and a block
  of heads a grid step, the heads' states the output block itself, resident in
  VMEM over the chunk axis.  With ``g_t`` the running sum of ``a`` inside the
  chunk, ``N[s, r] = b_r Σ_c k_s k_r exp(g_s − g_r)`` for ``r < s`` and
  ``B[t, s] = b_s Σ_c q_t k_s exp(g_t − g_s)`` for ``s ≤ t``:

      u = (I + N)⁻¹ (v − (k ∘ exp g) S₀) ,   o = (q ∘ exp g) S₀ + B u ,
      S_C = Diag(exp g_C) S₀ + (b k ∘ exp(g_C − g))ᵀ u

  — the recurrence regrouped.  The state, the decays, every exponent, mask
  and value between two products are float32; a product itself is plain
  bf16 × bf16 passes of the MXU that sum in float32, over the bf16 pieces
  its float32 operands hold (:func:`pieces`), where ``Precision.HIGHEST``
  on float32 operands is six passes (three pieces an operand):

  * ``sums = w a`` (:func:`dot_01`): ``w`` is zeros and ones, whole in one
    piece, and three pieces are all 24 bits of ``a`` — the float32 product
    in three passes (the other three multiplied zeros).  A decay keeps
    every bit.
  * every other product (:func:`dot_split`): two pieces an operand, ``x₁y₁
    + x₁y₂ + x₂y₁``, three passes, the smallest summed first.  Sixteen
    bits an operand; what is dropped is within ``2⁻¹⁵ Σ|x||y|`` a product.
    At K = V = 128 against the recurrence the output reads 1.1e-6 (of
    0.17) and the state 1.9e-5 (of 1.6) in the CPU's interpreter, 9e-7 (of
    0.08) and 1.8e-5 (of 0.8) on the v5e, where six passes read 4e-7 and
    1.5e-5; the served output is bf16, a step of 2e-3 of a value.
  * the inverse's first level is two products by the identity: not taken.

  No product stays at six passes.  ONE pass (an operand rounded to bf16)
  is another result — 1–4e-3 on the outputs — and is not this.  A grid
  step takes every stage for all its heads in turn: a head's products wait
  on one another, and another head's fill the MXU meanwhile.  A decay a
  channel means ``exp(g_s − g_r)`` does not factor into a row's and a
  column's part without one of them overflowing where the decay is strong
  (``exp(−g_r)`` passes float32 at 89 nats, which sixteen strong positions
  reach).  So the lower triangle is cut by **halving**: at level ``h = 1, 2,
  4, …`` the pairs whose ``s`` lies in the right half and ``r`` in the left
  half of the same block of ``2h`` positions are taken against the block's
  middle ``m``: ``exp(g_s − m) · exp(m − g_r)``, each exponent a sum of the
  ``a`` BETWEEN the two positions (a product with a constant 0/1 matrix, so
  nothing is cancelled) and each at most 0 — nothing overflows, and what
  underflows is smaller still in the true product.  The levels partition the
  triangle, and the same cut inverts ``I + N``: the inverse of a block is its
  halves' inverses less their product round the lower-left quarter, so ``log₂
  C`` merges of two products give the inverse by block substitution, which
  stays sound where a product of powers of ``N`` would cancel.  A position
  with ``a = 0`` and ``b = 0`` leaves the state as it is, which is how the
  pads of a prefill bucket are passed over.  The XLA form
  (:func:`kda_scan_xla`, the recurrence one position at a time, also the
  parity anchor) counts into ``kda.chunk_fallbacks``.
- :func:`kda_state_step` — one token a slot over the rows of ALL layers
  ``[L, S, H, V, K]``, the layer a prefetched scalar: the kernel
  (``kda_state_step``) reads a slot's block of heads once and writes it once,
  onto itself (``input_output_aliases``).  Keys, queries and decays come as
  rows over a head's lanes; what the update needs down the sublanes (the
  value and the step size) comes transposed, a head a lane, and the output
  leaves the same way.  The XLA form (:func:`kda_step_xla`) counts into
  ``kda.step_fallbacks``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import stats as _obs_stats
from ..platform import pallas_interpret

LANE = 128
CHUNK = 64              # positions a grid step of the chunked prefill
_SCAN_HEADS = 4         # heads a grid step of the chunked prefill
_STEP_HEADS = 16        # heads a grid step of the one-token update
_HIGHEST = lax.Precision.HIGHEST
_F32, _BF16 = jnp.float32, jnp.bfloat16
NN = (((1,), (0,)), ((), ()))       # x y
NT = (((1,), (1,)), ((), ()))       # x yᵀ


def _tiles_ok(K: int, V: int) -> bool:
    """Off the TPU the kernels interpret themselves at any size; Mosaic wants
    a head's key and value channels in whole lane tiles."""
    return pallas_interpret() or (K % LANE == 0 and V % LANE == 0)


def _head_block(H: int, most: int) -> int:
    return max(d for d in range(1, min(most, H) + 1) if H % d == 0)


# -- a prompt ---------------------------------------------------------------
def kda_scan_xla(q, k, v, a, b):
    """The recurrence one position at a time: q, k [T, H, K] (k a unit
    vector, q with its scale), v [T, H, V], a [T, H, K] (log-decay, 0 at a
    pad), b [T, H] (0 at a pad) → (o [T, H, V], S_Tᵀ [H, V, K]), float32."""
    H, K = q.shape[1:]
    V = v.shape[2]

    def step(S, row):
        qt, kt, vt, at, bt = row
        S = S * jnp.exp(at)[:, None, :]
        pred = jnp.einsum("hvk,hk->hv", S, kt, precision=_HIGHEST)
        S = S + (bt[:, None] * (vt - pred))[:, :, None] * kt[:, None, :]
        return S, jnp.einsum("hvk,hk->hv", S, qt, precision=_HIGHEST)

    S, o = lax.scan(step, jnp.zeros((H, V, K), _F32),
                    tuple(x.astype(_F32) for x in (q, k, v, a, b)))
    return o, S


@functools.lru_cache(maxsize=None)
def halving(C: int) -> tuple:
    """The chunk's constant matrices, float32.  ``sums`` ``[(levels + 1) · C,
    C]``: rows ``[l · C, (l + 1) · C)`` are level ``l``'s (``h = 2^l``) — row
    ``p`` sums the ``a`` between ``p`` and the middle of its block of ``2h``
    (the block's left half ends there) — and the last ``C`` rows the
    inclusive lower triangle (the running sum).  ``masks`` ``[levels, C,
    C]``: level ``l``'s pairs (``s`` in the right half, ``r`` in the left
    half of the same block)."""
    idx = np.arange(C)
    p = idx[None, :]
    sums, masks = [], []
    h = 1
    while h < C:
        right = (idx // h) % 2 == 1
        mid = idx // (2 * h) * 2 * h + h - 1
        sums.append(np.where(right[:, None],
                             (p > mid[:, None]) & (p <= idx[:, None]),
                             (p > idx[:, None]) & (p <= mid[:, None])))
        masks.append((idx[:, None] // (2 * h) == p // (2 * h))
                     & right[:, None] & ~right[None, :])
        h *= 2
    sums.append(idx[:, None] >= p)
    return (np.concatenate(sums).astype(np.float32),
            np.stack(masks).astype(np.float32))


def pieces(x, n: int = 2) -> tuple:
    """``x`` float32 as ``n`` bf16 pieces, the largest first: each is what
    bf16 holds of what the ones before left, so they sum to ``x`` to ``8 n``
    bits, and to all 24 of them at ``n = 3``."""
    out = []
    for _ in range(n - 1):
        out.append(x.astype(_BF16))
        x = x - out[-1].astype(_F32)
    return (*out, x.astype(_BF16))


def _pass(x, y, dims):
    """One pass of the MXU: bf16 operands, float32 sums."""
    return lax.dot_general(x, y, dims, preferred_element_type=_F32)


def dot_01(w, y):
    """``w`` of zeros and ones (bf16 holds it whole) times ``y`` float32: the
    float32 product in three passes, none of them over a piece of zeros."""
    y1, y2, y3 = pieces(y, 3)
    return (_pass(w, y3, NN) + _pass(w, y2, NN)) + _pass(w, y1, NN)


def dot_split(xs, ys, dims=NN):
    """Two operands in two pieces each (:func:`pieces`), three passes, the
    smallest summed first; what is dropped — ``x₂ y₂`` and what 16 bits do
    not hold of an operand — is within ``2⁻¹⁵ Σ|x||y|``."""
    (x1, x2), (y1, y2) = xs, ys
    return (_pass(x1, y2, dims) + _pass(x2, y1, dims)) + _pass(x1, y1, dims)


def _chunk_kernel(w_ref, m_ref, q_ref, k_ref, v_ref, a_ref, b_ref, o_ref,
                  s_ref, *, hb: int, K: int, V: int, levels: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[:] = jnp.zeros_like(s_ref)

    C = q_ref.shape[0]
    eye = (lax.broadcasted_iota(jnp.int32, (C, C), 0)
           == lax.broadcasted_iota(jnp.int32, (C, C), 1)).astype(_F32)
    w = w_ref[:].astype(_BF16)                          # 0/1: whole in bf16
    # every stage for all the block's heads in turn, not a head's stages in
    # turn: a head's products wait on one another (the inverse is a chain of
    # ten), and another head's fill the MXU meanwhile
    heads = range(hb)
    kl = [slice(i * K, (i + 1) * K) for i in heads]
    vl = [slice(i * V, (i + 1) * V) for i in heads]
    qf = [q_ref[:, kl[i]].astype(_F32) for i in heads]
    kf = [k_ref[:, kl[i]].astype(_F32) for i in heads]
    kb = [kf[i] * b_ref[0, :, i:i + 1] for i in heads]  # b_r k_r
    # every level's exponents and the running sum in one product: rows
    # [lv * C, (lv + 1) * C) are level lv's, the last C the running sum
    sums = [dot_01(w, a_ref[:, kl[i]]) for i in heads]  # [(levels + 1) C, K]
    N = [jnp.zeros((C, C), _F32) for _ in heads]
    B = [eye * jnp.sum(qf[i] * kb[i], axis=1, keepdims=True) for i in heads]
    for lv in range(levels):
        for i in heads:
            E = jnp.exp(sums[i][lv * C:(lv + 1) * C])   # every exponent <= 0
            both = dot_split(pieces(jnp.concatenate([kf[i] * E, qf[i] * E])),
                             pieces(kb[i] * E), NT)
            N[i] = N[i] + m_ref[lv] * both[:C]
            B[i] = B[i] + m_ref[lv] * both[C:]
    # (I + N)^-1 by halves; the first level's two products are by the
    # identity, and are not taken
    X = [eye - m_ref[0] * N[i] for i in heads]
    for lv in range(1, levels):
        Xs = [pieces(X[i]) for i in heads]
        mid = [dot_split(Xs[i], pieces(m_ref[lv] * N[i])) for i in heads]
        X = [X[i] - dot_split(pieces(mid[i]), Xs[i]) for i in heads]
    g = [sums[i][levels * C:] for i in heads]           # [C, K] running sum
    eg = [jnp.exp(g[i]) for i in heads]
    prev = [s_ref[i] for i in heads]                    # [V, K]
    # both reads of the state in one product: k's rows, then q's
    reads = [dot_split(pieces(jnp.concatenate([kf[i] * eg[i],
                                               qf[i] * eg[i]])),
                       pieces(prev[i]), NT) for i in heads]     # [2 C, V]
    u = [dot_split(pieces(X[i]),
                   pieces(v_ref[:, vl[i]].astype(_F32) - reads[i][:C]))
         for i in heads]                                        # [C, V]
    for i in heads:
        o_ref[:, vl[i]] = (reads[i][C:] + dot_split(pieces(B[i]), pieces(u[i]))
                           ).astype(o_ref.dtype)
    for i in heads:
        whole = g[i][C - 1:C, :]                        # [1, K]
        s_ref[i] = jnp.exp(whole) * prev[i] + dot_split(
            pieces(u[i].T), pieces(kb[i] * jnp.exp(whole - g[i])))


def scan_supported(T: int, K: int, V: int, chunk: int = CHUNK) -> bool:
    return T % chunk == 0 and chunk & (chunk - 1) == 0 and _tiles_ok(K, V)


@functools.partial(jax.jit, static_argnames=("chunk", "out_dtype",
                                             "interpret"))
def _scan_pallas(q, k, v, a, b, *, chunk, out_dtype, interpret):
    T, H, K = q.shape
    V = v.shape[2]
    hb = _head_block(H, _SCAN_HEADS)
    nb = H // hb
    sums, masks = (jnp.asarray(c) for c in halving(chunk))
    levels = masks.shape[0]
    # a head a lane of the step sizes, a block of heads a grid step
    bb = b.astype(_F32).reshape(T, nb, hb).transpose(1, 0, 2)

    def seq(width):
        return pl.BlockSpec((chunk, hb * width), lambda i, j: (j, i))

    o, S = pl.pallas_call(
        functools.partial(_chunk_kernel, hb=hb, K=K, V=V, levels=levels),
        name="kda_chunk_prefill",
        grid=(nb, T // chunk),
        in_specs=[pl.BlockSpec(sums.shape, lambda i, j: (0, 0)),
                  pl.BlockSpec(masks.shape, lambda i, j: (0, 0, 0)),
                  seq(K), seq(K), seq(V), seq(K),
                  pl.BlockSpec((1, chunk, hb), lambda i, j: (i, j, 0))],
        out_specs=[seq(V),
                   pl.BlockSpec((hb, V, K), lambda i, j: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, H * V), out_dtype),
                   jax.ShapeDtypeStruct((H, V, K), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
    )(sums, masks, q.reshape(T, H * K), k.reshape(T, H * K),
      v.reshape(T, H * V),
      a.astype(_F32).reshape(T, H * K), bb)
    return o.reshape(T, H, V), S


def kda_scan(q, k, v, a, b, chunk: int = CHUNK, out_dtype=jnp.float32):
    """q, k [T, H, K] (k a unit vector, q with its scale), v [T, H, V], a [T,
    H, K] float32 (log-decay; 0 at a pad), b [T, H] (0 at a pad) → (o [T, H,
    V] ``out_dtype``, S_Tᵀ [H, V, K] float32), from a zero state."""
    T, _, K = q.shape
    if not scan_supported(T, K, v.shape[2], chunk):
        _obs_stats.scope("kda").counter("chunk_fallbacks").inc()
        o, S = kda_scan_xla(q, k, v, a, b)
        return o.astype(out_dtype), S
    return _scan_pallas(q, k, v, a, b, chunk=chunk,
                        out_dtype=jnp.dtype(out_dtype),
                        interpret=bool(pallas_interpret()))


# -- one token a slot -------------------------------------------------------
def kda_step_xla(S, q, k, v, a, b):
    """S [S, H, V, K] float32 (value-major), q, k, a [S, H, K], v [S, H, V],
    b [S, H] → (o [S, H, V] float32, S')."""
    q, k, v, a, b = (x.astype(_F32) for x in (q, k, v, a, b))
    S = S * jnp.exp(a)[:, :, None, :]
    pred = jnp.einsum("shvk,shk->shv", S, k, precision=_HIGHEST)
    S = S + (b[..., None] * (v - pred))[..., None] * k[:, :, None, :]
    return jnp.einsum("shvk,shk->shv", S, q, precision=_HIGHEST), S


def _step_kernel(ly_ref, ea_ref, k_ref, q_ref, vt_ref, bt_ref, s_ref, o_ref,
                 out_ref, *, hb: int, K: int):
    del ly_ref
    for i in range(hb):
        lanes = slice(i * K, (i + 1) * K)
        kk = k_ref[0, :, lanes]                                 # [1, K]
        S = s_ref[0, 0, i] * ea_ref[0, :, lanes]                # [V, K]
        pred = jnp.sum(S * kk, axis=1, keepdims=True)           # [V, 1]
        S = S + bt_ref[0, 0, :, i:i + 1] \
            * (vt_ref[0, 0, :, i:i + 1] - pred) * kk
        out_ref[0, 0, i] = S
        o_ref[0, 0, :, i:i + 1] = jnp.sum(S * q_ref[0, :, lanes], axis=1,
                                          keepdims=True)


def _step_pallas(states, layer, q, k, v, a, b):
    _, S, H, V, K = states.shape
    hb = _head_block(H, _STEP_HEADS)
    nb = H // hb

    def rows(x):                        # [S, H, K] → [S, 1, H·K]
        return x.astype(_F32).reshape(S, 1, H * K)

    def cols(x):                        # [S, H, V] → [S, nb, V, hb]
        return x.astype(_F32).reshape(S, nb, hb, V).transpose(0, 1, 3, 2)

    row = pl.BlockSpec((1, 1, hb * K), lambda s, j, ly: (s, 0, j))
    col = pl.BlockSpec((1, 1, V, hb), lambda s, j, ly: (s, j, 0, 0))
    state = pl.BlockSpec((1, 1, hb, V, K),
                         lambda s, j, ly: (ly[0], s, j, 0, 0))
    o, states = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb, K=K),
        name="kda_state_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, nb),
            in_specs=[row, row, row, col, col, state],
            out_specs=[col, state]),
        out_shape=[jax.ShapeDtypeStruct((S, nb, V, hb), _F32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=pallas_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows(jnp.exp(a.astype(_F32))),
      rows(k), rows(q), cols(v),
      cols(jnp.broadcast_to(b.astype(_F32)[..., None], v.shape)), states)
    return o.transpose(0, 1, 3, 2).reshape(S, H, V), states


def kda_state_step(states, layer, q, k, v, a, b):
    """One token a slot, in place: states [L, S, H, V, K] float32 (every
    layer's rows, as they lie), layer an int or a traced scalar, q, k, a [S,
    H, K], v [S, H, V], b [S, H] → (o [S, H, V] float32, states')."""
    V, K = states.shape[3:]
    if not _tiles_ok(K, V):
        _obs_stats.scope("kda").counter("step_fallbacks").inc()
        o, new = kda_step_xla(
            lax.dynamic_index_in_dim(states, layer, keepdims=False), q, k, v,
            a, b)
        return o, lax.dynamic_update_index_in_dim(states, new, layer, 0)
    return _step_pallas(states, layer, q, k, v, a, b)


__all__ = ["kda_scan", "kda_scan_xla", "kda_state_step", "kda_step_xla",
           "scan_supported", "halving", "pieces", "dot_01", "dot_split",
           "CHUNK", "LANE"]
