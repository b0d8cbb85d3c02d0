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

  — the recurrence regrouped, every product on the MXU in float32.  A decay a
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
_F32 = jnp.float32


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


def _mm(x, y):
    return jnp.dot(x, y, precision=_HIGHEST, preferred_element_type=_F32)


def _nt(x, y):
    """``x yᵀ``."""
    return lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                           precision=_HIGHEST, preferred_element_type=_F32)


def _chunk_kernel(w_ref, m_ref, q_ref, k_ref, v_ref, a_ref, b_ref, o_ref,
                  s_ref, *, hb: int, K: int, V: int, levels: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[:] = jnp.zeros_like(s_ref)

    C = q_ref.shape[0]
    eye = (lax.broadcasted_iota(jnp.int32, (C, C), 0)
           == lax.broadcasted_iota(jnp.int32, (C, C), 1)).astype(_F32)
    for i in range(hb):
        kl, vl = slice(i * K, (i + 1) * K), slice(i * V, (i + 1) * V)
        qf, kf = q_ref[:, kl].astype(_F32), k_ref[:, kl].astype(_F32)
        vf, af = v_ref[:, vl].astype(_F32), a_ref[:, kl]
        kb = kf * b_ref[0, :, i:i + 1]                  # b_r k_r
        # every level's exponents and the running sum in one product: rows
        # [lv * C, (lv + 1) * C) are level lv's, the last C the running sum
        sums = _mm(w_ref[:], af)                        # [(levels + 1) C, K]
        N = jnp.zeros((C, C), _F32)
        B = eye * jnp.sum(qf * kb, axis=1, keepdims=True)
        for lv in range(levels):
            E = jnp.exp(sums[lv * C:(lv + 1) * C])      # every exponent <= 0
            both = _nt(jnp.concatenate([kf * E, qf * E]), kb * E)
            N = N + m_ref[lv] * both[:C]
            B = B + m_ref[lv] * both[C:]
        X = eye                                         # (I + N)^-1, by halves
        for lv in range(levels):
            X = X - _mm(_mm(X, m_ref[lv] * N), X)
        g = sums[levels * C:]                           # [C, K] running sum
        eg = jnp.exp(g)
        prev = s_ref[i]                                 # [V, K]
        u = _mm(X, vf - _nt(kf * eg, prev))             # [C, V]
        o_ref[:, vl] = (_nt(qf * eg, prev) + _mm(B, u)).astype(o_ref.dtype)
        whole = g[C - 1:C, :]                           # [1, K]
        s_ref[i] = jnp.exp(whole) * prev \
            + _mm(u.T, kb * jnp.exp(whole - g))


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
           "scan_supported", "halving", "CHUNK", "LANE"]
