"""Grouped-query attention (one softmax a head, ``group`` query heads over
one K/V head) over cached K/V rows.

A cached token is one row ``[k (n_kv·dh) | v (n_kv·dh)]``, K/V heads merged
into the minor axis, so at ``dh = 128`` a K/V head's keys and its values are
one lane tile each and every slice a kernel takes is a whole tile (the layout
of ``kernels/diffattn.py``, whose rows hold two half-width components a
head; here a head is one 128-wide key).  Keys are cached AFTER their rotary
rotation; nothing here knows a position but the causal mask.

- :func:`decode_attention` — one query token a slot against the slot's rows
  in a paged pool ``[L, NB, bs, 2·kw]``, handed over WHOLE with the layer as
  a prefetched scalar.  The Pallas kernel (``gqa_paged_decode_attn``) is
  ``diffattn.paged_walk``, the one paged walk there is for rows of this
  layout: one grid step a slot, the slot's live blocks only in chunks
  fetched by explicit async copies, the next chunk (or the next slot's
  first) always in flight — with a K/V head's ``group`` queries as the rows
  (padded to eight) of ONE product with the head's key tile, so a tile is
  read once for the whole group.  The XLA fallback gathers a slot's whole
  table and counts into ``attn.gqa_decode_fallbacks``.
- :func:`ring_decode_attention` — the same walk over a *window ring* (a
  slot's last W rows at ``position mod W``, ``decode.cache.HybridStateCache``)
  under a kernel name of its own, ``gqa_ring_decode_attn``, so that a trace
  tells a ring's walk from the pool's: the slot's own ring blocks are its
  table and ``min(context, W)`` its length — softmax does not care in which
  order the rows lie.  Its fallback counts into
  ``attn.gqa_ring_decode_fallbacks``.
- :func:`prefill_attention` — a prompt's causal flash attention
  (``gqa_flash_fwd``): a grid step is one query head's tile against one tile
  of its K/V head's rows, tiles above the diagonal are neither fetched nor
  computed.  The XLA fallback builds the dense masked scores and counts into
  ``attn.gqa_prefill_fallbacks``.
- :func:`group_prefill_attention` — the same with an optional window (key
  ``j`` visible to query ``t`` iff ``0 ≤ t − j < window``) and the GROUP as
  the unit: a grid step takes one K/V head's tile against the tiles of ALL
  its ``group`` query heads, stacked as the rows of one product, so a K/V
  tile is fetched once a group and not once a query head.  The grid's last
  axis covers only the tiles a query tile's window reaches; tiles left of it
  and above the diagonal are neither fetched nor computed, and only the
  tiles the diagonal or the window's edge crosses build a mask
  (``gqa_window_flash_fwd``; with no window ``gqa_group_flash_fwd``).  The
  XLA fallback counts into ``attn.gqa_window_prefill_fallbacks``.

At ``dh = 64`` a lane tile of a row is a PAIR of K/V heads (``n_kv`` even),
and the walk and the group flash forward run as they are over
``n_kv / 2`` tiles: a pair's ``2·group`` query heads are the rows that share
the tile, each widened to 128 lanes with the OTHER head's lanes zeroed
(:func:`_pair_rows` — ``kernels/diffattn.py``'s two components a head, here
two heads a tile), so a score is one 128-deep contraction with the key tile
and nothing is sliced inside a tile; of the value product's 128 lanes a row
keeps its own head's 64 (:func:`_unpair`).  The calls are named
``gqa64_paged_decode_attn`` / ``gqa64_ring_decode_attn`` /
``gqa64_group_flash_fwd`` / ``gqa64_window_flash_fwd``, so a trace tells
them from the 128-wide ones.  :func:`prefill_attention` (a query head a grid
step) stays 128-wide.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import stats as _obs_stats
from ..platform import pallas_interpret
from .diffattn import _first_tile, flash_tiles, paged_walk, visible

NEG_INF = -1e30
LANE = 128
HALF = LANE // 2
_FLASH_BLOCK = 256


def tiled(dh: int, n_kv: int) -> bool:
    """Whether heads ``dh`` wide lie in whole lane tiles of a cached row: one
    a tile, or a pair of K/V heads a tile."""
    return dh == LANE or (dh == HALF and n_kv % 2 == 0)


def _name(name: str, dh: int) -> str:
    return name if dh == LANE else name.replace("gqa_", "gqa64_", 1)


def _pair_rows(q, n_kv: int):
    """q [N, nh, 64] → [N, n_kv / 2, 2·group, 128]: the query heads of a
    pair of K/V heads as the rows that share the pair's lane tile, the first
    head's in lanes 0-63 and the second's in lanes 64-127, the other half
    zeros."""
    N, nh, dh = q.shape
    q = q.reshape(N, n_kv // 2, 2, nh // n_kv, dh)
    z = jnp.zeros_like(q[:, :, :1])
    rows = jnp.concatenate(
        [jnp.concatenate([q[:, :, :1], z], axis=-1),
         jnp.concatenate([z, q[:, :, 1:]], axis=-1)], axis=2)
    return rows.reshape(N, n_kv // 2, 2 * (nh // n_kv), LANE)


def _unpair(out, nh: int):
    """:func:`_pair_rows`' rows after the value product [N, n_kv / 2, ≥
    2·group, 128] → [N, nh, 64]: each row's own head's lanes — a select
    between the two halves of every row (a stack of two half-lane slices
    came back wrong from the TPU's compiler: PERF.md section 6, PR 44)."""
    N, tiles = out.shape[:2]
    group = nh // (2 * tiles)
    out = out[:, :, :2 * group].reshape(N, tiles, 2, group, LANE)
    second = lax.broadcasted_iota(jnp.int32, (1, 1, 2, 1, 1), 2) == 1
    return jnp.where(second, out[..., HALF:], out[..., :HALF]
                     ).reshape(N, nh, HALF)


def _split_rows(rows, n_kv: int):
    """rows [..., 2·kw] → k, v [..., n_kv, dh] float32."""
    kw = rows.shape[-1] // 2
    shape = rows.shape[:-1] + (n_kv, kw // n_kv)
    return (rows[..., :kw].reshape(shape).astype(jnp.float32),
            rows[..., kw:].reshape(shape).astype(jnp.float32))


def _split_q(q, n_kv: int):
    """q [N, nh, dh] → [N, n_kv, group, dh] float32."""
    N, nh, dh = q.shape
    return q.astype(jnp.float32).reshape(N, n_kv, nh // n_kv, dh)


def decode_attention_xla(q, pool, block_tables, context_lens, layer,
                         n_kv: int):
    S, nh, dh = q.shape
    rows = pool[layer][block_tables]            # [S, MB, bs, 2kw]
    rows = rows.reshape(S, -1, rows.shape[-1])
    k, v = _split_rows(rows, n_kv)
    s = jnp.einsum("sgrd,slgd->sgrl", _split_q(q, n_kv), k) * dh ** -0.5
    pos = jnp.arange(rows.shape[1], dtype=jnp.int32)
    live = pos[None, :] < context_lens[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None, None, :], s, NEG_INF), axis=-1)
    return jnp.einsum("sgrl,slgd->sgrd", p, v).reshape(S, nh, dh)


def _decode_pallas(q, pool, block_tables, context_lens, layer, n_kv,
                   name="gqa_paged_decode_attn"):
    S, nh, dh = q.shape
    rows = q.astype(jnp.float32) * dh ** -0.5
    rows = rows.reshape(S, n_kv, nh // n_kv, dh) if dh == LANE \
        else _pair_rows(rows, n_kv)
    tiles, shared = rows.shape[1:3]
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, -shared % 8), (0, 0))
                   ).astype(pool.dtype)
    out = paged_walk(rows, pool, block_tables, context_lens, layer, tiles,
                     _name(name, dh))
    if dh != LANE:
        return _unpair(out, nh)
    return out[:, :, :shared].reshape(S, nh, dh)


def decode_attention(q, pool, block_tables, context_lens, layer, n_kv: int):
    """q [S, nh, dh], pool [L, NB, bs, 2·kw] (all of it, as it lies),
    block_tables [S, MB] int32, context_lens [S] int32 (at least 1), layer
    an int or a traced scalar → [S, nh, dh] float32."""
    if not tiled(q.shape[-1], n_kv):
        _obs_stats.scope("attn").counter("gqa_decode_fallbacks").inc()
        return decode_attention_xla(q, pool, block_tables, context_lens,
                                    layer, n_kv)
    return _decode_pallas(q, pool, block_tables, context_lens, layer, n_kv)


def ring_decode_attention(q, rings, ring_tables, live_rows, layer,
                          n_kv: int):
    """q [S, nh, dh], rings [window layers, slots · W/rb, rb, 2·kw] (all of
    them, as they lie), ring_tables [S, W/rb] int32 (a slot's own ring
    blocks), live_rows [S] int32 (``min(context, W)``, at least 1), layer
    the window layer's index → [S, nh, dh] float32."""
    if not tiled(q.shape[-1], n_kv):
        _obs_stats.scope("attn").counter("gqa_ring_decode_fallbacks").inc()
        return decode_attention_xla(q, rings, ring_tables, live_rows, layer,
                                    n_kv)
    return _decode_pallas(q, rings, ring_tables, live_rows, layer, n_kv,
                          "gqa_ring_decode_attn")


def prefill_attention_xla(q, rows, n_kv: int, window=None):
    T, nh, dh = q.shape
    k, v = _split_rows(rows, n_kv)
    s = jnp.einsum("tgrd,jgd->grtj", _split_q(q, n_kv), k) * dh ** -0.5
    p = jax.nn.softmax(jnp.where(visible(T, window), s, NEG_INF), axis=-1)
    return jnp.einsum("grtj,jgd->tgrd", p, v).reshape(T, nh, dh)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  b: int, scale: float):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j <= i)
    def _tile():
        q = (q_ref[:].astype(jnp.float32) * scale).astype(q_ref.dtype)
        s = lax.dot_general(q, k_ref[:], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)     # [b, b]
        qpos = i * b + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = j * b + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
        m = m_scr[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[:]
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == i)        # the diagonal tile is a query tile's last
    def _finish():
        o_ref[:] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)
                    ).astype(o_ref.dtype)


def flash_tile(T: int) -> int:
    """The flash kernel's tile edge for a prompt of ``T`` positions."""
    return min(T, _FLASH_BLOCK)


def _flash_pallas(q, rows, n_kv):
    T, nh, dh = q.shape
    group = nh // n_kv
    b = flash_tile(T)

    def kv_map(lane0):
        def at(h, i, j):
            return (jnp.minimum(j, i), lane0 + h // group)
        return at

    out = pl.pallas_call(
        functools.partial(_flash_kernel, b=b, scale=dh ** -0.5),
        name="gqa_flash_fwd",
        grid=(nh, T // b, T // b),
        in_specs=[pl.BlockSpec((b, dh), lambda h, i, j: (i, h)),
                  pl.BlockSpec((b, dh), kv_map(0)),
                  pl.BlockSpec((b, dh), kv_map(n_kv))],
        out_specs=pl.BlockSpec((b, dh), lambda h, i, j: (i, h)),
        out_shape=jax.ShapeDtypeStruct((T, nh * dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((b, 1), jnp.float32),
                        pltpu.VMEM((b, 1), jnp.float32),
                        pltpu.VMEM((b, dh), jnp.float32)],
        interpret=pallas_interpret(),
    )(q.reshape(T, nh * dh), rows, rows)
    return out.reshape(T, nh, dh)


def prefill_attention(q, rows, n_kv: int):
    """Causal attention of one prompt: q [T, nh, dh], rows [T, 2·kw] (the
    prompt's own cache rows) → [T, nh, dh] float32.  Pad positions lie after
    every real one, so the causal mask alone keeps them out of every real
    row."""
    T = q.shape[0]
    b = flash_tile(T)
    if q.shape[-1] != LANE or T % b or b % 8:
        _obs_stats.scope("attn").counter("gqa_prefill_fallbacks").inc()
        return prefill_attention_xla(q, rows, n_kv)
    return _flash_pallas(q, rows, n_kv)


def _group_flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                        b: int, group: int, window):
    """Grid (n_kv, T/b, key tiles a window reaches): q_ref [b, group·128] —
    the group's query heads side by side, already scaled — against one K/V
    head's key and value tiles [b, 128].  The group's tiles are stacked as
    the ``group·b`` rows of ONE product with the key tile."""
    i = pl.program_id(1)
    j = pl.program_id(2)
    kb = _first_tile(i, b, window) + j

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def scores():
        q = jnp.concatenate([q_ref[:, r * LANE:(r + 1) * LANE]
                             for r in range(group)], axis=0)
        return lax.dot_general(q, k_ref[:], (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)

    def accumulate(s):
        m = m_scr[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[:]
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    # a tile wholly under the diagonal and wholly inside the window needs no
    # mask: its last key is older than the tile's first query, and its first
    # key is inside the window of the tile's last query
    inside = kb < i
    if window is not None:
        inside = jnp.logical_and(inside, (i - kb + 1) * b - 1 < window)

    @pl.when(inside)
    def _whole():
        accumulate(scores())

    @pl.when(jnp.logical_and(kb <= i, jnp.logical_not(inside)))
    def _edge():
        s = scores()
        tile = (b, s.shape[1])
        qpos = i * b + jnp.concatenate(
            [lax.broadcasted_iota(jnp.int32, tile, 0)] * group, axis=0)
        kpos = kb * b + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = kpos <= qpos
        if window is not None:
            keep = jnp.logical_and(keep, qpos - kpos < window)
        accumulate(jnp.where(keep, s, NEG_INF))

    @pl.when(kb == i)       # the diagonal tile is a query tile's last
    def _finish():
        out = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)
        for r in range(group):
            o_ref[:, r * LANE:(r + 1) * LANE] = out[r * b:(r + 1) * b]


def _group_flash_pallas(q, rows, n_kv, window):
    T, nh, dh = q.shape
    b, n_kw = flash_tiles(T, window)
    qs = (q.astype(jnp.float32) * dh ** -0.5).astype(rows.dtype)
    name = _name("gqa_group_flash_fwd" if window is None
                 else "gqa_window_flash_fwd", dh)
    if dh != LANE:
        # a pair of K/V heads is one tile and its 2·group query heads the
        # group that shares it
        qs, n_kv, dh = _pair_rows(qs, n_kv), n_kv // 2, LANE
    group = nh // n_kv

    def kv_map(lane0):
        def at(g, i, j):
            return (jnp.minimum(_first_tile(i, b, window) + j, i), lane0 + g)
        return at

    out = pl.pallas_call(
        functools.partial(_group_flash_kernel, b=b, group=group,
                          window=window),
        name=name,
        grid=(n_kv, T // b, n_kw),
        in_specs=[pl.BlockSpec((b, group * dh), lambda g, i, j: (i, g)),
                  pl.BlockSpec((b, dh), kv_map(0)),
                  pl.BlockSpec((b, dh), kv_map(n_kv))],
        out_specs=pl.BlockSpec((b, group * dh), lambda g, i, j: (i, g)),
        out_shape=jax.ShapeDtypeStruct((T, nh * dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((group * b, 1), jnp.float32),
                        pltpu.VMEM((group * b, 1), jnp.float32),
                        pltpu.VMEM((group * b, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_interpret(),
    )(qs.reshape(T, nh * dh), rows, rows)
    if q.shape[-1] != LANE:
        return _unpair(out.reshape(T, n_kv, group, LANE), nh)
    return out.reshape(T, nh, dh)


def group_prefill_attention(q, rows, n_kv: int, window=None):
    """Causal attention of one prompt with an optional window, a K/V head's
    tile fetched once for its whole group: q [T, nh, dh], rows [T, 2·kw] (the
    prompt's own cache rows) → [T, nh, dh] float32.  Pad positions lie after
    every real one, so the causal mask alone keeps them out of every real
    row."""
    T = q.shape[0]
    b, _ = flash_tiles(T, window)
    if not tiled(q.shape[-1], n_kv) or T % b or b % 8:
        _obs_stats.scope("attn").counter(
            "gqa_window_prefill_fallbacks").inc()
        return prefill_attention_xla(q, rows, n_kv, window)
    return _group_flash_pallas(q, rows, n_kv, window)


__all__ = ["decode_attention", "decode_attention_xla",
           "ring_decode_attention", "prefill_attention",
           "prefill_attention_xla", "group_prefill_attention", "flash_tile",
           "tiled", "LANE"]
