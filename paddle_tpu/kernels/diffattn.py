"""Differential attention (two softmaxes a head) over cached K/V rows.

A head ``h`` has a query ``[q¹ | q²]`` (``2·dh`` wide), its K/V head ``g =
h // group`` a key ``[k¹ | k²]`` and a value ``v`` (``2·dh`` wide each); the
kernels return BOTH ``softmax(q¹k¹ᵀ/√dh) v`` and ``softmax(q²k²ᵀ/√dh) v`` and
the model subtracts (``λ`` and the sub-layer norm are a layer's own).  A
cached token is one row ``[k (n_kv·2dh) | v (n_kv·2dh)]``, K/V heads merged
into the minor axis, so at ``2·dh = 128`` a K/V head's keys and its values
are one lane tile each and every slice a kernel takes is a whole tile.  A
component's query is the head's query with the other component's lanes
zeroed: the score is then one 128-deep contraction with the row's key tile
(the depth the MXU has anyway), and nothing is sliced inside a tile.

- :func:`decode_attention` — one query token a slot against the slot's rows
  in a paged pool ``[L, NB, bs, 2·kw]``, handed over WHOLE with the layer as
  a prefetched scalar (a layer's index is a loop counter where the layers
  are scanned).  The Pallas kernel walks a slot's context in chunks of
  ``_CHUNK_BLOCKS`` blocks fetched by explicit async copies, the next chunk
  in flight while this one is computed; a K/V head's tile is read once for
  the ``2·group`` queries that share it; chunks past the context are
  skipped.  Nothing in it knows a position: a *window ring* (a slot's last W
  rows at ``position mod W``) is the same call with the slot's own blocks as
  its table and ``min(context, W)`` as its length — softmax does not care
  in which order the rows lie.  ``name`` names the call
  (``diff_paged_decode_attn`` / ``diff_ring_decode_attn``), so that a trace
  tells the two uses apart.  The XLA fallback gathers a slot's whole table
  and counts into ``attn.diff_decode_fallbacks``.
- :func:`prefill_attention` — a prompt's causal flash attention with an
  optional window (key ``j`` visible to query ``t`` iff ``0 ≤ t − j <
  window``): the grid's last axis covers only the tiles a query tile's
  window reaches (three of 256 for a window of 512), tiles left of it are
  never fetched (``diff_window_flash_fwd``; with no window the call is named
  ``diff_full_flash_fwd``).  The XLA fallback builds the
  dense masked scores and counts into ``attn.diff_prefill_fallbacks``.
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

NEG_INF = -1e30
LANE = 128
# blocks a chunk: 32 x 16 tokens = 512 rows of 2,560 bf16 lanes, 2.6 MB, twice
_CHUNK_BLOCKS = 32
_FLASH_BLOCK = 256


def _check_impl(impl) -> None:
    if impl not in (None, "pallas", "xla"):
        raise ValueError(f"unknown differential attention impl {impl!r}")


def _split_rows(rows, n_kv: int):
    """rows [..., 2·kw] → k [..., n_kv, 2, dh], v [..., n_kv, 2·dh]."""
    kw = rows.shape[-1] // 2
    pair = kw // n_kv
    k = rows[..., :kw].reshape(*rows.shape[:-1], n_kv, 2, pair // 2)
    v = rows[..., kw:].reshape(*rows.shape[:-1], n_kv, pair)
    return k.astype(jnp.float32), v.astype(jnp.float32)


def _split_q(q, n_kv: int):
    """q [N, nh, 2·dh] → [N, n_kv, group, 2, dh] float32."""
    N, nh, pair = q.shape
    return q.astype(jnp.float32).reshape(N, n_kv, nh // n_kv, 2, pair // 2)


def decode_attention_xla(q, pool, block_tables, context_lens, layer,
                         n_kv: int):
    S, nh, pair = q.shape
    rows = pool[layer][block_tables]            # [S, MB, bs, 2kw]
    rows = rows.reshape(S, -1, rows.shape[-1])
    k, v = _split_rows(rows, n_kv)
    s = jnp.einsum("sgrcd,slgcd->sgrcl", _split_q(q, n_kv), k) \
        * (pair // 2) ** -0.5
    pos = jnp.arange(rows.shape[1], dtype=jnp.int32)
    live = pos[None, :] < context_lens[:, None]
    s = jnp.where(live[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("sgrcl,slgv->sgrcv", p, v).reshape(S, nh, 2, pair)


def _decode_kernel(bt_ref, cl_ref, ly_ref, q_ref, pool_ref, o_ref, buf, sem,
                   m_scr, l_scr, acc_scr, *, bs: int, chunk: int,
                   n_chunks: int, n_kv: int, kw: int):
    s = pl.program_id(0)
    j = pl.program_id(1)
    cl = cl_ref[s]
    layer = ly_ref[0]
    span = chunk * bs
    live = (cl + span - 1) // span

    def copies(c, slot):
        return [pltpu.make_async_copy(
            pool_ref.at[layer, bt_ref[s, c * chunk + b]],
            buf.at[slot, pl.ds(b * bs, bs)], sem.at[slot, b])
            for b in range(chunk)]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(jnp.logical_and(j == 0, live > 0))
    def _first():
        for cp in copies(0, 0):
            cp.start()

    @pl.when(j + 1 < live)
    def _ahead():
        for cp in copies(j + 1, (j + 1) % 2):
            cp.start()

    @pl.when(j < live)
    def _chunk():
        slot = j % 2
        for cp in copies(j, slot):
            cp.wait()
        pos = j * span + lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], span), 1)
        for g in range(n_kv):
            k = buf[slot, :, pl.ds(g * LANE, LANE)]             # [span, 128]
            v = buf[slot, :, pl.ds(kw + g * LANE, LANE)]
            sc = lax.dot_general(q_ref[0, g], k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            sc = jnp.where(pos < cl, sc, NEG_INF)
            m = m_scr[g]
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(m - m_new)
            m_scr[g] = m_new
            l_scr[g] = l_scr[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[g] = acc_scr[g] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == n_chunks - 1)
    def _finish():
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)
                    ).astype(o_ref.dtype)


def _component_rows(q, n_kv: int, dtype):
    """q [N, nh, 128] (already scaled) → [N, n_kv, QR, 128]: row ``2r + c``
    of a K/V head is query ``r`` of its group with component ``1 - c``'s
    lanes zeroed; QR is ``2·group`` rounded up to eight rows."""
    N, nh, pair = q.shape
    group = nh // n_kv
    lane = jnp.arange(pair) < pair // 2
    both = jnp.stack([jnp.where(lane, q, 0), jnp.where(lane, 0, q)], axis=2)
    rows = both.reshape(N, n_kv, 2 * group, pair)
    pad = -(2 * group) % 8
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return rows.astype(dtype)


def _decode_pallas(q, pool, block_tables, context_lens, layer, n_kv, name):
    S, nh, pair = q.shape
    bs, width = pool.shape[2], pool.shape[3]
    kw = width // 2
    MB = block_tables.shape[1]
    chunk = min(_CHUNK_BLOCKS, MB)
    n_chunks = -(-MB // chunk)
    bt = block_tables.astype(jnp.int32)
    if n_chunks * chunk != MB:      # a ragged last chunk reads block 0
        bt = jnp.pad(bt, ((0, 0), (0, n_chunks * chunk - MB)))
    qs = (q.astype(jnp.float32) * (pair // 2) ** -0.5)
    rows = _component_rows(qs, n_kv, pool.dtype)
    QR = rows.shape[2]
    kernel = functools.partial(_decode_kernel, bs=bs, chunk=chunk,
                               n_chunks=n_chunks, n_kv=n_kv, kw=kw)
    spec = pl.BlockSpec((1, n_kv, QR, LANE),
                        lambda s, j, bt, cl, ly: (s, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, n_chunks),
            in_specs=[spec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=spec,
            scratch_shapes=[pltpu.VMEM((2, chunk * bs, width), pool.dtype),
                            pltpu.SemaphoreType.DMA((2, chunk)),
                            pltpu.VMEM((n_kv, QR, 1), jnp.float32),
                            pltpu.VMEM((n_kv, QR, 1), jnp.float32),
                            pltpu.VMEM((n_kv, QR, LANE), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, n_kv, QR, LANE), jnp.float32),
        interpret=pallas_interpret(),
    )(bt, context_lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), rows, pool)
    group = nh // n_kv
    return out[:, :, :2 * group].reshape(S, nh, 2, pair)


def decode_attention(q, pool, block_tables, context_lens, layer, n_kv: int,
                     impl=None, name: str = "diff_paged_decode_attn"):
    """q [S, nh, 2·dh], pool [L, NB, bs, 2·kw] (all of it, as it lies),
    block_tables [S, MB] int32, context_lens [S] int32 (at least 1), layer
    an int or a traced scalar → both components' outputs [S, nh, 2, 2·dh]
    float32."""
    _check_impl(impl)
    if impl == "xla" or q.shape[-1] != LANE:
        _obs_stats.scope("attn").counter("diff_decode_fallbacks").inc()
        return decode_attention_xla(q, pool, block_tables, context_lens,
                                    layer, n_kv)
    return _decode_pallas(q, pool, block_tables, context_lens, layer, n_kv,
                          name)


def visible(T: int, window=None):
    """[T, T] bool: key j visible to query t."""
    t = jnp.arange(T)
    keep = t[:, None] >= t[None, :]
    if window is not None:
        keep = jnp.logical_and(keep, t[:, None] - t[None, :] < window)
    return keep


def prefill_attention_xla(q, rows, n_kv: int, window=None):
    T, nh, pair = q.shape
    k, v = _split_rows(rows, n_kv)
    s = jnp.einsum("tgrcd,jgcd->grctj", _split_q(q, n_kv), k) \
        * (pair // 2) ** -0.5
    p = jax.nn.softmax(jnp.where(visible(T, window), s, NEG_INF), axis=-1)
    return jnp.einsum("grctj,jgv->tgrcv", p, v).reshape(T, nh, 2, pair)


def row_attention(q, rows, mask, n_kv: int):
    """One query row against a prompt's rows (the last position of a prefill
    above the layers that write a cache): q [nh, 2·dh], rows [T, 2·kw], mask
    [T] bool → both components' outputs [nh, 2, 2·dh] float32.  Dense: one
    row of scores a head."""
    nh, pair = q.shape
    k, v = _split_rows(rows, n_kv)
    s = jnp.einsum("grcd,jgcd->grcj", _split_q(q[None], n_kv)[0], k) \
        * (pair // 2) ** -0.5
    p = jax.nn.softmax(jnp.where(mask, s, NEG_INF), axis=-1)
    return jnp.einsum("grcj,jgv->grcv", p, v).reshape(nh, 2, pair)


def _first_tile(i, b: int, window):
    if window is None:
        return 0
    return jnp.maximum(i * b - (window - 1), 0) // b


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  b: int, window, scale: float):
    i = pl.program_id(1)
    j = pl.program_id(2)
    kb = _first_tile(i, b, window) + j

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(kb <= i)
    def _tile():
        q = q_ref[:]
        lane = lax.broadcasted_iota(jnp.int32, q.shape, 1) < LANE // 2
        zero = jnp.zeros_like(q)
        both = jnp.concatenate([jnp.where(lane, q, zero),
                                jnp.where(lane, zero, q)], axis=0)
        both = (both.astype(jnp.float32) * scale).astype(q.dtype)
        s = lax.dot_general(both, k_ref[:], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [2b, b]
        row = lax.broadcasted_iota(jnp.int32, s.shape, 0)
        qpos = i * b + jnp.where(row >= b, row - b, row)
        kpos = kb * b + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = kpos <= qpos
        if window is not None:
            keep = jnp.logical_and(keep, qpos - kpos < window)
        s = jnp.where(keep, s, NEG_INF)
        m = m_scr[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[:]
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(kb == i)       # the diagonal tile is a query tile's last
    def _finish():
        out = acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = out[:b].astype(o_ref.dtype)
        o_ref[1] = out[b:].astype(o_ref.dtype)


def flash_tiles(T: int, window=None) -> tuple:
    """(tile edge, key tiles a query tile visits) of the flash kernel."""
    b = min(T, _FLASH_BLOCK)
    n = T // b
    if window is not None:
        n = min(n, -(-(window - 1) // b) + 1)
    return b, n


def _flash_pallas(q, rows, n_kv, window):
    T, nh, pair = q.shape
    group = nh // n_kv
    b, n_kw = flash_tiles(T, window)

    def kv_map(lane0):
        def at(h, i, j):
            return (jnp.minimum(_first_tile(i, b, window) + j, i),
                    lane0 + h // group)
        return at

    out = pl.pallas_call(
        functools.partial(_flash_kernel, b=b, window=window,
                          scale=(pair // 2) ** -0.5),
        name=("diff_full_flash_fwd" if window is None
              else "diff_window_flash_fwd"),
        grid=(nh, T // b, n_kw),
        in_specs=[pl.BlockSpec((b, LANE), lambda h, i, j: (i, h)),
                  pl.BlockSpec((b, LANE), kv_map(0)),
                  pl.BlockSpec((b, LANE), kv_map(n_kv))],
        out_specs=pl.BlockSpec((2, b, LANE), lambda h, i, j: (0, i, h)),
        out_shape=jax.ShapeDtypeStruct((2, T, nh * LANE), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2 * b, 1), jnp.float32),
                        pltpu.VMEM((2 * b, 1), jnp.float32),
                        pltpu.VMEM((2 * b, LANE), jnp.float32)],
        interpret=pallas_interpret(),
    )(q.reshape(T, nh * pair), rows, rows)
    return out.reshape(2, T, nh, pair).transpose(1, 2, 0, 3)


def prefill_attention(q, rows, n_kv: int, window=None):
    """Causal attention of one prompt: q [T, nh, 2·dh], rows [T, 2·kw] (the
    prompt's own cache rows) → both components' outputs [T, nh, 2, 2·dh]
    float32.  Pad positions lie after every real one, so the causal mask
    alone keeps them out of every real row."""
    T = q.shape[0]
    b, _ = flash_tiles(T, window)
    if q.shape[-1] != LANE or T % b or b % 8:
        _obs_stats.scope("attn").counter("diff_prefill_fallbacks").inc()
        return prefill_attention_xla(q, rows, n_kv, window)
    return _flash_pallas(q, rows, n_kv, window)


__all__ = ["decode_attention", "decode_attention_xla", "prefill_attention",
           "prefill_attention_xla", "row_attention", "flash_tiles", "visible",
           "LANE"]
