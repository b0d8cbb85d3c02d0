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
- :func:`prefill_attention` — a prompt's causal flash attention
  (``gqa_flash_fwd``): a grid step is one query head's tile against one tile
  of its K/V head's rows, tiles above the diagonal are neither fetched nor
  computed.  The XLA fallback builds the dense masked scores and counts into
  ``attn.gqa_prefill_fallbacks``.
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
from .diffattn import paged_walk

NEG_INF = -1e30
LANE = 128
_FLASH_BLOCK = 256


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


def _decode_pallas(q, pool, block_tables, context_lens, layer, n_kv):
    S, nh, dh = q.shape
    group = nh // n_kv
    rows = (q.astype(jnp.float32) * dh ** -0.5).reshape(S, n_kv, group, dh)
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, -group % 8), (0, 0))
                   ).astype(pool.dtype)
    out = paged_walk(rows, pool, block_tables, context_lens, layer, n_kv,
                     "gqa_paged_decode_attn")
    return out[:, :, :group].reshape(S, nh, dh)


def decode_attention(q, pool, block_tables, context_lens, layer, n_kv: int):
    """q [S, nh, dh], pool [L, NB, bs, 2·kw] (all of it, as it lies),
    block_tables [S, MB] int32, context_lens [S] int32 (at least 1), layer
    an int or a traced scalar → [S, nh, dh] float32."""
    if q.shape[-1] != LANE:
        _obs_stats.scope("attn").counter("gqa_decode_fallbacks").inc()
        return decode_attention_xla(q, pool, block_tables, context_lens,
                                    layer, n_kv)
    return _decode_pallas(q, pool, block_tables, context_lens, layer, n_kv)


def prefill_attention_xla(q, rows, n_kv: int):
    T, nh, dh = q.shape
    k, v = _split_rows(rows, n_kv)
    s = jnp.einsum("tgrd,jgd->grtj", _split_q(q, n_kv), k) * dh ** -0.5
    t = jnp.arange(T)
    p = jax.nn.softmax(jnp.where(t[:, None] >= t[None, :], s, NEG_INF),
                       axis=-1)
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


__all__ = ["decode_attention", "decode_attention_xla", "prefill_attention",
           "prefill_attention_xla", "flash_tile", "LANE"]
