"""Latent (MLA) attention over a paged latent pool.

The pool holds, a token a layer, one row ``[c (rank) | k_pe (rope) | 0]``
padded to a whole number of 128-lane tiles (DeepSeek-V2-Lite: 512 + 64 →
640), as ``[L, NB, bs, W]``: a block's ``[bs, W]`` fills whole tiles, so the
array keeps its row-major layout, scatters update it in place, and the
kernel is handed the WHOLE pool and the layer as a prefetched scalar (PR 28's
finding for the K/V pools, kept here; a program that scans over its layers
has no constant to give).

- :func:`decode_attention` — the absorbed path.  Every head's query is
  already in the row's coordinates (``[W_kvb,h^T q_nope | q_pe | 0]``), so a
  score is one dot with the cached row and the value is the row's first
  ``rank`` lanes: 16 query heads over ONE shared row, nothing expanded.  The
  Pallas kernel (``mla_paged_decode_attn``) walks a slot's context in chunks
  of ``_CHUNK_BLOCKS`` blocks; a chunk's blocks are fetched from HBM by
  explicit async copies (a 16-token block is 20 KB: one grid step a block
  would cost more in step overhead than in bytes), chunks past a slot's
  context are skipped.  The XLA fallback gathers the slot's whole table and
  counts into ``mla.decode_attn_fallbacks``.
- :func:`prefill_attention` — the expanded path of a prompt: causal flash
  attention with 192-wide scores and 128-wide values
  (``kernels/attention.py``'s flash forward, which takes a narrower ``v``),
  so no ``[T, T]`` score array exists.  The XLA fallback
  (``attention.mha_xla``) counts into ``mla.prefill_attn_fallbacks``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import attention as _attn
from ..observability import stats as _obs_stats
from ..platform import pallas_interpret

NEG_INF = _attn.NEG_INF
LANE = 128
# blocks a grid step: 32 x 16 tokens = 512 rows of 640 bf16 lanes, 640 KB
_CHUNK_BLOCKS = 32


def row_width(rank: int, rope: int) -> int:
    """Lanes of a pool row: ``rank + rope`` rounded up to whole lane tiles."""
    return -(-(rank + rope) // LANE) * LANE


def decode_attention_xla(q, pool, block_tables, context_lens, layer,
                         rank: int, sm_scale: float):
    S, H, W = q.shape
    MB = block_tables.shape[1]
    bs = pool.shape[2]
    rows = lax.dynamic_index_in_dim(pool, layer, keepdims=False)[
        block_tables].reshape(S, MB * bs, W)
    s = jnp.einsum("shw,stw->sht", q.astype(jnp.float32),
                   rows.astype(jnp.float32)) * sm_scale
    pos = jnp.arange(MB * bs, dtype=jnp.int32)
    s = jnp.where(pos[None, None, :] < context_lens[:, None, None], s,
                  NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("sht,str->shr", p,
                      rows[..., :rank].astype(jnp.float32))


def _decode_kernel(bt_ref, cl_ref, layer_ref, q_ref, pool_ref, o_ref, buf,
                   sem, m_scr, l_scr, acc_scr, *, bs: int, chunk: int,
                   n_chunks: int, rank: int):
    layer = layer_ref[0]
    s = pl.program_id(0)
    j = pl.program_id(1)
    cl = cl_ref[s]
    live = (cl + chunk * bs - 1) // (chunk * bs)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j < live)
    def _chunk():
        copies = []
        for b in range(chunk):
            cp = pltpu.make_async_copy(
                pool_ref.at[layer, bt_ref[s, j * chunk + b]],
                buf.at[pl.ds(b * bs, bs)], sem.at[b])
            cp.start()
            copies.append(cp)
        for cp in copies:
            cp.wait()
        rows = buf[:]                                   # [chunk * bs, W]
        sc = lax.dot_general(q_ref[0], rows, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        pos = j * chunk * bs + lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(pos < cl, sc, NEG_INF)
        m = m_scr[:]
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(rows.dtype), rows[:, :rank],
            preferred_element_type=jnp.float32)

    @pl.when(j == n_chunks - 1)
    def _finish():
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)
                    ).astype(o_ref.dtype)


def _decode_pallas(q, pool, block_tables, context_lens, layer, rank,
                   interpret):
    S, H, W = q.shape
    bs = pool.shape[2]
    MB = block_tables.shape[1]
    chunk = min(_CHUNK_BLOCKS, MB)
    n_chunks = -(-MB // chunk)
    bt = block_tables.astype(jnp.int32)
    if n_chunks * chunk != MB:      # a ragged last chunk reads trash block 0
        bt = jnp.pad(bt, ((0, 0), (0, n_chunks * chunk - MB)))
    kernel = functools.partial(_decode_kernel, bs=bs, chunk=chunk,
                               n_chunks=n_chunks, rank=rank)
    return pl.pallas_call(
        kernel,
        name="mla_paged_decode_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,    # tables, lengths, the pool's layer
            grid=(S, n_chunks),
            in_specs=[pl.BlockSpec((1, H, W), lambda s, j, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, rank), lambda s, j, *_: (s, 0, 0)),
            scratch_shapes=[pltpu.VMEM((chunk * bs, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((chunk,)),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, rank), jnp.float32),
        interpret=interpret,
    )(bt, context_lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, pool)


def decode_attention(q, pool, block_tables, context_lens, layer, rank: int,
                     sm_scale: float, impl=None, interpret=None):
    """q [S, H, W] absorbed queries in the pool row's coordinates (lanes past
    ``rank + rope`` zero), pool [L, NB, bs, W] (all of it, as it lies),
    block_tables [S, MB] int32, context_lens [S] int32, layer an int or a
    traced scalar (a program that scans over its layers) → ``sum_s p_s c_s``
    [S, H, rank] float32 (the caller applies ``W_kvb``'s value part)."""
    if impl == "xla":
        _obs_stats.scope("mla").counter("decode_attn_fallbacks").inc()
        return decode_attention_xla(q, pool, block_tables, context_lens,
                                    layer, rank, sm_scale)
    if impl not in (None, "pallas"):
        raise ValueError(f"unknown decode attention impl {impl!r}")
    if interpret is None:
        interpret = pallas_interpret()
    # the scale rides the query: [S, H, W] is small beside the scores
    qs = (q.astype(jnp.float32) * sm_scale).astype(pool.dtype)
    return _decode_pallas(qs, pool, block_tables, context_lens, layer, rank,
                          interpret)


def prefill_attention(q, k, v, sm_scale: float, impl=None):
    """Causal attention of one prompt: q, k [H, T, Dk], v [H, T, Dv] →
    [H, T, Dv].  Padded positions lie after every real one, so the causal
    mask alone keeps them out of every real row."""
    if impl == "xla":
        _obs_stats.scope("mla").counter("prefill_attn_fallbacks").inc()
        return _attn.mha_xla(q[None], k[None], v[None], causal=True,
                             sm_scale=sm_scale)[0]
    if impl not in (None, "pallas"):
        raise ValueError(f"unknown prefill attention impl {impl!r}")
    return _attn.mha_pallas(q[None], k[None], v[None], causal=True,
                            sm_scale=sm_scale)[0]


__all__ = ["decode_attention", "decode_attention_xla", "prefill_attention",
           "row_width", "LANE"]
