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
  ``rank`` lanes: 16 or 32 query heads over ONE shared row, nothing expanded.
  The Pallas kernel (``mla_paged_decode_attn``) has the walk of
  ``kernels/diffattn.py`` (its ``walk_schedule``, shared; the chunk's
  arithmetic is this kernel's own): a grid of slots, one grid step a slot,
  which walks the slot's LIVE blocks — ``ceil(context / bs)`` of its table,
  read from the prefetched lengths, so nothing past the frontier is copied,
  nothing past the table read and no table padded — in chunks of
  ``_CHUNK_BLOCKS`` blocks, fetched block by block (a 16-token block is 20
  KB) by explicit async copies into a double buffer.  The next fetch is
  always in flight: a slot's next chunk, or on its last chunk the next
  slot's first, is started before the current one is waited for.  The
  running maximum, sum and accumulator of the online softmax are the chunk
  loop's carry.  The XLA fallback gathers the slot's whole table and counts
  into ``mla.decode_attn_fallbacks``.

  The kernel alone, ms a call of 64 slots and one layer on a v5e (PERF.md §6,
  PR 62; ``chip_smoke.py phase_latent_walk``: N calls in one program, the
  difference of two lengths; contexts log-normal about the mean; "at the
  peak" is 576 numbers a live token over 819 GB/s, what
  ``mla_decode_attn_roofline`` counts — the 640-lane row's ceiling is 90%):

  ==========================================  =======  =======  =======  =======
  heads, mean live tokens a slot              32, 7 k  32, 2 k  16, 7 k  16, 2 k
  ==========================================  =======  =======  =======  =======
  at the HBM peak                               0.572    0.187    0.658    0.203
  (i) before PR 62: grid (slots, table's
  chunks), every chunk's 32 copies started
  and all waited for, the state in scratch      1.795    0.655    1.975    0.672
  the new schedule, chunks of 32, every
  copy started from the loop                    1.087    0.387    1.206    0.402
  ... whole chunks' copies straight-line        0.890    0.330    0.991    0.341
  (ii) as it is: ... and chunks of 64           0.774    0.305    0.866    0.317
  (iii) as it is, the copies taken out          0.415    0.173    0.445    0.170
  chunks of 128                                 0.795    0.338    0.891    0.362
  ==========================================  =======  =======  =======  =======

  (ii) is (iii) plus 14.5 ns a block: the copies' transfer is hidden, what a
  call pays is the products (vector loads of the chunk, twice: as keys and
  as values; 16 and 32 heads cost the same) PLUS the scalar core's work to
  start each copy, which the products do not hide: put into one block of
  straight-line code with the products (the halves two allocations, so that
  nothing orders them), the v5e's compiler still schedules the 64 starts
  behind the products, not among them (its bundles read off a compile for
  the described chip; PERF.md §6, PR 62).
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
from . import diffattn as _diff
from ..observability import stats as _obs_stats
from ..platform import pallas_interpret

NEG_INF = _attn.NEG_INF
LANE = 128
# blocks a chunk: 64 x 16 tokens = 1,024 rows of 640 bf16 lanes, 1.3 MB, twice
# (the double buffer).  By the table above: at 32 the products cost a quarter
# more a row (0.516 against 0.415 ms with the copies out); at 128 a slot's
# ragged last chunk, computed whole and started from the loop, costs more
# than the products give back
_CHUNK_BLOCKS = 64


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


def _decode_kernel(bt_ref, cl_ref, ly_ref, q_ref, pool_ref, o_ref, buf, sem,
                   half_scr, *, bs: int, chunk: int, max_blocks: int,
                   rank: int):
    """Grid (S,): one grid step a slot, its LIVE blocks fetched in chunks of
    ``chunk`` blocks into the double buffer by ``diffattn.walk_schedule`` —
    the slot's next chunk, or on its last the next slot's first, started
    before the current one is waited for (``half_scr`` carries the parity
    over the sequential grid).  A chunk is computed whole: every head's
    query against the chunk's rows, which are key and value at once.  The
    running max, sum and accumulator are the chunk loop's carry.  Rows of a
    ragged last chunk past the frontier's block hold what an earlier slot
    left there: their scores are masked by position, and the rows are zeroed
    before the value product (``0 x NaN`` is NaN)."""
    s = pl.program_id(0)
    span = chunk * bs
    H, W = q_ref.shape[1], q_ref.shape[2]
    live_blocks, start, wait, start_ahead = _diff.walk_schedule(
        bt_ref, cl_ref, ly_ref, pool_ref, buf, sem, bs=bs, chunk=chunk,
        max_blocks=max_blocks, whole_chunks_unrolled=True)

    @pl.when(s == 0)
    def _first():
        half_scr[0] = 0
        start(0, 0, 0)

    first_half = half_scr[0]
    cl = cl_ref[s]
    n_live = live_blocks(s)
    n_chunks = (n_live + chunk - 1) // chunk
    q = q_ref[0]

    def chunk_step(c, carry):
        m, l, acc = carry
        half = (first_half + c) % 2
        start_ahead(s, c, n_chunks, half)
        blocks = jnp.minimum(chunk, n_live - c * chunk)
        wait(blocks, half)

        def zero(b, carry):
            buf[half, pl.ds(pl.multiple_of(b * bs, bs), bs)] = jnp.zeros(
                (bs, W), buf.dtype)
            return carry

        lax.fori_loop(blocks, chunk, zero, 0)
        rows = buf[half]                                # [span, W]
        sc = lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        pos = c * span + lax.broadcasted_iota(jnp.int32, (H, span), 1)
        sc = jnp.where(pos < cl, sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        return (m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + jnp.dot(p.astype(rows.dtype), rows[:, :rank],
                                      preferred_element_type=jnp.float32))

    _, l, acc = lax.fori_loop(0, n_chunks, chunk_step, (
        jnp.full((H, 1), NEG_INF, jnp.float32),
        jnp.zeros((H, 1), jnp.float32),
        jnp.zeros((H, rank), jnp.float32)))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    half_scr[0] = (first_half + n_chunks) % 2


@functools.partial(jax.jit, static_argnames=("rank", "chunk", "interpret"))
def _walk_call(q, pool, bt, cl, layer, *, rank, chunk, interpret):
    """``layer`` is an int32 scalar, prefetched with the tables, and the
    call is a jitted function of its own: the scanned layers and an unscanned
    one beside them share ONE trace and ONE lowering of the kernel."""
    S, H, W = q.shape
    bs = pool.shape[2]
    return pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs, chunk=chunk,
                          max_blocks=bt.shape[1], rank=rank),
        name="mla_paged_decode_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,    # tables, lengths, the pool's layer
            grid=(S,),
            in_specs=[pl.BlockSpec((1, H, W), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, rank), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, chunk * bs, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, rank), jnp.float32),
        interpret=interpret,
    )(bt, cl, layer.reshape(1), q, pool)


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
    return _walk_call(qs, pool, block_tables.astype(jnp.int32),
                      context_lens.astype(jnp.int32),
                      jnp.asarray(layer, jnp.int32), rank=rank,
                      chunk=min(_CHUNK_BLOCKS, block_tables.shape[1]),
                      interpret=interpret)


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
