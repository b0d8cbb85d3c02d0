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
  are scanned).  The Pallas kernel (:func:`paged_walk`, which
  ``kernels/gqa.py`` calls with its own query rows) takes one grid step a
  slot and walks the slot's LIVE blocks — ``ceil(context / bs)`` of its
  table, read from the prefetched lengths — in chunks of ``_CHUNK_BLOCKS``
  blocks fetched by explicit async copies, block by block; nothing past the
  frontier is copied and nothing past the table read.  The next fetch is
  always in flight: a slot's next chunk, or on its last chunk the next
  slot's first, so a slot whose context is one chunk (every ring) hides its
  fetch too.  A K/V head's tile is read once for the ``2·group`` queries
  that share it.  Nothing in it knows a position: a *window ring* (a
  slot's last W rows at ``position mod W``) is the same call with the
  slot's own blocks as its table and ``min(context, W)`` as its length —
  softmax does not care in which order the rows lie.  ``name`` names the call
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
# copies a trip of the loop that starts a chunk's: eight descriptors'
# address arithmetic packs where one a trip serialises (PERF.md §6, PR 37)
_COPY_UNROLL = 8
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


def walk_schedule(bt_ref, cl_ref, ly_ref, pool_ref, buf, sem, *, bs: int,
                  chunk: int, max_blocks: int,
                  whole_chunks_unrolled: bool = False):
    """The copy schedule of a paged decode walk on a grid of slots — what
    :func:`_decode_kernel` here and ``kernels/mla.py``'s close over; the
    chunk's arithmetic is each kernel's own.  The pool stays in HBM, whole
    (``memory_space=pl.ANY``); ``buf`` is the double buffer ``[2, chunk·bs,
    width]``, ``sem`` one DMA semaphore a half, ``ly_ref`` the pool's layer
    (prefetched with the tables; in every copy's source index).  Returns

    - ``live_blocks(slot)``: ``ceil(context_len / bs)`` of the slot's table,
      one for an idle slot — a table entry past the frontier is not copied,
      none past the table is read;
    - ``start(slot, c, half)``: start the copies of chunk ``c`` of ``slot``,
      its live blocks, block ``b`` to rows ``b·bs`` of ``buf[half]``;
    - ``wait(n, half)``: wait for the ``n`` blocks started into ``half``;
    - ``start_ahead(s, c, n_chunks, half)``: the look-ahead rule — while
      chunk ``c`` of slot ``s`` (in ``half``) is waited for and computed, the
      slot's next chunk is in flight into the other half, and on its LAST
      chunk the next slot's first (none after the last slot's last).

    What costs here is the scalar core's work a copy, which no vector work
    hides across a loop's edge (PERF.md §6, PR 37): a chunk's copies are
    started ``_COPY_UNROLL`` a trip and signal ONE semaphore a half, which
    counts bytes — the wait is one descriptor a set bit of the chunk's block
    count, six at most, not one a block.  ``whole_chunks_unrolled`` (the
    latent pool's 20 KB blocks, PERF.md §6, PR 62): a chunk whose every block
    is live is started as straight-line code under one test of the half, so a
    copy's place in the buffer is a constant — 11–13 instruction bundles a
    copy where the loop's take 26 (the v5e's compiler, the chip described and
    not attached), 14.5 ns where they took 22 (on the chip)."""
    n_slots = pl.num_programs(0)
    layer = ly_ref[0]

    def live_blocks(slot):
        return jnp.clip((cl_ref[slot] + bs - 1) // bs, 1, max_blocks)

    def start_some(slot, first, n, half):
        def one(b):
            pltpu.make_async_copy(
                pool_ref.at[layer, bt_ref[slot, first + b]],
                buf.at[half, pl.ds(pl.multiple_of(b * bs, bs), bs)],
                sem.at[half]).start()

        def group(g, carry):
            for u in range(_COPY_UNROLL):
                one(g * _COPY_UNROLL + u)
            return carry

        def single(b, carry):
            one(b)
            return carry

        whole = n // _COPY_UNROLL
        lax.fori_loop(0, whole, group, 0)
        lax.fori_loop(whole * _COPY_UNROLL, n, single, 0)

    def start_whole(slot, first, half):
        """A whole chunk's copies as straight-line code, a copy's place in
        the buffer a constant: the half is decided once, not a copy."""
        for h in range(2):
            @pl.when(half == h)
            def _(h=h):
                for b in range(chunk):
                    pltpu.make_async_copy(
                        pool_ref.at[layer, bt_ref[slot, first + b]],
                        buf.at[h, pl.ds(b * bs, bs)], sem.at[h]).start()

    def start(slot, c, half):
        first = c * chunk
        n = jnp.minimum(chunk, live_blocks(slot) - first)
        if not whole_chunks_unrolled:
            start_some(slot, first, n, half)
            return

        @pl.when(n == chunk)
        def _whole():
            start_whole(slot, first, half)

        @pl.when(n < chunk)
        def _some():
            start_some(slot, first, n, half)

    def wait(n, half):
        k = 1
        while k <= chunk:
            @pl.when((n & k) != 0)
            def _(k=k):
                part = buf.at[half, pl.ds(0, k * bs)]
                pltpu.make_async_copy(part, part, sem.at[half]).wait()
            k *= 2

    def start_ahead(s, c, n_chunks, half):
        last = c + 1 == n_chunks

        @pl.when(jnp.logical_or(jnp.logical_not(last), s + 1 < n_slots))
        def _ahead():
            start(jnp.where(last, jnp.minimum(s + 1, n_slots - 1), s),
                  jnp.where(last, 0, c + 1), 1 - half)

    return live_blocks, start, wait, start_ahead


def _decode_kernel(bt_ref, cl_ref, ly_ref, q_ref, pool_ref, o_ref, buf, sem,
                   half_scr, *, bs: int, chunk: int, max_blocks: int,
                   n_kv: int, kw: int):
    """Grid (S,): one grid step a slot, its LIVE blocks fetched in chunks of
    ``chunk`` blocks into a double buffer by :func:`walk_schedule`, the layer
    (``ly_ref``, prefetched with the tables) in the copy's source index.

    The next fetch is always in flight: before the kernel waits for a chunk
    it starts the slot's next one into the other half, and on a slot's LAST
    chunk the next slot's first (buffer, semaphores and ``half_scr`` persist
    over the sequential grid).  ``half_scr`` carries which half that was from
    one grid step to the next, so every start is waited for exactly once, by
    the slot that computes it.

    A chunk is computed whole, as one K/V head's key tile against its QR
    query rows on the MXU; the running max, sum and accumulator of the online
    softmax are the chunk loop's carry (kept in scratch they serialised the
    heads).  Rows of a ragged last chunk past the frontier's block hold what
    an earlier slot left there: their scores are masked by position, and
    their value lanes are zeroed before the product (``0 x NaN`` is NaN)."""
    s = pl.program_id(0)
    span = chunk * bs
    QR = q_ref.shape[2]
    live_blocks, start, wait, start_ahead = walk_schedule(
        bt_ref, cl_ref, ly_ref, pool_ref, buf, sem, bs=bs, chunk=chunk,
        max_blocks=max_blocks)

    @pl.when(s == 0)
    def _first():
        half_scr[0] = 0
        start(0, 0, 0)

    first_half = half_scr[0]
    cl = cl_ref[s]
    n_live = live_blocks(s)
    n_chunks = (n_live + chunk - 1) // chunk

    def chunk_step(c, carry):
        half = (first_half + c) % 2
        start_ahead(s, c, n_chunks, half)
        blocks = jnp.minimum(chunk, n_live - c * chunk)
        wait(blocks, half)

        def zero(b, carry):
            buf[half, pl.ds(pl.multiple_of(b * bs, bs), bs), pl.ds(kw, kw)] \
                = jnp.zeros((bs, kw), buf.dtype)
            return carry

        lax.fori_loop(blocks, chunk, zero, 0)
        pos = c * span + lax.broadcasted_iota(jnp.int32, (QR, span), 1)
        out = []
        for g, (m, l, acc) in enumerate(carry):
            k = buf[half, :, pl.ds(g * LANE, LANE)]             # [span, 128]
            v = buf[half, :, pl.ds(kw + g * LANE, LANE)]
            sc = lax.dot_general(q_ref[0, g], k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            sc = jnp.where(pos < cl, sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(m - m_new)
            out.append((m_new,
                        l * alpha + jnp.sum(p, axis=-1, keepdims=True),
                        acc * alpha + jnp.dot(
                            p.astype(v.dtype), v,
                            preferred_element_type=jnp.float32)))
        return tuple(out)

    heads = lax.fori_loop(0, n_chunks, chunk_step, tuple(
        (jnp.full((QR, 1), NEG_INF, jnp.float32),
         jnp.zeros((QR, 1), jnp.float32),
         jnp.zeros((QR, LANE), jnp.float32)) for _ in range(n_kv)))
    for g, (_, l, acc) in enumerate(heads):
        o_ref[0, g] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    half_scr[0] = (first_half + n_chunks) % 2


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


@functools.partial(jax.jit, static_argnames=("n_kv", "name", "chunk",
                                             "interpret"))
def _walk_call(rows, pool, bt, cl, layer, *, n_kv, name, chunk, interpret):
    """``layer`` is an int32 scalar, prefetched with the tables, and the
    call is a jitted function of its own: every call of one shape and name —
    the scanned layers, and an unscanned layer beside them — shares ONE
    trace and ONE lowering of the kernel."""
    S = rows.shape[0]
    QR = rows.shape[2]
    bs, width = pool.shape[2], pool.shape[3]
    spec = pl.BlockSpec((1, n_kv, QR, LANE),
                        lambda s, bt, cl, ly: (s, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs, chunk=chunk,
                          max_blocks=bt.shape[1], n_kv=n_kv, kw=width // 2),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[spec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=spec,
            scratch_shapes=[pltpu.VMEM((2, chunk * bs, width), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct(rows.shape, jnp.float32),
        interpret=interpret,
    )(bt, cl, layer.reshape(1), rows, pool)


def paged_walk(rows, pool, block_tables, context_lens, layer, n_kv: int,
               name: str):
    """The paged decode walk under ``name``: query rows [S, n_kv, QR, 128]
    (scaled, in the pool's dtype; a K/V head's QR rows share its key tile)
    against each slot's live rows of ``pool[layer]`` → [S, n_kv, QR, 128]
    float32, one softmax a row.  ``kernels/gqa.py`` calls it with a group's
    queries as the rows."""
    MB = block_tables.shape[1]
    chunk = min(_CHUNK_BLOCKS, MB)
    return _walk_call(rows, pool, block_tables.astype(jnp.int32),
                      context_lens.astype(jnp.int32),
                      jnp.asarray(layer, jnp.int32), n_kv=n_kv, name=name,
                      chunk=chunk, interpret=pallas_interpret())


def _decode_pallas(q, pool, block_tables, context_lens, layer, n_kv, name):
    S, nh, pair = q.shape
    qs = (q.astype(jnp.float32) * (pair // 2) ** -0.5)
    out = paged_walk(_component_rows(qs, n_kv, pool.dtype), pool,
                     block_tables, context_lens, layer, n_kv, name)
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


__all__ = ["decode_attention", "decode_attention_xla", "paged_walk",
           "walk_schedule", "prefill_attention", "prefill_attention_xla",
           "row_attention",
           "flash_tiles", "visible", "LANE"]
