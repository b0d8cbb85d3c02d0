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
- :func:`group_prefill_attention` — a prompt's causal flash attention with
  an optional window (key ``j`` visible to query ``t`` iff ``0 ≤ t − j <
  window``) and the GROUP as the unit: a grid step takes one K/V head's key
  tile against the query tiles of ALL its ``group`` query heads, stacked
  (once a query tile, in VMEM) as the rows of one product, so a K/V tile is
  fetched once a group and not once a query head.  Scores are in exp2 units
  (``dh**-0.5 · log2 e`` folded into the one multiply the queries get).  The
  grid's last axis covers only the key tiles a query tile's window reaches;
  tiles left of it and above the diagonal are neither fetched nor computed,
  and only the tiles the diagonal or the window's edge crosses build a mask
  (``gqa_window_flash_fwd``; with no window ``gqa_group_flash_fwd``).  With
  the prompt's real ``length`` (a prefetched scalar) the query tiles that
  hold only padding fetch nothing, compute nothing and come back as zeros.
  The XLA fallback builds the dense masked scores and counts into
  ``attn.gqa_window_prefill_fallbacks``.
- :func:`prefill_attention` — the same kernel with no window under the name
  ``gqa_flash_fwd`` (``decode/falcon_h1.py``'s group of five); its fallback
  counts into ``attn.gqa_prefill_fallbacks``.

The plan of tiles (:func:`flash_plan`): query tiles of 256 rows, key tiles of
the widest multiple of 256 up to 1,024 that divides the prompt's rung,
whatever the window.  The kernel alone on one v5e, µs a call (PR 46; bf16
operands, float32 out, 20 calls back to back, best of three; ``parent`` is
square 256 tiles with ``exp`` and every tile computed; then query rows x key
columns, without a ``length`` / with one of 0.87 ``T``; ``% peak`` is the
256 x 1,024 plan's share of 197 TFLOP/s over the real (query, visible key)
pairs)::

        T  window  parent      256x256      256x512     256x1024     512x1024  % peak
    group 7 of 128 (28 query heads over 4 K/V heads)
     2048       -     996      899/743      589/508      518/461      500/499   29/25
     2048    4096     996      901/749      592/508      518/459      504/508   29/25
     6144       -    7281    6460/5163    3658/2980    2768/2303    2620/2329   50/45
     6144    4096    6625    5866/4898    3402/2874    2679/2284    2557/2302   46/43
    12288       -   28901  25818/20660  14444/11771   10530/8720    9890/8179   52/48
    12288    4096   17225  15330/13407    9193/8145    7391/6600    7071/6331   41/39
    pairs of 64 (32 over 8; a 64-deep contraction widened to 128: ceiling 50%)
     3072       -    2141    2184/1928    1535/1384    1182/1089    1143/1142   17/14
    12288       -   28705  29525/23844  18851/15462  12690/10684  11950/10027   25/22
    group 5 of 128 (10 over 2; parent: one query head a grid step)
     1024       -     186      198/204      204/190      184/194      188/188     7/5
     2048       -     472      348/297      265/235      200/203      199/194   27/20
     3072       -     952      690/603      501/444      341/310      325/327   36/30

A group of 16 (128 query heads over 8 K/V heads of 128; PR 59, the kernel
alone on one v5e, 10 calls back to back, best of three; without a ``length``
/ with one of 0.87 ``T``; ``% peak`` the 256 x 1,024 plan's) keeps the plan:
4,096 stacked query rows a product compile under the 64 MB of VMEM and are
the fastest of the five read — fewer query rows (128, 64) lose 5–7%, a
narrower key tile 20–50%; every row within 1.2e-2 of its own scale::

        T  window      64x1024      128x512     128x1024      256x512     256x1024  % peak
     4096       -    7433/6465    9995/8369    7318/6387    8444/7151    6958/6095   40/35
     4096    4096    7447/6478   10021/8396    7329/6394    8472/7155    6969/6103   40/35
     8192       -  22865/19070  33333/27077  22347/18587  27579/22460  21041/17520   53/48
     8192    4096  19608/17157  26778/23165  19173/16784  22391/19468  18175/15952   46/43

Its walks (32 slots of 1–8.8 k rows of 4,096 B, sixteen query rows a K/V
head): the pool's 0.67 GB in 1,019 µs (659 GB/s, 80% of the HBM peak), the
rings' 0.45 GB in 717 µs (634 GB/s).  A group past eight is NAMED in its
kernels (``gqa16_window_flash_fwd`` … ``gqa16_paged_decode_attn``:
:func:`_name`), as the pairs of 64 are.

The exp2 units and the query tile stacked once are the 256x256 column (x1.1);
the rest is the key tile's width: a grid step's update of the softmax state
(``acc`` [group·256, 128] read, scaled and written; ``m``, ``l``, ``alpha`` a
lane-padded column each) costs as much as 256 columns of scores, so four
times the columns a step is x2.4 at 12,288.  Under the 4,096 window two of
five visited tiles are edge tiles at 1,024 and the wide tile still wins
(7,391 against 9,193), so the window does not choose a narrower one.  Query
tiles of 128 rows are 2–5% slower than 256 at a 1,024-wide key tile, of 512
rows 4–6% faster at 12,288 and no better below, where a coarser tile skips
less padding (not taken); 2,048-wide key tiles compile and buy nothing
(10,614 at 12,288 with no window, 8,536 under it).  What is left is the
diagonal tile: up to three quarters of a 1,024-wide one is masked, 30% of
the computed elements at 2,048 and 7% at 12,288 (the last column).  Every
row of every result agreed with :func:`prefill_attention_xla` on the chip to
1.0e-2 of the row's own scale (2.9e-3 of the result's), pad tiles exactly
zero.

At ``dh = 64`` a lane tile of a row is a PAIR of K/V heads (``n_kv`` even),
and the walk and the flash forward run as they are over
``n_kv / 2`` tiles: a pair's ``2·group`` query heads are the rows that share
the tile, each widened to 128 lanes with the OTHER head's lanes zeroed
(:func:`_pair_rows` — ``kernels/diffattn.py``'s two components a head, here
two heads a tile), so a score is one 128-deep contraction with the key tile
and nothing is sliced inside a tile; of the value product's 128 lanes a row
keeps its own head's 64 (:func:`_unpair`).  The calls are named
``gqa64_paged_decode_attn`` / ``gqa64_ring_decode_attn`` /
``gqa64_group_flash_fwd`` / ``gqa64_window_flash_fwd`` / ``gqa64_flash_fwd``,
so a trace tells them from the 128-wide ones.
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
from .diffattn import paged_walk, visible

NEG_INF = -1e30
LANE = 128
HALF = LANE // 2
LOG2E = 1.4426950408889634
# the flash forward's tiles: query rows, and the widest key tile (the table
# in the module's docstring)
_Q_TILE = 256
_K_TILE = 1024
# the largest group whose kernels bear the plain names (:func:`_name`)
_NAMED_GROUP = 8


def tiled(dh: int, n_kv: int) -> bool:
    """Whether heads ``dh`` wide lie in whole lane tiles of a cached row: one
    a tile, or a pair of K/V heads a tile."""
    return dh == LANE or (dh == HALF and n_kv % 2 == 0)


def _name(name: str, dh: int, group: int = 1) -> str:
    """A kernel's name by its head layout: ``gqa64_`` a pair of 64-wide K/V
    heads a lane tile; ``gqa<group>_`` a group past eight (16: ``gqa16_`` —
    4,096 stacked query rows a product of the flash forward, two sublane
    tiles of query rows a K/V head in the walks), so that a trace, and a
    roofline that counts by the layout, tells it from a group of five or
    seven."""
    if dh != LANE:
        return name.replace("gqa_", "gqa64_", 1)
    if group > _NAMED_GROUP:
        return name.replace("gqa_", f"gqa{group}_", 1)
    return name


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
                     _name(name, dh, nh // n_kv))
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


def prefill_attention_xla(q, rows, n_kv: int, window=None, start: int = 0):
    """Dense masked attention; ``q`` may be the queries of positions ``start
    …`` alone (a long prompt's reference, a block of queries at a time)."""
    T, nh, dh = q.shape
    k, v = _split_rows(rows, n_kv)
    s = jnp.einsum("tgrd,jgd->grtj", _split_q(q, n_kv), k) * dh ** -0.5
    keep = visible(rows.shape[0], window)[start:start + T]
    p = jax.nn.softmax(jnp.where(keep, s, NEG_INF), axis=-1)
    return jnp.einsum("grtj,jgd->tgrd", p, v).reshape(T, nh, dh)


def _key_span(i, bq: int, bk: int, window, xp=jnp):
    """(first, last) key tile of ``bk`` columns that query tile ``i`` of
    ``bq`` rows reaches: the tile of its first query's oldest visible key and
    the tile of its last query's own position (the causal frontier)."""
    last = ((i + 1) * bq - 1) // bk
    if window is None:
        return 0, last
    return xp.maximum(i * bq - (window - 1), 0) // bk, last


def _last_real(n, bq: int):
    """The last query tile that holds a real position of a prompt of ``n``
    (tile 0 of an empty one)."""
    return (jnp.maximum(n, 1) - 1) // bq


def _key_tile(i, j, n, bq: int, bk: int, window):
    """The key tile grid step ``(i, j)`` holds, of a prompt of ``n`` real
    positions: from the first tile query tile ``i`` reaches to its last, where
    it stays (a tile past the frontier is never fetched); a query tile of
    padding stays where the last real tile's walk ended and fetches nothing."""
    first, last = _key_span(jnp.minimum(i, _last_real(n, bq)), bq, bk,
                            window)
    return jnp.where(i * bq < n, jnp.minimum(first + j, last), last)


def _group_flash_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, q_scr, m_scr,
                        l_scr, acc_scr, *, bq: int, bk: int, group: int,
                        window):
    """Grid (n_kv, T/bq, key tiles a query tile reaches): q_ref [bq,
    group·128] — the group's query heads side by side, already scaled into
    exp2 units — against one K/V head's key and value tiles [bk, 128].  The
    group's tiles are stacked (once a query tile, into ``q_scr``) as the
    ``group·bq`` rows of ONE product with the key tile.  A query tile at or
    past ``len_ref[0]`` holds only padding: it writes zeros and does no
    product."""
    i = pl.program_id(1)
    j = pl.program_id(2)
    first, last = _key_span(i, bq, bk, window)
    kb = first + j
    real = i * bq < len_ref[0]

    @pl.when(jnp.logical_and(j == 0, real))
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        for r in range(group):
            q_scr[r * bq:(r + 1) * bq] = q_ref[:, r * LANE:(r + 1) * LANE]

    @pl.when(jnp.logical_and(j == 0, jnp.logical_not(real)))
    def _pad():
        o_ref[:] = jnp.zeros_like(o_ref)

    def scores():
        return lax.dot_general(q_scr[:], k_ref[:], (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)

    def accumulate(s):
        m = m_scr[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[:]
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    # a tile wholly under the diagonal and wholly inside the window needs no
    # mask: its last key is no younger than the tile's first query, and its
    # first key is inside the window of the tile's last query
    inside = (kb + 1) * bk - 1 <= i * bq
    if window is not None:
        inside = jnp.logical_and(inside, (i + 1) * bq - 1 - kb * bk < window)
    live = jnp.logical_and(real, kb <= last)

    @pl.when(jnp.logical_and(live, inside))
    def _whole():
        accumulate(scores())

    @pl.when(jnp.logical_and(live, jnp.logical_not(inside)))
    def _edge():
        s = scores()
        qpos = i * bq + jnp.concatenate(
            [lax.broadcasted_iota(jnp.int32, (bq, bk), 0)] * group, axis=0)
        kpos = kb * bk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = kpos <= qpos
        if window is not None:
            keep = jnp.logical_and(keep, qpos - kpos < window)
        accumulate(jnp.where(keep, s, NEG_INF))

    # the tile of the causal frontier is a query tile's last
    @pl.when(jnp.logical_and(real, kb == last))
    def _finish():
        out = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)
        for r in range(group):
            o_ref[:, r * LANE:(r + 1) * LANE] = out[r * bq:(r + 1) * bq]


def flash_plan(T: int, window=None) -> tuple:
    """(query rows, key columns, key tiles a query tile visits) of the group
    flash forward for a prompt of ``T`` positions."""
    bq = min(T, _Q_TILE)
    bk = max(b for b in range(bq, max(_K_TILE, bq) + 1, bq) if T % b == 0) \
        if T % bq == 0 else bq
    i = np.arange(max(T // bq, 1))
    first, last = _key_span(i, bq, bk, window, np)
    return bq, bk, int((last - first).max()) + 1


def _group_flash_pallas(q, rows, n_kv, window, length, name):
    T, nh, dh = q.shape
    bq, bk, n_kw = flash_plan(T, window)
    # one multiply takes the scores into exp2 units: exp(x) lowers to
    # exp2(x · log2 e) on the vector unit, a multiply a score element
    qs = (q.astype(jnp.float32) * (dh ** -0.5 * LOG2E)).astype(rows.dtype)
    if dh != LANE:
        # a pair of K/V heads is one tile and its 2·group query heads the
        # group that shares it
        qs, n_kv, dh = _pair_rows(qs, n_kv), n_kv // 2, LANE
    group = nh // n_kv
    length = jnp.asarray(T if length is None else length, jnp.int32)

    def q_map(g, i, j, n):      # a tile of padding fetches no new tile
        return (jnp.minimum(i, _last_real(n[0], bq)), g)

    def kv_map(lane0):
        def at(g, i, j, n):
            return (_key_tile(i, j, n[0], bq, bk, window), lane0 + g)
        return at

    out = pl.pallas_call(
        functools.partial(_group_flash_kernel, bq=bq, bk=bk, group=group,
                          window=window),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_kv, T // bq, n_kw),
            in_specs=[pl.BlockSpec((bq, group * dh), q_map),
                      pl.BlockSpec((bk, dh), kv_map(0)),
                      pl.BlockSpec((bk, dh), kv_map(n_kv))],
            out_specs=pl.BlockSpec((bq, group * dh),
                                   lambda g, i, j, n: (i, g)),
            scratch_shapes=[pltpu.VMEM((group * bq, dh), rows.dtype),
                            pltpu.VMEM((group * bq, 1), jnp.float32),
                            pltpu.VMEM((group * bq, 1), jnp.float32),
                            pltpu.VMEM((group * bq, dh), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, nh * dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_interpret(),
    )(length.reshape(1), qs.reshape(T, nh * dh), rows, rows)
    if q.shape[-1] != LANE:
        return _unpair(out.reshape(T, n_kv, group, LANE), nh)
    return out.reshape(T, nh, dh)


def _flash(q, rows, n_kv, window, length, name, fallbacks):
    T, nh, dh = q.shape
    bq, bk, _ = flash_plan(T, window)
    if not tiled(dh, n_kv) or T % bk or bq % 8:
        _obs_stats.scope("attn").counter(fallbacks).inc()
        return prefill_attention_xla(q, rows, n_kv, window)
    return _group_flash_pallas(q, rows, n_kv, window, length,
                               _name(name, dh, nh // n_kv))


def prefill_attention(q, rows, n_kv: int, length=None):
    """Causal attention of one prompt: q [T, nh, dh], rows [T, 2·kw] (the
    prompt's own cache rows) → [T, nh, dh] float32; ``length`` as
    :func:`group_prefill_attention`'s."""
    return _flash(q, rows, n_kv, None, length, "gqa_flash_fwd",
                  "gqa_prefill_fallbacks")


def group_prefill_attention(q, rows, n_kv: int, window=None, length=None):
    """Causal attention of one prompt with an optional window, a K/V head's
    tile fetched once for its whole group: q [T, nh, dh], rows [T, 2·kw] (the
    prompt's own cache rows) → [T, nh, dh] float32.  ``length`` (an int or a
    traced int32 scalar) is the prompt's real length: pad positions lie after
    every real one, so the causal mask keeps them out of every real row, and
    a query tile that holds only padding is not computed — its rows come
    back as zeros.  With no ``length`` every tile is computed."""
    return _flash(q, rows, n_kv, window, length,
                  "gqa_group_flash_fwd" if window is None
                  else "gqa_window_flash_fwd", "gqa_window_prefill_fallbacks")


__all__ = ["decode_attention", "decode_attention_xla",
           "ring_decode_attention", "prefill_attention",
           "prefill_attention_xla", "group_prefill_attention", "flash_plan",
           "tiled", "LANE"]
