"""Routed experts: top-k routing, a plan of rows made by counting and a grouped
gated unit (SwiGLU or ReGLU) over the experts — the serving form of a sparse
mixture, where every assignment is computed (no capacity, no token dropped).

- :func:`route_topk` — scores over all experts in float32 (``softmax``, or
  ``sigmoid``: an expert's own), the ``k`` largest (``lax.top_k`` keeps the
  lower index on a tie) — of ``score + bias`` where a selection bias is
  given, which chooses and does not weigh —, weights the chosen scores
  themselves, renormalised only when asked (over ``sum + eps``), times
  ``scale``.
- :func:`plan_groups` — the plan of rows: the valid tokens' assignments in
  expert order (a stable sort's: ties in the order ``t · K + k``), each
  expert's rows padded to a multiple of the row tile, so a tile of rows
  belongs to exactly one expert.  Made by COUNTING, not by sorting the
  assignments: an assignment's place among its expert's is the number of
  earlier assignments with its key — within a block of 256 every pair
  compared, before the block a one-hot over the experts summed a block and
  then over the blocks — so ``row_of`` is known where the assignment stands.
  The experts' counts, their padded runs and the tile → expert map are
  sums over comparisons with ``E`` entries; no table is indexed by a key
  and nothing is searched.  What is left is the ONE inverse map, the token
  of a row.  A decode step's plan (at most 1,024 assignments) compares every
  row with every assignment; a prefill's takes one ``lax.sort`` — of the
  ROWS: the assignments keyed by their rows together with, an expert, the
  ``tile − 1`` rows that can pad its run, so the sorted tokens ARE the map
  (``T·K + E·(tile − 1)`` keys, the plan's own length) and nothing is
  gathered or scattered after it.  Integers throughout; the cost does not
  depend on how the assignments fall.

  The plan alone on one v5e, µs of device time a plan (PR 52; 64 experts
  planned; forty plans in one program, so the host's dispatch is not in
  it; the parent's two ``argsort``s, three ``searchsorted``s and seven
  gathers → this one; uniform choices with a seventh of the tokens padding
  | every assignment on one expert | every token invalid)::

      a step, top-4 of 64               278 →  18 |   264 →  12 |   256 →  17
      a step, top-6 of 64               308 →  15 |   308 →  15 |   311 →  13
      a step, top-8 of 256, 64 held     317 →  17 |   314 →  15 |   312 →  14
      2,048 x 6                         980 →  42 |   973 →  44 |   976 →  44
      8,192 x 6                       2,409 → 115 | 2,442 → 122 | 2,413 → 118
      12,288 x 6                      3,270 → 184 | 3,271 → 180 | 3,274 → 186
      16,384 x 8 of 256, 64 held      5,165 → 254 | 5,174 → 258 | 5,171 → 256

  The inverse map the other ways (the whole plan, at 2,048 × 6 / 8,192 × 6 /
  16,384 × 8; the count with no inverse map reads 21–26 / 61–77 / 206–213):
  one sort of ``(key, iota)`` and a gather of [R] from it 186 / 548 / 1,354;
  a scatter of the tokens to their rows 82 / 305 / 843; this sort of the
  rows 42 / 115 / 254.  The count as a product with a triangular 0/1 matrix
  on the MXU (bf16 in, float32 out) read 37 / 106 / 246 where the
  comparisons read 42 / 119 / 275 in the same call: within a tenth, and not
  worth a float in an integer plan nor a [T·K, E] float32 temporary.
- :func:`grouped_glu` — ``(act(x Wg_e) * (x Wu_e)) Wd_e`` for every row tile
  against its expert's three matrices, ``act`` the gate's activation
  (:data:`ACTS`: ``silu`` — SwiGLU — or ``relu`` — ReGLU).  ONE Pallas kernel
  name a gate (``moe_grouped_swiglu`` / ``moe_grouped_reglu``) and two walks
  of the plan, chosen by the plan's tile alone (counted, when the call is
  lowered, in ``moe.grouped_<glu>_tile_walks`` / ``_expert_walks``):

  * *a decode step's plan* (``row_tile``: at most 128 tokens, 16-row tiles)
    is walked **a tile a grid step**: the scalar-prefetched tile→expert map
    is the weight index map, so an expert's matrices stream from HBM once
    while its tiles are consecutive and the tiles past the last real one
    repeat its indices (no copy, no compute).  Six rows an expert are ONE
    tile an expert: every grid step fetches the next expert's matrices under
    this one's, nothing waits, and the walk reads 92% of 819 GB/s — so the
    step keeps this walk to the character.
  * *a prefill's plan* (128-row tiles) is walked **an expert a grid step**
    (:func:`_expert_walk_kernel`): under the tile walk an expert's matrices
    (17.3 MB at 2048 x 1408, 21 µs of HBM) started to arrive only during the
    LAST tile of the expert before (12 µs of products), so three quarters of
    every fetch stood exposed.  Here the matrices are the STEP's blocks —
    the next expert's are in flight for the whole of this one's rows — and
    the rows stay in HBM: the kernel copies an expert's contiguous run in
    and its products out itself, a piece of up to :data:`_PIECE_TILES` tiles
    at a time through a double buffer, the next piece's rows (this expert's,
    or the next one's first) always in flight.  The products are the tile
    walk's own, 128 rows at a time: results are equal to the bit.

  In both, experts with no row are never read, and where the layers are
  scanned the matrices are handed over as the whole stack ``[layers, E, …]``
  with the layer's index: the map points at ``layer · E + expert`` and no
  layer's experts are sliced out of the stack.  The XLA fallback is three
  ``lax.ragged_dot`` over the same padded rows and counts into
  ``moe.grouped_swiglu_fallbacks`` / ``moe.grouped_reglu_fallbacks``.

  The kernel alone on one v5e, µs a call (PR 45; bf16, 64 experts, top-6,
  float32 rows out at [2048, 1408], bf16 at the others; plans of uniform
  routing, "skewed" 55 experts of 1–11 tiles; tile walk → expert walk, and
  the larger of FLOP ÷ 197 T and bytes ÷ 819 G over the computed rows)::

      plan (tiles)          [2048, 1408]       [2048, 1536]       [2560, 768]
      one expert (128)      1527 → 1526 (1439) 1659 → 1659 (1570) 1059 → 1062 ( 981)
      1,024 tokens ( 64)    1659 → 1666 (1475) 1737 → 1747 (1557) 1158 → 1166 (1024)
      2,048 tokens (128)    2532 → 1852 (1598) 2615 → 1876 (1639) 1762 → 1307 (1127)
      4,096 tokens (224)    3638 → 2653 (2518) 3816 → 2887 (2747) 2527 → 1837 (1717)
      8,192 tokens (422)    5915 → 4920 (4744) 6301 → 5359 (5175) 4093 → 3395 (3235)
      skewed 2,049 (133)    2378 → 2137 (1495) 2503 → 2237 (1631) 1653 → 1495 (1019)

  A 128-row product runs at 94% of the peak with no fetch to wait for (the
  one-expert row), so taller products buy nothing (256 rows: 1521; 512 rows
  spill: 2748) and pieces are only how far ahead rows are fetched: pieces of
  2 tiles read 3116 / 5402 at 4,096 / 8,192 (a second piece's rows queue
  behind the next expert's 17 MB), of 8 no better than 4.  One tile an
  expert (1,024 tokens) is all bytes under either walk.  What is left is
  the skewed row: one expert's fetch ahead is all VMEM's two buffers allow,
  so a one-tile expert still exposes half of the next one's fetch.
- :func:`combine` — ``sum_k w[t, k] * y[row of (t, k)]``: for a decode
  step's few tokens ONE gather of [T, K, D] summed over K, for a prefill's
  thousands (the plan's tile again) K gathers of [T, D], each weighed and
  added — [T, 6, D] pads six rows to a tile's eight and is copied once more
  to be reshaped, 1.3 ms a layer at 3,072 tokens beside the kernel's 2.0.
- :func:`routed_experts` — the three together, one function for a prefill's
  thousands of rows and a decode step's 64: ``sum_k w[t, k] * expert(x[t])``
  and the dispatch's load figures.  A model whose routing is known before
  the experts' input (the router reads an earlier activation) calls
  :func:`plan_groups` there and :func:`planned_experts` here.

**An expert too wide for VMEM** (:func:`f_block`; PR 59: Command A+'s 4,096
x 4,096 experts are 96 MB of bf16 each, and a v5e's VMEM holds neither two of
them nor one twice) is walked a block of its intermediate axis at a time —
``act(x Wg[:, f]) * (x Wu[:, f])`` against ``Wd[f, :]``, summed over the
blocks ``f``: the tile walk takes the blocks as a second grid axis and sums
them in a float32 scratch (:func:`_grouped_glu_fblock_kernel`), the expert
walk is called a block at a time (the block a prefetched scalar of its index
maps, one kernel for all of them) and the calls' float32 rows are summed in
XLA.  An expert that fits is walked whole, as before.

**A share of a layer.**  :func:`plan_groups` (and :func:`routed_experts`)
take ``first``: the stacks then hold experts ``first … first + held − 1`` of a
layer whose router is wider — what one chip of an expert-parallel deployment
holds.  The router's scores, its choice and its renormalisation stay over all
the experts; only assignments to held experts get a row, the walks' maps stay
the plan's (numbered from 0, as the stacks are), and what the experts held
elsewhere would add is left out.  Nothing stands in for the other chips.
:func:`plan_rows` bounds a share's plan by EVERY choice of the wider router
(a token may choose nothing but held experts), eight times what an eighth
share's rows are expected to be; a prefill that hands :func:`planned_experts`
a ``row_block`` (:func:`share_block_rows`) has the plan's rows gathered,
walked and weighed a block at a time, as many blocks as hold a real row — an
even router's plan is one block, and no assignment is capped or dropped.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import stats as _obs_stats
from ..platform import pallas_interpret

# one expert's three matrices (5.8 MB each at 2048 x 1408 bf16),
# double-buffered, pass the 16 MB default scoped-VMEM limit; a v5e has 128 MB
_VMEM_LIMIT = 100 * 1024 * 1024
_VMEM_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT,
                                    dimension_semantics=("arbitrary",))
# rows a tile: a decode step's few rows an expert take the smallest tile the
# dtype packs; a prefill's hundreds fill 128-row tiles
_PREFILL_TILE = 128
# the most VMEM one grid step's three weight blocks may take, double-buffered:
# an expert wider than that (4096 x 4096: 192 MB) is walked a block of its
# intermediate axis at a time
_WEIGHT_BLOCKS_BYTES = 64 * 1024 * 1024


def f_block(D: int, F: int, itemsize: int, matrices: int = 3) -> int:
    """Columns of an expert's intermediate axis a grid step holds: all ``F``
    where its ``matrices`` (three of a gated unit, two of an ungated one) fit
    VMEM twice over, else ``F`` halved until they do."""
    fb = F
    while 2 * matrices * D * fb * itemsize > _WEIGHT_BLOCKS_BYTES \
            and fb % 256 == 0:
        fb //= 2
    return fb


SCORES = {"softmax": functools.partial(jax.nn.softmax, axis=-1),
          "sigmoid": jax.nn.sigmoid}


def route_topk(logits, k: int, scale: float = 1.0, normalize: bool = False,
               score: str = "softmax", bias=None, eps: float = 0.0):
    """logits [T, E] (router outputs, any float), bias [E] or None (added to
    the scores for the choice alone) → (ids [T, k] int32, weights [T, k]
    f32).  Scores and weights are float32."""
    if score not in SCORES:
        raise ValueError(f"unknown router score {score!r}; one of "
                         f"{sorted(SCORES)}")
    s = SCORES[score](logits.astype(jnp.float32))
    if bias is None:
        chosen, ids = lax.top_k(s, k)
    else:
        _, ids = lax.top_k(s + bias.astype(jnp.float32), k)
        chosen = jnp.take_along_axis(s, ids, axis=-1)
    if normalize:
        total = jnp.sum(chosen, axis=-1, keepdims=True)
        chosen = chosen / (total + jnp.float32(eps) if eps else total)
    return ids.astype(jnp.int32), chosen * jnp.float32(scale)


class GroupPlan(NamedTuple):
    """Where every assignment's row lies (:func:`plan_groups`)."""

    row_token: jax.Array     # [R] int32: the token a row computes (T: none)
    row_of: jax.Array        # [T, K] int32: an assignment's row (R: none)
    tile_expert: jax.Array   # [R // tile] int32: expert of a row tile
    active_tiles: jax.Array  # [1] int32: tiles that hold a real row (>= 1)
    padded_sizes: jax.Array  # [E] int32: rows of each expert, padded
    load: jax.Array          # [3] int32: assignments, experts touched, the
    #                          largest load of one expert


def plan_rows(tokens: int, k: int, experts: int, tile: int) -> int:
    """Rows (a static bound) that the assignments of ``tokens`` tokens can
    need: every expert that has one pads its group by up to ``tile - 1``."""
    most = tokens * k
    rows = most + min(experts, most) * (tile - 1)
    return -(-rows // tile) * tile


# assignments a block of the running count (:func:`_plan_by_count`), and the
# most assignments whose rows are found by comparison, without a sort: a
# decode step's (``row_tile``: at most 128 tokens, of up to eight choices)
_COUNT_BLOCK = 256
_COMPARED_ASSIGNMENTS = 1024


def plan_groups(ids, valid, experts: int, tile: int, first=0) -> GroupPlan:
    """ids [T, K] int32 (chosen experts), valid [T] bool (False: the token is
    padding or its slot has no stream — it is routed nowhere and enters no
    count).

    ``first`` (an int or a traced scalar) makes the layer a SHARE of a wider
    one: the router chose among all of its experts, and the ``experts``
    planned here are ``first … first + experts − 1`` of them — the ones whose
    matrices this chip holds, which the plan numbers from 0 as the stacks do.
    An assignment to an expert held elsewhere gets no row and enters no
    count; its weight stays what the router gave it, so the sum over the
    shares is the whole layer's.  A layer held whole is the share from 0."""
    return _plan_by_count(ids, valid, jnp.asarray(first, jnp.int32),
                          experts=experts, tile=tile)


@functools.partial(jax.jit, static_argnames=("experts", "tile"))
def _plan_by_count(ids, valid, first, *, experts, tile):
    """:func:`plan_groups`' body, traced once for all the layers of a program
    (as :func:`_expert_walk` is).  The plan is a stable sort's — assignments
    in expert order, ties in the order ``t · K + k`` — made without sorting
    the assignments: an assignment's place among its expert's is a COUNT of
    the earlier ones with its key, so its row is known where it stands."""
    T, K = ids.shape
    N, E = T * K, experts
    R = plan_rows(T, K, E, tile)
    local = ids - first.astype(ids.dtype)
    key = jnp.where(valid[:, None] & (local >= 0) & (local < E),
                    local, E).reshape(N).astype(jnp.int32)
    held = key < E
    # the running count, a block of assignments at a time: how many of the
    # block's EARLIER assignments have this one's key (every pair of the
    # block compared), and how many of the blocks before have it (a one-hot
    # over the experts, summed a block, then over the blocks)
    B = -(-N // _COUNT_BLOCK)
    keyb = jnp.pad(key, (0, B * _COUNT_BLOCK - N),
                   constant_values=E).reshape(B, _COUNT_BLOCK)
    at = jnp.arange(_COUNT_BLOCK, dtype=jnp.int32)
    earlier = jnp.sum((keyb[:, :, None] == keyb[:, None, :])
                      & (at[:, None] < at[None, :]), axis=1,
                      dtype=jnp.int32)                              # [B, blk]
    one_hot = keyb[:, None, :] == jnp.arange(E, dtype=jnp.int32)[:, None]
    in_block = jnp.sum(one_hot, axis=2, dtype=jnp.int32)            # [B, E]
    counts = jnp.sum(in_block, axis=0, dtype=jnp.int32)             # [E]
    before = jnp.cumsum(in_block, axis=0, dtype=jnp.int32) - in_block
    padded = -(-counts // tile) * tile
    pend = jnp.cumsum(padded, dtype=jnp.int32)          # inclusive ends
    pstart = pend - padded
    # assignments → rows: the expert's first row, the count of the blocks
    # before and the count within the block; no table is indexed by a key
    row_flat = earlier + jnp.sum(
        jnp.where(one_hot, (before + pstart)[:, :, None], 0), axis=1,
        dtype=jnp.int32)
    row_flat = jnp.where(held, row_flat.reshape(-1)[:N], R)
    # rows → tokens, the one inverse map
    token = jnp.arange(N, dtype=jnp.int32) // K
    if N <= _COMPARED_ASSIGNMENTS:
        # a decode step's: every row against every assignment, no sort
        r = jnp.arange(R, dtype=jnp.int32)
        row_token = T + jnp.sum(
            jnp.where(row_flat[None, :] == r[:, None], token[None, :] - T, 0),
            axis=1, dtype=jnp.int32)
    else:
        # ONE sort, of the ROWS: the assignments by their rows and, an
        # expert, the ``tile − 1`` rows that may pad its run by theirs (those
        # that do not, and the assignments with no row, last: ``R``) — the
        # sorted tokens are the map itself, nothing is gathered or scattered
        c = jnp.arange(tile - 1, dtype=jnp.int32)
        pad_rows = jnp.where(c < (padded - counts)[:, None],
                             (pstart + counts)[:, None] + c, R)
        _, row_token = lax.sort(
            (jnp.concatenate([row_flat, pad_rows.reshape(-1)]),
             jnp.concatenate([jnp.where(held, token, T),
                              jnp.full((E * (tile - 1),), T, jnp.int32)])),
            num_keys=1, is_stable=False)
        row_token = jnp.concatenate(
            [row_token[:R],
             jnp.full((max(R - row_token.shape[0], 0),), T, jnp.int32)])
    # tiles → experts: how many experts end at or before the tile's first row
    active = jnp.maximum(pend[-1] // tile, 1)
    first_row = jnp.minimum(jnp.arange(R // tile, dtype=jnp.int32),
                            active - 1) * tile
    tile_expert = jnp.minimum(
        jnp.sum(pend <= first_row[:, None], axis=1, dtype=jnp.int32), E - 1)
    load = jnp.stack([jnp.sum(counts), jnp.sum(counts > 0), jnp.max(counts)])
    return GroupPlan(row_token, row_flat.reshape(T, K), tile_expert,
                     active.reshape(1), padded, load.astype(jnp.int32))


# the gate's activation by name: the kernel's name and the fallback's counter
# follow it
ACTS = {"silu": (jax.nn.silu, "swiglu"), "relu": (jax.nn.relu, "reglu")}


def _relu2(v):
    return jnp.square(jax.nn.relu(v))


# ... and the activation of a unit with NO gate, ``act(x W_upᵀ) W_down`` — two
# matrices an expert (``wg`` is None wherever three are taken), under a kernel
# name and counters of its own.  BOTH matrices lie ``[E, F, D]``, the first as
# a checkpoint keeps it (``[out, in]``) and multiplied transposed: an
# intermediate width that is no whole number of lane tiles (1,856 = 14.5 x
# 128) is then the SUBLANE axis of both (116 bf16 tiles) and nothing is
# padded — as the lane axis of ``[E, D, F]`` XLA keeps it in a layout of its
# own and copies the whole stack to the kernel's every call (3.2 GB of
# temporaries at 5 x 64 x 2,688 x 1,856 in a compile for a described v5e).
UNGATED = {"relu2": (_relu2, "relu2")}
_NT = (((1,), (1,)), ((), ()))


def _gate(act: str):
    try:
        return ACTS[act] if act in ACTS else UNGATED[act]
    except KeyError:
        raise ValueError(f"unknown gate activation {act!r}; one of "
                         f"{sorted(ACTS)}, or ungated {sorted(UNGATED)}"
                         ) from None


def _unit_weights(act: str, wg, wu, wd) -> tuple:
    """The matrices the unit ``act`` multiplies by, in the kernels' order: a
    gated unit's three, an ungated one's two (and no ``wg``)."""
    if (act in UNGATED) != (wg is None):
        raise ValueError(f"{act!r} is a unit of "
                         f"{'two' if act in UNGATED else 'three'} matrices")
    return (wu, wd) if wg is None else (wg, wu, wd)


def _weight_specs(matrices: int, up, down) -> list:
    """The block specs of a unit's matrices: gate and up ``[D, F]`` and down
    ``[F, D]`` of a gated unit; both ``[F, D]`` of an ungated one."""
    return [up, up, down] if matrices == 3 else [down, down]


def _glu_tile(x, w_refs, gate, dtype):
    """One tile of rows through the step's expert: both walks' products.
    ``w_refs``: gate, up and down — or, of an ungated unit, up and down."""
    wu_ref, wd_ref = w_refs[-2:]
    if len(w_refs) == 3:
        g = jnp.dot(x, w_refs[0][0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (gate(g) * u).astype(wd_ref.dtype)
    else:
        h = gate(lax.dot_general(x, wu_ref[0], _NT,
                                 preferred_element_type=jnp.float32)
                 ).astype(wd_ref.dtype)
    return jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32
                   ).astype(dtype)


def _grouped_glu_kernel(te_ref, na_ref, x_ref, *refs, gate):
    *w_refs, o_ref = refs

    @pl.when(pl.program_id(0) < na_ref[0])
    def _():
        o_ref[:] = _glu_tile(x_ref[:], w_refs, gate, o_ref.dtype)


def _grouped_glu_fblock_kernel(te_ref, na_ref, x_ref, *refs, gate):
    """The tile walk of experts too wide for VMEM: grid (tiles, blocks of the
    intermediate axis), a tile's products summed over the blocks in ``acc``
    (float32) and written with the last."""
    *w_refs, o_ref, acc = refs
    f = pl.program_id(1)

    @pl.when(pl.program_id(0) < na_ref[0])
    def _():
        y = _glu_tile(x_ref[:], w_refs, gate, jnp.float32)

        @pl.when(f == 0)
        def _first():
            acc[:] = y

        @pl.when(f > 0)
        def _more():
            acc[:] = acc[:] + y

        @pl.when(f == pl.num_programs(1) - 1)
        def _last():
            o_ref[:] = acc[:].astype(o_ref.dtype)


# tiles of one piece of the expert walk: how many of an expert's rows are
# fetched at once (4 MB in and 4 MB of float32 out, twice, beside two
# experts' 35 MB of matrices); the table in the module's docstring
_PIECE_TILES = 4


def _expert_walk_kernel(ex_ref, st_ref, nt_ref, n_ref, f_ref, x_hbm, *refs,
                        gate, tile, piece_tiles):
    """Grid (E,): one grid step an expert that has rows, in the order of the
    plan (``ex_ref`` the expert of a step, ``st_ref`` its first row,
    ``nt_ref`` its tiles, ``n_ref`` how many steps have an expert, ``f_ref``
    the block of the intermediate axis this call computes: the index maps'
    alone); the steps after the last repeat its indices and do nothing.  The
    expert's
    three matrices are the step's blocks, so the NEXT expert's are fetched
    for the whole of this one's rows.  The rows stay in HBM: the kernel
    copies them in, a piece of up to ``piece_tiles`` tiles at a time, into
    a double buffer — tile by tile, each copy of one static shape — and
    the products' rows out again the same way.

    The next piece's rows are always in flight: before a piece is waited
    for, the next one is started into the other half — this expert's next,
    or after its last the next expert's first (scratch and semaphores
    persist over the sequential grid; ``state`` carries the half and the
    tiles of the output copy still in flight).  A piece's output copy has
    the whole of the next piece's products to land in."""
    # scalar arithmetic by ``lax`` primitives on int32 constants: an operator
    # on a traced scalar is a ``jnp`` function traced on its own, and ninety
    # of them were most of this body's trace (0.3 s a kernel on the chip's
    # host, twice a prefill program where a model has two stacks of experts)
    *w_refs, o_hbm, xbuf, obuf, sem, state = refs
    zero, one, two, tile_, piece_, rows_ = (
        jnp.int32(v) for v in (0, 1, 2, tile, piece_tiles,
                               piece_tiles * tile))
    j = pl.program_id(0)
    n = n_ref[0]

    def copy_rows(first_row, tiles, half, into: bool, wait: bool):
        """Start (or wait for) the copies of ``tiles`` tiles of rows from
        ``first_row``: HBM → ``xbuf[half]``, or ``obuf[half]`` → HBM."""
        def one(t, carry):
            off = lax.mul(t, tile_)
            at = pl.ds(pl.multiple_of(lax.add(first_row, off), tile), tile)
            here = pl.ds(pl.multiple_of(off, tile), tile)
            if into:
                cp = pltpu.make_async_copy(
                    x_hbm.at[at], xbuf.at[half, here], sem.at[0, half])
            else:
                cp = pltpu.make_async_copy(
                    obuf.at[half, here], o_hbm.at[at], sem.at[1, half])
            cp.wait() if wait else cp.start()
            return carry

        lax.fori_loop(zero, tiles, one, 0)

    def piece_of(step, p):
        """(first row, tiles) of piece ``p`` of the expert of ``step``."""
        return (lax.add(st_ref[step], lax.mul(p, rows_)),
                lax.min(lax.sub(nt_ref[step], lax.mul(p, piece_)), piece_))

    @pl.when(lax.eq(j, zero))
    def _first():
        state[0] = zero       # the half the next piece's rows arrive in
        state[1] = zero       # tiles of the output copy in flight
        state[2] = zero       # ... and their first row

        @pl.when(lax.gt(n, zero))
        def _():
            copy_rows(*piece_of(zero, zero), zero, into=True, wait=False)

    @pl.when(lax.lt(j, n))
    def _expert():
        first_half = state[0]
        pieces = lax.div(lax.add(nt_ref[j], lax.sub(piece_, one)), piece_)
        more = lax.lt(lax.add(j, one), n)       # an expert after this one

        def piece(p, carry):
            half = lax.rem(lax.add(first_half, p), two)
            other = lax.sub(one, half)
            row, tiles = piece_of(j, p)
            last = lax.eq(lax.add(p, one), pieces)

            @pl.when(lax.bitwise_not(last))
            def _next_piece():
                copy_rows(*piece_of(j, lax.add(p, one)), other, into=True,
                          wait=False)

            @pl.when(lax.bitwise_and(last, more))
            def _next_expert():
                copy_rows(*piece_of(lax.add(j, one), zero), other, into=True,
                          wait=False)

            copy_rows(row, tiles, half, into=True, wait=True)

            def one_tile(t, carry):
                at = pl.ds(pl.multiple_of(lax.mul(t, tile_), tile), tile)
                obuf[half, at] = _glu_tile(xbuf[half, at], w_refs, gate,
                                           obuf.dtype)
                return carry

            lax.fori_loop(zero, tiles, one_tile, 0)
            # the piece before this one's rows have left the other half
            copy_rows(state[2], state[1], other, into=False, wait=True)
            copy_rows(row, tiles, half, into=False, wait=False)
            state[1] = tiles
            state[2] = row
            return carry

        lax.fori_loop(zero, pieces, piece, 0)
        state[0] = lax.rem(lax.add(first_half, pieces), two)

        @pl.when(lax.bitwise_not(more))
        def _drain():
            copy_rows(state[2], state[1], lax.sub(one, state[0]), into=False,
                      wait=True)


@functools.partial(jax.jit, static_argnames=("tile", "act", "out_dtype",
                                             "interpret"))
def _expert_walk(x_rows, ws, sizes, layer_base, *, tile, act, out_dtype,
                 interpret):
    """The prefill form of :func:`grouped_glu`: see the module's docstring
    and :func:`_expert_walk_kernel`.  ``sizes`` the plan's ``padded_sizes``.
    A function of its own under ``jax.jit``: a program whose layers are not
    scanned calls it once a layer, and the kernel's body (every ``pl.when``
    and loop a trace of its own) is traced and lowered once for all of them
    — six times over it was 1.9 s of every prefill program's first call, a
    tenth of ``dsv2l_doc_sat``'s warm set-up."""
    gate, glu = _gate(act)
    R, D = x_rows.shape
    E, F = sizes.shape[0], ws[-1].shape[-2]
    fb = f_block(D, F, ws[-1].dtype.itemsize, len(ws))
    has = sizes > 0
    n = jnp.sum(has, dtype=jnp.int32)
    # the experts with rows first, in the plan's order; the steps after the
    # last repeat it, so nothing is fetched for them
    order = jnp.argsort(jnp.logical_not(has), stable=True).astype(jnp.int32)
    step = order[jnp.minimum(jnp.arange(E, dtype=jnp.int32),
                             jnp.maximum(n - 1, 0))]
    first_row = (jnp.cumsum(sizes) - sizes).astype(jnp.int32)[step]
    rows_max = _PIECE_TILES * tile
    # an expert too wide for VMEM: a call a block of its intermediate axis,
    # each over all the rows, the calls' float32 rows summed
    part_dtype = out_dtype if fb == F else jnp.dtype(jnp.float32)

    def up(j, ex, st, nt, n, f):
        return (ex[j], 0, f[0])

    def down(j, ex, st, nt, n, f):
        return (ex[j], f[0], 0)

    def walk(f):
        return pl.pallas_call(
            functools.partial(_expert_walk_kernel, gate=gate, tile=tile,
                              piece_tiles=_PIECE_TILES),
            name=f"moe_grouped_{glu}",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(E,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)]
                + _weight_specs(len(ws), pl.BlockSpec((1, D, fb), up),
                                pl.BlockSpec((1, fb, D), down)),
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=[pltpu.VMEM((2, rows_max, D), x_rows.dtype),
                                pltpu.VMEM((2, rows_max, D), part_dtype),
                                pltpu.SemaphoreType.DMA((2, 2)),
                                pltpu.SMEM((3,), jnp.int32)]),
            out_shape=jax.ShapeDtypeStruct((R, D), part_dtype),
            compiler_params=_VMEM_PARAMS,
            interpret=interpret,
        )(step + layer_base, first_row, sizes[step] // tile, n.reshape(1),
          jnp.asarray(f, jnp.int32).reshape(1), x_rows, *ws)

    if fb == F:
        return walk(0)
    # (rows no expert owns are never written, in any call: ``combine`` reads
    # none of them)
    return lax.fori_loop(0, F // fb, lambda f, y: y + walk(f),
                         jnp.zeros((R, D), part_dtype)).astype(out_dtype)


def _one_layer(w, layer):
    return w if layer is None else lax.dynamic_index_in_dim(
        w, layer, keepdims=False)


def grouped_glu_xla(x_rows, wg, wu, wd, plan: GroupPlan, act: str = "silu",
                    layer=None, out_dtype=jnp.float32):
    """The fallback: the same padded rows through three ``lax.ragged_dot``
    (two of an ungated unit)."""
    gate, _ = _gate(act)
    ws = [_one_layer(w, layer) for w in _unit_weights(act, wg, wu, wd)]

    def rd(a, w):
        return lax.ragged_dot(a, w, plan.padded_sizes,
                              preferred_element_type=jnp.float32)
    if len(ws) == 3:
        h = gate(rd(x_rows, ws[0])) * rd(x_rows, ws[1])
    else:
        h = gate(rd(x_rows, jnp.swapaxes(ws[0], -1, -2)))
    return rd(h.astype(ws[-1].dtype), ws[-1]).astype(out_dtype)


def grouped_glu(x_rows, wg, wu, wd, plan: GroupPlan, tile: int, impl=None,
                interpret=None, act: str = "silu", layer=None,
                out_dtype=jnp.float32):
    """x_rows [R, D] (rows in expert order, :func:`plan_groups`), wg / wu
    [E, D, F], wd [E, F, D] → [R, D] ``out_dtype`` (every product accumulated
    in float32; a prefill of twelve thousand positions has 82 thousand rows,
    0.84 GB in float32); with ``layer`` (an int or a traced scalar) the
    matrices are stacks ``[layers, E, …]`` and the layer's are used.  Rows of
    tiles past ``plan.active_tiles`` are left as they are found: no
    assignment points at them."""
    gate, glu = _gate(act)
    if impl == "xla":
        _obs_stats.scope("moe").counter(f"grouped_{glu}_fallbacks").inc()
        return grouped_glu_xla(x_rows, wg, wu, wd, plan, act, layer,
                               out_dtype)
    if impl not in (None, "pallas"):
        raise ValueError(f"unknown grouped_glu impl {impl!r}")
    if interpret is None:
        interpret = pallas_interpret()
    R, D = x_rows.shape
    ws = _unit_weights(act, wg, wu, wd)
    E, F = wd.shape[-3], wd.shape[-2]
    layer_base = 0
    if layer is not None:
        # a stack is its layers' experts end to end: nothing is sliced out
        ws = tuple(w.reshape((-1,) + w.shape[2:]) for w in ws)
        layer_base = jnp.asarray(layer, jnp.int32) * E
    # the plan's tile chooses the walk: hundreds of rows an expert step by
    # expert, a decode step's few by tile
    by_expert = tile == _PREFILL_TILE
    _obs_stats.scope("moe").counter(
        f"grouped_{glu}_{'expert' if by_expert else 'tile'}_walks").inc()
    if by_expert:
        return _expert_walk(x_rows, ws, plan.padded_sizes,
                            jnp.asarray(layer_base, jnp.int32), tile=tile,
                            act=act, out_dtype=jnp.dtype(out_dtype),
                            interpret=bool(interpret))
    tile_expert = plan.tile_expert
    if layer is not None:
        tile_expert = tile_expert + layer_base
    fb = f_block(D, F, wu.dtype.itemsize, len(ws))
    if fb != F:
        return _tile_walk_by_block(x_rows, ws, tile_expert,
                                   plan.active_tiles, tile, fb, gate, glu,
                                   out_dtype, interpret)

    def rows(i, te, na):
        return (jnp.minimum(i, na[0] - 1), 0)

    def up(i, te, na):
        return (te[i], 0, 0)

    return pl.pallas_call(
        functools.partial(_grouped_glu_kernel, gate=gate),
        name=f"moe_grouped_{glu}",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R // tile,),
            in_specs=[pl.BlockSpec((tile, D), rows)]
            + _weight_specs(len(ws), pl.BlockSpec((1, D, F), up),
                            pl.BlockSpec((1, F, D), up)),
            out_specs=pl.BlockSpec((tile, D), rows)),
        out_shape=jax.ShapeDtypeStruct((R, D), out_dtype),
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
    )(tile_expert, plan.active_tiles, x_rows, *ws)


def _tile_walk_by_block(x_rows, ws, tile_expert, active_tiles, tile: int,
                        fb: int, gate, glu: str, out_dtype, interpret):
    """The tile walk of experts too wide for VMEM
    (:func:`_grouped_glu_fblock_kernel`): every tile against its expert's
    matrices a block of ``fb`` columns of the intermediate axis at a time.
    The tiles past the last real one stay on its last block: nothing is
    fetched for them."""
    R, D = x_rows.shape
    nf = ws[-1].shape[-2] // fb

    def rows(i, f, te, na):
        return (jnp.minimum(i, na[0] - 1), 0)

    def block(i, f, na):
        return jnp.where(i < na[0], f, nf - 1)

    def up(i, f, te, na):
        return (te[i], 0, block(i, f, na))

    def down(i, f, te, na):
        return (te[i], block(i, f, na), 0)

    return pl.pallas_call(
        functools.partial(_grouped_glu_fblock_kernel, gate=gate),
        name=f"moe_grouped_{glu}",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R // tile, nf),
            in_specs=[pl.BlockSpec((tile, D), rows)]
            + _weight_specs(len(ws), pl.BlockSpec((1, D, fb), up),
                            pl.BlockSpec((1, fb, D), down)),
            out_specs=pl.BlockSpec((tile, D), rows),
            scratch_shapes=[pltpu.VMEM((tile, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R, D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT,
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(tile_expert, active_tiles, x_rows, *ws)


def row_tile(tokens: int, dtype) -> int:
    small = 16 if jnp.dtype(dtype).itemsize < 4 else 8
    return small if tokens <= 128 else _PREFILL_TILE


def _picked(y, row_of):
    """y's rows ``row_of`` in float32; zero where there is no row."""
    R = y.shape[0]
    return jnp.where((row_of < R)[..., None],
                     y[jnp.minimum(row_of, R - 1)].astype(jnp.float32), 0.0)


@jax.jit
def _combine_by_choice(y, weights, row_of):
    """Traced once for all the layers of a program, as :func:`_expert_walk`
    is."""
    out = weights[:, 0, None] * _picked(y, row_of[:, 0])
    for k in range(1, weights.shape[1]):
        out = out + weights[:, k, None] * _picked(y, row_of[:, k])
    return out


def combine(y, weights, plan: GroupPlan, by_choice: bool = False):
    """y [R, D] (:func:`grouped_glu`'s rows), weights [T, K] float32 → the
    weighted sum of every token's chosen rows [T, D] float32; an assignment
    with no row (its token was not valid) adds nothing.  ``by_choice`` (a
    prefill's thousands of tokens): K gathers of [T, D], each weighed and
    added to the sum in the order of the choices, in place of ONE gather of
    [T, K, D] — which pads every token's six rows to the eight of an (8, 128)
    tile and is copied once more to be reshaped: 815 → 406 µs at 2,048
    tokens of float32 rows, 751 → 207 of bf16 (PERF.md §6, PR 45)."""
    if by_choice:
        return _combine_by_choice(y, weights, plan.row_of)
    rows = _picked(y, plan.row_of)                               # [T, K, D]
    return jnp.sum(weights[..., None] * rows, axis=1)


def share_block_rows(tokens: int, k: int, held: int, router: int,
                     tile: int) -> int:
    """Rows a block of a SHARE's plan (``planned_experts(row_block=)``): what
    ``tokens`` tokens' ``k`` choices of ``router`` experts are expected to
    leave on the ``held`` of them, an eighth more, and every held expert's
    padding — so that an even router's plan is one block and a lopsided one
    is several, each as large.  A bound on memory, never on the rows
    computed."""
    most = -(-tokens * k * held // router)
    return plan_rows(-(-most * 9 // (8 * k)), k, held, tile)


def _blocked_experts(x_pad, weights, plan: GroupPlan, wg, wu, wd, tile: int,
                     act: str, layer, out_dtype, row_block: int):
    """:func:`planned_experts` a block of ``row_block`` of the plan's rows at
    a time, as many blocks as hold a real row (a loop whose length the plan
    decides on the device): a block's rows are gathered, walked an expert a
    grid step — an expert whose run straddles a block's edge is fetched in
    both — and their weighted sum added to the tokens'.  Every row of the
    plan is computed; what is bounded is the memory: ``row_block`` rows in
    and out, whatever the static bound :func:`plan_rows` has to allow."""
    T, K = weights.shape
    R, B = plan.row_token.shape[0], row_block
    row_token = jnp.pad(plan.row_token, (0, -R % B), constant_values=T)
    pend = jnp.cumsum(plan.padded_sizes, dtype=jnp.int32)
    pstart = pend - plan.padded_sizes

    def block(b, out):
        b0 = b * B
        rows = x_pad[lax.dynamic_slice(row_token, (b0,), (B,))]
        # the experts' runs cut at the block's edges: still in expert order
        sizes = jnp.clip(pend, b0, b0 + B) - jnp.clip(pstart, b0, b0 + B)
        y = grouped_glu(rows, wg, wu, wd, plan._replace(padded_sizes=sizes),
                        tile, act=act, layer=layer, out_dtype=out_dtype)
        # a row of another block (and ``R``: no row) is no row of this one
        mine = (plan.row_of >= b0) & (plan.row_of < jnp.minimum(b0 + B, R))
        return out + _combine_by_choice(
            y, weights, jnp.where(mine, plan.row_of - b0, B))

    return lax.fori_loop(0, -(-pend[-1] // B), block,
                         jnp.zeros((T, x_pad.shape[1]), jnp.float32))


def planned_experts(x, weights, plan: GroupPlan, wg, wu, wd, tile: int,
                    impl=None, act: str = "silu", layer=None,
                    out_dtype=jnp.float32, row_block=None):
    """The experts of a dispatch whose :func:`plan_groups` is already made
    (with the same ``tile``): x [T, D], weights [T, K] → [T, D] float32, the
    experts' rows kept in ``out_dtype`` until they are weighed and summed.
    ``row_block`` (a multiple of ``tile``; :func:`share_block_rows`) bounds
    the rows of a prefill's plan that lie gathered at once: a plan with more
    is walked a block at a time (:func:`_blocked_experts`), to the same sum."""
    x_pad = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)], axis=0)
    if row_block is not None and impl is None and tile == _PREFILL_TILE \
            and plan.row_token.shape[0] > row_block:
        return _blocked_experts(x_pad, weights, plan, wg, wu, wd, tile, act,
                                layer, out_dtype, row_block)
    y = grouped_glu(x_pad[plan.row_token], wg, wu, wd, plan, tile, impl=impl,
                    act=act, layer=layer, out_dtype=out_dtype)
    return combine(y, weights, plan, by_choice=tile == _PREFILL_TILE)


def routed_experts(x, ids, weights, valid, wg, wu, wd, impl=None,
                   act: str = "silu", first=0):
    """x [T, D] (the experts' input, the model's activation dtype), ids /
    weights [T, K] from :func:`route_topk`, valid [T] bool → (sum over the
    chosen experts of ``w * expert(x)`` [T, D] float32, load [3] int32:
    assignments, experts touched, the largest load of one).  With ``first``
    the stacks hold experts ``first …`` of a wider layer's
    (:func:`plan_groups`) and the sum is this share's part."""
    tile = row_tile(x.shape[0], x.dtype)
    plan = plan_groups(ids, valid, wd.shape[0], tile, first)
    return planned_experts(x, weights, plan, wg, wu, wd, tile, impl=impl,
                           act=act), plan.load


__all__ = ["route_topk", "plan_groups", "plan_rows", "share_block_rows",
           "f_block", "grouped_glu", "grouped_glu_xla", "combine",
           "planned_experts", "routed_experts", "GroupPlan", "row_tile",
           "ACTS", "UNGATED", "SCORES"]
