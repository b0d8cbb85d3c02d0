"""Routed experts: top-k routing by sort and a grouped gated unit (SwiGLU or
ReGLU) over the experts — the serving form of a sparse mixture, where every
assignment is computed (no capacity, no token dropped).

- :func:`route_topk` — scores over all experts in float32 (``softmax``, or
  ``sigmoid``: an expert's own), the ``k`` largest (``lax.top_k`` keeps the
  lower index on a tie) — of ``score + bias`` where a selection bias is
  given, which chooses and does not weigh —, weights the chosen scores
  themselves, renormalised only when asked (over ``sum + eps``), times
  ``scale``.
- :func:`plan_groups` — the sort: the valid tokens' assignments in expert
  order, each expert's rows padded to a multiple of the row tile, so a tile
  of rows belongs to exactly one expert.  Gathers and two small sorts; no
  scatter.
- :func:`grouped_glu` — ``(act(x Wg_e) * (x Wu_e)) Wd_e`` for every row tile
  against its expert's three matrices, ``act`` the gate's activation
  (:data:`ACTS`: ``silu`` — SwiGLU — or ``relu`` — ReGLU).  ONE Pallas kernel,
  named after the gate (``moe_grouped_swiglu`` / ``moe_grouped_reglu``), walks
  the tiles; the scalar-prefetched tile→expert map is its weight index map,
  so an expert's matrices stream from HBM once while its tiles are
  consecutive, experts with no row are never read, and the tiles past the
  last real one repeat its indices (no copy, no compute).  Where the layers
  are scanned the matrices are handed over as the whole stack ``[layers, E,
  …]`` with the layer's index: the map points at ``layer · E + expert`` and
  no layer's experts are sliced out of the stack.  The XLA fallback is three
  ``lax.ragged_dot`` over the same padded rows and counts into
  ``moe.grouped_swiglu_fallbacks`` / ``moe.grouped_reglu_fallbacks``.
  :func:`grouped_swiglu` is the call with the gate fixed at ``silu``.
- :func:`combine` — ``sum_k w[t, k] * y[row of (t, k)]``.
- :func:`routed_experts` — the three together, one function for a prefill's
  thousands of rows and a decode step's 64: ``sum_k w[t, k] * expert(x[t])``
  and the dispatch's load figures.  A model whose routing is known before
  the experts' input (the router reads an earlier activation) calls
  :func:`plan_groups` there and :func:`planned_experts` here.
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
_VMEM_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=100 * 1024 * 1024,
                                    dimension_semantics=("arbitrary",))
# rows a tile: a decode step's few rows an expert take the smallest tile the
# dtype packs; a prefill's hundreds fill 128-row tiles
_PREFILL_TILE = 128


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


def plan_groups(ids, valid, experts: int, tile: int) -> GroupPlan:
    """ids [T, K] int32 (chosen experts), valid [T] bool (False: the token is
    padding or its slot has no stream — it is routed nowhere and enters no
    count)."""
    T, K = ids.shape
    N = T * K
    R = plan_rows(T, K, experts, tile)
    key = jnp.where(valid[:, None], ids, experts).reshape(N).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sorted_key = key[order]
    starts = jnp.searchsorted(
        sorted_key, jnp.arange(experts + 1, dtype=jnp.int32),
        side="left").astype(jnp.int32)                  # [E + 1]
    counts = starts[1:] - starts[:-1]                   # [E]
    padded = -(-counts // tile) * tile
    pend = jnp.cumsum(padded).astype(jnp.int32)         # inclusive ends
    pstart = pend - padded
    # rows → assignments
    r = jnp.arange(R, dtype=jnp.int32)
    e_r = jnp.minimum(jnp.searchsorted(pend, r, side="right"),
                      experts - 1).astype(jnp.int32)
    off = r - pstart[e_r]
    real = (off >= 0) & (off < counts[e_r])
    src = order[jnp.clip(starts[e_r] + off, 0, N - 1)] // K
    row_token = jnp.where(real, src, T).astype(jnp.int32)
    # assignments → rows
    rank = jnp.argsort(order).astype(jnp.int32)         # place in sorted order
    k_a = jnp.minimum(key, experts - 1)
    row_flat = jnp.where(key < experts, pstart[k_a] + rank - starts[k_a], R)
    # tiles → experts
    n_tiles = R // tile
    active = jnp.maximum(-(-pend[-1] // tile), 1).astype(jnp.int32)
    last = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32), active - 1)
    tile_expert = jnp.minimum(
        jnp.searchsorted(pend, last * tile, side="right"),
        experts - 1).astype(jnp.int32)
    load = jnp.stack([starts[experts], jnp.sum(counts > 0),
                      jnp.max(counts)]).astype(jnp.int32)
    return GroupPlan(row_token, row_flat.reshape(T, K).astype(jnp.int32),
                     tile_expert, active.reshape(1), padded.astype(jnp.int32),
                     load)


# the gate's activation by name: the kernel's name and the fallback's counter
# follow it
ACTS = {"silu": (jax.nn.silu, "swiglu"), "relu": (jax.nn.relu, "reglu")}


def _gate(act: str):
    try:
        return ACTS[act]
    except KeyError:
        raise ValueError(f"unknown gate activation {act!r}; one of "
                         f"{sorted(ACTS)}") from None


def _grouped_glu_kernel(te_ref, na_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
                        *, gate):
    @pl.when(pl.program_id(0) < na_ref[0])
    def _():
        x = x_ref[:]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (gate(g) * u).astype(wd_ref.dtype)
        o_ref[:] = jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32
                           ).astype(o_ref.dtype)


def _one_layer(w, layer):
    return w if layer is None else lax.dynamic_index_in_dim(
        w, layer, keepdims=False)


def grouped_glu_xla(x_rows, wg, wu, wd, plan: GroupPlan, act: str = "silu",
                    layer=None, out_dtype=jnp.float32):
    """The fallback: the same padded rows through three ``lax.ragged_dot``."""
    gate, _ = _gate(act)
    wg, wu, wd = (_one_layer(w, layer) for w in (wg, wu, wd))

    def rd(a, w):
        return lax.ragged_dot(a, w, plan.padded_sizes,
                              preferred_element_type=jnp.float32)
    h = (gate(rd(x_rows, wg)) * rd(x_rows, wu)).astype(wd.dtype)
    return rd(h, wd).astype(out_dtype)


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
    E, F = wg.shape[-3], wg.shape[-1]
    tile_expert = plan.tile_expert
    if layer is not None:
        # a stack is its layers' experts end to end: nothing is sliced out
        wg, wu, wd = (w.reshape((-1,) + w.shape[2:]) for w in (wg, wu, wd))
        tile_expert = tile_expert + jnp.asarray(layer, jnp.int32) * E

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
            in_specs=[pl.BlockSpec((tile, D), rows),
                      pl.BlockSpec((1, D, F), up),
                      pl.BlockSpec((1, D, F), up),
                      pl.BlockSpec((1, F, D), up)],
            out_specs=pl.BlockSpec((tile, D), rows)),
        out_shape=jax.ShapeDtypeStruct((R, D), out_dtype),
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
    )(tile_expert, plan.active_tiles, x_rows, wg, wu, wd)


def grouped_swiglu(x_rows, wg, wu, wd, plan: GroupPlan, tile: int, impl=None,
                   interpret=None):
    """:func:`grouped_glu` with the gate ``silu``."""
    return grouped_glu(x_rows, wg, wu, wd, plan, tile, impl, interpret)


def grouped_swiglu_xla(x_rows, wg, wu, wd, plan: GroupPlan):
    return grouped_glu_xla(x_rows, wg, wu, wd, plan)


def row_tile(tokens: int, dtype) -> int:
    small = 16 if jnp.dtype(dtype).itemsize < 4 else 8
    return small if tokens <= 128 else _PREFILL_TILE


def combine(y, weights, plan: GroupPlan):
    """y [R, D] (:func:`grouped_glu`'s rows), weights [T, K] float32 → the
    weighted sum of every token's chosen rows [T, D] float32; an assignment
    with no row (its token was not valid) adds nothing."""
    R = y.shape[0]
    here = plan.row_of < R
    picked = jnp.where(here[..., None],
                       y[jnp.minimum(plan.row_of, R - 1)].astype(jnp.float32),
                       0.0)                                      # [T, K, D]
    return jnp.sum(weights[..., None] * picked, axis=1)


def planned_experts(x, weights, plan: GroupPlan, wg, wu, wd, tile: int,
                    impl=None, act: str = "silu", layer=None,
                    out_dtype=jnp.float32):
    """The experts of a dispatch whose :func:`plan_groups` is already made
    (with the same ``tile``): x [T, D], weights [T, K] → [T, D] float32, the
    experts' rows kept in ``out_dtype`` until they are weighed and summed."""
    x_pad = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)], axis=0)
    y = grouped_glu(x_pad[plan.row_token], wg, wu, wd, plan, tile, impl=impl,
                    act=act, layer=layer, out_dtype=out_dtype)
    return combine(y, weights, plan)


def routed_experts(x, ids, weights, valid, wg, wu, wd, impl=None,
                   act: str = "silu"):
    """x [T, D] (the experts' input, the model's activation dtype), ids /
    weights [T, K] from :func:`route_topk`, valid [T] bool → (sum over the
    chosen experts of ``w * expert(x)`` [T, D] float32, load [3] int32:
    assignments, experts touched, the largest load of one)."""
    tile = row_tile(x.shape[0], x.dtype)
    plan = plan_groups(ids, valid, wg.shape[0], tile)
    return planned_experts(x, weights, plan, wg, wu, wd, tile, impl=impl,
                           act=act), plan.load


__all__ = ["route_topk", "plan_groups", "plan_rows", "grouped_glu",
           "grouped_glu_xla", "grouped_swiglu", "grouped_swiglu_xla", "combine",
           "planned_experts", "routed_experts", "GroupPlan", "row_tile",
           "ACTS", "SCORES"]
