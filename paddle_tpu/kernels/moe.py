"""Routed experts: top-k routing by sort and a grouped SwiGLU over the
experts — the serving form of a sparse mixture, where every assignment is
computed (no capacity, no token dropped).

- :func:`route_topk` — ``softmax`` scores over all experts in float32, the
  ``k`` largest (``lax.top_k`` keeps the lower index on a tie), weights the
  chosen scores themselves, renormalised only when asked, times ``scale``.
- :func:`plan_groups` — the sort: the valid tokens' assignments in expert
  order, each expert's rows padded to a multiple of the row tile, so a tile
  of rows belongs to exactly one expert.  Gathers and two small sorts; no
  scatter.
- :func:`grouped_swiglu` — ``(silu(x Wg_e) * (x Wu_e)) Wd_e`` for every row
  tile against its expert's three matrices.  The Pallas kernel
  (``moe_grouped_swiglu``) walks the tiles; the scalar-prefetched tile→expert
  map is its weight index map, so an expert's matrices stream from HBM once
  while its tiles are consecutive, experts with no row are never read, and
  the tiles past the last real one repeat its indices (no copy, no compute).
  The XLA fallback is three ``lax.ragged_dot`` over the same padded rows and
  counts into ``moe.grouped_swiglu_fallbacks``.
- :func:`routed_experts` — the three together, one function for a prefill's
  thousands of rows and a decode step's 64: ``sum_k w[t, k] * expert(x[t])``
  and the dispatch's load figures.
"""
from __future__ import annotations

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


def route_topk(logits, k: int, scale: float = 1.0, normalize: bool = False):
    """logits [T, E] (router outputs, any float) → (ids [T, k] int32,
    weights [T, k] f32).  Scores and weights are float32."""
    s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    chosen, ids = lax.top_k(s, k)
    if normalize:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
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


def _grouped_swiglu_kernel(te_ref, na_ref, x_ref, wg_ref, wu_ref, wd_ref,
                           o_ref):
    @pl.when(pl.program_id(0) < na_ref[0])
    def _():
        x = x_ref[:]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(wd_ref.dtype)
        o_ref[:] = jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)


def grouped_swiglu_xla(x_rows, wg, wu, wd, plan: GroupPlan):
    """The fallback: the same padded rows through three ``lax.ragged_dot``."""
    def rd(a, w):
        return lax.ragged_dot(a, w, plan.padded_sizes,
                              preferred_element_type=jnp.float32)
    h = (jax.nn.silu(rd(x_rows, wg)) * rd(x_rows, wu)).astype(wd.dtype)
    return rd(h, wd)


def grouped_swiglu(x_rows, wg, wu, wd, plan: GroupPlan, tile: int, impl=None,
                   interpret=None):
    """x_rows [R, D] (rows in expert order, :func:`plan_groups`), wg / wu
    [E, D, F], wd [E, F, D] → [R, D] float32.  Rows of tiles past
    ``plan.active_tiles`` are left as they are found: no assignment points at
    them."""
    if impl == "xla":
        _obs_stats.scope("moe").counter("grouped_swiglu_fallbacks").inc()
        return grouped_swiglu_xla(x_rows, wg, wu, wd, plan)
    if impl not in (None, "pallas"):
        raise ValueError(f"unknown grouped_swiglu impl {impl!r}")
    if interpret is None:
        interpret = pallas_interpret()
    R, D = x_rows.shape
    F = wg.shape[2]

    def rows(i, te, na):
        return (jnp.minimum(i, na[0] - 1), 0)

    def up(i, te, na):
        return (te[i], 0, 0)

    return pl.pallas_call(
        _grouped_swiglu_kernel,
        name="moe_grouped_swiglu",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R // tile,),
            in_specs=[pl.BlockSpec((tile, D), rows),
                      pl.BlockSpec((1, D, F), up),
                      pl.BlockSpec((1, D, F), up),
                      pl.BlockSpec((1, F, D), up)],
            out_specs=pl.BlockSpec((tile, D), rows)),
        out_shape=jax.ShapeDtypeStruct((R, D), jnp.float32),
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
    )(plan.tile_expert, plan.active_tiles, x_rows, wg, wu, wd)


def row_tile(tokens: int, dtype) -> int:
    small = 16 if jnp.dtype(dtype).itemsize < 4 else 8
    return small if tokens <= 128 else _PREFILL_TILE


def routed_experts(x, ids, weights, valid, wg, wu, wd, impl=None):
    """x [T, D] (the experts' input, the model's activation dtype), ids /
    weights [T, K] from :func:`route_topk`, valid [T] bool → (sum over the
    chosen experts of ``w * expert(x)`` [T, D] float32, load [3] int32:
    assignments, experts touched, the largest load of one)."""
    T, D = x.shape
    E = wg.shape[0]
    tile = row_tile(T, x.dtype)
    plan = plan_groups(ids, valid, E, tile)
    x_pad = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], axis=0)
    y = grouped_swiglu(x_pad[plan.row_token], wg, wu, wd, plan, tile,
                       impl=impl)
    R = y.shape[0]
    here = plan.row_of < R
    picked = jnp.where(here[..., None],
                       y[jnp.minimum(plan.row_of, R - 1)], 0.0)  # [T, K, D]
    return jnp.sum(weights[..., None] * picked, axis=1), plan.load


__all__ = ["route_topk", "plan_groups", "plan_rows", "grouped_swiglu",
           "grouped_swiglu_xla", "routed_experts", "GroupPlan", "row_tile"]
