"""The grouped expert kernel (``kernels/moe.py``) in interpret mode against
``lax.ragged_dot`` and against the definition, a gate at a time: ONE kernel
whose gate is an argument (``silu``: SwiGLU, ``relu``: ReGLU), named after
it; and the stacked form, in which a layer's experts are reached through the
tile→expert map and never sliced out of the stack.  Two walks, chosen by the
plan's tile alone: a decode step's small tiles a grid step each, a prefill's
128-row tiles an EXPERT a grid step (its rows copied in and out by the kernel
in pieces of up to four tiles) — the same cases run under both."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import moe
from paddle_tpu.observability import stats
from paged_walks import eqns_under, moe_walks as _walks

GATES = {"silu": lambda a: a / (1.0 + np.exp(-a)),
         "relu": lambda a: np.maximum(a, 0.0)}
NAMES = {"silu": "swiglu", "relu": "reglu"}


def _case(seed, T=40, D=32, F=48, E=8, K=3, layers=None):
    rng = np.random.RandomState(seed)
    lead = () if layers is None else (layers,)
    x = jnp.asarray(rng.randn(T, D), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(*lead, E, D, F) * 0.2, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(*lead, E, F, D) * 0.2, jnp.float32)
    ids, w = moe.route_topk(jnp.asarray(rng.randn(T, E), jnp.float32), K,
                            normalize=True)
    return x, wg, wu, wd, ids, w


# assignments an expert, for plans of 128-row tiles: an expert with no row,
# one row, exactly a tile, a tile and a row, five tiles (a full piece of the
# walk and a tile); the first and the last expert empty; all on one expert
COUNTS = {
    "mixed": [0, 1, 128, 129, 5 * 128 - 3, 7, 2, 0],
    "one_expert": [0, 0, 0, 300, 0, 0, 0, 0],
    "ends_empty": [0, 200, 3, 0, 0, 129, 256, 0],
    "full_pieces": [512, 0, 1024, 0, 0, 0, 384, 2],
}


def _counted_case(seed, counts, D=32, F=48, K=2, layers=None):
    """As :func:`_case` with the experts' loads given: ``counts[e]``
    assignments on expert ``e``, shuffled over the tokens."""
    rng = np.random.RandomState(seed)
    E, N = len(counts), sum(counts)
    assert N % K == 0
    lead = () if layers is None else (layers,)
    x = jnp.asarray(rng.randn(N // K, D), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(*lead, E, D, F) * 0.2, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(*lead, E, F, D) * 0.2, jnp.float32)
    ids = rng.permutation(np.repeat(np.arange(E), counts)).reshape(-1, K)
    w = rng.rand(N // K, K) + 0.1
    return (x, wg, wu, wd, jnp.asarray(ids, jnp.int32),
            jnp.asarray(w / w.sum(-1, keepdims=True), jnp.float32))


def _dense(x, wg, wu, wd, ids, w, valid, act):
    x, wg, wu, wd, w = (np.asarray(a, np.float64) for a in (x, wg, wu, wd, w))
    out = np.zeros(x.shape)
    for e in range(wg.shape[0]):
        share = (w * (np.asarray(ids) == e)).sum(-1, keepdims=True)
        out += share * ((GATES[act](x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return out * np.asarray(valid)[:, None]


@pytest.mark.parametrize("act", sorted(GATES))
def test_grouped_kernel_matches_ragged_dot_and_the_definition(act):
    x, wg, wu, wd, ids, w = _case(0)
    valid = jnp.asarray(np.arange(x.shape[0]) < 33)
    counter = f"moe.grouped_{NAMES[act]}_fallbacks"
    before = stats.to_dict().get(counter, 0)
    walks = _walks(act)
    y0, load0 = moe.routed_experts(x, ids, w, valid, wg, wu, wd, impl="xla",
                                   act=act)
    assert stats.to_dict()[counter] == before + 1
    y1, load1 = jax.jit(lambda *a: moe.routed_experts(
        *a, impl="pallas", act=act))(x, ids, w, valid, wg, wu, wd)
    assert stats.to_dict()[counter] == before + 1
    # forty tokens: the step's small tiles, walked a tile a grid step
    assert _walks(act) == (walks[0], walks[1] + 1)
    np.testing.assert_allclose(y1, y0, atol=1e-4)
    np.testing.assert_allclose(y1, _dense(x, wg, wu, wd, ids, w, valid, act),
                               atol=1e-4)
    assert np.asarray(load0).tolist() == np.asarray(load1).tolist()
    assert int(load0[0]) == 33 * 3 and np.all(np.asarray(y1)[33:] == 0)


@pytest.mark.parametrize("out_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("counts", sorted(COUNTS))
@pytest.mark.parametrize("act", sorted(GATES))
def test_the_expert_walk_matches_ragged_dot_and_the_definition(act, counts,
                                                               out_dtype):
    """A prefill's plan (128-row tiles) is walked an expert a grid step:
    every assignment computed, whatever the experts' loads."""
    x, wg, wu, wd, ids, w = _counted_case(5, COUNTS[counts])
    T, E = x.shape[0], wg.shape[0]
    valid = jnp.ones((T,), bool)
    tile = moe.row_tile(T, x.dtype)
    assert tile == 128
    plan = moe.plan_groups(ids, valid, E, tile)
    assert np.asarray(plan.padded_sizes).tolist() == [
        -(-c // 128) * 128 for c in COUNTS[counts]]
    fallbacks = f"moe.grouped_{NAMES[act]}_fallbacks"
    before, walks = stats.to_dict().get(fallbacks, 0), _walks(act)

    def fn(impl):
        return jax.jit(lambda *a: moe.planned_experts(
            a[0], a[1], plan, *a[2:], tile, impl=impl, act=act,
            out_dtype=jnp.dtype(out_dtype)))(x, w, wg, wu, wd)

    y1 = fn("pallas")
    assert _walks(act) == (walks[0] + 1, walks[1])
    assert stats.to_dict().get(fallbacks, 0) == before
    want = _dense(x, wg, wu, wd, ids, w, valid, act)
    # bf16 rows: eight bits of each of a token's K rows
    atol = 1e-4 if out_dtype == "float32" else 2e-2 * np.abs(want).max()
    np.testing.assert_allclose(y1, fn("xla"), atol=atol)
    np.testing.assert_allclose(y1, want, atol=atol)


@pytest.mark.parametrize("tile,counts", [(8, "mixed"), (128, "mixed"),
                                         (128, "one_expert")])
@pytest.mark.parametrize("act", sorted(GATES))
def test_rows_past_the_last_active_tile_are_left_as_found(act, tile, counts):
    """Neither walk computes or writes a row past the plan's last padded
    one: the interpreter's own fill (NaN) is still there, whatever the rows
    held coming in, and every row before is the fallback's."""
    x, wg, wu, wd, ids, w = _counted_case(6, COUNTS[counts])
    T, E = x.shape[0], wg.shape[0]
    plan = moe.plan_groups(ids, jnp.ones((T,), bool), E, tile)
    R, rows = plan.row_token.shape[0], int(np.sum(plan.padded_sizes))
    assert 0 < rows < R and int(plan.active_tiles[0]) * tile == rows
    x_rows = jnp.concatenate([x, jnp.zeros((1, x.shape[1]))])[plan.row_token]
    x_rows = x_rows.at[rows:].set(1.0)
    walks = _walks(act)
    y = moe.grouped_glu(x_rows, wg, wu, wd, plan, tile, impl="pallas",
                        act=act)
    assert _walks(act) == (walks[0] + (tile == 128), walks[1] + (tile != 128))
    assert np.isnan(np.asarray(y[rows:])).all()
    np.testing.assert_allclose(
        y[:rows], moe.grouped_glu_xla(x_rows, wg, wu, wd, plan, act)[:rows],
        atol=1e-4)


@pytest.mark.parametrize("tokens,dtype,tile,walk", [
    (64, "bfloat16", 16, "tile"), (64, "float32", 8, "tile"),
    (128, "bfloat16", 16, "tile"), (129, "bfloat16", 128, "expert"),
    (2048, "bfloat16", 128, "expert")])
@pytest.mark.parametrize("act", sorted(GATES))
def test_the_plan_s_tile_chooses_the_walk_and_a_step_keeps_its_own(
        act, tokens, dtype, tile, walk):
    """A decode step's plans (at most 128 tokens: 16-row tiles, 8 in
    float32) are walked a tile a grid step as they always were; a prefill's
    an expert a grid step — counted when the call is lowered."""
    E, K, D, F = 8, 2, 32, 48
    assert moe.row_tile(tokens, dtype) == tile
    sds = jax.ShapeDtypeStruct
    walks = _walks(act)
    jaxpr = jax.make_jaxpr(lambda *a: moe.routed_experts(*a, act=act))(
        sds((tokens, D), dtype), sds((tokens, K), jnp.int32),
        sds((tokens, K), jnp.float32), sds((tokens,), bool),
        sds((E, D, F), dtype), sds((E, D, F), dtype), sds((E, F, D), dtype))
    took = tuple(b - a for a, b in zip(walks, _walks(act)))
    assert took == ((1, 0) if walk == "expert" else (0, 1))
    call, = [e for e in eqns_under(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert call.params["name"] == f"moe_grouped_{NAMES[act]}"
    grid = tuple(call.params["grid_mapping"].grid)
    R = moe.plan_rows(tokens, K, E, tile)
    assert grid == ((E,) if walk == "expert" else (R // tile,))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("counts", sorted(COUNTS))
def test_a_prefill_s_rows_are_combined_a_choice_at_a_time(counts, dtype):
    """``combine`` by choice (K gathers of [T, D], what ``planned_experts``
    asks for with a plan of 128-row tiles) is the one gather of [T, K, D]
    to the last place, tokens that are not valid (no row: zero) included."""
    _, _, _, _, ids, w = _counted_case(7, COUNTS[counts])
    T, E = ids.shape[0], len(COUNTS[counts])
    valid = jnp.asarray(np.arange(T) % 11 != 3)
    plan = moe.plan_groups(ids, valid, E, 128)
    R = plan.row_token.shape[0]
    y = jnp.asarray(np.random.RandomState(8).randn(R, 32), dtype)
    one = moe.combine(y, w, plan)
    by_choice = jax.jit(lambda y, w: moe.combine(y, w, plan, by_choice=True)
                        )(y, w)
    assert by_choice.dtype == one.dtype == jnp.float32
    np.testing.assert_allclose(by_choice, one, rtol=0, atol=4e-6)
    assert np.all(np.asarray(by_choice)[~np.asarray(valid)] == 0)
    want = np.einsum("tk,tkd->td", np.asarray(w, np.float64), np.where(
        np.asarray(valid)[:, None, None],
        np.asarray(y, np.float64)[np.minimum(np.asarray(plan.row_of),
                                             R - 1)], 0.0))
    np.testing.assert_allclose(by_choice, want, atol=1e-5)


@pytest.mark.parametrize("act", sorted(GATES))
def test_the_gate_is_the_one_asked_for_and_names_the_kernel(act):
    x, wg, wu, wd, ids, w = _case(1)
    valid = jnp.ones((x.shape[0],), bool)
    other = next(a for a in GATES if a != act)

    def fn(*a):
        return moe.routed_experts(*a, impl="pallas", act=act)[0]

    y = jax.jit(fn)(x, ids, w, valid, wg, wu, wd)
    # the other gate's result is another result: a swap cannot hide
    wrong = _dense(x, wg, wu, wd, ids, w, valid, other)
    assert np.abs(np.asarray(y) - wrong).max() > 0.1
    names = [e.params["name"] for e in eqns_under(jax.make_jaxpr(fn)(
        x, ids, w, valid, wg, wu, wd).jaxpr)
        if e.primitive.name == "pallas_call"]
    assert names == [f"moe_grouped_{NAMES[act]}"]


def test_an_unknown_gate_is_refused():
    x, wg, wu, wd, ids, w = _case(2)
    with pytest.raises(ValueError, match="gate activation"):
        moe.routed_experts(x, ids, w, jnp.ones((x.shape[0],), bool), wg, wu,
                           wd, act="gelu")


@pytest.mark.parametrize("counts", [None, "mixed", "one_expert"])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("act", sorted(GATES))
def test_a_layer_of_a_stack_is_reached_by_the_map_and_not_sliced(act, impl,
                                                                 counts):
    """Planned before the experts' input exists (the router read an earlier
    activation): ``plan_groups`` first, ``planned_experts`` with the stack and
    a traced layer index after — a step's plan (``counts`` None) and a
    prefill's, each by its walk."""
    layers, layer = 3, 2
    if counts is None:
        x, wg, wu, wd, ids, w = _case(3, layers=layers)
    else:
        x, wg, wu, wd, ids, w = _counted_case(3, COUNTS[counts],
                                              layers=layers)
    T, E = x.shape[0], wg.shape[1]
    valid = jnp.asarray(np.arange(T) != 7)
    tile = moe.row_tile(T, x.dtype)
    assert tile == (8 if counts is None else 128)

    def fn(x, ids, w, valid, wg, wu, wd, layer):
        plan = moe.plan_groups(ids, valid, E, tile)
        return moe.planned_experts(x, w, plan, wg, wu, wd, tile, impl=impl,
                                   act=act, layer=layer)

    y = jax.jit(fn)(x, ids, w, valid, wg, wu, wd, jnp.int32(layer))
    np.testing.assert_allclose(
        y, _dense(x, wg[layer], wu[layer], wd[layer], ids, w, valid, act),
        atol=1e-4)
    if impl == "pallas":
        # the kernel is handed the whole stack, as layers x experts matrices
        call, = [e for e in eqns_under(jax.make_jaxpr(fn)(
            x, ids, w, valid, wg, wu, wd, jnp.int32(layer)).jaxpr)
            if e.primitive.name == "pallas_call"]
        shapes = [tuple(v.aval.shape) for v in call.invars]
        assert (layers * E,) + tuple(wg.shape[2:]) in shapes


# -- the router as one function: the score, a selection bias that chooses and
# does not weigh, the renormalisation's epsilon --
def _old_route_topk(logits, k, scale=1.0, normalize=False):
    """``route_topk`` as its two softmax callers had it before the score
    became an argument."""
    s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    chosen, ids = jax.lax.top_k(s, k)
    if normalize:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return ids.astype(jnp.int32), chosen * jnp.float32(scale)


@pytest.mark.parametrize("k,scale,normalize,dtype", [
    (6, 1.0, False, "float32"),         # decode/mla.py's call
    (6, 1.0, True, "float32"),          # decode/smallthinker.py's
    (3, 2.5, True, "bfloat16")], ids=["mla", "smallthinker", "scaled_bf16"])
def test_the_softmax_callers_keep_their_results_to_the_bit(k, scale,
                                                           normalize, dtype):
    logits = jnp.asarray(np.random.default_rng(k).standard_normal((37, 64)),
                         dtype)
    for fn in (lambda f: f, jax.jit):
        ids, w = fn(lambda l: moe.route_topk(l, k, scale, normalize))(logits)
        ids0, w0 = fn(lambda l: _old_route_topk(l, k, scale, normalize))(
            logits)
        np.testing.assert_array_equal(ids, ids0)
        np.testing.assert_array_equal(w, w0)


def test_a_sigmoid_router_s_bias_chooses_and_does_not_weigh():
    rng = np.random.default_rng(4)
    r = rng.standard_normal((200, 16)).astype(np.float32)
    bias = (rng.standard_normal(16) * 0.3).astype(np.float32)
    ids, w = moe.route_topk(jnp.asarray(r), 4, 1.0, True, score="sigmoid",
                            bias=jnp.asarray(bias), eps=1e-6)
    s = 1.0 / (1.0 + np.exp(-r.astype(np.float64)))
    want_ids = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :4]
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    chosen = np.take_along_axis(s, np.asarray(ids), 1)
    np.testing.assert_allclose(
        w, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
    # the bias changed the chosen set of many tokens ...
    plain_ids, plain_w = moe.route_topk(jnp.asarray(r), 4, 1.0, True,
                                        score="sigmoid", eps=1e-6)
    turned = (np.sort(plain_ids, -1) != np.sort(ids, -1)).any(-1)
    assert 0.3 < turned.mean() < 1.0
    # ... and where it did not, it moved no weight (the order of the four
    # is the order of score + bias)
    same = ~turned
    np.testing.assert_allclose(np.sort(np.asarray(w)[same], -1),
                               np.sort(np.asarray(plain_w)[same], -1),
                               rtol=1e-6)
    # weights of s + b would be another model's
    other = np.take_along_axis(s + bias, np.asarray(ids), 1)
    assert np.abs(other / other.sum(-1, keepdims=True) - w).max() > 0.01


def test_the_router_s_score_scale_and_epsilon():
    r = jnp.asarray([[0.0, 2.0, -1.0, 1.0]], jnp.float32)
    ids, w = moe.route_topk(r, 2, 3.0, False, score="sigmoid")
    np.testing.assert_array_equal(ids, [[1, 3]])
    s = 1.0 / (1.0 + np.exp(-np.array([2.0, 1.0])))
    np.testing.assert_allclose(w[0], 3.0 * s, rtol=1e-6)
    _, wn = moe.route_topk(r, 2, 1.0, True, score="sigmoid", eps=0.5)
    np.testing.assert_allclose(wn[0], s / (s.sum() + 0.5), rtol=1e-6)
    with pytest.raises(ValueError, match="score"):
        moe.route_topk(r, 2, score="tanh")


# -- a share of a layer's experts (``first``) --------------------------------
def _loop_plan(ids, valid, experts, tile, first=0):
    """What a plan is, one assignment at a time: (row_token, row_of, tile
    → expert of the tiles that hold a row, padded sizes, load)."""
    ids, valid = np.asarray(ids), np.asarray(valid)
    T, K = ids.shape
    R = moe.plan_rows(T, K, experts, tile)
    row_token, row_of = np.full(R, T), np.full((T, K), R)
    counts, tiles, r = [], [], 0
    for e in range(experts):
        mine = [(t, k) for t in range(T) for k in range(K)
                if valid[t] and ids[t, k] - first == e]
        for j, (t, k) in enumerate(mine):
            row_token[r + j], row_of[t, k] = t, r + j
        counts.append(len(mine))
        tiles += [e] * -(-len(mine) // tile)
        r += -(-len(mine) // tile) * tile
    counts = np.asarray(counts)
    return (row_token, row_of, np.asarray(tiles), -(-counts // tile) * tile,
            [counts.sum(), (counts > 0).sum(), counts.max()])


@pytest.mark.parametrize("tile", [8, 128])
def test_a_whole_layer_s_plan_is_the_share_from_zero_one_row_at_a_time(tile):
    x, wg, wu, wd, ids, w = _case(11, T=300 if tile == 128 else 40)
    valid = jnp.arange(ids.shape[0]) % 7 != 3
    got = moe.plan_groups(ids, valid, 8, tile)
    for a, b in zip(got, moe.plan_groups(ids, valid, 8, tile, first=0)):
        np.testing.assert_array_equal(a, b)
    row_token, row_of, tiles, padded, load = _loop_plan(ids, valid, 8, tile)
    np.testing.assert_array_equal(got.row_token, row_token)
    np.testing.assert_array_equal(got.row_of, row_of)
    assert int(got.active_tiles[0]) == len(tiles)
    np.testing.assert_array_equal(got.tile_expert[:len(tiles)], tiles)
    np.testing.assert_array_equal(got.padded_sizes, padded)
    np.testing.assert_array_equal(got.load, load)


@pytest.mark.parametrize("tile,T", [(8, 40), (128, 300)],
                         ids=["a_step_s_tiles", "a_prefill_s_tiles"])
def test_the_shares_plan_their_own_experts_and_add_up_to_the_layer(tile, T):
    x, wg, wu, wd, ids, w = _case(12, T=T, E=16, K=4)
    valid = jnp.arange(T) % 5 != 2
    whole = moe.planned_experts(
        x, w, moe.plan_groups(ids, valid, 16, tile), wg, wu, wd, tile)
    total, loads = 0.0, []
    for first in (0, 4, 8, 12):
        plan = moe.plan_groups(ids, valid, 4, tile, first=jnp.int32(first))
        held = np.asarray((ids >= first) & (ids < first + 4)
                          & valid[:, None])
        # an assignment to an expert held elsewhere gets no row
        R = plan.row_token.shape[0]
        np.testing.assert_array_equal(np.asarray(plan.row_of) < R, held)
        assert int(plan.load[0]) == held.sum()
        sl = slice(first, first + 4)
        total = total + moe.planned_experts(
            x, w, plan, wg[sl], wu[sl], wd[sl], tile)
        loads.append(np.asarray(plan.load))
    np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)
    assert sum(int(a[0]) for a in loads) == int(valid.sum()) * 4
    # routed_experts takes the share by its stacks and ``first``
    part, load = moe.routed_experts(x, ids, w, valid, wg[4:8], wu[4:8],
                                    wd[4:8], first=4)
    np.testing.assert_array_equal(load, loads[1])
    if tile == 8:
        assert moe.row_tile(T, x.dtype) == 8
        plan = moe.plan_groups(ids, valid, 4, tile, first=4)
        np.testing.assert_allclose(part, moe.planned_experts(
            x, w, plan, wg[4:8], wu[4:8], wd[4:8], tile), rtol=1e-6)


def _sorted_plan(ids, valid, experts, tile, first):
    """The plan by a NumPy stable sort: the valid, held assignments in expert
    order (ties in the order ``t * K + k``), each expert's run padded to the
    tile — all six fields of ``GroupPlan``."""
    T, K = ids.shape
    R = moe.plan_rows(T, K, experts, tile)
    local = ids.astype(np.int64) - first
    key = np.where(valid[:, None] & (local >= 0) & (local < experts), local,
                   experts).reshape(-1)
    order = np.argsort(key, kind="stable")
    counts = np.bincount(key, minlength=experts + 1)[:experts]
    padded = -(-counts // tile) * tile
    pend = np.cumsum(padded)
    row_token, row_of, at = np.full(R, T), np.full(T * K, R), 0
    for e in range(experts):
        mine = order[at:at + counts[e]]
        rows = pend[e] - padded[e] + np.arange(counts[e])
        row_token[rows], row_of[mine] = mine // K, rows
        at += counts[e]
    active = max(-(-int(pend[-1]) // tile), 1)
    tile_expert = np.repeat(np.arange(experts), padded // tile)
    # the tiles past the last real one repeat it; with no row at all the map
    # points at the last expert, whose matrices no tile computes with
    fill = tile_expert[-1] if len(tile_expert) else experts - 1
    tile_expert = np.concatenate(
        [tile_expert, np.full(R // tile - len(tile_expert), fill)])
    return moe.GroupPlan(row_token, row_of.reshape(T, K), tile_expert,
                         [active], padded,
                         [counts.sum(), (counts > 0).sum(), counts.max()])


# (tokens, choices a token, experts planned, the router's width, first, and
# whether ``first`` reaches the plan as a traced scalar)
PLAN_SHAPES = {
    "a_step_6_of_64": (64, 6, 64, 64, 0, False),
    "a_step_4_of_64": (64, 4, 64, 64, 0, False),
    "a_step_8_of_256_share_0": (64, 8, 64, 256, 0, False),
    "a_step_8_of_256_share_traced": (64, 8, 64, 256, 128, True),
    "2048_by_6_of_64": (2048, 6, 64, 64, 0, False),
    "3000_by_8_of_256_share_traced": (3000, 8, 64, 256, 64, True),
}
PLAN_CASES = (
    [(s, t, v, i) for s in sorted(PLAN_SHAPES) if s.startswith("a_step")
     for t in (8, 16, 128) for v in ("all", "tail", "none")
     for i in ("uniform", "one_expert", "none_held")]
    + [(s, 128, v, i) for s in sorted(PLAN_SHAPES) if s[0].isdigit()
       for v in ("all", "tail", "none")
       for i in ("uniform", "one_expert", "none_held")]
    + [(s, 16, "tail", "uniform") for s in sorted(PLAN_SHAPES)
       if s[0].isdigit()])


@pytest.mark.parametrize("shape,tile,valid,ids", PLAN_CASES,
                         ids=["-".join(map(str, c)) for c in PLAN_CASES])
def test_the_plan_is_the_stable_sort_s_to_the_integer(shape, tile, valid,
                                                       ids):
    T, K, E, wide, first, traced = PLAN_SHAPES[shape]
    rng = np.random.RandomState(len(shape) + tile)
    if ids == "uniform":
        chosen = np.argsort(rng.rand(T, wide), axis=1)[:, :K]
    elif ids == "one_expert":
        chosen = np.full((T, K), first + 3)
    else:           # every choice an expert held elsewhere (or none's at all)
        chosen = (first + E + rng.randint(0, E, (T, K))) % max(wide, 2 * E)
        assert not ((chosen >= first) & (chosen < first + E)).any()
    ok = {"all": np.ones(T, bool), "tail": np.arange(T) < T - T // 7,
          "none": np.zeros(T, bool)}[valid]
    chosen = chosen.astype(np.int32)
    plan = (jax.jit(moe.plan_groups, static_argnums=(2, 3)) if traced
            else moe.plan_groups)
    got = plan(jnp.asarray(chosen), jnp.asarray(ok), E, tile,
               jnp.int32(first) if traced else first)
    want = _sorted_plan(chosen, ok, E, tile, first)
    for name, a, b in zip(moe.GroupPlan._fields, got, want):
        assert a.dtype == jnp.int32, name
        np.testing.assert_array_equal(a, np.asarray(b).reshape(a.shape),
                                      err_msg=name)


# -- the ungated unit: act(x W_upᵀ) W_down, two matrices, both [E, F, D] ------
def _ungated_case(seed, T=40, D=32, F=48, E=8, K=3, layers=None, counts=None,
                  dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    lead = () if layers is None else (layers,)
    if counts is not None:
        E, T = len(counts), sum(counts) // K
    x = jnp.asarray(rng.randn(T, D), dtype)
    wu = jnp.asarray(rng.randn(*lead, E, F, D) * D ** -0.5, dtype)
    wd = jnp.asarray(rng.randn(*lead, E, F, D) * F ** -0.5, dtype)
    if counts is None:
        ids, w = moe.route_topk(jnp.asarray(rng.randn(T, E), jnp.float32), K,
                                normalize=True)
    else:
        ids = jnp.asarray(rng.permutation(np.repeat(
            np.arange(E), counts)).reshape(-1, K), jnp.int32)
        w = rng.rand(T, K) + 0.1
        w = jnp.asarray(w / w.sum(-1, keepdims=True), jnp.float32)
    return x, wu, wd, ids, w


def _dense_relu2(x, wu, wd, ids, w):
    x, wu, wd, w = (np.asarray(a, np.float64) for a in (x, wu, wd, w))
    out = np.zeros(x.shape)
    for e in range(wu.shape[0]):
        share = (w * (np.asarray(ids) == e)).sum(-1, keepdims=True)
        out += share * ((np.maximum(x @ wu[e].T, 0.0) ** 2) @ wd[e])
    return out


# (tokens or an expert's counts, D, F): a step's tile walk and a prefill's
# expert walk at a small size, and both at NVIDIA-Nemotron-3-Nano's published
# expert — 2,688 x 1,856, no whole number of lane tiles
UNGATED_CASES = {
    "step": (dict(T=40), 8), "prefill": (dict(counts=COUNTS["mixed"], K=2),
                                         128),
    "step_published": (dict(T=24, D=2688, F=1856, E=4, K=2), 8),
    "prefill_published": (dict(counts=[130, 0, 1, 125], D=2688, F=1856,
                               K=2), 128)}


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("case", sorted(UNGATED_CASES))
def test_the_ungated_unit_is_relu2_of_two_matrices(case, impl):
    kw, tile = UNGATED_CASES[case]
    x, wu, wd, ids, w = _ungated_case(11, **kw)
    valid = jnp.ones((x.shape[0],), bool)
    plan = moe.plan_groups(ids, valid, wu.shape[0], tile)

    def fn(x, wu, wd, w):
        return moe.planned_experts(x, w, plan, None, wu, wd, tile, impl=impl,
                                   act="relu2")

    before = stats.snapshot()
    y = np.asarray(jax.jit(fn)(x, wu, wd, w))
    want = _dense_relu2(x, wu, wd, ids, w)
    assert np.abs(y - want).max() < 2e-5 * np.abs(want).max()
    names = [e.params["name"] for e in eqns_under(jax.make_jaxpr(fn)(
        x, wu, wd, w).jaxpr) if e.primitive.name == "pallas_call"]
    assert names == (["moe_grouped_relu2"] if impl == "pallas" else [])
    after = stats.snapshot()
    walk = "expert" if tile == 128 else "tile"
    counter = "moe.grouped_relu2_fallbacks" if impl == "xla" \
        else f"moe.grouped_relu2_{walk}_walks"
    assert after.get(counter, 0) > before.get(counter, 0)
    # a gated unit in its place is another result
    gated = _dense(x, np.swapaxes(wu, -1, -2), np.swapaxes(wu, -1, -2), wd,
                   ids, w, np.ones(x.shape[0]), "silu")
    assert np.abs(y - gated).max() > 0.05 * np.abs(want).max()


def test_the_ungated_unit_takes_no_gate_and_a_gated_one_needs_it():
    x, wu, wd, ids, w = _ungated_case(12)
    valid = jnp.ones((x.shape[0],), bool)
    with pytest.raises(ValueError, match="two matrices"):
        moe.routed_experts(x, ids, w, valid, wu, wu, wd, act="relu2")
    with pytest.raises(ValueError, match="three matrices"):
        moe.routed_experts(x, ids, w, valid, None, wu, wd, act="silu")
    # two matrices fit VMEM where three of the same width do not
    assert moe.f_block(4096, 4096, 2, matrices=2) == 2048
    assert moe.f_block(4096, 4096, 2) == 1024
    assert moe.f_block(2688, 1856, 2, matrices=2) == 1856


@pytest.mark.parametrize("tile,kw", [(8, dict(T=40)),
                                     (128, dict(counts=COUNTS["ends_empty"],
                                                K=2))],
                         ids=["step", "prefill"])
def test_a_layer_of_an_ungated_stack_and_a_share_of_it(tile, kw):
    """The stack is handed over whole with the layer's index, in bf16 rows
    out, and the held half of the experts computes its own part."""
    x, wu, wd, ids, w = _ungated_case(13, layers=3, **kw)
    valid = jnp.ones((x.shape[0],), bool)
    E = wu.shape[1]
    whole = _dense_relu2(x, wu[1], wd[1], ids, w)
    parts = []
    for first in (0, E // 2):
        plan = moe.plan_groups(ids, valid, E // 2, tile, first=first)
        parts.append(np.asarray(moe.planned_experts(
            x, w, plan, None, wu[:, first:first + E // 2],
            wd[:, first:first + E // 2], tile, act="relu2", layer=1)))
    assert np.abs(parts[0] + parts[1] - whole).max() \
        < 2e-5 * np.abs(whole).max()
    assert np.abs(parts[0]).max() > 0 and np.abs(parts[1]).max() > 0
