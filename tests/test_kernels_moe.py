"""The grouped expert kernel (``kernels/moe.py``) in interpret mode against
``lax.ragged_dot`` and against the definition, a gate at a time: ONE kernel
whose gate is an argument (``silu``: SwiGLU, ``relu``: ReGLU), named after
it; and the stacked form, in which a layer's experts are reached through the
tile→expert map and never sliced out of the stack."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import moe
from paddle_tpu.observability import stats
from paged_walks import eqns_under

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
    y0, load0 = moe.routed_experts(x, ids, w, valid, wg, wu, wd, impl="xla",
                                   act=act)
    assert stats.to_dict()[counter] == before + 1
    y1, load1 = jax.jit(lambda *a: moe.routed_experts(
        *a, impl="pallas", act=act))(x, ids, w, valid, wg, wu, wd)
    assert stats.to_dict()[counter] == before + 1
    np.testing.assert_allclose(y1, y0, atol=1e-4)
    np.testing.assert_allclose(y1, _dense(x, wg, wu, wd, ids, w, valid, act),
                               atol=1e-4)
    assert np.asarray(load0).tolist() == np.asarray(load1).tolist()
    assert int(load0[0]) == 33 * 3 and np.all(np.asarray(y1)[33:] == 0)


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


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("act", sorted(GATES))
def test_a_layer_of_a_stack_is_reached_by_the_map_and_not_sliced(act, impl):
    """Planned before the experts' input exists (the router read an earlier
    activation): ``plan_groups`` first, ``planned_experts`` with the stack and
    a traced layer index after."""
    layers, layer = 3, 2
    x, wg, wu, wd, ids, w = _case(3, layers=layers)
    T, E = x.shape[0], wg.shape[1]
    valid = jnp.asarray(np.arange(T) != 7)
    tile = moe.row_tile(T, x.dtype)

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
