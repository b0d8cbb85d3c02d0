"""``softmax_with_cross_entropy`` writes nothing of the classes' width beside
the logits it is handed: the forward takes the label's logit from the logits,
the grad rule is the closed form ``(softmax - onehot) * g`` from ``Logits``,
``Label`` and ``Loss@GRAD`` alone.  Both against the expression they replaced
(kept here, not in the package) and its ``jax.vjp``; a program that
differentiates through ``Softmax`` still takes the old ``vjp_grad``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from op_harness import check_grad
from paddle_tpu.core import unique_name
from paddle_tpu.core.executor import Executor, Scope, scope_guard
from paddle_tpu.core.program import Program, program_guard
from paddle_tpu.core.registry import LowerContext
from paddle_tpu.models import transformer
from paddle_tpu.observability import stats
from paddle_tpu.ops import nn_ops

L = fluid.layers
V = 11
IGNORED = 3


def _old_xent(logits, label, attrs):
    """The lowering before the closed form: a ``log_softmax`` tensor in the
    statistics' dtype, the label's entry gathered out of THAT."""
    sdt = jnp.promote_types(logits.dtype, jnp.float32)
    lse = jax.nn.logsumexp(logits.astype(sdt), axis=-1, keepdims=True)
    log_softmax = logits.astype(sdt) - lse
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * log_softmax, axis=-1, keepdims=True)
    else:
        li = label.squeeze(-1) if label.ndim >= 2 and label.shape[-1] == 1 \
            else label
        li = li.astype(jnp.int32)
        picked = jnp.take_along_axis(log_softmax, li[..., None], axis=-1)
        if attrs.get("ignore_index", -100) != -100:
            picked = picked * (li[..., None] != attrs["ignore_index"]).astype(
                log_softmax.dtype)
        loss = -picked
    return {"Softmax": jnp.exp(log_softmax).astype(logits.dtype),
            "Loss": loss.astype(logits.dtype)}


def _case(dtype, lead, label_form, soft, ignore):
    rng = np.random.RandomState(len(lead) * 7 + soft * 3 + ignore)
    logits = jnp.asarray(rng.uniform(-3, 3, lead + (V,)), dtype)
    if soft:
        p = rng.uniform(0.1, 1.0, lead + (V,))
        # rows that do not sum to one: the closed form carries sum(label)
        label = jnp.asarray(p / p.sum(-1, keepdims=True) * 1.25, dtype)
    else:
        trailing, int_dtype = label_form
        ids = rng.randint(0, V, lead + (1,) * trailing)
        ids.reshape(-1)[::3] = IGNORED
        label = jnp.asarray(ids, int_dtype)
    attrs = {"soft_label": soft, "ignore_index": IGNORED if ignore else -100}
    g = jnp.asarray(rng.uniform(-2, 2, lead + (1,)), dtype)  # non-uniform
    return logits, label, attrs, g


CASES = [
    pytest.param(dtype, lead, form, soft, ignore,
                 id="-".join([np.dtype(dtype).name, f"{len(lead) + 1}d",
                              "soft" if soft else
                              f"{'col' if form[0] else 'flat'}-{form[1]}",
                              "ignore" if ignore else "all"]))
    for dtype in ("float64", "float32", jnp.bfloat16)
    for lead in ((6,), (2, 3))
    for soft, forms in ((False, [(1, "int32"), (1, "int64"), (0, "int32"),
                                 (0, "int64")]), (True, [None]))
    for form in forms
    for ignore in ((False, True) if not soft else (False,))
]
GRAD_TOL = {"float64": 1e-12, "float32": 2e-6, "bfloat16": 8e-3}


@pytest.mark.parametrize("dtype, lead, form, soft, ignore", CASES)
def test_forward_equals_the_expression_it_replaced(dtype, lead, form, soft,
                                                   ignore):
    logits, label, attrs, _ = _case(dtype, lead, form, soft, ignore)
    got = nn_ops._softmax_xent(LowerContext(), {"Logits": [logits],
                                                "Label": [label]}, attrs)
    want = _old_xent(logits, label, attrs)
    for slot in ("Loss", "Softmax"):
        assert got[slot][0].dtype == want[slot].dtype == logits.dtype
        assert got[slot][0].shape == want[slot].shape
        # the same operations on the same values: equal to the bit, bf16 too
        np.testing.assert_array_equal(np.asarray(got[slot][0], np.float64),
                                      np.asarray(want[slot], np.float64))
    if ignore:
        assert (np.asarray(got["Loss"][0], np.float64) == 0).any()


@pytest.mark.parametrize("dtype, lead, form, soft, ignore", CASES)
def test_closed_form_grad_equals_the_vjp_of_the_old_expression(
        dtype, lead, form, soft, ignore):
    logits, label, attrs, g = _case(dtype, lead, form, soft, ignore)
    old, pull = jax.vjp(lambda x: _old_xent(x, label, attrs)["Loss"], logits)
    (want,) = pull(g)
    # the rule reads neither of the forward's outputs: poison both
    ins = {"Logits": [logits], "Label": [label], "Loss@GRAD": [g],
           "Softmax": [jnp.full_like(logits, jnp.nan)],
           "Loss": [jnp.full_like(old, jnp.nan)]}
    out = nn_ops._softmax_xent_grad(LowerContext(), ins, attrs)
    assert list(out) == ["Logits@GRAD"]
    (got,) = out["Logits@GRAD"]
    assert got.dtype == logits.dtype and got.shape == logits.shape
    tol = GRAD_TOL[np.dtype(dtype).name]
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64),
                               rtol=tol, atol=tol)
    if ignore:
        rows = np.asarray(label).reshape(lead) == IGNORED
        assert rows.any() and not np.asarray(got, np.float64)[rows].any()


@pytest.mark.parametrize("kind", ["soft", "ignore", "3d"])
def test_closed_form_grad_against_finite_differences(kind):
    """Through a program and the executor, in float64: independent of the old
    expression (tests/test_op_grads.py holds the plain 2-D case)."""
    rng = np.random.RandomState(3)
    lead = (2, 3) if kind == "3d" else (4,)
    logits = rng.uniform(-1, 1, lead + (6,))
    if kind == "soft":
        p = rng.uniform(0.1, 1.0, lead + (6,))
        label = p / p.sum(-1, keepdims=True)
    else:
        label = rng.randint(0, 6, lead + (1,)).astype("int64")
    check_grad(
        lambda v: L.softmax_with_cross_entropy(
            v["x"], v["label"], soft_label=kind == "soft",
            ignore_index=int(label.reshape(-1)[0]) if kind == "ignore"
            else -100),
        {"x": logits, "label": label}, wrt=["x"])


def _build(build_fn, seed):
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = seed
    with program_guard(prog, startup), unique_name.guard():
        return prog, startup, build_fn()


def _counters():
    scope = stats.scope("loss")
    return (scope.counter("xent_closed_form_grads"),
            scope.counter("xent_vjp_fallback_grads"))


def _classifier(through_softmax):
    x = L.data("x", [8])
    y = L.data("y", [1], dtype="int64")
    logits = L.fc(x, 5)
    loss, softmax = L.softmax_with_cross_entropy(logits, y,
                                                 return_softmax=True)
    cost = L.mean(loss)
    if through_softmax:     # a confidence penalty: Softmax@GRAD reaches the op
        cost = L.elementwise_add(
            cost, L.scale(L.reduce_sum(L.square(softmax)), 0.05))
    fluid.optimizer.SGD(0.5).minimize(cost)
    return cost, logits, softmax


@pytest.mark.parametrize("through_softmax", [False, True])
def test_a_gradient_through_softmax_takes_the_old_rule_and_trains(
        through_softmax):
    """Which rule ran is chosen from what the grad op was handed
    (``Softmax@GRAD`` in its inputs), and either trains; the logits'
    gradient equals ``jax.grad`` of the same cost."""
    prog, startup, (cost, logits, _) = _build(
        lambda: _classifier(through_softmax), 11)
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(32, 8).astype("float32"),
            "y": rng.randint(0, 5, (32, 1)).astype("int64")}
    closed, fallback = _counters()
    before = closed.value, fallback.value
    dlogits = prog.global_block.var(logits.name + "@GRAD")
    with scope_guard(Scope()):
        exe = Executor()
        exe.run(startup)
        first, z, dz = exe.run(prog, feed=feed,
                               fetch_list=[cost, logits, dlogits])
        costs = [float(exe.run(prog, feed=feed, fetch_list=[cost])[0])
                 for _ in range(20)]
    assert costs[-1] < 0.8 * float(first)
    # counted once a lowering, and the two fetch lists are two programs
    assert (closed.value - before[0], fallback.value - before[1]) == \
        ((0, 2) if through_softmax else (2, 0))

    def same_cost(z):
        logp = jax.nn.log_softmax(z)
        c = -jnp.mean(jnp.take_along_axis(logp, jnp.asarray(feed["y"]), -1))
        return c + 0.05 * jnp.sum(jnp.exp(logp) ** 2) if through_softmax else c

    np.testing.assert_allclose(dz, jax.grad(same_cost)(jnp.asarray(z)),
                               rtol=2e-4, atol=1e-6)


def test_the_transformers_loss_takes_the_closed_form():
    prog, startup, (names, loss, _) = _build(lambda: transformer.build(
        src_vocab=32, tgt_vocab=32, max_len=8, d_model=16, n_head=2,
        d_ffn=32, n_layer=1, dropout=0.0, warmup_steps=10,
        dtype="bfloat16"), 5)
    rng = np.random.RandomState(0)
    feed = {n: np.ones((4, 8), "float32") if n.endswith("mask")
            else rng.randint(0, 32, (4, 8)).astype("int64") for n in names}
    closed, fallback = _counters()
    before = closed.value, fallback.value
    with scope_guard(Scope()):
        exe = Executor()
        exe.run(startup)
        losses = [float(exe.run(prog, feed=feed, fetch_list=[loss])[0])
                  for _ in range(12)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert (closed.value - before[0], fallback.value - before[1]) == (1, 0)
