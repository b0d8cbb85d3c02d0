"""``softmax_with_cross_entropy`` writes nothing of the classes' width beside
the logits it is handed: the forward takes the label's logit from the logits,
the grad rule is the closed form ``(softmax - onehot) * g`` from ``Logits``,
``Label`` and ``Loss@GRAD`` alone.  Both against the expression they replaced
(kept here, not in the package) and its ``jax.vjp``; a program that
differentiates through ``Softmax`` still takes the old ``vjp_grad``."""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from op_harness import check_grad
from paddle_tpu.core import unique_name
from paddle_tpu.core.executor import (Executor, Scope, _as_device_array,
                                      scope_guard)
from paddle_tpu.core.lowering import analyze_block, build_block_fn
from paddle_tpu.core.program import Program, program_guard
from paddle_tpu.core.registry import LowerContext
from paddle_tpu.kernels import xent
from paddle_tpu.models import transformer
from paddle_tpu.observability import stats
from paddle_tpu.ops import math_ops, nn_ops
from paddle_tpu.parallel import BuildStrategy, ParallelExecutor

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


def test_the_transformers_loss_takes_the_fused_op_and_its_closed_form():
    """``transformer.build`` has ONE head: the projection and the loss as
    ``fc_softmax_with_cross_entropy`` (on the CPU its fallback, counted when
    the op is lowered), whose grad rule is the closed form; neither of the
    pair's rules is lowered any more."""
    prog, startup, (names, loss, _) = _build(lambda: transformer.build(
        src_vocab=32, tgt_vocab=32, max_len=8, d_model=16, n_head=2,
        d_ffn=32, n_layer=1, dropout=0.0, warmup_steps=10,
        dtype="bfloat16"), 5)
    rng = np.random.RandomState(0)
    feed = {n: np.ones((4, 8), "float32") if n.endswith("mask")
            else rng.randint(0, 32, (4, 8)).astype("int64") for n in names}
    closed, fallback = _counters()
    chose = _xent_choice()
    before = [c.value for c in (closed, fallback) + chose]
    with scope_guard(Scope()):
        exe = Executor()
        exe.run(startup)
        losses = [float(exe.run(prog, feed=feed, fetch_list=[loss])[0])
                  for _ in range(12)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert [c.value - b for c, b in zip((closed, fallback) + chose, before)] \
        == [0, 0, 0, 1]
    types = [op.type for op in prog.global_block.ops]
    assert types.count("fc_softmax_with_cross_entropy") == 1
    assert types.count("fc_softmax_with_cross_entropy_grad") == 1
    assert "softmax_with_cross_entropy" not in types
    assert prog.global_block.var("tgt.out_proj").shape == (16, 32)


# ---------------------------------------------------------------------------
# fc_softmax_with_cross_entropy: the projection and the loss as one op, held
# to the ``mul`` + ``softmax_with_cross_entropy`` pair it stands for
# ---------------------------------------------------------------------------

D_IN = 7


def _fused_case(dtype, lead, label_form, ignore):
    logits, label, attrs, g = _case(dtype, lead, label_form, False, ignore)
    rng = np.random.RandomState(len(lead))
    x = jnp.asarray(rng.uniform(-1, 1, lead + (D_IN,)), dtype)
    w = jnp.asarray(rng.uniform(-1, 1, (D_IN, V)), dtype)
    return x, w, label, dict(attrs, x_num_col_dims=len(lead)), g


def _pair(x, w, label, attrs):
    """The two ops' lowerings, one after the other."""
    ctx = LowerContext()
    logits = math_ops._mul(ctx, {"X": [x], "Y": [w]}, {
        "x_num_col_dims": attrs["x_num_col_dims"], "y_num_col_dims": 1})
    return nn_ops._softmax_xent(
        ctx, {"Logits": logits["Out"], "Label": [label]},
        {"soft_label": False, "ignore_index": attrs["ignore_index"]})["Loss"][0]


FUSED_CASES = [p for p in CASES if not p.values[3]]     # hard labels


@pytest.mark.parametrize("dtype, lead, form, soft, ignore", FUSED_CASES)
def test_the_fused_op_is_the_pair_it_stands_for(dtype, lead, form, soft,
                                                ignore):
    x, w, label, attrs, g = _fused_case(dtype, lead, form, ignore)
    out = nn_ops._fc_softmax_xent(
        LowerContext(), {"X": [x], "W": [w], "Label": [label]}, attrs)
    assert sorted(out) == ["LSE", "Logits", "Loss"]
    (loss,), (lse,), (logits,) = out["Loss"], out["LSE"], out["Logits"]
    assert loss.dtype == logits.dtype == x.dtype
    assert lse.dtype == jnp.promote_types(x.dtype, jnp.float32)
    assert (loss.shape, lse.shape, logits.shape) == \
        (lead + (1,), lead + (1,), lead + (V,))
    # the fallback IS the pair's expression: equal to the bit
    want, pull = jax.vjp(lambda x, w: _pair(x, w, label, attrs), x, w)
    np.testing.assert_array_equal(np.asarray(loss, np.float64),
                                  np.asarray(want, np.float64))
    grads = nn_ops._fc_softmax_xent_grad(LowerContext(), {
        "X": [x], "W": [w], "Label": [label], "Loss@GRAD": [g],
        "Logits": [logits], "LSE": [lse],
        "Loss": [jnp.full_like(loss, jnp.nan)]}, attrs)
    assert sorted(grads) == ["W@GRAD", "X@GRAD"]
    tol = GRAD_TOL[np.dtype(dtype).name] * (4 if dtype == "float32" else 1)
    for got, want, like in zip((grads["X@GRAD"][0], grads["W@GRAD"][0]),
                               pull(g), (x, w)):
        assert got.dtype == like.dtype and got.shape == like.shape
        np.testing.assert_allclose(np.asarray(got, np.float64),
                                   np.asarray(want, np.float64),
                                   rtol=8 * tol, atol=8 * tol)
    if ignore:
        rows = np.asarray(label).reshape(lead) == IGNORED
        assert rows.any() and not np.asarray(loss, np.float64)[rows].any()


def test_the_fused_grad_rule_reads_the_forwards_lse():
    """Handed a log-sum-exp ``ln 2`` too large, the rule's softmax halves: it
    took ``LSE`` as it was handed, and computed none of its own."""
    x, w, label, attrs, g = _fused_case("float32", (6,), (1, "int32"), False)
    out = nn_ops._fc_softmax_xent(
        LowerContext(), {"X": [x], "W": [w], "Label": [label]}, attrs)

    def dx(lse):
        return nn_ops._fc_softmax_xent_grad(LowerContext(), {
            "X": [x], "W": [w], "Label": [label], "Loss@GRAD": [g],
            "Logits": out["Logits"], "LSE": [lse]}, attrs)["X@GRAD"][0]

    classes = jnp.arange(V)[None, :] == label.reshape(-1, 1)
    softmax = jax.nn.softmax(out["Logits"][0], axis=-1)
    for shift, scale in ((0.0, 1.0), (np.log(2.0), 0.5)):
        want = ((softmax * scale - classes) * g) @ w.T
        np.testing.assert_allclose(np.asarray(dx(out["LSE"][0] + shift)),
                                   np.asarray(want), rtol=2e-5, atol=2e-6)


# one whole row block of the kernel's, in sequences of 256
HEAD = dict(B=xent.ROW_BLOCK // 256, T=256, D=128, V=300)


def _head(fused, ignore_index=-100, rows="float32", **size):
    """The end of ``models/transformer.build`` over activations fed as data,
    with the one op or with the pair, on the same parameter."""
    s = dict(HEAD, **size)
    x = L.data("x", [s["T"], s["D"]], dtype=rows, stop_gradient=False)
    lbl = L.unsqueeze(L.data("lbl_ids", [s["T"]], dtype="int64"), [2])
    attr = fluid.ParamAttr(name="tgt.out_proj")
    with fluid.name_scope("out_proj"):
        if fused:
            loss = L.fc_softmax_with_cross_entropy(
                x, lbl, s["V"], num_flatten_dims=2, param_attr=attr,
                ignore_index=ignore_index)
        else:
            loss = L.softmax_with_cross_entropy(
                L.fc(x, s["V"], num_flatten_dims=2, bias_attr=False,
                     param_attr=attr), lbl, ignore_index=ignore_index)
    cost = L.reduce_sum(L.elementwise_mul(L.squeeze(loss, [2]),
                                          L.data("weight", [s["T"]])))
    fluid.optimizer.SGD(0.1).minimize(cost)
    return loss, cost


def _head_feed(rows=jnp.float32, **size):
    s = dict(HEAD, **size)
    rng = np.random.RandomState(1)
    lbl = rng.randint(0, s["V"], (s["B"], s["T"])).astype("int64")
    lbl[:, ::5] = IGNORED
    return {"x": jnp.asarray(rng.randn(s["B"], s["T"], s["D"]), rows),
            "lbl_ids": lbl,
            "weight": rng.uniform(0.5, 1.5, (s["B"], s["T"])).astype("float32")}


def _run_head(fused, ignore_index, feed, rows="float32", **size):
    prog, startup, (loss, cost) = _build(
        lambda: _head(fused, ignore_index, rows, **size), 9)
    fetch = [loss, cost, prog.global_block.var("x@GRAD"),
             prog.global_block.var("tgt.out_proj@GRAD")]
    with scope_guard(Scope()):
        exe = Executor()
        exe.run(startup)
        return prog, [np.asarray(v, np.float64) for v in
                      exe.run(prog, feed=feed, fetch_list=fetch)]


def _xent_choice():
    scope = stats.scope("loss")
    return (scope.counter("proj_xent_kernel"),
            scope.counter("proj_xent_fallbacks"))


def _as_on_a_tpu(monkeypatch):
    """The op's choice made as on a TPU; the kernel it then takes runs in
    interpret mode here."""
    real = nn_ops._proj_xent_impl
    monkeypatch.setattr(nn_ops, "_proj_xent_impl",
                        lambda backend, *a, **kw: real("tpu", *a, **kw))


@pytest.mark.parametrize("ignore_index", [-100, IGNORED], ids=["all", "ignore"])
def test_the_fused_program_equals_the_pairs_program(ignore_index):
    """Loss, ``X@GRAD`` and ``W@GRAD`` through the executor, the same weights
    (one parameter name, one seed); on the CPU the op takes the fallback and
    counts it."""
    size = dict(B=2, T=6, D=8, V=13)
    feed = _head_feed(**size)
    kernel, fallback = _xent_choice()
    before = kernel.value, fallback.value
    prog, fused = _run_head(True, ignore_index, feed, **size)
    assert (kernel.value - before[0], fallback.value - before[1]) == (0, 1)
    _, pair = _run_head(False, ignore_index, feed, **size)
    for got, want in zip(fused, pair):
        assert np.abs(want).max() > 0
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    if ignore_index == IGNORED:
        ignored = feed["lbl_ids"] == IGNORED
        assert not fused[0][ignored].any() and not fused[2][ignored].any()
        assert fused[0][~ignored].all()
    types = [op.type for op in prog.global_block.ops]
    assert "fc_softmax_with_cross_entropy_grad" in types
    assert not {"mul", "softmax_with_cross_entropy", "mul_grad"} & set(types)


def test_the_fused_programs_clone_for_test_runs_the_op_alone():
    """A clone pruned to the loss — what an evaluation runs — keeps the op
    with its three outputs and drops its grad op; its loss is the training
    program's forward loss on the same weights."""
    size = dict(B=2, T=6, D=8, V=13)
    feed = _head_feed(**size)
    prog, startup, (loss, _) = _build(lambda: _head(True, IGNORED, **size), 9)
    test_prog = prog.clone().prune([loss.name])
    ops = test_prog.global_block.ops
    assert [op.type for op in ops].count("fc_softmax_with_cross_entropy") == 1
    assert not any(op.type.endswith("_grad") or op.type == "sgd" for op in ops)
    fused = [op for op in ops if op.type == "fc_softmax_with_cross_entropy"][0]
    assert sorted(fused.outputs) == ["LSE", "Logits", "Loss"]
    with scope_guard(Scope()):
        exe = Executor()
        exe.run(startup)
        feed_eval = {k: v for k, v in feed.items() if k != "weight"}
        (evaluated,) = exe.run(test_prog, feed=feed_eval, fetch_list=[loss])
        (trained,) = exe.run(prog, feed=feed, fetch_list=[loss])
        (after,) = exe.run(test_prog, feed=feed_eval, fetch_list=[loss])
    np.testing.assert_array_equal(evaluated, trained)
    assert np.abs(after - evaluated).max() > 0      # the step moved tgt.out_proj


@pytest.mark.parametrize("rows", ["float32", "bfloat16"])
def test_the_kernel_in_a_program_equals_the_fallback(monkeypatch, rows):
    """The op's choice made as on a TPU, at rows that fill one row block:
    the kernel (interpret mode) through the executor, forward and grad op,
    against the same program on the fallback; rows that do not fill a block
    fall back and are counted so."""
    feed = _head_feed(jnp.float32 if rows == "float32" else jnp.bfloat16)
    # a bf16 parameter under float32 activations, as transformer.build
    # (dtype="bfloat16") hands them: the variable is declared bf16
    _, want = _run_head(True, IGNORED, feed, "bfloat16")
    kernel, fallback = _xent_choice()
    _as_on_a_tpu(monkeypatch)
    before = kernel.value, fallback.value
    _, got = _run_head(True, IGNORED, feed, "bfloat16")
    assert (kernel.value - before[0], fallback.value - before[1]) == (1, 0)
    # the kernel rounds float32 rows to bf16, as the TPU's product does and
    # the CPU's does not; bf16 rows differ by the product's last bit
    for a, b in zip(got, want):
        scale = np.abs(b).max()
        assert scale > 0
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-2 * scale)
    short = dict(B=HEAD["B"] - 1)           # a sequence short of a block
    _run_head(True, IGNORED, _head_feed(**short), "bfloat16", **short)
    assert (kernel.value - before[0], fallback.value - before[1]) == (1, 1)


def test_the_kernel_under_a_dp_mesh_is_the_one_device_result(monkeypatch):
    """``ParallelExecutor`` dp = 4 over CPU devices, a row block a shard: the
    op wraps the kernel per shard (``w`` whole on each) and the loss and the
    updated parameter are one device's; a plain ``Executor`` over the scope
    the mesh placed takes the fallback, as ``fused_attention`` does there."""
    size = dict(B=4 * HEAD["B"])
    feed = _head_feed(**size)
    _as_on_a_tpu(monkeypatch)
    kernel, fallback = _xent_choice()
    results = {}
    for dp in (1, 4):
        prog, startup, (loss, cost) = _build(
            lambda: _head(True, IGNORED, "bfloat16", **size), 9)
        scope, exe = Scope(), Executor()
        exe.run(startup, scope=scope)
        before = kernel.value, fallback.value
        if dp == 1:
            out = exe.run(prog, feed=feed, fetch_list=[loss, cost],
                          scope=scope)
        else:
            pe = ParallelExecutor(
                loss_name=cost.name, main_program=prog, scope=scope,
                places=jax.devices()[:dp],
                build_strategy=BuildStrategy(mesh_shape={"dp": dp}))
            out = pe.run(feed=feed, fetch_list=[loss.name, cost.name])
        assert (kernel.value - before[0], fallback.value - before[1]) == (1, 0)
        results[dp] = [np.asarray(v, np.float64) for v in out] + [
            np.asarray(scope.find_var("tgt.out_proj"), np.float64)]
        if dp > 1:
            test_prog = prog.clone().prune([loss.name])
            feed_eval = {k: v for k, v in feed.items() if k != "weight"}
            (evaluated,) = exe.run(test_prog, feed=feed_eval,
                                   fetch_list=[loss], scope=scope)
            assert exe._spans_devices and np.isfinite(evaluated).all()
            assert (kernel.value - before[0],
                    fallback.value - before[1]) == (1, 1)
            pe.close()
        exe.close()
    # the parameter is bf16 and its gradient a sum over four shards, rounded
    # to bf16 before the update: a few ulps
    for (one, four), tol in zip(zip(results[1], results[4]),
                                (2e-5, 2e-5, 2 ** -6)):
        np.testing.assert_allclose(four, one, rtol=tol, atol=tol * 1e-1)


def _lowered_names(prog, startup, feed, fetch):
    """``op_name``s of the program's lowering BEFORE any optimisation."""
    scope = Scope()
    with scope_guard(scope):
        Executor().run(startup)
        names = sorted(feed)
        plan = analyze_block(prog, 0, names, [f.name for f in fetch])
        block = prog.global_block
        vals = [_as_device_array(feed[n], block.var_or_none(n)) for n in names]
        state = [[np.asarray(scope.find_var(n)) for n in reads]
                 for reads in (plan.donated_reads, plan.const_reads)]
        text = jax.jit(build_block_fn(prog, plan)).lower(
            vals, *state, jax.random.PRNGKey(0)).as_text(debug_info=True)
    return collections.Counter(
        re.findall(r'loc\("jit\(fn_s1\)/([^"]*)"', text))


def test_the_lowered_backward_holds_no_reduction_over_the_classes():
    """The forward (here the fallback) reduces over the classes once for the
    maximum and once for the sum; the grad op's half holds the exponential,
    the one-hot and the two products, and NO reduction, logarithm or second
    ``logsumexp``: it read ``LSE``."""
    size = dict(B=2, T=6, D=8, V=13)
    prog, startup, (_, cost) = _build(lambda: _head(True, **size), 9)
    # both gradients fetched: jit drops what nothing reads before it lowers
    names = _lowered_names(prog, startup, _head_feed(**size),
                           [cost, prog.global_block.var("x@GRAD")])
    fwd, bwd = ("fwd/out_proj/fc_softmax_with_cross_entropy",
                "bwd/out_proj/fc_softmax_with_cross_entropy_grad")
    ours = {n for n in names if "fc_softmax_with_cross_entropy" in n}
    assert {n.rsplit("/", 1)[0].split("/jit(")[0] for n in ours} == {fwd, bwd}
    for primitive in ("reduce_max", "reduce_sum", "log", "dot_general"):
        assert names[f"{fwd}/{primitive}"] == 1, primitive
    for primitive in ("exp", "iota", "eq"):
        assert names[f"{bwd}/{primitive}"] == 1, primitive
    assert names[f"{bwd}/dot_general"] == 2
    assert not any(n.startswith(bwd) and n.rsplit("/", 1)[1] in
                   ("reduce_max", "reduce_sum", "log", "logsumexp",
                    "stop_gradient") for n in names)


def test_the_pipelines_cost_of_the_fused_op_is_its_product():
    """Stage balancing (``pipeline/transpiler.py``) counts the one op as the
    ``mul`` it holds, not as its outputs' elements."""
    from paddle_tpu.pipeline.transpiler import op_flops_estimate
    size = dict(B=2, T=6, D=8, V=13)
    cost = {}
    for fused, kind in ((True, "fc_softmax_with_cross_entropy"),
                        (False, "mul")):
        prog, _, _ = _build(lambda: _head(fused, **size), 9)
        (op,) = [op for op in prog.global_block.ops if op.type == kind]
        cost[fused] = op_flops_estimate(prog.global_block, op, batch=2)
    assert cost[True] == cost[False] == 2.0 * 2 * 6 * 13 * 8
