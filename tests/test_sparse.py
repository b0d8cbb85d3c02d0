"""SelectedRows sparse-gradient stack tests.

Parity model (reference test strategy: test_sgd_op.py sparse cases,
test_adam_op.py TestSparseAdamOp): the sparse path must produce the same
trained parameters as the dense path on identical programs, including
duplicate ids, regularization, and global-norm clipping.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import unique_name
from paddle_tpu.core.executor import Executor, Scope, scope_guard
from paddle_tpu.core.program import Program, program_guard
from paddle_tpu.core.types import VarType

V, D = 50, 8


def _merge_ref(rows, vals, height):
    dense = np.zeros((height,) + vals.shape[1:], vals.dtype)
    np.add.at(dense, rows, vals)
    return dense


def test_merge_rows_sums_duplicates():
    import jax.numpy as jnp
    from paddle_tpu.core.selected_rows import SelectedRows, merge_rows

    rng = np.random.RandomState(0)
    rows = np.array([3, 1, 3, 7, 1, 3], np.int64)
    vals = rng.randn(6, 4).astype(np.float64)
    m = merge_rows(SelectedRows(jnp.asarray(rows), jnp.asarray(vals), 10))
    got = np.zeros((10, 4))
    r, v = np.asarray(m.rows), np.asarray(m.values)
    for i in range(len(r)):
        if r[i] < 10:
            assert got[r[i]].sum() == 0, "duplicate row in merged output"
            got[r[i]] += v[i]
    np.testing.assert_allclose(got, _merge_ref(rows, vals, 10), rtol=1e-12)
    # sentinel slots: exactly n - n_unique of them
    assert (r == 10).sum() == 6 - 3


def _train(optimizer_fn, is_sparse, steps=4, regularizer=None, clip=None,
           seed=0, cover_all=False):
    """Train a tiny embedding+fc model; return the final embedding table.

    ``cover_all``: every table row appears in every batch — required for
    exact dense parity of *lazy* accumulator optimizers (momentum/adam),
    whose sparse path deliberately skips accumulator decay on untouched
    rows (reference adam_op.h SelectedRows semantics).
    """
    rng = np.random.RandomState(seed)
    prog, startup = Program(), Program()
    prog.random_seed = 5
    with program_guard(prog, startup), unique_name.guard():
        ids = fluid.layers.data("ids", [6], dtype="int64")
        label = fluid.layers.data("label", [1])
        emb = fluid.layers.embedding(
            ids, [V, D], is_sparse=is_sparse,
            param_attr=fluid.ParamAttr(
                name="emb.w",
                initializer=fluid.initializer.Uniform(-0.5, 0.5),
                regularizer=regularizer))
        pooled = fluid.layers.reduce_sum(emb, dim=1)
        pred = fluid.layers.fc(pooled, 1,
                               param_attr=fluid.ParamAttr(name="fc.w"))
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, label))
        if clip is not None:
            fluid.clip.set_gradient_clip(clip)
        optimizer_fn().minimize(loss)
        if clip is not None:
            fluid.clip.set_gradient_clip(None)
    exe = Executor()
    sc = Scope()
    with scope_guard(sc):
        exe.run(startup)
        for i in range(steps):
            if cover_all:
                # [10, 6] = 60 slots: every one of the V=50 rows appears,
                # plus 10 random duplicates
                flat = np.concatenate(
                    [rng.permutation(V), rng.randint(0, V, 10)])
                idb = flat.reshape(10, 6).astype("int64")
                lb = rng.randn(10, 1).astype("float32")
            else:
                # duplicate ids inside one batch on purpose
                idb = rng.randint(0, V, (3, 6)).astype("int64")
                idb[:, 0] = idb[:, 1]
                lb = rng.randn(3, 1).astype("float32")
            exe.run(prog, feed={"ids": idb, "label": lb}, fetch_list=[loss])
        w = np.asarray(sc.find_var("emb.w"))
    return w


@pytest.mark.parametrize("opt,cover_all", [
    (lambda: fluid.optimizer.SGD(0.1), False),
    (lambda: fluid.optimizer.Adagrad(0.1), False),
    # lazy accumulator optimizers: exact parity needs full row coverage
    (lambda: fluid.optimizer.Momentum(0.1, 0.9), True),
    (lambda: fluid.optimizer.Adam(0.1), True),
])
def test_sparse_dense_optimizer_parity(opt, cover_all):
    wd = _train(opt, is_sparse=False, cover_all=cover_all)
    ws = _train(opt, is_sparse=True, cover_all=cover_all)
    np.testing.assert_allclose(ws, wd, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("opt,cover_all", [
    (lambda: fluid.optimizer.Adagrad(0.1), False),
    (lambda: fluid.optimizer.Momentum(0.1, 0.9), True),
    (lambda: fluid.optimizer.Adam(0.1), True),
])
def test_sparse_sorted_fallback_parity(opt, cover_all):
    """Force the large-table sorted merge_rows path (the branch production
    tables above FLAGS_sparse_dense_update_max_elems take) and check it
    matches the dense reference too."""
    from paddle_tpu.core import flags
    old = flags.get_flags("sparse_dense_update_max_elems")
    flags.set_flags({"sparse_dense_update_max_elems": 0})
    try:
        wd = _train(opt, is_sparse=False, cover_all=cover_all)
        ws = _train(opt, is_sparse=True, cover_all=cover_all)
    finally:
        flags.set_flags({"sparse_dense_update_max_elems": old})
    np.testing.assert_allclose(ws, wd, rtol=1e-5, atol=1e-6)


def test_sparse_parity_with_l2_and_global_norm_clip():
    reg = fluid.regularizer.L2Decay(0.05)
    mk = lambda: fluid.optimizer.Adam(0.05)
    # cover_all: L2 decay on the sparse path is lazy (touched rows only),
    # so exact dense parity needs every row touched every step
    wd = _train(mk, False, regularizer=reg, cover_all=True,
                clip=fluid.clip.GradientClipByGlobalNorm(0.7))
    ws = _train(mk, True, regularizer=reg, cover_all=True,
                clip=fluid.clip.GradientClipByGlobalNorm(0.7))
    np.testing.assert_allclose(ws, wd, rtol=1e-5, atol=1e-6)


def test_sparse_update_touches_only_looked_up_rows():
    """Rows never looked up must keep their initial values (the whole point
    of the sparse path) — including under L2 decay AND global-norm clipping,
    whose intermediate vars must stay SelectedRows end to end."""
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        ids = fluid.layers.data("ids", [4], dtype="int64")
        emb = fluid.layers.embedding(
            ids, [V, D], is_sparse=True,
            param_attr=fluid.ParamAttr(
                name="emb.w", regularizer=fluid.regularizer.L2Decay(0.1)))
        loss = fluid.layers.mean(emb)
        fluid.clip.set_gradient_clip(fluid.clip.GradientClipByGlobalNorm(0.5))
        fluid.optimizer.Adam(0.5).minimize(loss)
        fluid.clip.set_gradient_clip(None)
    exe = Executor()
    sc = Scope()
    with scope_guard(sc):
        exe.run(startup)
        w0 = np.asarray(sc.find_var("emb.w")).copy()
        idb = np.array([[1, 2, 3, 1], [2, 4, 5, 5]], "int64")
        exe.run(prog, feed={"ids": idb}, fetch_list=[loss])
        w1 = np.asarray(sc.find_var("emb.w"))
    touched = sorted(set(idb.ravel().tolist()))
    untouched = [i for i in range(V) if i not in touched]
    assert not np.allclose(w1[touched], w0[touched]), "touched rows unchanged"
    np.testing.assert_array_equal(w1[untouched], w0[untouched])


def test_negative_padding_idx_counts_from_end():
    """padding_idx=-1 must pad row V-1 (reference nn.py: size[0]+idx), not
    silently disable padding."""
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        ids = fluid.layers.data("ids", [3], dtype="int64")
        emb = fluid.layers.embedding(
            ids, [V, D], padding_idx=-1,
            param_attr=fluid.ParamAttr(
                name="emb.w",
                initializer=fluid.initializer.Constant(1.0)))
        out = fluid.layers.reduce_sum(emb, dim=2)
    exe = Executor()
    with scope_guard(Scope()):
        exe.run(startup)
        (o,) = exe.run(prog, feed={"ids": np.array([[V - 1, 0, V - 1]],
                                                   "int64")},
                       fetch_list=[out])
    np.testing.assert_allclose(np.asarray(o), [[0.0, D, 0.0]])


def test_grad_var_is_marked_selected_rows():
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        ids = fluid.layers.data("ids", [4], dtype="int64")
        emb = fluid.layers.embedding(
            ids, [V, D], is_sparse=True,
            param_attr=fluid.ParamAttr(name="emb.w"))
        loss = fluid.layers.mean(emb)
        fluid.optimizer.SGD(0.1).minimize(loss)
    gv = prog.global_block.var("emb.w@GRAD")
    assert gv.type == VarType.SELECTED_ROWS


def test_unsupported_sparse_optimizer_raises():
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        ids = fluid.layers.data("ids", [4], dtype="int64")
        emb = fluid.layers.embedding(ids, [V, D], is_sparse=True)
        loss = fluid.layers.mean(emb)
        fluid.optimizer.Ftrl(0.1).minimize(loss)
    exe = Executor()
    with scope_guard(Scope()):
        exe.run(startup)
        with pytest.raises(NotImplementedError, match="sparse"):
            exe.run(prog, feed={"ids": np.zeros((2, 4), "int64")},
                    fetch_list=[loss])


def test_is_distributed_requires_sparse_grads():
    """The sharded-table path moves SelectedRows slices; a dense gradient
    for a distributed table is rejected loudly (no silent downgrade)."""
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        ids = fluid.layers.data("ids", [4], dtype="int64")
        with pytest.raises(ValueError, match="is_sparse"):
            fluid.layers.embedding(ids, [V, D], is_distributed=True,
                                   is_sparse=False)
        # supported spelling: builds a lookup_table op marked for the
        # DistributeTranspiler's prefetch rewrite
        out = fluid.layers.embedding(ids, [V, D], is_distributed=True,
                                     is_sparse=True)
        (op,) = [o for o in prog.global_block.ops
                 if o.type == "lookup_table"]
        assert op.attr("is_distributed") is True
        assert out.shape[-1] == D


def test_sparse_grads_under_dp_mesh():
    """Sparse (SelectedRows) grads must survive GSPMD lowering: losses on a
    dp=8 mesh with dp-sharded id feeds match single-device training."""
    from paddle_tpu.parallel import BuildStrategy, ParallelExecutor

    def build():
        prog, startup = Program(), Program()
        prog.random_seed = 11
        with program_guard(prog, startup), unique_name.guard():
            ids = fluid.layers.data("ids", [6], dtype="int64")
            label = fluid.layers.data("label", [1])
            emb = fluid.layers.embedding(
                ids, [V, D], is_sparse=True,
                param_attr=fluid.ParamAttr(
                    name="emb.w",
                    initializer=fluid.initializer.Uniform(-0.5, 0.5)))
            pooled = fluid.layers.reduce_sum(emb, dim=1)
            pred = fluid.layers.fc(pooled, 1,
                                   param_attr=fluid.ParamAttr(name="fc.w"))
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, label))
            fluid.optimizer.SGD(0.1).minimize(loss)
        return prog, startup, loss

    rng = np.random.RandomState(7)
    batches = [(rng.randint(0, V, (16, 6)).astype("int64"),
                rng.randn(16, 1).astype("float32")) for _ in range(6)]

    prog, startup, loss = build()
    exe = Executor()
    with scope_guard(Scope()):
        exe.run(startup)
        single = [float(exe.run(prog, feed={"ids": i, "label": l},
                                fetch_list=[loss])[0]) for i, l in batches]

    prog, startup, loss = build()
    exe = Executor()
    with scope_guard(Scope()):
        exe.run(startup)
        pe = ParallelExecutor(loss_name=loss.name, main_program=prog,
                              build_strategy=BuildStrategy(
                                  mesh_shape={"dp": 8}))
        multi = [float(np.asarray(
            pe.run(feed={"ids": i, "label": l}, fetch_list=[loss.name])[0]))
            for i, l in batches]
    np.testing.assert_allclose(multi, single, rtol=2e-4, atol=1e-5)


def test_deepfm_large_table_trains():
    """DeepFM CTR with a 1M-row sparse table: the step must run without ever
    materialising the dense [1M, D] gradient, and the loss must drop."""
    from paddle_tpu.models import deepfm

    prog, startup = Program(), Program()
    prog.random_seed = 3
    with program_guard(prog, startup), unique_name.guard():
        feeds, avg_cost, _ = deepfm.build(sparse_dim=int(1e6), lr=1e-3)
    rng = np.random.RandomState(0)
    feed = {
        "dense": rng.rand(16, 13).astype("float32"),
        "sparse": rng.randint(0, int(1e6), (16, 26)).astype("int64"),
        "label": (rng.rand(16, 1) > 0.5).astype("float32"),
    }
    exe = Executor()
    with scope_guard(Scope()):
        exe.run(startup)
        losses = []
        for i in range(8):
            (l,) = exe.run(prog, feed=feed, fetch_list=[avg_cost])
            losses.append(float(l))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# FLAGS_sparse_fused_kernel: fused Pallas gather/update parity suite
# (interpret mode — the CPU tier-1 coverage of kernels/sparse.py)
# ---------------------------------------------------------------------------

FV, FD, FN = 23, 5, 17  # shared shapes so eager pallas jits cache across tests


def _fused_flag(on):
    from paddle_tpu.core import flags
    flags.set_flags({"sparse_fused_kernel": bool(on)})


def _mk_sr(seed=0, dyadic=False, n=FN):
    import jax.numpy as jnp
    from paddle_tpu.core.selected_rows import SelectedRows

    rng = np.random.RandomState(seed)
    rows = rng.randint(0, FV, n).astype(np.int32)
    if n >= 2:
        rows[1] = rows[0]  # guaranteed duplicate
    if dyadic:
        vals = rng.randint(-8, 8, (n, FD)).astype(np.float32)
    else:
        vals = rng.randn(n, FD).astype(np.float32)
    return SelectedRows(jnp.asarray(rows), jnp.asarray(vals), FV)


def _opt_rule(name):
    from paddle_tpu.core import registry
    return registry.get(name).lower


def _rule_ins(extra, seed=1):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    ins = {"LearningRate": [jnp.asarray(np.float32(0.1))]}
    for slot, positive in extra.items():
        a = rng.randn(FV, FD).astype(np.float32)
        ins[slot] = [jnp.asarray(np.abs(a) if positive else a)]
    return ins


@pytest.mark.parametrize("op,slots,attrs,extra_ins", [
    ("adam", ("ParamOut", "Moment1Out", "Moment2Out"),
     {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8},
     {"Param": False, "Moment1": True, "Moment2": True}),
    ("momentum", ("ParamOut", "VelocityOut"), {"mu": 0.9},
     {"Param": False, "Velocity": False}),
    ("adagrad", ("ParamOut", "MomentOut"), {"epsilon": 1e-6},
     {"Param": False, "Moment": True}),
])
def test_fused_update_matches_sorted_reference(op, slots, attrs, extra_ins):
    """Interpret-mode parity vs the sorted merge_rows path on the same
    duplicate-bearing batch.  Tolerance is one-ulp class (the two lowerings
    may fuse/contract elementwise chains differently); the dyadic test
    below pins the duplicate-merge semantics bit-exactly."""
    import jax.numpy as jnp
    from paddle_tpu.core import flags
    from paddle_tpu.core.registry import LowerContext

    sr = _mk_sr()
    ins = _rule_ins(extra_ins)
    ins["Grad"] = [sr]
    if op == "adam":
        ins["Beta1Pow"] = [jnp.asarray(np.float32(0.9))]
        ins["Beta2Pow"] = [jnp.asarray(np.float32(0.999))]
    ctx = LowerContext()
    rule = _opt_rule(op)
    old = flags.get_flags("sparse_dense_update_max_elems")
    try:
        _fused_flag(False)
        flags.set_flags({"sparse_dense_update_max_elems": 0})  # sorted path
        ref = rule(ctx, ins, attrs)
        _fused_flag(True)
        got = rule(ctx, ins, attrs)
    finally:
        _fused_flag(False)
        flags.set_flags({"sparse_dense_update_max_elems": old})
    for slot in slots:
        np.testing.assert_allclose(
            np.asarray(got[slot][0]), np.asarray(ref[slot][0]),
            rtol=2e-6, atol=1e-6, err_msg=f"{op}.{slot}")


def test_fused_update_duplicate_exactness_dyadic():
    """Duplicate-id exactness, bit-for-bit: with power-of-two constants and
    integer-valued inputs every op is exact, so ANY semantic error (missed
    duplicate, wrong row, reordered merge) shows as a hard mismatch."""
    import jax.numpy as jnp
    from paddle_tpu.kernels import sparse as S

    sr = _mk_sr(seed=3, dyadic=True, n=9)
    rng = np.random.RandomState(4)
    p = jnp.asarray(rng.randint(-16, 16, (FV, FD)).astype(np.float32))
    v = jnp.asarray(rng.randint(-16, 16, (FV, FD)).astype(np.float32))
    _fused_flag(True)
    try:
        out = S.fused_momentum(p, v, sr, jnp.float32(0.5), 0.5, False)
    finally:
        _fused_flag(False)
    assert out is not None
    pn, vn = np.asarray(out[0]), np.asarray(out[1])
    pr, vr = np.asarray(p).copy(), np.asarray(v).copy()
    merged = {}
    for r, gv in zip(np.asarray(sr.rows), np.asarray(sr.values)):
        merged[int(r)] = merged.get(int(r), 0) + gv
    for r, gsum in merged.items():
        vr[r] = 0.5 * vr[r] + gsum
        pr[r] = pr[r] - 0.5 * vr[r]
    np.testing.assert_array_equal(pn, pr)
    np.testing.assert_array_equal(vn, vr)
    untouched = [i for i in range(FV) if i not in merged]
    np.testing.assert_array_equal(pn[untouched], np.asarray(p)[untouched])


def test_fused_update_empty_batch():
    import jax.numpy as jnp
    from paddle_tpu.core.selected_rows import SelectedRows
    from paddle_tpu.kernels import sparse as S

    p = jnp.ones((FV, FD), jnp.float32)
    m = jnp.ones((FV, FD), jnp.float32)
    sr = SelectedRows(jnp.zeros((0,), jnp.int32),
                      jnp.zeros((0, FD), jnp.float32), FV)
    _fused_flag(True)
    try:
        out = S.fused_adam(p, m, m, sr, jnp.float32(0.1), 0.9, 0.999, 1e-8)
        g = S.fused_gather([p], jnp.zeros((0,), jnp.int32))
    finally:
        _fused_flag(False)
    assert out is not None and g is not None
    for t in out:
        np.testing.assert_array_equal(np.asarray(t), np.asarray(p))
    assert g[0].shape == (0, FD)


def test_fused_gather_out_of_range_matches_take():
    """Ids beyond [-H, H) NaN-fill exactly like jnp.take mode="fill" —
    ids come from user feed data, so a data bug must fail as loudly on
    the fused path as it does flag-off (the NaN sentinel fires; nothing
    silently trains a clamped row)."""
    import jax.numpy as jnp
    from paddle_tpu.kernels import sparse as S

    t = jnp.arange(float(FV * FD)).reshape(FV, FD)
    ids = jnp.asarray([0, FV, -1, -FV, -FV - 1, 3], jnp.int32)
    _fused_flag(True)
    try:
        (got,) = S.fused_gather([t], ids)
    finally:
        _fused_flag(False)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.take(t, ids, axis=0)))


def test_fused_fallback_on_fault_counts_and_recovers(monkeypatch):
    """A kernel build fault degrades to the sorted path (same numerics),
    never a failed step, and the fallback is counted."""
    import jax.numpy as jnp
    from paddle_tpu.core import flags
    from paddle_tpu.core.registry import LowerContext
    from paddle_tpu.kernels import sparse as S
    from paddle_tpu.observability import stats as obs

    sr = _mk_sr(seed=5)
    ins = _rule_ins({"Param": False, "Moment": True}, seed=6)
    ins["Grad"] = [sr]
    ctx = LowerContext()
    rule = _opt_rule("adagrad")
    old = flags.get_flags("sparse_dense_update_max_elems")
    try:
        _fused_flag(False)
        flags.set_flags({"sparse_dense_update_max_elems": 0})
        ref = rule(ctx, ins, {"epsilon": 1e-6})

        def boom(*a, **k):
            raise RuntimeError("injected kernel build fault")

        monkeypatch.setattr(S, "_rowwise_update", boom)
        before = obs.to_dict().get("sparse_fused.update_fallbacks", 0)
        _fused_flag(True)
        got = rule(ctx, ins, {"epsilon": 1e-6})
        after = obs.to_dict().get("sparse_fused.update_fallbacks", 0)
    finally:
        _fused_flag(False)
        flags.set_flags({"sparse_dense_update_max_elems": old})
    assert after == before + 1, (before, after)
    for slot in ("ParamOut", "MomentOut"):
        np.testing.assert_array_equal(np.asarray(got[slot][0]),
                                      np.asarray(ref[slot][0]))


def _two_table_program(adam_lr=0.1):
    prog, startup = Program(), Program()
    prog.random_seed = 5
    with program_guard(prog, startup), unique_name.guard():
        ids = fluid.layers.data("ids", [5], dtype="int64")
        label = fluid.layers.data("label", [1])
        emb = fluid.layers.embedding(
            ids, [V, D], is_sparse=True,
            param_attr=fluid.ParamAttr(
                name="t.emb", initializer=fluid.initializer.Uniform(-.5, .5)))
        emb1 = fluid.layers.embedding(
            ids, [V, 1], is_sparse=True,
            param_attr=fluid.ParamAttr(
                name="t.w1", initializer=fluid.initializer.Uniform(-.5, .5)))
        pooled = fluid.layers.reduce_sum(emb, dim=1)
        first = fluid.layers.reduce_sum(emb1, dim=1)
        pred = fluid.layers.fc(pooled, 1,
                               param_attr=fluid.ParamAttr(name="t.fc"))
        pred = fluid.layers.elementwise_add(pred, first)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, label))
        fluid.optimizer.Adam(adam_lr).minimize(loss)
    return prog, startup, loss


def _jaxpr_census(jaxpr):
    from paddle_tpu.kernels.sparse import jaxpr_census
    return jaxpr_census(jaxpr)


def _whole_step_census(flag_on):
    import jax
    from paddle_tpu.core.lowering import analyze_block, build_block_fn

    _fused_flag(flag_on)
    try:
        prog, startup, loss = _two_table_program()
        exe = Executor()
        sc = Scope()
        with scope_guard(sc):
            exe.run(startup)
            plan = analyze_block(prog, 0, ["ids", "label"], [loss.name])
            fn = build_block_fn(prog, plan, training=True)
            feeds = [np.zeros((3, 5), np.int64), np.zeros((3, 1), np.float32)]
            donated = [np.asarray(sc.find_var(n)) for n in plan.donated_reads]
            const = [np.asarray(sc.find_var(n)) for n in plan.const_reads]
            jaxpr = jax.make_jaxpr(fn)(feeds, donated, const,
                                       jax.random.PRNGKey(0))
        return _jaxpr_census(jaxpr.jaxpr)
    finally:
        _fused_flag(False)


def test_fused_whole_step_kernel_count_pin():
    """THE structural pin (ISSUE 10 acceptance): the compiled two-table
    train step under FLAGS_sparse_fused_kernel carries <= 1 scatter-class
    launch per table — today it carries ZERO (the fused path has no
    scatter-class ops at all) plus exactly 3 pallas launches (1 multi-table
    gather + 2 row-wise updates).  Flag off, the masked-dense path's
    per-table scatter-add is visible — the census sees what it pins."""
    sc_on, pl_on = _whole_step_census(True)
    assert sc_on <= 2, f"scatter-class count {sc_on} > 1 per table"
    assert sc_on == 0, f"fused path regressed: {sc_on} scatter ops"
    assert pl_on == 3, f"expected 3 pallas launches, got {pl_on}"
    sc_off, pl_off = _whole_step_census(False)
    assert sc_off >= 2 and pl_off == 0, (sc_off, pl_off)


def test_fused_deepfm_step_trains_and_matches_unfused():
    """End-to-end executor parity: 4 fused train steps on the two-table
    model reproduce the flag-off run's tables (and the loss drops)."""

    rng = np.random.RandomState(7)
    idb = rng.randint(0, V, (3, 5)).astype("int64")
    idb[:, 0] = idb[:, 1]  # in-batch duplicates
    lb = rng.randn(3, 1).astype("float32")

    def train(flag):
        _fused_flag(flag)
        try:
            prog, startup, loss = _two_table_program(adam_lr=0.01)
            exe = Executor()
            sc = Scope()
            losses = []
            with scope_guard(sc):
                exe.run(startup)
                for _ in range(4):
                    (lv,) = exe.run(prog, feed={"ids": idb, "label": lb},
                                    fetch_list=[loss])
                    losses.append(float(lv))
                return (losses, np.asarray(sc.find_var("t.emb")).copy(),
                        np.asarray(sc.find_var("t.w1")).copy())
        finally:
            _fused_flag(False)

    l_off, emb_off, w1_off = train(False)
    l_on, emb_on, w1_on = train(True)
    assert np.isfinite(l_on).all() and l_on[-1] < l_on[0], l_on
    np.testing.assert_allclose(emb_on, emb_off, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(w1_on, w1_off, rtol=1e-6, atol=1e-7)


def test_fused_runtime_fault_recovery_disables_kernels():
    """The counted-fallback contract at the DISPATCH layer: a whole-step
    compile fault with the flag on (the class the trace-time try/except
    in kernels/sparse.py cannot see — Mosaic rejects something on a real
    TPU) re-lowers once WITHOUT the fused kernels, counted; with the
    flag off the lazy-jit fault re-raises untouched."""
    import jax
    from paddle_tpu.core import executor as ex_mod
    from paddle_tpu.core.lowering import analyze_block
    from paddle_tpu.observability import stats as obs

    _fused_flag(True)
    try:
        prog, startup, loss = _two_table_program()
        exe = Executor()
        sc = Scope()
        with scope_guard(sc):
            exe.run(startup)
            plan = analyze_block(prog, 0, ["ids", "label"], [loss.name])
            entry = ex_mod._CacheEntry(plan, None)  # lazy-jit entry
            # recovery gates on the entry's trace-time latch, not the flag
            entry.fused_used = {"sparse_fused": True}
            feeds = [np.zeros((3, 5), np.int64), np.zeros((3, 1), np.float32)]
            donated = [np.asarray(sc.find_var(n)) for n in plan.donated_reads]
            const = [np.asarray(sc.find_var(n)) for n in plan.const_reads]
            before = obs.to_dict().get("sparse_fused.runtime_disables", 0)
            jitted = exe._recover_disk_entry(
                entry, prog, RuntimeError("injected compile fault"), [])
            jaxpr = jax.make_jaxpr(jitted)(feeds, donated, const,
                                           jax.random.PRNGKey(0))
            after = obs.to_dict().get("sparse_fused.runtime_disables", 0)
        n_sc, n_pl = _jaxpr_census(jaxpr.jaxpr)
        assert n_pl == 0, f"recovery re-lower still has {n_pl} pallas calls"
        assert n_sc >= 2, "expected the masked-dense scatters back"
        assert after == before + 1, (before, after)
        assert entry.jitted is jitted

        # AOT/disk entries recover in two levels: _recover_disk_entry's
        # first re-lower keeps the fused kernels (the common fault is a
        # stale entry, not a kernel); if THAT faults too, the call site's
        # second-level _recover_fused_fault drops them — once per entry
        aot = ex_mod._CacheEntry(plan, None)
        aot.aot_ms = 1.0
        j1 = exe._recover_disk_entry(
            aot, prog, RuntimeError("stale entry"), [])
        jaxpr1 = jax.make_jaxpr(j1)(feeds, donated, const,
                                    jax.random.PRNGKey(0))
        assert _jaxpr_census(jaxpr1.jaxpr)[1] == 3  # fused still on
        j2 = exe._recover_fused_fault(
            aot, prog, RuntimeError("fused mosaic fault"), [])
        jaxpr2 = jax.make_jaxpr(j2)(feeds, donated, const,
                                    jax.random.PRNGKey(0))
        assert _jaxpr_census(jaxpr2.jaxpr)[1] == 0
        assert aot.fused_disabled
        with pytest.raises(RuntimeError, match="again"):
            exe._recover_fused_fault(
                aot, prog, RuntimeError("faults again"), [])

        # a lowering that emitted NO fused kernels re-raises untouched
        # even with the flag on (no wasted re-lower, no bogus count)
        with pytest.raises(RuntimeError, match="injected"):
            exe._recover_disk_entry(
                ex_mod._CacheEntry(plan, None), prog,
                RuntimeError("injected compile fault"), [])

        # flag flipped OFF after an entry traced WITH fused kernels:
        # the entry latch is authoritative, so it still recovers
        _fused_flag(False)
        late = ex_mod._CacheEntry(plan, None)
        late.fused_used = {"sparse_fused": True}
        j3 = exe._recover_disk_entry(
            late, prog, RuntimeError("late flag flip"), [])
        jaxpr3 = jax.make_jaxpr(j3)(feeds, donated, const,
                                    jax.random.PRNGKey(0))
        assert _jaxpr_census(jaxpr3.jaxpr)[1] == 0
    finally:
        _fused_flag(False)


def test_fused_lookup_gather_groups_by_ids():
    """The lowering peephole fuses only same-Ids sparse lookups; a lookup
    over different ids keeps its own gather, and outputs are bit-identical
    to the unfused forward."""
    import jax

    from paddle_tpu.core.lowering import analyze_block, build_block_fn

    def build():
        prog, startup = Program(), Program()
        prog.random_seed = 9
        with program_guard(prog, startup), unique_name.guard():
            ids = fluid.layers.data("ids", [4], dtype="int64")
            other = fluid.layers.data("other", [4], dtype="int64")
            a = fluid.layers.embedding(
                ids, [V, D], is_sparse=True,
                param_attr=fluid.ParamAttr(name="g.a"))
            b = fluid.layers.embedding(
                ids, [V, 1], is_sparse=True,
                param_attr=fluid.ParamAttr(name="g.b"))
            c = fluid.layers.embedding(
                other, [V, D], is_sparse=True,
                param_attr=fluid.ParamAttr(name="g.c"))
            out = fluid.layers.concat(
                [fluid.layers.reduce_sum(a, dim=2),
                 fluid.layers.reduce_sum(b, dim=2),
                 fluid.layers.reduce_sum(c, dim=2)], axis=1)
        return prog, startup, out

    def run(flag):
        _fused_flag(flag)
        try:
            prog, startup, out = build()
            exe = Executor()
            sc = Scope()
            with scope_guard(sc):
                exe.run(startup)
                plan = analyze_block(prog, 0, ["ids", "other"], [out.name])
                fn = build_block_fn(prog, plan, training=False)
                feeds = [np.arange(8).reshape(2, 4) % V,
                         (np.arange(8).reshape(2, 4) * 3) % V]
                const = [np.asarray(sc.find_var(n)) for n in plan.const_reads]
                donated = [np.asarray(sc.find_var(n))
                           for n in plan.donated_reads]
                import jax as _jax
                jaxpr = _jax.make_jaxpr(fn)(feeds, donated, const,
                                            _jax.random.PRNGKey(0))
                o, _, _ = fn(feeds, donated, const, _jax.random.PRNGKey(0))
            return _jaxpr_census(jaxpr.jaxpr), np.asarray(o[0])
        finally:
            _fused_flag(False)

    (sc_on, pl_on), o_on = run(True)
    (sc_off, pl_off), o_off = run(False)
    assert pl_on == 1, f"expected ONE fused gather launch, got {pl_on}"
    assert pl_off == 0
    np.testing.assert_array_equal(o_on, o_off)


def test_fused_lookup_gather_rejects_clobbered_group():
    """An op between two same-Ids lookups that WRITES one of the tables
    kills the fusion (hoisting the gather would read the stale table);
    semantics stay flag-off-identical."""
    import jax

    from paddle_tpu.core.lowering import analyze_block, build_block_fn

    def build():
        prog, startup = Program(), Program()
        prog.random_seed = 9
        with program_guard(prog, startup), unique_name.guard():
            ids = fluid.layers.data("ids", [4], dtype="int64")
            a = fluid.layers.embedding(
                ids, [V, D], is_sparse=True,
                param_attr=fluid.ParamAttr(name="c.a"))
            # overwrite grouped table c.a BETWEEN the two lookups: any
            # intervening write to a grouped var must kill the fusion
            bump = fluid.layers.fill_constant([V, D], "float32", 2.0)
            fluid.layers.assign(bump, output=prog.global_block.var("c.a"))
            b = fluid.layers.embedding(
                ids, [V, D], is_sparse=True,
                param_attr=fluid.ParamAttr(name="c.b"))
            out = fluid.layers.concat(
                [fluid.layers.reduce_sum(a, dim=2),
                 fluid.layers.reduce_sum(b, dim=2)], axis=1)
        return prog, startup, out

    def run(flag):
        _fused_flag(flag)
        try:
            prog, startup, out = build()
            exe = Executor()
            sc = Scope()
            with scope_guard(sc):
                exe.run(startup)
                plan = analyze_block(prog, 0, ["ids"], [out.name])
                fn = build_block_fn(prog, plan, training=False)
                feeds = [np.arange(8).reshape(2, 4) % V]
                const = [np.asarray(sc.find_var(n)) for n in plan.const_reads]
                donated = [np.asarray(sc.find_var(n))
                           for n in plan.donated_reads]
                jaxpr = jax.make_jaxpr(fn)(feeds, donated, const,
                                           jax.random.PRNGKey(0))
                o, _, _ = fn(feeds, donated, const, jax.random.PRNGKey(0))
            return _jaxpr_census(jaxpr.jaxpr), np.asarray(o[0])
        finally:
            _fused_flag(False)

    (_, pl_on), o_on = run(True)
    (_, pl_off), o_off = run(False)
    assert pl_on == 0, "clobbered group must not fuse"
    np.testing.assert_array_equal(o_on, o_off)


def test_dense_grad_and_mask_single_scatter():
    """VERDICT r4 #4: the masked-dense lazy update derives grad AND
    touched-mask from ONE scatter-add (the count rides along as a
    trailing column) — scatter-op count was the flat-cost binding term
    when last measured (PERF.md §5), so this is pinned structurally."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.selected_rows import (SelectedRows,
                                               dense_grad_and_mask)

    rows = jnp.asarray(np.array([3, 1, 3, 7], np.int32))
    vals = jnp.asarray(np.arange(16, dtype=np.float32).reshape(4, 4))
    sr = SelectedRows(rows, vals, height=10)

    def f(rows, vals):
        return dense_grad_and_mask(SelectedRows(rows, vals, height=10))

    jaxpr = jax.make_jaxpr(f)(rows, vals)
    n_scatter = sum(str(eqn.primitive).startswith("scatter")
                    for eqn in jaxpr.jaxpr.eqns)
    assert n_scatter == 1, jaxpr

    # and the semantics are unchanged: duplicates sum, mask is exact
    gd, t = f(rows, vals)
    want = np.zeros((10, 4), np.float32)
    for r, v in zip(np.asarray(rows), np.asarray(vals)):
        want[r] += v
    np.testing.assert_allclose(np.asarray(gd), want)
    np.testing.assert_array_equal(
        np.asarray(t).ravel(),
        [False, True, False, True, False, False, False, True, False,
         False])
