"""SelectedRows sparse-gradient stack tests.

Parity model (reference test strategy: test_sgd_op.py sparse cases,
test_adam_op.py TestSparseAdamOp): the sparse path must produce the same
trained parameters as the dense path on identical programs, including
duplicate ids, regularization, and global-norm clipping.
"""
import contextlib

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


@contextlib.contextmanager
def _update_path(path):
    """Lower sparse updates through ``path``: "sorted" or "masked_dense"."""
    from paddle_tpu.core import flags
    old = flags.get_flags("sparse_dense_update_max_elems")
    flags.set_flags({"sparse_dense_update_max_elems":
                     0 if path == "sorted" else old})
    try:
        yield
    finally:
        flags.set_flags({"sparse_dense_update_max_elems": old})


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
    with _update_path("sorted"):
        wd = _train(opt, is_sparse=False, cover_all=cover_all)
        ws = _train(opt, is_sparse=True, cover_all=cover_all)
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
# the two update paths (sorted merge_rows above
# FLAGS_sparse_dense_update_max_elems, masked dense below it) at the rule
# level, and the whole step they lower into
# ---------------------------------------------------------------------------

FV, FD = 23, 5

# optimizer -> (output slots, attrs, state slots: must the state be positive)
_RULES = {
    "adam": (("ParamOut", "Moment1Out", "Moment2Out"),
             {"beta1": 0.5, "beta2": 0.75, "epsilon": 1e-8},
             {"Param": False, "Moment1": False, "Moment2": True}),
    "momentum": (("ParamOut", "VelocityOut"), {"mu": 0.5},
                 {"Param": False, "Velocity": False}),
    "adagrad": (("ParamOut", "MomentOut"), {"epsilon": 1e-6},
                {"Param": False, "Moment": True}),
}

# id batch -> (rows, are the values small integers: every sum is exact)
_ID_BATCHES = {
    "distinct": (np.array([4, 0, 19, 7, 22, 11], np.int32), False),
    "duplicates_dyadic": (np.array([3, 3, 9, 3, 0, 9, 22, 15, 0], np.int32),
                          True),
    "empty": (np.zeros((0,), np.int32), False),
}


@pytest.mark.parametrize("ids", sorted(_ID_BATCHES))
@pytest.mark.parametrize("path", ["sorted", "masked_dense"])
@pytest.mark.parametrize("op", sorted(_RULES))
def test_sparse_update_matches_dense_reference(op, path, ids):
    """Each lazy optimizer on each update path against the SAME rule fed
    the dense gradient (duplicates summed by ``np.add.at``), kept to the
    touched rows.  Integer-valued gradients sum exactly in any order and
    both sides then run the same elementwise arithmetic, so that batch is
    held bit for bit: a missed duplicate or a wrong row is a hard
    mismatch.  An empty batch leaves every row as it was."""
    import jax.numpy as jnp
    from paddle_tpu.core import registry
    from paddle_tpu.core.registry import LowerContext
    from paddle_tpu.core.selected_rows import SelectedRows

    slots, attrs, state = _RULES[op]
    rows, dyadic = _ID_BATCHES[ids]
    rng = np.random.RandomState(len(rows))
    draw = ((lambda *s: rng.randint(-8, 8, s).astype(np.float32)) if dyadic
            else (lambda *s: rng.randn(*s).astype(np.float32)))
    vals = draw(len(rows), FD)
    ins = {"LearningRate": [jnp.asarray(np.float32(0.5))]}
    for slot, positive in state.items():
        a = draw(FV, FD)
        ins[slot] = [jnp.asarray(np.abs(a) + 1 if positive else a)]
    if op == "adam":
        ins["Beta1Pow"] = [jnp.asarray(np.float32(0.5))]
        ins["Beta2Pow"] = [jnp.asarray(np.float32(0.75))]
    rule = registry.get(op).lower
    with _update_path(path):
        got = rule(LowerContext(), {**ins, "Grad": [SelectedRows(
            jnp.asarray(rows), jnp.asarray(vals), FV)]}, attrs)
    want = rule(LowerContext(),
                {**ins, "Grad": [jnp.asarray(_merge_ref(rows, vals, FV))]},
                attrs)
    touched = np.zeros((FV, 1), bool)
    touched[rows] = True
    for slot in slots:
        old = np.asarray(ins[slot[:-len("Out")]][0])
        ref = np.where(touched, np.asarray(want[slot][0]), old)
        if dyadic:
            np.testing.assert_array_equal(np.asarray(got[slot][0]), ref,
                                          err_msg=f"{op}.{slot}")
        else:
            np.testing.assert_allclose(np.asarray(got[slot][0]), ref,
                                       rtol=2e-6, atol=1e-6,
                                       err_msg=f"{op}.{slot}")


@pytest.mark.parametrize("idb,nan_rows", [
    # ids in [-V, 0) count from the end, as numpy's do; below -V nothing
    ([[-1, 0, -V, -V - 1]], [3]),
    ([[V - 1, V, 0, V + 7]], [1, 3]),
], ids=["below_zero", "at_height"])
def test_lookup_table_out_of_range_fills_as_take(idb, nan_rows):
    """Ids come from user feed data: one outside the table must fail
    loudly (a NaN row, ``jnp.take``'s fill, which the numerics sentinel
    sees), never train a clamped row."""
    import jax.numpy as jnp

    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        ids = fluid.layers.data("ids", [4], dtype="int64")
        emb = fluid.layers.embedding(
            ids, [V, D], param_attr=fluid.ParamAttr(
                name="emb.w",
                initializer=fluid.initializer.Uniform(-0.5, 0.5)))
    exe = Executor()
    sc = Scope()
    idb = np.asarray(idb, "int64")
    with scope_guard(sc):
        exe.run(startup)
        (out,) = exe.run(prog, feed={"ids": idb}, fetch_list=[emb])
        w = np.asarray(sc.find_var("emb.w"))
    out = np.asarray(out)
    np.testing.assert_array_equal(
        out, np.asarray(jnp.take(jnp.asarray(w), idb, axis=0)))
    assert np.isnan(out[0, nan_rows]).all(), out
    ok = [i for i in range(4) if i not in nan_rows]
    np.testing.assert_array_equal(out[0, ok], w[idb[0, ok]])


def _two_table_program(optimizer):
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
        optimizer().minimize(loss)
    return prog, startup, loss


def _jaxpr_census(jaxpr):
    """(scatter-class eqn count, pallas launch count) over ``jaxpr`` and
    every sub-jaxpr."""
    import jax

    n_scatter = n_pallas = 0
    for eq in jaxpr.eqns:
        nm = str(eq.primitive)
        n_scatter += nm.startswith("scatter")
        n_pallas += nm == "pallas_call"
        for v in eq.params.values():
            for leaf in jax.tree_util.tree_leaves(
                    v, is_leaf=lambda x: hasattr(x, "eqns")
                    or hasattr(x, "jaxpr")):
                inner = getattr(leaf, "jaxpr", leaf)
                if hasattr(inner, "eqns"):
                    s, p = _jaxpr_census(inner)
                    n_scatter += s
                    n_pallas += p
    return n_scatter, n_pallas


@pytest.mark.parametrize("optimizer", [
    lambda: fluid.optimizer.Adam(0.1),
    lambda: fluid.optimizer.Momentum(0.1, 0.9),
    lambda: fluid.optimizer.Adagrad(0.1),
], ids=["adam", "momentum", "adagrad"])
def test_masked_dense_step_has_one_scatter_a_table(optimizer):
    """The count of scatter-class ops was the flat cost of the sparse step
    when last measured (~1 ms each): the whole two-table train step on the
    masked-dense path holds ONE scatter-add a table and no Pallas call."""
    import jax
    from paddle_tpu.core.lowering import analyze_block, build_block_fn

    prog, startup, loss = _two_table_program(optimizer)
    exe = Executor()
    sc = Scope()
    with scope_guard(sc), _update_path("masked_dense"):
        exe.run(startup)
        plan = analyze_block(prog, 0, ["ids", "label"], [loss.name])
        fn = build_block_fn(prog, plan, training=True)
        feeds = [np.zeros((3, 5), np.int64), np.zeros((3, 1), np.float32)]
        donated = [np.asarray(sc.find_var(n)) for n in plan.donated_reads]
        const = [np.asarray(sc.find_var(n)) for n in plan.const_reads]
        jaxpr = jax.make_jaxpr(fn)(feeds, donated, const,
                                   jax.random.PRNGKey(0))
    assert _jaxpr_census(jaxpr.jaxpr) == (2, 0)


def test_tiny_deepfm_trains_the_same_on_both_update_paths():
    """models/deepfm.py (two sparse tables under Adam) over one batch with
    duplicate ids: the sorted and the masked-dense path reach the same
    losses, and they fall."""
    from paddle_tpu.models import deepfm

    rng = np.random.RandomState(7)
    feed = {"dense": rng.rand(8, 13).astype("float32"),
            "sparse": rng.randint(0, 40, (8, 26)).astype("int64"),
            "label": (rng.rand(8, 1) > 0.5).astype("float32")}
    feed["sparse"][:, 0] = feed["sparse"][:, 1]

    def train(path):
        prog, startup = Program(), Program()
        prog.random_seed = 3
        with program_guard(prog, startup), unique_name.guard():
            _, avg_cost, _ = deepfm.build(sparse_dim=40, lr=1e-2)
        exe = Executor()
        with scope_guard(Scope()), _update_path(path):
            exe.run(startup)
            return [float(exe.run(prog, feed=feed,
                                  fetch_list=[avg_cost])[0])
                    for _ in range(4)]

    sorted_losses, dense_losses = train("sorted"), train("masked_dense")
    assert np.isfinite(dense_losses).all()
    assert dense_losses[-1] < dense_losses[0], dense_losses
    np.testing.assert_allclose(sorted_losses, dense_losses,
                               rtol=1e-5, atol=1e-6)


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
    assert _jaxpr_census(jaxpr.jaxpr) == (1, 0), jaxpr

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
