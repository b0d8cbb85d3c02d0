"""Executor/Scope tests (reference executor tests + book/fit_a_line)."""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import unique_name
from paddle_tpu.core.executor import Executor, Scope, scope_guard
from paddle_tpu.core.program import Program, program_guard


def test_fit_a_line_converges():
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [13])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.01).minimize(loss)
    scope = Scope()
    exe = Executor()
    with scope_guard(scope):
        exe.run(startup)
        rng = np.random.RandomState(0)
        w = rng.randn(13, 1).astype("float32")
        for _ in range(300):
            xb = rng.randn(32, 13).astype("float32")
            yb = xb @ w + 0.7
            (l,) = exe.run(prog, feed={"x": xb, "y": yb.astype("float32")},
                           fetch_list=[loss])
        assert float(l) < 0.05


def test_scope_isolation():
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [2])
        pred = fluid.layers.fc(x, 1, bias_attr=False,
                               param_attr=fluid.ParamAttr(name="w_iso"))
    s1, s2 = Scope(), Scope()
    exe = Executor()
    with scope_guard(s1):
        exe.run(startup)
    with scope_guard(s2):
        exe.run(startup)
        s2.set_var("w_iso", np.zeros((2, 1), dtype="float32"))
        out2 = exe.run(prog, feed={"x": np.ones((1, 2), "float32")},
                       fetch_list=[pred])
    with scope_guard(s1):
        out1 = exe.run(prog, feed={"x": np.ones((1, 2), "float32")},
                       fetch_list=[pred])
    assert np.allclose(out2[0], 0.0)
    assert not np.allclose(out1[0], 0.0)


def test_program_cache_and_shape_bucket():
    """Different batch sizes recompile but produce consistent results."""
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [3])
        out = fluid.layers.scale(x, scale=2.0)
    exe = Executor()
    scope = Scope()
    with scope_guard(scope):
        for bs in (4, 8, 4):
            xb = np.ones((bs, 3), "float32")
            (o,) = exe.run(prog, feed={"x": xb}, fetch_list=[out])
            assert o.shape == (bs, 3) and np.allclose(o, 2.0)
    assert len(exe._cache) == 2  # two shape buckets


def test_persistable_state_updates():
    """batch_norm running stats update across runs (write-back of MeanOut)."""
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [4])
        bn = fluid.layers.batch_norm(x, moving_mean_name="bn_mean_test")
    exe = Executor()
    scope = Scope()
    with scope_guard(scope):
        exe.run(startup)
        m0 = np.asarray(scope.find_var("bn_mean_test")).copy()
        xb = np.full((8, 4), 5.0, "float32")
        exe.run(prog, feed={"x": xb}, fetch_list=[bn])
        m1 = np.asarray(scope.find_var("bn_mean_test"))
        assert not np.allclose(m0, m1)
        assert np.all(m1 > 0)  # moving toward batch mean of 5


def test_rng_state_advances():
    """Two dropout runs draw different masks (threaded PRNG state)."""
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [64])
        d = fluid.layers.dropout(x, 0.5)
    exe = Executor()
    scope = Scope()
    with scope_guard(scope):
        xb = np.ones((2, 64), "float32")
        (a,) = exe.run(prog, feed={"x": xb}, fetch_list=[d])
        (b,) = exe.run(prog, feed={"x": xb}, fetch_list=[d])
        assert not np.allclose(a, b)


def test_save_load_persistables(tmp_path):
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [3])
        pred = fluid.layers.fc(x, 2, param_attr=fluid.ParamAttr(name="sl_w"),
                               bias_attr=fluid.ParamAttr(name="sl_b"))
    exe = Executor()
    s1 = Scope()
    with scope_guard(s1):
        exe.run(startup)
        fluid.io.save_persistables(exe, str(tmp_path), prog)
        w = np.asarray(s1.find_var("sl_w"))
    s2 = Scope()
    with scope_guard(s2):
        fluid.io.load_persistables(exe, str(tmp_path), prog)
        w2 = np.asarray(s2.find_var("sl_w"))
    assert np.allclose(w, w2)


def test_save_load_inference_model(tmp_path):
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [3])
        pred = fluid.layers.fc(x, 2, act="softmax")
    exe = Executor()
    s1 = Scope()
    xb = np.ones((2, 3), "float32")
    with scope_guard(s1):
        exe.run(startup)
        with program_guard(prog, startup):
            fluid.io.save_inference_model(str(tmp_path), ["x"], [pred], exe, prog)
        (ref,) = exe.run(prog, feed={"x": xb}, fetch_list=[pred])
    s2 = Scope()
    with scope_guard(s2):
        iprog, feeds, fetches = fluid.io.load_inference_model(str(tmp_path), exe)
        assert feeds == ["x"]
        (got,) = exe.run(iprog, feed={"x": xb}, fetch_list=fetches)
    assert np.allclose(ref, got, atol=1e-6)


def _fc_momentum(rng, K):
    x = fluid.layers.data("x", [5])
    y = fluid.layers.data("y", [1])
    p = fluid.layers.fc(x, 1, param_attr=fluid.ParamAttr(name="w"))
    loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
    fluid.optimizer.Momentum(0.05, 0.9).minimize(loss)
    xs = rng.randn(K, 8, 5).astype("float32")
    return loss, {"x": xs, "y": xs.sum(2, keepdims=True).astype("float32")}


def _mnist_momentum(rng, K):
    from paddle_tpu.models import mnist
    _, loss, _ = mnist.build(with_optimizer=False)
    fluid.optimizer.Momentum(0.05, 0.9).minimize(loss)
    return loss, {"pixel": rng.rand(K, 4, 1, 28, 28).astype("float32"),
                  "label": rng.randint(0, 10, (K, 4, 1)).astype("int64")}


def _deepfm_sparse_adam(rng, K):
    from paddle_tpu.models import deepfm
    _, loss, _ = deepfm.build(sparse_dim=40, lr=1e-3)
    return loss, {"dense": rng.rand(K, 4, 13).astype("float32"),
                  "sparse": rng.randint(0, 40, (K, 4, 26)).astype("int64"),
                  "label": (rng.rand(K, 4, 1) > 0.5).astype("float32")}


@pytest.mark.parametrize("build,K", [
    (_fc_momentum, 6),
    (_mnist_momentum, 1), (_mnist_momentum, 4),
    (_deepfm_sparse_adam, 1), (_deepfm_sparse_adam, 4),
])
def test_run_steps_matches_eager_loop(build, K):
    """Executor.run_steps: K scanned steps over stacked feeds must match
    K eager run() calls (fetched losses and every parameter after;
    RNG-free programs) — a scan of one step, a convolutional program,
    and Adam over dense and SelectedRows gradients included."""
    def drive(scanned):
        prog, startup = Program(), Program()
        prog.random_seed = 11
        with program_guard(prog, startup), unique_name.guard():
            loss, feed = build(np.random.RandomState(0), K)
        scope, exe = Scope(), Executor()
        with scope_guard(scope):
            exe.run(startup)
            if scanned:
                (losses,) = exe.run_steps(prog, feed=feed,
                                          fetch_list=[loss.name])
            else:
                losses = [exe.run(prog,
                                  feed={n: v[i] for n, v in feed.items()},
                                  fetch_list=[loss.name])[0]
                          for i in range(K)]
            params = {p.name: np.asarray(scope.find_var(p.name)).copy()
                      for p in prog.all_parameters()}
        return [float(v) for v in losses], params

    el, ep = drive(scanned=False)
    sl, sp = drive(scanned=True)
    assert len(sl) == K and sorted(sp) == sorted(ep) and ep
    np.testing.assert_allclose(sl, el, rtol=1e-5)
    for name, want in ep.items():
        # Adam divides by the gradient's own size: an element whose
        # gradient is rounding noise moves by a fraction of lr = 1e-3
        np.testing.assert_allclose(sp[name], want, rtol=1e-5, atol=1e-5,
                                   err_msg=name)


def test_lod_tensor_feed_shim():
    """create_lod_tensor feeds ragged rows through the reference API; the
    executor expands it to the padded array + @LEN companion."""
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.executor import Executor, Scope, scope_guard
    from paddle_tpu.core.program import Program, program_guard

    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        d = fluid.layers.data("seq", [1], dtype="float32", lod_level=1)
        pooled = fluid.layers.sequence_pool(d, "sum")

    lt = fluid.create_lod_tensor(
        [[1.0, 2.0], [3.0], [4.0, 5.0, 6.0]], [[2, 1, 3]], None)
    assert lt.recursive_sequence_lengths() == [[2, 1, 3]]
    assert lt.lod() == [[0, 2, 3, 6]]

    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
        out, = exe.run(prog, feed={"seq": lt}, fetch_list=[pooled.name])
    np.testing.assert_allclose(np.asarray(out).reshape(-1), [3.0, 3.0, 15.0])


def test_async_run_lazy_fetches():
    """Executor.run returns lazy fetches by default: ndarray-compatible
    (ufuncs, float(), indexing, formatting), one batched flush on first
    access, and sync=True preserves plain-numpy semantics.  Training
    results must be identical either way."""
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.executor import (Executor, LazyFetch, Scope,
                                          scope_guard)
    from paddle_tpu.core.program import Program, program_guard

    def train(sync):
        prog, startup = Program(), Program()
        prog.random_seed = 5
        with program_guard(prog, startup), unique_name.guard():
            x = fluid.layers.data("x", [4])
            y = fluid.layers.data("y", [1])
            p = fluid.layers.fc(x, 1, param_attr=fluid.ParamAttr(name="w"))
            loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
            fluid.optimizer.SGD(0.1).minimize(loss)
        scope, exe = Scope(), Executor()
        rng = np.random.RandomState(0)
        losses = []
        with scope_guard(scope):
            exe.run(startup)
            for _ in range(5):
                xb = rng.randn(8, 4).astype("float32")
                yb = xb.sum(1, keepdims=True).astype("float32")
                l, = exe.run(prog, feed={"x": xb, "y": yb},
                             fetch_list=[loss.name], sync=sync)
                losses.append(l)
        return losses

    lazy = train(sync=False)
    plain = train(sync=True)
    assert all(isinstance(l, LazyFetch) for l in lazy)
    assert all(isinstance(l, np.ndarray) for l in plain)
    # ndarray-duck surface
    l0 = lazy[0]
    assert l0.shape == () or l0.shape == (1,)
    assert float(l0) == float(np.asarray(l0))
    assert f"{float(l0):.3f}"
    np.testing.assert_allclose(np.asarray(lazy), np.asarray(plain),
                               rtol=1e-6)
    assert float(lazy[-1]) < float(lazy[0])  # it actually trained


def test_async_run_persistable_fetch_is_eager():
    """Fetching a persistable var returns a materialized array (its device
    buffer is donated by the NEXT run; a deferred read would explode)."""
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.executor import (Executor, LazyFetch, Scope,
                                          scope_guard)
    from paddle_tpu.core.program import Program, program_guard

    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [4])
        y = fluid.layers.data("y", [1])
        p = fluid.layers.fc(x, 1, param_attr=fluid.ParamAttr(name="w"))
        loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    scope, exe = Scope(), Executor()
    rng = np.random.RandomState(0)
    with scope_guard(scope):
        exe.run(startup)
        ws = []
        for _ in range(3):
            xb = rng.randn(8, 4).astype("float32")
            yb = xb.sum(1, keepdims=True).astype("float32")
            l, w = exe.run(prog, feed={"x": xb, "y": yb},
                           fetch_list=[loss.name, "w"])
            assert not isinstance(w, LazyFetch)
            ws.append(np.asarray(w).copy())
        # reads of earlier fetched params stay valid despite donation
        assert not np.allclose(ws[0], ws[-1])


def test_async_run_pending_backstop():
    """More than _MAX_PENDING unread fetches trigger the in-constructor
    flush (regression: the backstop once called a deleted method), and
    every value is still correct afterwards."""
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.executor import (Executor, LazyFetch, Scope,
                                          scope_guard)
    from paddle_tpu.core.program import Program, program_guard

    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [2])
        out = fluid.layers.scale(x, scale=3.0)
    scope, exe = Scope(), Executor()
    n = LazyFetch._MAX_PENDING + 40
    fetched = []
    with scope_guard(scope):
        exe.run(startup)
        for i in range(n):
            xb = np.full((1, 2), float(i), "float32")
            (o,) = exe.run(prog, feed={"x": xb}, fetch_list=[out.name])
            fetched.append(o)
    for i, o in enumerate(fetched):
        np.testing.assert_allclose(np.asarray(o), 3.0 * i)
