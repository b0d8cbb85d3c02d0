"""Serving plane (paddle_tpu/serving): continuous batching onto the
bucket ladder, pad-to-bucket parity, admission control, the INFER wire,
versioned hot-swap with zero drops / zero recompiles, registry replica
groups with health-gated failover, /servingz, and the warm-pool
create_predictor wiring (Executor.warm_start bucket ladders)."""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import unique_name
from paddle_tpu.core.executor import Executor, Scope, scope_guard
from paddle_tpu.core.program import Program, program_guard
from paddle_tpu.inference.predictor import (AnalysisConfig, Predictor,
                                            create_predictor)
from paddle_tpu.serving import (BucketLadder, DynamicBatcher, ModelManager,
                                ModelServer, Overloaded, ServingClient)
from paddle_tpu.serving.batcher import _pad_rows

L = fluid.layers


# -- model builders ---------------------------------------------------------

def _mnist_predictor(seed=1):
    from paddle_tpu.models.mnist import cnn_model

    prog, startup = Program(), Program()
    prog.random_seed = seed
    with program_guard(prog, startup), unique_name.guard():
        x = L.data("pixel", [1, 28, 28])
        y = cnn_model(x)
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
    return Predictor(prog, ["pixel"], [y.name], scope)


def _transformer_predictor(seed=1, T=8):
    from paddle_tpu.models.transformer import transformer

    prog, startup = Program(), Program()
    prog.random_seed = seed
    with program_guard(prog, startup), unique_name.guard():
        src = L.data("src_ids", [T], dtype="int64")
        tgt = L.data("tgt_ids", [T], dtype="int64")
        sm = L.data("src_mask", [T])
        tm = L.data("tgt_mask", [T])
        logits = transformer(src, tgt, sm, tm, src_vocab=64, tgt_vocab=64,
                             max_len=T, d_model=32, n_head=2, d_ffn=64,
                             n_layer=1, dropout=0.0)
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
    return Predictor(prog, ["src_ids", "tgt_ids", "src_mask", "tgt_mask"],
                     [logits.name], scope)


def _mlp_predictor(seed=1):
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = seed
    with program_guard(prog, startup), unique_name.guard():
        x = L.data("x", [8])
        h = L.fc(x, 16, act="relu")
        y = L.fc(h, 4, act="softmax")
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
    return Predictor(prog, ["x"], [y.name], scope)


def _same_answer(got, want) -> bool:
    """A reply coalesced into a wider batch bucket equals the answer to
    the same feed run alone only up to float rounding: XLA:CPU tiles and
    accumulates a [4, 8] matmul differently from a [1, 8] one, so the
    softmax outputs differ in the last bits across batch shapes.  The
    bound is a few float32 ulps of these O(1) probabilities, far below
    the gap between the two model versions the swap tests tell apart."""
    return np.allclose(got, want, rtol=1e-5, atol=1e-6)


def _mnist_req(rng, rows=1):
    return {"pixel": rng.randn(rows, 1, 28, 28).astype("float32")}


def _tfm_req(rng, rows=1, T=8):
    return {"src_ids": rng.randint(0, 64, (rows, T)).astype("int64"),
            "tgt_ids": rng.randint(0, 64, (rows, T)).astype("int64"),
            "src_mask": np.ones((rows, T), "float32"),
            "tgt_mask": np.ones((rows, T), "float32")}


class _StubPredictor:
    """Batcher-surface stub with a controllable service time."""

    feed_names = ["x"]
    fetch_names = ["y"]

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s
        self.calls = []

    def run(self, feed):
        if self.delay_s:
            time.sleep(self.delay_s)
        x = np.asarray(feed["x"])
        self.calls.append(x.shape[0])
        return [x * 2.0]


# -- bucket ladder ----------------------------------------------------------

def test_bucket_ladder_snap_and_flags():
    lad = BucketLadder((8, 1, 4, 2))      # unsorted, deduped, sorted
    assert lad.sizes == (1, 2, 4, 8) and lad.max == 8
    assert [lad.snap(n) for n in (1, 2, 3, 4, 5, 8)] == [1, 2, 4, 4, 8, 8]
    with pytest.raises(ValueError):
        lad.snap(9)
    with pytest.raises(ValueError):
        BucketLadder(())
    # the flag default parses into the documented ladder
    assert BucketLadder().sizes == (1, 2, 4, 8, 16, 32)


def test_pad_rows_repeats_last_row():
    a = np.arange(6, dtype="float32").reshape(3, 2)
    p = _pad_rows(a, 2)
    assert p.shape == (5, 2)
    np.testing.assert_array_equal(p[3], a[-1])
    np.testing.assert_array_equal(p[4], a[-1])
    assert _pad_rows(a, 0) is a


# -- pad-to-bucket parity ---------------------------------------------------

def _serve_batch(pred, reqs, buckets, top_delay_ms=120.0):
    """Run ``reqs`` through one DynamicBatcher so they coalesce into a
    single batch (submits land well inside the dispatch delay)."""
    b = DynamicBatcher(pred, name="parity", buckets=buckets,
                       max_delay_ms=top_delay_ms, max_queue_rows=1024)
    try:
        futs = [b.submit(r) for r in reqs]
        outs = [f.result(timeout=120) for f in futs]
    finally:
        b.close()
    assert b.stats.batches == 1, "requests did not coalesce into one batch"
    return outs


def test_pad_parity_mnist_at_and_past_bucket_boundary():
    """Padded serving dispatch ≡ the unpadded run: exactly at a bucket
    boundary the coalesced batch is bit-identical to a direct
    Predictor.run of the same rows; one past the boundary, the padded
    dispatch matches a direct run of the identically padded batch
    bit-for-bit (pad rows change nothing), and the per-request unpadded
    runs to float tolerance (XLA may vectorize different batch shapes
    differently — that is batch-size, not padding)."""
    pred = _mnist_predictor()
    rng = np.random.RandomState(0)

    # exactly at the bucket boundary: 4 requests -> bucket 4, no pads
    reqs = [_mnist_req(rng) for _ in range(4)]
    outs = _serve_batch(pred, reqs, buckets=(4,))
    direct = np.asarray(pred.run(
        {"pixel": np.concatenate([r["pixel"] for r in reqs])})[0])
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(o[0]), direct[i:i + 1])

    # one past the boundary: 5 requests -> bucket 8, 3 pad rows
    reqs5 = [_mnist_req(rng) for _ in range(5)]
    outs5 = _serve_batch(pred, reqs5, buckets=(8,))
    rows = np.concatenate([r["pixel"] for r in reqs5])
    padded = np.asarray(pred.run({"pixel": _pad_rows(rows, 3)})[0])
    for i, o in enumerate(outs5):
        np.testing.assert_array_equal(np.asarray(o[0]), padded[i:i + 1])
    for r, o in zip(reqs5, outs5):
        np.testing.assert_allclose(np.asarray(o[0]),
                                   np.asarray(pred.run(r)[0]),
                                   rtol=2e-5, atol=1e-6)


def test_pad_rows_do_not_contaminate_real_rows():
    """Row independence pinned: the same real rows dispatched at bucket
    8 once with pad rows and once with OTHER real rows in the pad
    positions produce bit-identical real-row outputs."""
    pred = _mnist_predictor()
    rng = np.random.RandomState(1)
    real = rng.randn(5, 1, 28, 28).astype("float32")
    other = rng.randn(3, 1, 28, 28).astype("float32")
    a = np.asarray(pred.run({"pixel": _pad_rows(real, 3)})[0])
    b = np.asarray(pred.run(
        {"pixel": np.concatenate([real, other])})[0])
    np.testing.assert_array_equal(a[:5], b[:5])


def test_pad_parity_transformer_at_and_past_bucket_boundary():
    pred = _transformer_predictor()
    rng = np.random.RandomState(2)

    reqs = [_tfm_req(rng) for _ in range(2)]       # exactly bucket 2
    outs = _serve_batch(pred, reqs, buckets=(2,))
    direct = np.asarray(pred.run(
        {n: np.concatenate([r[n] for r in reqs])
         for n in pred.feed_names})[0])
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(o[0]), direct[i:i + 1])

    reqs3 = [_tfm_req(rng) for _ in range(3)]      # past it: bucket 4
    outs3 = _serve_batch(pred, reqs3, buckets=(4,))
    padded_feed = {n: _pad_rows(np.concatenate([r[n] for r in reqs3]), 1)
                   for n in pred.feed_names}
    padded = np.asarray(pred.run(padded_feed)[0])
    for i, o in enumerate(outs3):
        np.testing.assert_array_equal(np.asarray(o[0]), padded[i:i + 1])
    for r, o in zip(reqs3, outs3):
        np.testing.assert_allclose(np.asarray(o[0]),
                                   np.asarray(pred.run(r)[0]),
                                   rtol=2e-5, atol=1e-6)


def test_bad_shape_request_rejected_alone_not_poisoning_batch():
    """A request with a wrong trailing shape is rejected at submit and
    never coalesced — well-formed requests batched in the same window
    still succeed (review hardening: one malformed request must not
    fail its whole batch).  A stray float64 request is cast at submit
    instead of promoting the coalesced batch."""
    pred = _mlp_predictor(4)
    b = DynamicBatcher(pred, name="guard", buckets=(4,), max_delay_ms=60.0)
    try:
        rng = np.random.RandomState(0)
        good = [b.submit({"x": rng.randn(1, 8).astype("float32")})
                for _ in range(2)]
        with pytest.raises(ValueError, match="sample shape"):
            b.submit({"x": np.zeros((1, 9), "float32")})
        f64 = b.submit({"x": rng.randn(1, 8)})        # float64: cast
        outs = [f.result(timeout=60) for f in good + [f64]]
        for o in outs:
            assert np.asarray(o[0]).dtype == np.float32
            assert np.asarray(o[0]).shape == (1, 4)
    finally:
        b.close()

    # stub predictors (no program) latch the contract from the first
    # accepted request
    stub = _StubPredictor()
    b2 = DynamicBatcher(stub, buckets=(2,), max_delay_ms=1.0)
    try:
        b2.submit({"x": np.zeros((1, 3), "float32")}).result(timeout=30)
        with pytest.raises(ValueError, match="sample shape"):
            b2.submit({"x": np.zeros((1, 5), "float32")})
    finally:
        b2.close()


def test_manager_concurrent_duplicate_load_refused():
    """Two racing loads of the same (name, version) cannot both build
    (the loser's batcher threads would leak): the key is reserved
    under one lock hold."""
    pred = _mlp_predictor(6)
    mgr = ModelManager()
    errs, oks = [], []

    def loader():
        try:
            mgr.load("dup", "1", predictor=pred, warm=False,
                     buckets=(1, 2), activate=True)
            oks.append(1)
        except ValueError as e:
            errs.append(str(e))
    threads = [threading.Thread(target=loader) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert len(oks) == 1 and len(errs) == 3, (oks, errs)
    mgr.close()


def test_oversize_request_rejected_at_submit():
    b = DynamicBatcher(_StubPredictor(), buckets=(2, 4),
                       max_delay_ms=1.0)
    try:
        with pytest.raises(ValueError, match="top bucket"):
            b.submit({"x": np.zeros((5, 3), "float32")})
        with pytest.raises(ValueError, match="missing feed"):
            b.submit({"z": np.zeros((1, 3), "float32")})
    finally:
        b.close()


def test_batcher_coalesces_and_occupancy_accounting():
    stub = _StubPredictor(delay_s=0.02)
    b = DynamicBatcher(stub, buckets=(1, 2, 4), max_delay_ms=60.0,
                       max_queue_rows=64)
    try:
        futs = [b.submit({"x": np.full((1, 3), i, "float32")})
                for i in range(4)]
        outs = [f.result(timeout=30) for f in futs]
        for i, o in enumerate(outs):
            np.testing.assert_array_equal(o[0], np.full((1, 3), 2.0 * i))
        # 4 rows coalesced the moment the top bucket filled
        assert 4 in stub.calls
        snap = b.stats.snapshot()
        assert snap["requests"] == 4 and snap["shed"] == 0
        assert snap["p99_ms"] is not None
    finally:
        b.close()


# -- admission control ------------------------------------------------------

def test_admission_bounded_queue_sheds_typed():
    stub = _StubPredictor(delay_s=0.25)
    b = DynamicBatcher(stub, buckets=(1,), max_delay_ms=0.0,
                       max_queue_rows=2)
    try:
        first = b.submit({"x": np.zeros((1, 2), "float32")})
        time.sleep(0.05)          # scheduler picked it up: queue empty
        b.submit({"x": np.zeros((1, 2), "float32")})
        b.submit({"x": np.zeros((1, 2), "float32")})
        with pytest.raises(Overloaded) as ei:
            b.submit({"x": np.zeros((1, 2), "float32")})
        e = ei.value
        assert e.limit_rows == 2 and e.queue_rows == 2
        assert e.model == "model" and e.est_delay_ms is None
        # typed round-trip (what the wire carries)
        e2 = Overloaded.from_dict(e.to_dict())
        assert e2.limit_rows == 2
        assert b.stats.snapshot()["shed"] == 1
        first.result(timeout=30)
    finally:
        b.close()


def test_admission_queue_delay_slo_sheds():
    stub = _StubPredictor(delay_s=0.12)
    b = DynamicBatcher(stub, buckets=(1,), max_delay_ms=0.0,
                       max_queue_rows=1024, queue_delay_slo_ms=10.0)
    try:
        # first batch teaches the service-time EWMA (~120 ms >> 10 ms);
        # an IDLE server admits even then (no backlog = no queue delay)
        b.submit({"x": np.zeros((1, 2), "float32")}).result(timeout=30)
        ok = b.submit({"x": np.zeros((1, 2), "float32")})   # idle: admitted
        time.sleep(0.02)   # now in flight: ~120 ms of work ahead
        with pytest.raises(Overloaded) as ei:
            b.submit({"x": np.zeros((1, 2), "float32")})
        assert ei.value.est_delay_ms is not None
        assert ei.value.slo_ms == 10.0
        ok.result(timeout=30)
        b.drain(timeout=30)
    finally:
        b.close()


# -- hot swap ---------------------------------------------------------------

def test_hot_swap_under_load_zero_drops_zero_recompiles():
    """serving_lite core scenario, in-process: version B loads + warms
    its whole ladder while A serves, the router flips atomically, A
    drains — no request fails, every reply matches v1 or v2 exactly,
    and the executor compile counters do not move in the serving
    window after B's warm (zero shape recompiles / cache misses)."""
    from paddle_tpu import observability as obs

    pred1, pred2 = _mlp_predictor(1), _mlp_predictor(2)
    mgr = ModelManager()
    mgr.load("mlp", "1", predictor=pred1, buckets=(1, 2, 4),
             activate=True, max_delay_ms=2.0)
    rng = np.random.RandomState(0)
    feeds = [{"x": rng.randn(1, 8).astype("float32")} for _ in range(8)]
    want1 = [np.asarray(pred1.run(f)[0]) for f in feeds]
    want2 = [np.asarray(pred2.run(f)[0]) for f in feeds]

    stop = threading.Event()
    errs, results = [], []
    lock = threading.Lock()

    def client():
        i = 0
        while not stop.is_set():
            f = feeds[i % 8]
            try:
                out = mgr.infer("mlp", f, timeout=60)
            except Exception as e:  # pragma: no cover - the assertion
                errs.append(repr(e))
                return
            with lock:
                results.append((i % 8, np.asarray(out[0])))
            i += 1

    threads = [threading.Thread(target=client) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.15)

    # pred2's executors are fresh: warm happens inside swap; counters
    # must not move after that warm while serving continues
    swap_info = mgr.swap("mlp", "2", predictor=pred2, buckets=(1, 2, 4),
                         max_delay_ms=2.0)
    counters = obs.stats.default_registry().to_dict()
    base = {k: counters.get(k, 0) for k in
            ("executor.cache_misses", "executor.shape_recompiles")}
    time.sleep(0.25)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    assert not errs, errs
    assert swap_info["drained"] and swap_info["previous"] == "1"
    assert mgr.active_version("mlp") == "2"
    counters = obs.stats.default_registry().to_dict()
    for k, v in base.items():
        assert counters.get(k, 0) == v, f"{k} moved during serving"
    # every reply is v1's or v2's answer for that feed
    for idx, got in results:
        ok1 = _same_answer(got, want1[idx][:got.shape[0]])
        ok2 = _same_answer(got, want2[idx][:got.shape[0]])
        assert ok1 or ok2
        assert not (ok1 and ok2), "the two versions must stay apart"
    # after the flip, new requests answer with v2
    out = np.asarray(mgr.infer("mlp", feeds[0], timeout=60)[0])
    np.testing.assert_array_equal(out, want2[0])
    with pytest.raises(ValueError, match="ACTIVE"):
        mgr.retire("mlp", "2")
    mgr.close()


# -- wire: server + client --------------------------------------------------

def test_serving_lite_server_client_swap_and_servingz():
    """The tier-1 serving_lite smoke: in-process ModelServer over the
    real framed-TCP wire, registry-announced replica, concurrent
    remote clients, one hot-swap under load (zero drops), /servingz
    served over HTTP, typed overload on the wire."""
    from paddle_tpu.distributed.registry import RegistryServer
    from paddle_tpu.observability import debug_server

    reg = RegistryServer("127.0.0.1:0")
    reg.start()
    reg_ep = f"127.0.0.1:{reg.port}"
    pred1, pred2 = _mlp_predictor(1), _mlp_predictor(2)
    srv = ModelServer("127.0.0.1:0", registry_ep=reg_ep, replica_id="r0",
                      lease_ttl=1.0)
    srv.load("mlp", "1", predictor=pred1, buckets=(1, 2, 4),
             activate=True, max_delay_ms=2.0)
    srv.start()
    http = debug_server.start(port=0)
    try:
        rng = np.random.RandomState(0)
        feeds = [{"x": rng.randn(1, 8).astype("float32")} for _ in range(8)]
        want1 = [np.asarray(pred1.run(f)[0]) for f in feeds]
        want2 = [np.asarray(pred2.run(f)[0]) for f in feeds]

        # discovery via the registry lease
        cli = ServingClient(registry_ep=reg_ep, refresh_s=0.2)
        assert cli.replicas("mlp") == [srv.endpoint]
        got = cli.infer("mlp", feeds[0])
        np.testing.assert_array_equal(np.asarray(got[0]), want1[0])
        # fetch names ride the reply
        pairs = cli.infer_pairs("mlp", feeds[1])
        assert pairs[0][0] == pred1.fetch_names[0]

        stop = threading.Event()
        errs, n_ok = [], [0]
        lock = threading.Lock()

        def client_loop():
            c = ServingClient(endpoints=[srv.endpoint])
            i = 0
            while not stop.is_set():
                f = feeds[i % 8]
                try:
                    out = np.asarray(c.infer("mlp", f)[0])
                except Exception as e:  # pragma: no cover
                    errs.append(repr(e))
                    return
                assert (_same_answer(out, want1[i % 8])
                        or _same_answer(out, want2[i % 8]))
                with lock:
                    n_ok[0] += 1
                i += 1

        threads = [threading.Thread(target=client_loop) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.15)
        info = srv.swap("mlp", "2", predictor=pred2, buckets=(1, 2, 4),
                        max_delay_ms=2.0)
        assert info["drained"]
        time.sleep(0.2)
        stop.set()
        for t in threads:
            t.join(timeout=60)
        assert not errs, errs
        assert n_ok[0] > 0
        np.testing.assert_array_equal(
            np.asarray(cli.infer("mlp", feeds[0])[0]), want2[0])

        # the lease data payload carries the live version fleet-wide
        from paddle_tpu.distributed import registry as dreg
        deadline = time.time() + 5
        while time.time() < deadline:
            snap = dreg.fetch_snapshot(cli._client, reg_ep)
            data = snap["data"].get("serving/mlp/r0") or {}
            if data.get("version") == "2":
                break
            time.sleep(0.2)
        assert data.get("version") == "2", snap["data"]

        # /servingz over HTTP: router + per-model gauges
        with urllib.request.urlopen(
                f"http://127.0.0.1:{http.port}/servingz", timeout=10) as r:
            page = json.loads(r.read().decode("utf-8"))
        assert srv.endpoint in page
        card = page[srv.endpoint]
        assert card["active"] == {"mlp": "2"}
        assert card["models"]["mlp@2"]["state"] == "SERVING"
        assert card["models"]["mlp@2"]["requests"] > 0

        # typed overload over the wire: a slow stub behind a 1-row queue
        stub = _StubPredictor(delay_s=0.3)
        srv.load("slow", "1", predictor=stub, warm=False, buckets=(1,),
                 activate=True, max_delay_ms=0.0, max_queue_rows=1)
        ServingClient(endpoints=[srv.endpoint]).infer(
            "slow", {"x": np.zeros((1, 2), "float32")})

        def fire():
            # one client per thread: a shared client's striped
            # connections would serialize the burst before the server
            c = ServingClient(endpoints=[srv.endpoint])
            try:
                c.infer("slow", {"x": np.zeros((1, 2), "float32")})
            except Overloaded:
                sheds.append(1)
        sheds = []
        burst = [threading.Thread(target=fire) for _ in range(6)]
        for t in burst:
            t.start()
        for t in burst:
            t.join(timeout=60)
        assert sheds, "burst past the queue bound never shed"
    finally:
        debug_server.stop()
        srv.stop()
        reg.stop()


def test_client_failover_across_replicas():
    """Two registry-announced replicas; killing one (clean bye) routes
    every subsequent request to the survivor — health-gated, no errors
    surface to callers."""
    from paddle_tpu.distributed.registry import RegistryServer

    reg = RegistryServer("127.0.0.1:0")
    reg.start()
    reg_ep = f"127.0.0.1:{reg.port}"
    pred = _mlp_predictor(1)
    servers = []
    for rid in ("a", "b"):
        s = ModelServer("127.0.0.1:0", registry_ep=reg_ep, replica_id=rid,
                        lease_ttl=0.5)
        s.load("mlp", "1", predictor=pred, buckets=(1, 2),
               activate=True, max_delay_ms=1.0)
        s.start()
        servers.append(s)
    try:
        cli = ServingClient(registry_ep=reg_ep, refresh_s=0.1,
                            cooldown_s=0.5)
        assert sorted(cli.replicas("mlp")) == sorted(
            s.endpoint for s in servers)
        feed = {"x": np.ones((1, 8), "float32")}
        want = np.asarray(pred.run(feed)[0])
        # round-robin actually alternates replicas
        for _ in range(4):
            np.testing.assert_allclose(np.asarray(cli.infer("mlp", feed)[0]),
                                       want, rtol=1e-6)
        servers[0].stop()     # clean bye: lease dropped immediately
        time.sleep(0.3)
        for _ in range(4):    # all traffic lands on the survivor
            np.testing.assert_allclose(np.asarray(cli.infer("mlp", feed)[0]),
                                       want, rtol=1e-6)
        assert cli.replicas("mlp") == [servers[1].endpoint]
    finally:
        for s in servers:
            s.stop()
        reg.stop()


def test_client_static_endpoint_benching():
    """A dead endpoint in a static list is benched after one connect
    failure and traffic flows to the live one."""
    pred = _mlp_predictor(1)
    srv = ModelServer("127.0.0.1:0")
    srv.load("mlp", "1", predictor=pred, buckets=(1, 2), activate=True,
             max_delay_ms=0.0)
    srv.start()
    try:
        dead = "127.0.0.1:1"        # nothing listens on port 1
        cli = ServingClient(endpoints=[dead, srv.endpoint], cooldown_s=60)
        feed = {"x": np.ones((2, 8), "float32")}
        for _ in range(3):
            out = cli.infer("mlp", feed)
            assert np.asarray(out[0]).shape == (2, 4)
        with cli._lock:
            assert dead in cli._down
    finally:
        srv.stop()


# -- warm pool / persistent cache satellites --------------------------------

def test_executor_warm_start_accepts_spec_ladder():
    """Executor.warm_start with a LIST of feed-spec dicts precompiles
    one executable per entry; subsequent runs at those shapes are pure
    cache hits."""
    from paddle_tpu import observability as obs

    prog, startup = Program(), Program()
    prog.random_seed = 7
    with program_guard(prog, startup), unique_name.guard():
        x = L.data("x", [6])
        y = L.fc(x, 3)
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
    out = exe.warm_start(prog,
                         [{"x": ((b, 6), "float32")} for b in (2, 4)],
                         [y.name], scope=scope)
    assert out["segments"] == 2 and out["warmed"] == 2
    d0 = obs.stats.default_registry().to_dict()
    for b in (2, 4):
        exe.run(prog, feed={"x": np.zeros((b, 6), "float32")},
                fetch_list=[y.name], scope=scope)
    d1 = obs.stats.default_registry().to_dict()
    assert d1.get("executor.cache_hits", 0) - \
        d0.get("executor.cache_hits", 0) == 2
    assert d1.get("executor.cache_misses", 0) == \
        d0.get("executor.cache_misses", 0)


def test_create_predictor_warm_starts_from_compile_cache(tmp_path):
    """The satellite: with FLAGS_compile_cache_dir set and warm-start
    batch sizes on the AnalysisConfig, create_predictor precompiles the
    ladder — and a SECOND predictor (the redeploy shape) hydrates from
    disk with persistent hits, its first request a pure cache hit."""
    from paddle_tpu import observability as obs

    d = str(tmp_path / "m")
    prog, startup = Program(), Program()
    prog.random_seed = 3
    with program_guard(prog, startup), unique_name.guard():
        x = L.data("x", [8])
        y = L.fc(x, 4, act="softmax")
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [y], exe, main_program=prog)

    saved = fluid.get_flags("compile_cache_dir")
    fluid.set_flags({"compile_cache_dir": str(tmp_path / "cache")})
    try:
        cfg = AnalysisConfig(d)
        cfg.set_warm_start([1, 2])
        p1 = create_predictor(cfg)            # compiles + stores
        c0 = obs.stats.default_registry().to_dict()
        p2 = create_predictor(cfg)            # hydrates from disk
        c1 = obs.stats.default_registry().to_dict()
        hits = c1.get("executor.persistent_hits", 0) - \
            c0.get("executor.persistent_hits", 0)
        assert hits >= 2, (c0, c1)
        # first request at a warmed size: in-memory executable hit
        xv = np.random.RandomState(0).randn(2, 8).astype("float32")
        (a,) = p1.run({"x": xv})
        (b,) = p2.run({"x": xv})
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
        c2 = obs.stats.default_registry().to_dict()
        assert c2.get("executor.cache_misses", 0) == \
            c1.get("executor.cache_misses", 0)
    finally:
        fluid.set_flags({"compile_cache_dir": saved})


def test_create_predictor_without_warm_flags_unchanged(tmp_path):
    """Flags unset ⇒ byte-identical create_predictor: no warm-start,
    no disk I/O (the compile-cache dir flag stays empty)."""
    d = str(tmp_path / "m")
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        x = L.data("x", [8])
        y = L.fc(x, 4)
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [y], exe, main_program=prog)
    cfg = AnalysisConfig(d)
    cfg.set_warm_start([1, 2])   # asked for, but cache flag is unset
    pred = create_predictor(cfg)
    assert not pred._exe._cache  # nothing precompiled
    (out,) = pred.run({"x": np.zeros((3, 8), "float32")})
    assert np.asarray(out).shape == (3, 4)


def test_manager_warm_pool_covers_ladder_and_sample_shapes():
    """ModelManager.load(warm=True) precompiles every bucket; a model
    with symbolic feed dims warms through explicit sample_shapes."""
    pred = _mlp_predictor(5)
    mgr = ModelManager()
    sm = mgr.load("mlp", "1", predictor=pred, buckets=(2, 4),
                  activate=True, max_delay_ms=1.0)
    assert sm.warm_info["warmed"] == 2
    assert len(pred._exe._cache) >= 2
    # serving at warmed sizes: zero new compiles
    from paddle_tpu import observability as obs
    d0 = obs.stats.default_registry().to_dict()
    mgr.infer("mlp", {"x": np.zeros((2, 8), "float32")}, timeout=60)
    d1 = obs.stats.default_registry().to_dict()
    assert d1.get("executor.cache_misses", 0) == \
        d0.get("executor.cache_misses", 0)
    mgr.close()

    with pytest.raises(ValueError, match="symbolic|static"):
        bad = _transformer_predictor()
        bad._program.global_block.var("src_ids").shape = (-1, -1)
        ModelManager().load("t", "1", predictor=bad, buckets=(2,),
                            activate=True)


# ---------------------------------------------------------------------------
# graceful drain (ISSUE 14 satellite): zero dropped requests
# ---------------------------------------------------------------------------

def test_drained_replica_drops_zero_requests_under_load():
    """ModelServer.stop(drain=True) mid-load: the lease deregisters
    FIRST (discovery clients fail over before the socket dies),
    straggler submits get a typed Draining (rotate, like Overloaded),
    in-flight batches finish — and across the whole window not one
    client request errors or drops."""
    from paddle_tpu.distributed.registry import RegistryServer
    from paddle_tpu.serving import Draining

    reg = RegistryServer("127.0.0.1:0")
    reg.start()
    reg_ep = f"127.0.0.1:{reg.port}"
    stubs = [_StubPredictor(delay_s=0.02), _StubPredictor(delay_s=0.02)]
    srvs = []
    for i, stub in enumerate(stubs):
        s = ModelServer("127.0.0.1:0", registry_ep=reg_ep,
                        replica_id=f"r{i}", lease_ttl=1.0)
        s.load("mlp", "1", predictor=stub, warm=False, buckets=(1, 2, 4),
               activate=True, max_delay_ms=1.0)
        s.start()
        srvs.append(s)
    stop = threading.Event()
    errs, n_ok = [], [0]
    lock = threading.Lock()

    def client_loop():
        c = ServingClient(registry_ep=reg_ep, refresh_s=0.1,
                          cooldown_s=0.2)
        x = np.ones((1, 8), "float32")
        while not stop.is_set():
            try:
                out = c.infer("mlp", {"x": x})
                np.testing.assert_array_equal(np.asarray(out[0]), x * 2.0)
            except Exception as e:  # noqa: BLE001 — ANY error = a drop
                errs.append(repr(e))
                return
            with lock:
                n_ok[0] += 1
    threads = [threading.Thread(target=client_loop) for _ in range(3)]
    try:
        for t in threads:
            t.start()
        deadline = time.monotonic() + 10
        while n_ok[0] < 30 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert n_ok[0] >= 30, (n_ok, errs)
        before_drain = n_ok[0]
        srvs[0].stop(drain=True)          # drain r0 under live load
        # r0's lease is gone (deregistered first, not aged out)
        from paddle_tpu.distributed import registry as reg_mod
        from paddle_tpu.distributed import transport
        snap = reg_mod.fetch_snapshot(transport.RPCClient(0), reg_ep)
        assert "serving/mlp/r0" not in snap["leases"], snap["leases"]
        # traffic keeps flowing on the survivor, still zero errors
        deadline = time.monotonic() + 10
        while n_ok[0] < before_drain + 30 \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert n_ok[0] >= before_drain + 30, (n_ok, errs)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        for s in srvs[1:]:
            s.stop()
        reg.stop()
    assert errs == [], errs
    # r0 really served its share before the drain (the drain happened
    # under load, not after traffic had already moved away)
    assert stubs[0].calls, "r0 never served — the drain wasn't under load"


def test_draining_reply_is_typed_and_inflight_finishes():
    """The wire pin: a straggler INFER racing the drain gets the typed
    Draining reply (tag 'D', fields round-tripped), while the request
    accepted BEFORE the drain completes successfully inside it."""
    from paddle_tpu.serving import Draining

    stub = _StubPredictor(delay_s=0.6)    # wide drain window
    srv = ModelServer("127.0.0.1:0")
    srv.load("mlp", "1", predictor=stub, warm=False, buckets=(1,),
             activate=True, max_delay_ms=1.0)
    srv.start()
    c = ServingClient(endpoints=[srv.endpoint])
    x = np.ones((1, 8), "float32")
    inflight = {}

    def one_request():
        inflight["out"] = np.asarray(c.infer("mlp", {"x": x})[0])
    t = threading.Thread(target=one_request)
    t.start()
    time.sleep(0.2)                       # accepted, now executing
    drainer = threading.Thread(target=srv.stop,
                               kwargs={"drain": True})
    drainer.start()
    time.sleep(0.1)                       # draining flag is up
    with pytest.raises(Draining) as ei:
        ServingClient(endpoints=[srv.endpoint]).infer("mlp", {"x": x})
    assert ei.value.model == "mlp" and ei.value.endpoint == srv.endpoint
    t.join(timeout=10)
    drainer.join(timeout=10)
    np.testing.assert_array_equal(inflight["out"], x * 2.0)
