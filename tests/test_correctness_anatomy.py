"""Correctness anatomy (ISSUE 17): the golden canary prober
(record -> replay through the real submit path -> per-replica streaks),
the cross-replica divergence sentinel (reply digests / decode token
hashes / DP parameter checksums grouped fleet-wide so a lying replica
is NAMED), the `corrupt` fault kind feeding both, the supervisor's
quarantine policy (detect -> name -> DRAIN, zero dropped requests), the
flags-off byte-identity pins on wire + lease + STATS_PULL, and the
operator surfaces (/canaryz, dump_metrics --canaryz, fleet table)."""
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from dist_model import retry_flaky
from paddle_tpu.core import flags as _flags
from paddle_tpu.distributed import faults as _faults
from paddle_tpu.distributed import registry as reg_mod
from paddle_tpu.distributed import transport
from paddle_tpu.observability import (aggregate, audit, canary,
                                      debug_server, flight, stats, tenant)
from paddle_tpu.serving.client import ServingClient
from paddle_tpu.serving.server import ModelServer, replica_key

HERE = os.path.dirname(os.path.abspath(__file__))
RUNNER = os.path.join(HERE, "serving_replica_runner.py")


def _tool(name):
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


class _StubPredictor:
    feed_names = ["x"]
    fetch_names = ["y"]

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s
        self.calls = 0

    def run(self, feed):
        self.calls += 1
        if self.delay_s:
            time.sleep(self.delay_s)
        return [np.asarray(feed["x"]) * 2.0]


def _feed(rows=1, cols=4, fill=1.0):
    return {"x": np.full((rows, cols), fill, "float32")}


def _stub_pairs(feeds):
    return [("y", np.asarray(feeds["x"]) * 2.0)]


def _stub_pairs_t(feeds, tenant=None):
    return _stub_pairs(feeds)


def _write_goldens(tmp_path, model="mlp", n=2):
    golden_cli = _tool("golden")
    gs = golden_cli.record_cases(
        _stub_pairs, model,
        [_feed(fill=1.0), _feed(fill=3.0)][:n],
        provenance={"recorded_by": "test_correctness_anatomy"})
    path = str(tmp_path / "golden.json")
    golden_cli.write_goldens(gs, path)
    return path


@pytest.fixture
def canary_flags(tmp_path):
    path = _write_goldens(tmp_path)
    _flags.set_flags({"canary_probe": True,
                      "canary_golden_path": path,
                      "canary_fail_streak": 2,
                      "canary_interval_s": 60.0})  # tests drive cycles
    canary.reset()
    try:
        yield path
    finally:
        _flags.set_flags({"canary_probe": False,
                          "canary_golden_path": "",
                          "canary_fail_streak": 3,
                          "canary_interval_s": 5.0})
        canary.reset()


@pytest.fixture
def audit_flag():
    _flags.set_flags({"divergence_check": True})
    audit.reset()
    try:
        yield
    finally:
        _flags.set_flags({"divergence_check": False})
        audit.reset()


@pytest.fixture
def clean_faults():
    _faults.clear()
    try:
        yield
    finally:
        _faults.clear()


def _wait(cond, timeout=20.0, poll=0.03, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(poll)
    pytest.fail(f"timed out waiting for {msg}")


# -- digests + the audit ring ------------------------------------------------

def test_digests_are_deterministic_and_content_sensitive():
    pairs = [("y", np.arange(6, dtype="float32").reshape(2, 3))]
    d1 = audit.digest_pairs(pairs)
    d2 = audit.digest_pairs([("y", np.arange(6, dtype="float32")
                              .reshape(2, 3))])
    assert d1 == d2 and len(d1) == 16
    # one ULP of one element moves the digest
    bad = np.arange(6, dtype="float32").reshape(2, 3)
    bad[1, 2] = np.nextafter(bad[1, 2], np.float32(np.inf))
    assert audit.digest_pairs([("y", bad)]) != d1
    # dtype and shape are part of the content (a cast is a change)
    assert audit.digest_pairs(
        [("y", np.arange(6, dtype="float64").reshape(2, 3))]) != d1
    assert audit.digest_pairs(
        [("y", np.arange(6, dtype="float32").reshape(3, 2))]) != d1
    # request hash: name-sorted over the feeds, key-order independent
    h1 = audit.request_hash({"a": np.ones(2), "b": np.zeros(2)})
    h2 = audit.request_hash({"b": np.zeros(2), "a": np.ones(2)})
    assert h1 == h2
    assert audit.request_hash({"a": np.ones(2)}) != h1


def test_token_rolling_hash_order_sensitive():
    h1 = audit.fold_token(audit.fold_token(audit.fnv1a64(b""), 5), 7)
    h2 = audit.fold_token(audit.fold_token(audit.fnv1a64(b""), 7), 5)
    assert h1 != h2


def test_audit_ring_bounded_and_rider_shape(audit_flag):
    r = audit.ring()
    for i in range(audit._RING + 20):
        r.note("m", "1", f"req{i}", f"{i:016x}")
    snap = r.snapshot()
    assert snap["models"]["m"] == audit._RING
    assert snap["noted"] == audit._RING + 20
    recent = audit.recent_digests(limit=4)
    assert [e[1] for e in recent["m"]] == \
        [f"req{i}" for i in range(audit._RING + 16, audit._RING + 20)]
    assert all(len(e) == 3 for e in recent["m"])
    # re-answering the same (version, request) refreshes, not duplicates
    r.note("m", "1", "reqX", "aa")
    r.note("m", "1", "reqX", "bb")
    assert sum(1 for e in r.recent(limit=64)["m"] if e[1] == "reqX") == 1


def test_name_divergent_names_minority_and_reports_pairs():
    e = lambda d: [["1", "req0", d]]  # noqa: E731
    out = audit.name_divergent({"r0": {"m": e("aa")}, "r1": {"m": e("bb")},
                                "r2": {"m": e("aa")}})
    assert out["groups"] == 1 and not out["suspect"]
    (f,) = out["divergent"]
    assert f["replica"] == "r1" and f["digest"] == "bb"
    assert f["majority"] == "aa" and f["agreeing"] == 2
    # 2-way disagreement: no quorum — a suspect PAIR, never a guess
    out = audit.name_divergent({"r0": {"m": e("aa")}, "r1": {"m": e("bb")}})
    assert not out["divergent"]
    assert out["suspect"][0]["replicas"] == {"r0": "aa", "r1": "bb"}
    # agreement and single-replica groups raise nothing
    out = audit.name_divergent({"r0": {"m": e("aa")}, "r1": {"m": e("aa")},
                                "r2": {"n": e("zz")}})
    assert not out["divergent"] and not out["suspect"]


# -- the corrupt fault kind --------------------------------------------------

def test_corrupt_rule_parses_and_site_dispatch(clean_faults):
    (r,) = _faults.parse("corrupt:serving_reply:n=1,bits=3")
    assert r.kind == _faults.CORRUPT and r.bits == 3 and r.n == 1
    _faults.inject("corrupt:serving_reply@r1")
    # replica-qualified: r1's site alias fires, r0's does not
    assert _faults.corrupt_fault("serving_reply@r0", "serving_reply") \
        is None
    assert _faults.corrupt_fault("serving_reply@r1", "serving_reply") == 1
    # a corrupt rule is SITE-ONLY: the wire/event hooks must neither
    # fire it nor burn its budget, even on a matching target
    assert _faults.server_fault("serving_reply@r1") is None
    assert _faults.io_fault("serving_reply@r1") is None
    assert _faults.corrupt_fault("serving_reply@r1") == 1  # still firing


def test_corrupt_array_is_finite_and_outside_rtol():
    a = np.linspace(0.0, 5.0, 8, dtype="float32").reshape(2, 4)
    b = _faults.corrupt_array(a)
    assert b.shape == a.shape and b.dtype == a.dtype
    assert (a != b).sum() == 1
    assert np.isfinite(b).all()          # invisible to the NaN sentinel
    i = int(np.argmax(a != b))
    rel = abs(float(b.flat[i]) - float(a.flat[i])) / abs(float(a.flat[i]))
    assert rel > 1e-3                    # far outside any sane rtol
    # the original buffer is untouched (a fresh copy is returned)
    assert float(a[1, 3]) == 5.0
    # int dtypes corrupt too (decode token buffers)
    ib = _faults.corrupt_array(np.arange(4, dtype="int32"))
    assert (ib != np.arange(4, dtype="int32")).sum() == 1


# -- goldens: record / load / compare ----------------------------------------

def test_golden_record_write_load_replay_roundtrip(tmp_path):
    path = _write_goldens(tmp_path)
    gs = canary.load_goldens(path)
    assert gs.n_cases() == 2
    assert gs.provenance["recorded_by"] == "test_correctness_anatomy"
    case = gs.cases("mlp")[0]
    np.testing.assert_array_equal(case["feeds"]["x"], _feed()["x"])
    golden_cli = _tool("golden")
    # replay against the same build: all pass
    assert golden_cli.replay_cases(_stub_pairs, gs, "mlp") == [None, None]
    # replay against a drifted build: every case names its mismatch
    drifted = lambda feeds: [  # noqa: E731
        ("y", np.asarray(feeds["x"]) * 2.001)]
    res = golden_cli.replay_cases(drifted, gs, "mlp")
    assert all(r is not None and "max_abs_diff" in r for r in res)
    # a future format version is refused, not misread
    payload = json.loads(open(path).read())
    payload["format_version"] = 99
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(payload))
    with pytest.raises(ValueError, match="format_version"):
        canary.load_goldens(str(bad))


def test_compare_pairs_modes():
    exp = [("y", np.ones((1, 3), "float32"))]
    ok = [("y", np.ones((1, 3), "float32") * (1 + 1e-7))]
    assert canary.compare_pairs(exp, ok, rtol=1e-5) is None
    assert "max_abs_diff" in canary.compare_pairs(
        exp, [("y", np.ones((1, 3), "float32") * 1.5)], rtol=1e-5)
    assert "shape" in canary.compare_pairs(
        exp, [("y", np.ones((1, 4), "float32"))], rtol=1e-5)
    assert "missing" in canary.compare_pairs(exp, [], rtol=1e-5)


# -- the prober --------------------------------------------------------------

def test_prober_streaks_health_and_canaryz(canary_flags):
    fails0 = stats.counter("canary.failures").value
    mlp0 = stats.counter("canary.mlp.failures").value
    p = canary.prober()
    assert p.goldens.n_cases() == 2
    good, bad = _StubPredictor(), _StubPredictor()
    canary.register_target("serving/mlp/r0", "mlp",
                           lambda f, t: [("y", good.run(f)[0])])
    canary.register_target(
        "serving/mlp/r1", "mlp",
        lambda f, t: [("y", bad.run(f)[0] + 0.5)])
    assert canary.health_dimension() == {"canary": "ok"}
    res = canary.probe_once()
    assert res == {"serving/mlp/r0": True, "serving/mlp/r1": False}
    s = p.streaks()
    assert s["serving/mlp/r0"]["pass_streak"] == 1
    assert s["serving/mlp/r1"]["fail_streak"] == 1
    assert "max_abs_diff" in s["serving/mlp/r1"]["last_fail"]
    # below FLAGS_canary_fail_streak=2: still ok (transient damping)
    assert canary.health_dimension() == {"canary": "ok"}
    canary.probe_once()
    assert canary.health_dimension() == {
        "canary": "fail", "canary_targets": ["serving/mlp/r1"]}
    # metric series + flight note landed (deltas: counters persist)
    assert stats.counter("canary.failures").value - fails0 == 2
    assert stats.counter("canary.mlp.failures").value - mlp0 == 2
    assert any(e["msg"] == "canary_fail" and e["target"] == "serving/mlp/r1"
               for e in flight.events())
    # lease rider carries the streak; unknown target rides nothing
    rid = canary.lease_rider("serving/mlp/r1")
    assert rid["fail_streak"] == 2 and rid["failures"] == 2
    assert canary.lease_rider("serving/mlp/r9") is None
    # a recovered replica clears within one passing cycle
    canary.unregister_target("serving/mlp/r1")
    canary.register_target("serving/mlp/r1", "mlp",
                           lambda f, t: [("y", good.run(f)[0])])
    canary.probe_once()
    assert canary.health_dimension() == {"canary": "ok"}
    # text rendering shows the per-target table
    text = canary.canaryz_text()
    assert "serving/mlp/r0" in text and "fail_strk" in text
    snap = canary.canaryz()["canary"]
    assert snap["targets"] == 2 and snap["cycles"] == 3
    assert 0.0 <= snap["overhead_frac"] <= 1.0


def test_probe_error_counts_as_failure(canary_flags):
    def boom(f, t):
        raise RuntimeError("replica gone")
    canary.register_target("serving/mlp/r0", "mlp", boom)
    assert canary.probe_once() == {"serving/mlp/r0": False}
    s = canary.prober().streaks()["serving/mlp/r0"]
    assert "probe error" in s["last_fail"]


def test_canary_tenant_excluded_from_metering(canary_flags):
    _flags.set_flags({"tenant_accounting": True})
    tenant.reset()
    try:
        tenant.account(tenant.CANARY, requests=5, rows=5)
        tenant.account("acme", requests=1)
        snap = tenant.meter().snapshot()
        assert snap["tenants"]["acme"]["requests"] == 1
        assert tenant.CANARY not in snap["tenants"]
        assert snap["tracked"] == 1
    finally:
        _flags.set_flags({"tenant_accounting": False})
        tenant.reset()


# -- serving plane: wire probes, digests, corrupt site -----------------------

def test_model_server_probe_through_wire_and_injected_corruption(
        canary_flags, audit_flag, clean_faults):
    """One replica, real sockets: the canary target registers on
    start(), probes pass through the full serde/batcher path, reply
    digests land in the audit ring — and an injected corrupt rule
    flips BOTH planes (probe fails, digest moves) because corruption
    is applied before digesting, exactly like real SDC."""
    srv = ModelServer("127.0.0.1:0", replica_id="r0")
    srv.load("mlp", "1", predictor=_StubPredictor(), warm=False,
             buckets=(1, 2), activate=True, max_delay_ms=1.0)
    srv.start()
    try:
        key = replica_key("mlp", "r0")
        assert key in canary.prober().streaks()
        assert canary.probe_once() == {key: True}
        recent = audit.recent_digests()
        assert "mlp" in recent and len(recent["mlp"]) == 2
        clean = {e[1]: e[2] for e in recent["mlp"]}
        _faults.inject("corrupt:serving_reply@r0")
        assert canary.probe_once() == {key: False}
        poisoned = {e[1]: e[2]
                    for e in audit.recent_digests()["mlp"]}
        assert set(poisoned) == set(clean)        # same requests...
        assert any(poisoned[k] != clean[k] for k in clean)  # ...new bytes
        _faults.clear()
        assert canary.probe_once() == {key: True}
    finally:
        srv.stop()


def test_serving_lease_rides_canary_and_digests(canary_flags, audit_flag):
    reg = reg_mod.RegistryServer("127.0.0.1:0")
    reg.start()
    reg_ep = f"127.0.0.1:{reg.port}"
    srv = ModelServer("127.0.0.1:0", registry_ep=reg_ep,
                      replica_id="r0", lease_ttl=0.2)
    srv.load("mlp", "1", predictor=_StubPredictor(), warm=False,
             buckets=(1, 2), activate=True, max_delay_ms=1.0)
    srv.start()
    rpc = transport.RPCClient(0)
    try:
        canary.probe_once()

        def lease_data():
            snap = reg_mod.fetch_snapshot(rpc, reg_ep)
            return (snap.get("data") or {}).get(replica_key("mlp", "r0"))
        _wait(lambda: (lease_data() or {}).get("canary") is not None,
              msg="canary rider on the lease")
        data = lease_data()
        assert data["canary"]["probes"] >= 1
        assert data["canary"]["fail_streak"] == 0
        assert [e[1] for e in data["digests"]["mlp"]]
        # the heartbeat health dimension rides too
        health = reg_mod.fetch_health(rpc, reg_ep)
        assert health[replica_key("mlp", "r0")]["canary"] == "ok"
    finally:
        srv.stop()
        reg.stop()


# -- decode plane ------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_lm():
    from paddle_tpu.decode import (DecodeEngine, LMConfig, SamplingParams,
                                   TransformerLM)
    cfg = LMConfig(vocab=64, d_model=32, n_head=2, d_ffn=64, n_layer=1,
                   max_seq_len=64)
    lm = TransformerLM(cfg)
    params = lm.init_params(seed=3)
    return DecodeEngine, SamplingParams, lm, params


def test_decode_stream_digests_group_across_engines(tiny_lm, audit_flag):
    """Two engines with identical params answer the same prompt: their
    token rolling hashes agree, keyed by the same prompt hash — the
    grouping invariant the cross-replica sentinel needs."""
    DecodeEngine, SamplingParams, lm, params = tiny_lm
    prompt = np.arange(6, dtype="int32")
    digests = []
    for _ in range(2):
        audit.reset()
        eng = DecodeEngine(lm, params, name="dec", max_slots=2,
                           block_tokens=8, prefill_buckets=(16,),
                           max_queue=4)
        try:
            eng.generate(prompt, max_new_tokens=4)
            _wait(lambda: "dec" in (audit.recent_digests() or {}),
                  msg="stream digest noted")
            digests.append(audit.recent_digests()["dec"])
        finally:
            eng.close()
    assert digests[0] == digests[1]
    assert digests[0][0][1] == audit.request_hash(
        np.asarray(prompt, np.int32).reshape(-1))
    out = audit.name_divergent({"r0": {"dec": digests[0]},
                                "r1": {"dec": digests[1]},
                                "r2": {"dec": [[digests[0][0][0],
                                                digests[0][0][1],
                                                "feedfeedfeedfeed"]]}})
    assert out["divergent"][0]["replica"] == "r2"


def test_decode_cancelled_stream_leaves_no_digest(tiny_lm, audit_flag):
    """A cancelled stream's truncation is client timing, not model
    output — digesting it would fabricate divergence."""
    DecodeEngine, SamplingParams, lm, params = tiny_lm
    eng = DecodeEngine(lm, params, name="dec_c", max_slots=1,
                       block_tokens=8, prefill_buckets=(16,), max_queue=4)
    try:
        h = eng.submit(np.arange(6, dtype="int32"),
                       SamplingParams(max_new_tokens=48))
        h.cancel()
        eng.generate(np.arange(4, dtype="int32"), max_new_tokens=2)
        recent = audit.recent_digests() or {}
        hashes = [e[1] for e in recent.get("dec_c", ())]
        assert audit.request_hash(
            np.arange(6, dtype="int32")) not in hashes
    finally:
        eng.close()


# -- training: DP parameter checksums ----------------------------------------

def _run_dp_replica(steps, corrupt=False):
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.executor import Executor, Scope, scope_guard
    from paddle_tpu.core.program import Program, program_guard
    from paddle_tpu.parallel import ParallelExecutor

    audit.reset()
    if corrupt:
        _faults.inject("corrupt:param_shard")
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 7
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [4])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.01).minimize(loss)
    rng = np.random.RandomState(0)
    batches = [(rng.randn(8, 4).astype("float32"),
                rng.randn(8, 1).astype("float32")) for _ in range(steps)]
    exe = Executor()
    scope = Scope()
    with scope_guard(scope):
        exe.run(startup)
        pe = ParallelExecutor(loss_name=loss.name, main_program=prog,
                              scope=scope)
        for xb, yb in batches:
            pe.run(feed={"x": xb, "y": yb}, fetch_list=[loss])
    recent = audit.recent_digests()
    _faults.clear()
    return (recent or {}).get(audit.PARAMS_MODEL)


def test_param_checksums_name_diverged_dp_replica(audit_flag, clean_faults):
    """Every K steps each replica folds a name-sorted parameter
    checksum keyed ``step:<n>``; identical replicas agree, the one
    with an injected param-shard corruption is NAMED by majority."""
    _flags.set_flags({"divergence_param_steps": 2})
    try:
        r0 = _run_dp_replica(4)
        r1 = _run_dp_replica(4, corrupt=True)
        r2 = _run_dp_replica(4)
    finally:
        _flags.set_flags({"divergence_param_steps": 50})
    assert [e[1] for e in r0] == ["step:2", "step:4"]
    assert r0 == r2
    assert r1 != r0          # the corrupted walk moved the checksum
    out = audit.name_divergent({
        "t0": {audit.PARAMS_MODEL: r0},
        "t1": {audit.PARAMS_MODEL: r1},
        "t2": {audit.PARAMS_MODEL: r2}})
    assert out["divergent"]
    assert all(f["replica"] == "t1" for f in out["divergent"])


def test_param_checksum_off_by_default(clean_faults):
    assert not audit.enabled()
    assert _run_dp_replica(2) is None
    assert audit.recent_digests() is None


# -- flags off: byte identity ------------------------------------------------

def test_flags_off_no_series_no_riders_no_wire_change():
    """Default build: no new canary/divergence series register, the
    health dimension is empty, every rider is None, and the STATS_PULL
    snapshot carries no correctness keys."""
    assert not canary.enabled() and not audit.enabled()
    names_before = set(stats.default_registry().names())
    assert canary.health_dimension() == {}
    assert canary.lease_rider("serving/mlp/r0") is None
    assert canary.export_state() is None
    assert audit.recent_digests() is None
    assert audit.export_state() is None
    assert canary.register_target("x", "m", _stub_pairs_t) is False
    assert canary.probe_once() == {}
    assert canary.maybe_start_from_flags() is False
    # none of that registered a single new metric series
    assert set(stats.default_registry().names()) == names_before
    payload = json.loads(aggregate.local_snapshot_payload())
    assert "canary" not in payload and "audit" not in payload
    merged = aggregate.merge_snapshots({"w0": stats.export_state()})
    assert "canary" not in merged and "audit" not in merged
    # heartbeat payload: no canary dimension
    hb = reg_mod.Heartbeat("127.0.0.1:1", "t/cor", "127.0.0.1:2",
                           role="X")
    assert "canary" not in hb._health_payload()
    # disabled pages say so instead of rendering empty tables
    assert "disabled" in canary.canaryz()["canary"]
    assert "disabled" in audit.auditz()["audit"]


def test_flags_off_serving_lease_byte_identity():
    """With both flags off a replica's lease data payload carries no
    digest rider and no canary rider — byte-identical to the
    pre-correctness-plane build — and inference is untouched."""
    srv = ModelServer("127.0.0.1:0", replica_id="r0")
    srv.load("mlp", "1", predictor=_StubPredictor(), warm=False,
             buckets=(1,), activate=True, max_delay_ms=1.0)
    srv.start()
    try:
        c = ServingClient(endpoints=[srv.endpoint])
        out = c.infer("mlp", _feed())
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      _feed()["x"] * 2.0)
        data = srv._model_data("mlp")()
        assert "canary" not in data and "digests" not in data
    finally:
        srv.stop()


# -- STATS_PULL riders + fleet merge -----------------------------------------

def test_stats_pull_riders_and_fleet_merge(canary_flags, audit_flag):
    canary.register_target("serving/mlp/r0", "mlp", _stub_pairs_t)
    canary.probe_once()
    audit.note_reply("mlp", "1", "req0", "aa")
    payload = json.loads(aggregate.local_snapshot_payload())
    assert payload["canary"]["targets"] == 1
    assert payload["audit"]["recent"]["mlp"]
    # fleet merge: the sentinel runs over per-worker rings
    w = lambda d: {"recent": {"mlp": [["1", "req0", d]]},  # noqa: E731
                   "noted": 1, "models": {"mlp": 1}}
    verdict = audit.merge_states({"w0": w("aa"), "w1": w("aa"),
                                  "w2": w("bb")})
    assert verdict["noted"] == 3
    assert verdict["divergent"][0]["replica"] == "w2"
    # canary merge: streak union, totals sum, overhead takes the worst
    can0 = {"targets": 1, "golden_cases": 2, "cycles": 3,
            "overhead_frac": 0.01, "fail_streak_threshold": 2,
            "streaks": {"serving/mlp/r0": {"fail_streak": 0}}}
    can1 = {"targets": 1, "golden_cases": 2, "cycles": 5,
            "overhead_frac": 0.04, "fail_streak_threshold": 2,
            "streaks": {"serving/mlp/r1": {"fail_streak": 4}}}
    m = canary.merge_states({"w0": can0, "w1": can1})
    assert m["targets"] == 2 and m["cycles"] == 8
    assert m["overhead_frac"] == 0.04
    assert m["failing"] == ["serving/mlp/r1"]


# -- the supervisor: detect -> name -> quarantine ----------------------------

@pytest.mark.chaos_lite
@retry_flaky()
def test_e2e_corrupt_replica_named_and_quarantined(tmp_path):
    """THE acceptance chain, with real worker processes: the
    supervisor spawns 3 serving replicas (each its own audit ring +
    prober, armed via FLAGS_* env), one of which silently corrupts
    every reply (``env_once`` fault arming, chaos-suite idiom).  The
    lying replica's own canary probes fail within one cycle, the
    divergence sentinel NAMES it from the digest riders its leases
    carry, the supervisor confirms after hysteresis and DRAINs exactly
    that worker — while client traffic drops zero requests — and the
    flight record carries detect -> fail -> quarantine -> drain in
    order, with the naming somewhere beside it."""
    from paddle_tpu.distributed.supervisor import (DEAD, DRAINING, LIVE,
                                                   FleetSpec, RoleSpec,
                                                   Supervisor)
    golden_path = _write_goldens(tmp_path)
    flight.clear_events()
    f0 = stats.counter("supervisor.canary_fails").value
    q0 = stats.counter("supervisor.canary_quarantines").value
    d0 = stats.counter("supervisor.divergence_named").value
    keys = [replica_key("mlp", f"r{i}") for i in range(3)]
    bad_key = keys[1]
    spec = FleetSpec(
        roles={"serving": RoleSpec(
            count=3, argv=[sys.executable, RUNNER],
            env={"PADDLE_REGISTRY": "{registry}",
                 "REPLICA_ID": "r{index}",
                 "JAX_PLATFORMS": "cpu",
                 "FLAGS_canary_probe": "1",
                 "FLAGS_canary_golden_path": golden_path,
                 "FLAGS_canary_interval_s": "0.1",
                 "FLAGS_canary_fail_streak": "1",
                 "FLAGS_divergence_check": "1"},
            # only the FIRST spawn of worker 1 lies (a replacement
            # would come up clean — the chaos-suite idiom)
            env_once={1: {"FLAGS_fault_inject":
                          "corrupt:serving_reply@r1"}},
            logical=keys, health_role="SERVING", grace_s=10.0)},
        hysteresis=2, quarantine_on_canary_fail=True, name="t_cor")
    sup = Supervisor(spec, poll_s=0.1, registry_poll_s=0.25)
    sup.start()
    stop_evt = threading.Event()
    errs, counts = [], [0, 0]
    seen_status, seen_div = {}, {}

    def client_loop(idx):
        c = ServingClient(registry_ep=sup.registry_ep, refresh_s=0.1,
                          cooldown_s=0.3)
        i = 0
        while not stop_evt.is_set():
            # unique feeds per request: organic traffic never repeats
            # a request hash across replicas, so only the canary's
            # golden feeds (common by construction) group fleet-wide
            i += 1
            x = np.full((1, 4), float(idx * 100000 + i), "float32")
            try:
                out = c.infer("mlp", {"x": x})
                # shape only: r1's VALUES are wrong — that is the
                # point of silent corruption — but nothing drops
                assert np.asarray(out[0]).shape == (1, 4)
            except Exception as e:  # noqa: BLE001 — ANY error = a drop
                errs.append(repr(e))
                return
            counts[idx] += 1
            time.sleep(0.004)
    threads = [threading.Thread(target=client_loop, args=(i,))
               for i in (0, 1)]

    def _bad_worker():
        return sup.workers.get("serving-1")

    def _quarantined():
        st = sup.status()
        if st.get("canary_fails"):
            seen_status.update(st)
        if st.get("divergence"):
            seen_div.update(st["divergence"])
        w = _bad_worker()
        return w is not None and w.state in (DRAINING, DEAD)
    try:
        _wait(lambda: sum(1 for w in sup.workers.values()
                          if w.state == LIVE) == 3,
              timeout=90, msg="3 replicas LIVE")
        for t in threads:
            t.start()
        _wait(lambda: sum(counts) >= 20, msg="baseline traffic")
        _wait(_quarantined, timeout=60,
              msg="supervisor quarantine-drain of serving-1")
        # exactly the liar was drained; its siblings keep serving
        for w in sup.workers.values():
            if w.name != "serving-1":
                assert w.state == LIVE, (w.name, w.state)
        before = sum(counts)
        _wait(lambda: sum(counts) >= before + 20,
              msg="survivors keep serving after the drain")
        _wait(lambda: _bad_worker().state == DEAD, timeout=30,
              msg="drained worker reaped")
        # the drain deregistered the lease (graceful, not a kill)
        snap = reg_mod.fetch_snapshot(transport.RPCClient(0),
                                      sup.registry_ep)
        assert bad_key not in (snap.get("leases") or {})
        # status surfaced the confirmed fail + named divergence
        assert bad_key in seen_status.get("canary_fails", {})
        assert seen_status["roles"]["serving"]["canary_fail_streak"] >= 2
        assert any(f["replica"] == bad_key
                   for f in seen_div.get("divergent", ())), seen_div
        # counters: one confirmed fail, one quarantine, >=1 naming
        assert stats.counter("supervisor.canary_fails").value - f0 == 1
        assert stats.counter(
            "supervisor.canary_quarantines").value - q0 == 1
        assert stats.counter(
            "supervisor.divergence_named").value - d0 >= 1
        # the flight record carries the canary chain IN ORDER: detect →
        # confirmed fail → quarantine → drain all come out of one
        # observation path (_observe_canary_locked), which is the order
        # the code guarantees.  The divergence naming is an independent
        # signal — digests ride the lease data and refresh on lease
        # renewal — so it may land before or after the drain; it must be
        # present and name only the liar (asserted below), not be ordered
        events = flight.events()
        msgs = [e["msg"] for e in events]
        chain = ["supervisor_canary_detect", "supervisor_canary_fail",
                 "supervisor_canary_quarantine", "supervisor_drain"]
        idx = [msgs.index(m) for m in chain]
        assert idx == sorted(idx), list(zip(chain, idx))
        named = [e for e in events
                 if e["msg"] == "supervisor_divergence_named"]
        assert named and all(e["replica"] == bad_key for e in named)
        quar = next(e for e in events
                    if e["msg"] == "supervisor_canary_quarantine")
        assert quar["worker"] == "serving-1" and quar["key"] == bad_key
    finally:
        stop_evt.set()
        for t in threads:
            t.join(timeout=10)
        sup.stop()
    assert errs == [], errs      # zero dropped requests, end to end


def test_clean_soak_zero_false_positives(canary_flags, audit_flag):
    """No fault injected: many probe cycles + digest notes across 3
    replica targets produce zero failures, zero divergence findings,
    and an ok health dimension throughout."""
    fails0 = stats.counter("canary.failures").value
    preds = [_StubPredictor() for _ in range(3)]
    for i, p in enumerate(preds):
        canary.register_target(
            f"serving/mlp/r{i}", "mlp",
            lambda f, t, _p=p: [("y", _p.run(f)[0])])
    per_replica = {}
    for i in range(3):
        audit.reset()
        for fill in (1.0, 2.0, 3.0):
            feeds = _feed(fill=fill)
            audit.note_reply("mlp", "1", audit.request_hash(feeds),
                             audit.digest_pairs(_stub_pairs(feeds)))
        per_replica[f"r{i}"] = audit.recent_digests()
    for _ in range(6):
        res = canary.probe_once()
        assert all(res.values()), res
    assert canary.health_dimension() == {"canary": "ok"}
    assert stats.counter("canary.failures").value - fails0 == 0
    out = audit.name_divergent(per_replica)
    assert out["groups"] == 3
    assert not out["divergent"] and not out["suspect"]


def test_supervisor_canary_clear_and_vanished_worker():
    """Damping bookkeeping: a worker that stops failing clears; one
    that vanishes from the health view is forgotten; a sibling key in
    the same view is never blamed for another target's failure."""
    from paddle_tpu.distributed.supervisor import FleetSpec, RoleSpec, \
        Supervisor
    spec = FleetSpec(roles={"s": RoleSpec(count=0, argv=["true"])},
                     hysteresis=2, name="t_clear")
    sup = Supervisor(spec)            # never started: observe directly
    fail = {"w0": {"canary": "fail", "canary_targets": ["t"]}}
    with sup.lock:
        sup._observe_canary_locked(fail)
        assert sup._canary_streak == {"w0": 1}
        assert not sup._canary_confirmed       # damped
        sup._observe_canary_locked(fail)
        assert "w0" in sup._canary_confirmed   # confirmed at hysteresis
        sup._observe_canary_locked({"w0": {"canary": "ok"}})
        assert not sup._canary_confirmed       # one ok poll clears
        sup._observe_canary_locked(fail)
        sup._observe_canary_locked(fail)
        assert "w0" in sup._canary_confirmed
        sup._observe_canary_locked({})         # deregistered: forgotten
        assert not sup._canary_confirmed and not sup._canary_streak
        # per-target attribution: when the failing target's OWN key is
        # visible in the same view, blame lands there alone (a multi-
        # model process stamps every heartbeat with one dimension)
        view = {"serving/m/r0": {"canary": "fail",
                                 "canary_targets": ["serving/m/r1"]},
                "serving/m/r1": {"canary": "fail",
                                 "canary_targets": ["serving/m/r1"]}}
        sup._observe_canary_locked(view)
        assert sup._canary_streak == {"serving/m/r1": 1}
    assert any(e["msg"] == "supervisor_canary_clear"
               for e in flight.events())


def test_fleetspec_quarantine_flag_roundtrips():
    from paddle_tpu.distributed.supervisor import FleetSpec, RoleSpec
    spec = FleetSpec(roles={"s": RoleSpec(count=1, argv=["true"])},
                     quarantine_on_canary_fail=True)
    d = spec.to_dict()
    assert d["quarantine_on_canary_fail"] is True
    assert FleetSpec.from_dict(d).quarantine_on_canary_fail is True
    assert FleetSpec.from_dict(
        {"roles": {"s": {"count": 1, "argv": ["true"]}}}
    ).quarantine_on_canary_fail is False


# -- operator surfaces -------------------------------------------------------

def test_canaryz_http_and_dump_metrics_modes(capsys, canary_flags,
                                             audit_flag):
    dump_metrics = _tool("dump_metrics")
    canary.register_target("serving/mlp/r0", "mlp", _stub_pairs_t)
    canary.probe_once()
    audit.note_reply("mlp", "1", "req0", "aa")
    srv = debug_server.start(port=0)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/canaryz", timeout=5).read()
        page = json.loads(body)
        assert page["canary"]["targets"] == 1
        assert page["audit"]["noted"] == 1
        assert "canaryz" in urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/", timeout=5).read().decode()
        rc = dump_metrics.main([str(srv.port), "--canaryz"])
        assert rc == 0
        page = json.loads(capsys.readouterr().out)
        assert page["canary"]["streaks"]["serving/mlp/r0"]["probes"] == 1
        rc = dump_metrics.main([str(srv.port), "--canaryz", "--text"])
        assert rc == 0
        assert "serving/mlp/r0" in capsys.readouterr().out
    finally:
        debug_server.stop()


def test_fleet_status_role_table_renders_canary(capsys):
    fleet_cli = _tool("fleet")
    status = {"fleet": "f", "state": "RUNNING",
              "roles": {"serving": {"count": 3, "target": 3, "hold": False,
                                    "canary_fail_streak": 4}},
              "slo_breaches": {}, "canary_fails": {}}
    fleet_cli._print_role_table(status)
    out = capsys.readouterr().out
    assert "canary" in out and "fail:4" in out
    # a role without canary data renders '-' instead of crashing
    fleet_cli._print_role_table(
        {"roles": {"trainer": {"count": 1, "target": 1}},
         "state": "RUNNING"})
    assert "-" in capsys.readouterr().out


def test_golden_cli_show_and_replay(tmp_path, capsys):
    golden_cli = _tool("golden")
    path = _write_goldens(tmp_path)
    assert golden_cli.main(["show", path]) == 0
    page = json.loads(capsys.readouterr().out)
    assert page["models"]["mlp"]["cases"] == 2
    # replay against a live server: the offline parity check
    srv = ModelServer("127.0.0.1:0")
    srv.load("mlp", "1", predictor=_StubPredictor(), warm=False,
             buckets=(1,), activate=True, max_delay_ms=1.0)
    srv.start()
    try:
        rc = golden_cli.main(["replay", path, "--model", "mlp",
                              "--endpoint", srv.endpoint])
        assert rc == 0
        assert "2/2" in capsys.readouterr().out
    finally:
        srv.stop()
