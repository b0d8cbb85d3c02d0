"""Saturation anatomy (ISSUE 16): phase-level utilization + capacity
modeling (busy-window accounting, the operational-law knee estimate and
its binding-phase verdict, delay-injection flipping the verdict), the
wire-optional per-tenant metering plane (proportional device-ms
attribution, the space-saving heavy-hitter sketch, /tenantz), the
flags-off byte-identity guarantees on wire + heartbeat + metric
surface, the lease-data headroom chain into ElasticController and the
supervisor, the fleet STATS_PULL merge, and the operator surfaces
(dump_metrics modes, fleet status table)."""
import json
import time

import numpy as np
import pytest

from paddle_tpu.core import flags as _flags
from paddle_tpu.distributed import faults as _faults
from paddle_tpu.distributed import serde
from paddle_tpu.observability import (aggregate, capacity, debug_server,
                                      stats, tenant)
from paddle_tpu.serving.batcher import DynamicBatcher
from paddle_tpu.serving.client import ServingClient
from paddle_tpu.serving import server as _serving_server


class _StubPredictor:
    feed_names = ["x"]
    fetch_names = ["y"]

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s

    def run(self, feed):
        if self.delay_s:
            time.sleep(self.delay_s)
        return [np.asarray(feed["x"]) * 2.0]


class _LazyOut:
    """Materialization-deferred output: the sleep happens inside
    ``np.asarray`` on the completer thread, so busy time lands in the
    tracker's ``device`` component (like a real device readback)."""

    def __init__(self, arr, delay_s):
        self._arr = arr
        self._delay_s = delay_s

    def __array__(self, dtype=None):
        time.sleep(self._delay_s)
        a = self._arr
        return a.astype(dtype) if dtype is not None else a


class _LazyDevicePredictor:
    feed_names = ["x"]
    fetch_names = ["y"]

    def __init__(self, device_s):
        self.device_s = device_s

    def run(self, feed):
        return [_LazyOut(np.asarray(feed["x"]) * 2.0, self.device_s)]


@pytest.fixture
def cap_flag():
    _flags.set_flags({"capacity_attribution": True})
    try:
        yield
    finally:
        _flags.set_flags({"capacity_attribution": False})
        capacity.reset()


@pytest.fixture
def tenant_flag():
    _flags.set_flags({"tenant_accounting": True})
    tenant.reset()
    try:
        yield
    finally:
        _flags.set_flags({"tenant_accounting": False})
        tenant.reset()


@pytest.fixture
def clean_faults():
    _faults.clear()
    try:
        yield
    finally:
        _faults.clear()


def _feed(rows=1, cols=3):
    return {"x": np.ones((rows, cols), "float32")}


# -- the capacity model ------------------------------------------------------

def test_busy_window_memory_is_bounded():
    w = capacity._BusyWindow()
    for i in range(10 * capacity._SLOTS):
        w.add(1.0, 1.0, now=i * capacity._SLOT_S)
    assert len(w._slots) <= capacity._SLOTS
    busy, work = w.window(now=10 * capacity._SLOTS * capacity._SLOT_S,
                          window_s=4 * capacity._SLOT_S)
    assert busy <= 5.0 and work <= 5.0


def test_predicted_max_qps_matches_measured_knee(cap_flag):
    """The acceptance pin: drive a pipeline whose device stage serially
    costs ~8 ms/request to saturation; the operational-law estimate
    ``predicted_max_qps = 1000/S_device`` lands within 20% of the
    measured throughput knee, and the binding phase is NAMED."""
    dev_s = 0.008
    b = DynamicBatcher(_LazyDevicePredictor(dev_s), name="t_cap_knee",
                       buckets=(1,), max_delay_ms=0.5)
    try:
        n = 25
        t0 = time.monotonic()
        futs = [b.submit(_feed()) for _ in range(n)]
        [f.result(timeout=60) for f in futs]
        measured_qps = n / (time.monotonic() - t0)
        snap = b.stats.capacity().snapshot(window_s=120.0)
        assert snap["binding_phase"] == "device"
        assert snap["completed"] == n
        assert snap["predicted_max_qps"] == pytest.approx(
            measured_qps, rel=0.20)
        # saturated load really was saturated, and the verdict says so
        assert snap["utilization"] >= capacity.APPROACHING_UTIL
        assert snap["verdict"] in ("approaching", "saturated")
        assert snap["headroom_frac"] == pytest.approx(
            1.0 - snap["utilization"], abs=1e-6)
        # the bucket fit recorded the padded-batch service time
        fit = snap["bucket_fits"]["device"]["1"]
        assert fit["count"] == n
        assert fit["mean_ms"] >= dev_s * 1e3 * 0.9
        # utilization gauges registered (flag armed -> series exist)
        names = stats.default_registry().names()
        assert "serving.t_cap_knee.util.device" in names
        assert "serving.t_cap_knee.util.headroom_frac" in names
    finally:
        b.close()
    # close() unregisters the tracker (no stale /capacityz entries)
    assert capacity.get("serving.t_cap_knee") is None


def test_dispatch_delay_flips_binding_verdict(cap_flag, clean_faults):
    """A fault-injected `delay:serving_dispatch` must move the binding
    phase from `device` to `dispatch` — the verdict names the phase an
    operator should actually fix."""
    b = DynamicBatcher(_LazyDevicePredictor(0.004), name="t_cap_flip",
                       buckets=(1,), max_delay_ms=0.5)
    try:
        for _ in range(6):
            b.infer(_feed(), timeout=30)
        snap = b.stats.capacity().snapshot(window_s=120.0)
        assert snap["binding_phase"] == "device"

        _faults.inject("delay:serving_dispatch:ms=120")
        for _ in range(3):
            b.infer(_feed(), timeout=30)
        snap2 = b.stats.capacity().snapshot(window_s=120.0)
        assert snap2["binding_phase"] == "dispatch"
        assert snap2["components"]["dispatch"]["busy_ms"] >= 300.0
        # the capacity card rides the batcher's /servingz snapshot
        full = b.stats.snapshot()
        assert full["capacity"]["binding_phase"] == "dispatch"
    finally:
        b.close()


def test_headroom_rider_and_healthz(cap_flag):
    t = capacity.tracker("serving.t_hz", ("device", "reply"))
    assert t.headroom() is None          # no completions yet
    t.note("device", 10.0, work=1)
    t.note_done(1)
    hr = t.headroom()
    assert set(hr) == {"headroom_frac", "binding_phase",
                       "predicted_max_qps"}
    assert hr["binding_phase"] == "device"
    # /healthz folds the compact rider in when the plane is armed
    hz = debug_server._healthz()
    assert hz["headroom"]["serving.t_hz"] == hr


# -- per-tenant metering -----------------------------------------------------

def test_tenant_device_ms_sums_to_batch_device_wall(cap_flag, tenant_flag):
    """The acceptance pin: a mixed-tenant batch's device wall splits by
    row share, so per-tenant device-ms sums to the measured device busy
    time within 1% — attribution never invents or loses capacity."""
    b = DynamicBatcher(_LazyDevicePredictor(0.005), name="t_ten_sum",
                       buckets=(8,), max_delay_ms=20.0)
    try:
        tenants = ("t0", "t1", "t2", None)
        futs = [b.submit(_feed(), tenant=tenants[i % 4])
                for i in range(8)]
        [f.result(timeout=30) for f in futs]
        device_busy = b.stats.capacity().snapshot(
            window_s=120.0)["components"]["device"]["busy_ms"]
        assert device_busy > 0
        snap = tenant.meter(create=False).snapshot()
        assert set(snap["tenants"]) == {"t0", "t1", "t2",
                                        tenant.UNTENANTED}
        total = sum(rec["device_ms"] for rec in snap["tenants"].values())
        assert total == pytest.approx(device_busy, rel=0.01)
        for rec in snap["tenants"].values():
            assert rec["requests"] == 2 and rec["rows"] == 2
            assert rec["p99_ms"] > 0
    finally:
        b.close()


def test_space_saving_sketch_evicts_and_rolls_up():
    m = tenant.TenantMeter(k=3)
    for _ in range(60):
        m.account("t_hot", requests=1)
    for _ in range(4):
        m.account("t_warm", requests=1, rows=2)
    for _ in range(3):
        m.account("t_cold", requests=1, rows=2, device_ms=1.0)
    # at capacity: a newcomer evicts the minimum-weight entry (t_cold),
    # whose usage rolls into `other`; the newcomer inherits the evicted
    # weight as its error bound (the space-saving guarantee)
    m.account("newcomer", requests=1)
    s = m.snapshot()
    assert s["tracked"] == 3 and s["evictions"] == 1
    assert "t_cold" not in s["tenants"] and "newcomer" in s["tenants"]
    assert s["tenants"]["newcomer"]["requests"] == 1
    assert s["tenants"]["newcomer"]["weight_error"] == 3.0
    assert s[tenant.OTHER]["requests"] == 3
    assert s[tenant.OTHER]["rows"] == 6
    assert s[tenant.OTHER]["device_ms"] == pytest.approx(3.0)
    # a true heavy hitter survives an adversarial singleton stream
    for i in range(50):
        m.account(f"adv{i}", requests=1)
    assert "t_hot" in m.snapshot()["tenants"]


def test_tenant_id_clipping_and_untenanted():
    m = tenant.TenantMeter(k=4)
    m.account(None, requests=1)
    m.account("x" * 200, requests=1)
    s = m.snapshot()
    assert tenant.UNTENANTED in s["tenants"]
    assert "x" * tenant._MAX_ID_LEN in s["tenants"]
    assert all(len(t) <= tenant._MAX_ID_LEN for t in s["tenants"])


# -- flags off: byte identity ------------------------------------------------

def test_flags_off_no_series_no_riders_no_wire_change(clean_faults):
    """Default build: no `.util.` series, no capacity/tenants snapshot
    keys, no STATS_PULL riders, no /healthz headroom, and the INFER
    frame without a tenant id is byte-identical to a tenant-unaware
    client's."""
    assert not capacity.enabled() and not tenant.enabled()
    b = DynamicBatcher(_StubPredictor(), name="t_cap_off", buckets=(1, 2),
                       max_delay_ms=1.0)
    try:
        # a tenant id with the flag off is IGNORED, not an error
        b.submit(_feed(), tenant="mallory").result(timeout=10)
        assert b.stats.capacity() is None
        assert "capacity" not in b.stats.snapshot()
        assert not any(".util." in n
                       for n in stats.default_registry().names()
                       if n.startswith("serving.t_cap_off"))
    finally:
        b.close()
    assert capacity.export_state() is None
    assert tenant.export_state() is None
    assert tenant.meter(create=True) is None      # flag off: no meter
    payload = json.loads(aggregate.local_snapshot_payload())
    assert "capacity" not in payload and "tenants" not in payload
    merged = aggregate.merge_snapshots({"w0": stats.export_state()})
    assert "capacity" not in merged and "tenants" not in merged
    assert "headroom" not in debug_server._healthz()
    # disabled pages say so instead of rendering empty tables
    assert "disabled" in capacity.capacityz()["capacity"]
    assert "disabled" in tenant.tenantz()["tenants"]


def test_infer_wire_tenant_optional_byte_identity():
    """The tenant id rides a reserved serde feed pair ONLY when set:
    absent, the frame bytes are identical to a tenant-unaware build;
    present, the reserved pair round-trips the id for the server."""
    def _frame(pairs):
        # dumps_batch_vec returns a buffer list (vectorized send):
        # joining yields the on-the-wire frame bytes
        return b"".join(bytes(b) for b in serde.dumps_batch_vec(pairs))

    captured = []
    reply = _serving_server._TAG_RESULT + _frame(
        [("y", np.zeros((1, 3), "float32"))])

    class _CaptureRPC:
        def _raw_request(self, ep, tag, model, payload, **kw):
            if isinstance(payload, (list, tuple)):
                payload = b"".join(bytes(b) for b in payload)
            captured.append(bytes(payload))
            return reply

    sc = ServingClient(endpoints=["127.0.0.1:1"])
    sc._client = _CaptureRPC()
    feed = {"x": np.arange(6, dtype="float32").reshape(2, 3)}
    sc.infer("m", feed)
    sc.infer("m", feed, tenant=None)
    baseline = _frame(
        [(n, np.asarray(v)) for n, v in sorted(feed.items())])
    assert captured[0] == captured[1] == baseline
    sc.infer("m", feed, tenant="acme")
    assert captured[2] != baseline
    pairs = dict(serde.loads_batch(memoryview(captured[2]), copy=True))
    assert set(pairs) == {"x", _serving_server.TENANT_FEED_KEY}
    # the exact decode recipe the server applies
    raw = pairs[_serving_server.TENANT_FEED_KEY]
    assert bytes(np.asarray(raw, np.uint8)).decode("utf-8") == "acme"


# -- decode plane ------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_engine_cls():
    from paddle_tpu.decode import (DecodeEngine, LMConfig, SamplingParams,
                                   TransformerLM)
    cfg = LMConfig(vocab=64, d_model=32, n_head=2, d_ffn=64, n_layer=1,
                   max_seq_len=64)
    lm = TransformerLM(cfg)
    params = lm.init_params(seed=3)
    return DecodeEngine, SamplingParams, lm, params


def test_decode_capacity_and_tenant_accounting(tiny_engine_cls, cap_flag,
                                               tenant_flag):
    """Decode half of the attribution invariant: prefill walls go whole
    to their tenant, decode steps split evenly over LIVE slots — so
    per-tenant device-ms sums to the engine's busy time within 1%, and
    token counts attribute per tenant."""
    DecodeEngine, SamplingParams, lm, params = tiny_engine_cls
    eng = DecodeEngine(lm, params, name="t_cap_dec", max_slots=2,
                       block_tokens=8, prefill_buckets=(16, 32),
                       max_queue=8)
    try:
        h1 = eng.submit(np.arange(6, dtype="int32"),
                        SamplingParams(max_new_tokens=4), tenant="acme")
        h2 = eng.submit(np.arange(5, dtype="int32"),
                        SamplingParams(max_new_tokens=3), tenant="zoo")
        h1.result(timeout=120)
        h2.result(timeout=120)
        # retirement accounting is post-result; wait for both folds
        deadline = time.monotonic() + 10
        while True:
            snap = tenant.meter(create=False).snapshot()
            recs = snap["tenants"]
            if {"acme", "zoo"} <= set(recs) and all(
                    recs[t].get("p99_ms") for t in ("acme", "zoo")):
                break
            assert time.monotonic() < deadline
            time.sleep(0.02)
        cap = eng.stats.capacity().snapshot(window_s=300.0)
        assert set(cap["components"]) == {"prefill", "decode"}
        assert cap["completed"] == 2
        assert cap["binding_phase"] in ("prefill", "decode")
        assert "16" in cap["bucket_fits"]["prefill"]
        # token attribution: prefill tokens = prompt length; decode
        # tokens = generated minus the one the prefill produced
        assert recs["acme"]["prefill_tokens"] == 6
        assert recs["acme"]["decode_tokens"] == 3
        assert recs["zoo"]["prefill_tokens"] == 5
        assert recs["zoo"]["decode_tokens"] == 2
        # device-ms closure within 1%
        busy = sum(c["busy_ms"] for c in cap["components"].values())
        attributed = sum(r["device_ms"] for r in recs.values())
        assert attributed == pytest.approx(busy, rel=0.01)
        # /decodez carries the capacity card
        assert eng.decodez()["capacity"]["completed"] == 2

        # cancellation attributes to its tenant
        h3 = eng.submit(np.arange(3, dtype="int32"),
                        SamplingParams(max_new_tokens=40), tenant="acme")
        assert h3.next_token(timeout=60) is not None
        h3.cancel()
        h3.result(timeout=60)
        deadline = time.monotonic() + 10
        while tenant.meter(create=False).snapshot()[
                "tenants"]["acme"].get("cancellations", 0) < 1:
            assert time.monotonic() < deadline
            time.sleep(0.02)
    finally:
        eng.close()
    assert capacity.get("decode.t_cap_dec") is None


# -- fleet merge -------------------------------------------------------------

def test_capacity_tenant_fleet_merge(cap_flag, tenant_flag):
    t = capacity.tracker("serving.m", ("device", "reply"))
    t.note("device", 40.0, work=8)
    t.note_done(8)
    time.sleep(0.25)        # age the window so util < 1 deterministically
    w0 = capacity.export_state()
    assert w0 and "serving.m" in w0
    assert 0.0 < w0["serving.m"]["utilization"] < 1.0
    # a second, much tighter replica: the fleet view takes its headroom
    # (min) while predicted ceilings SUM across replicas
    w1 = {"serving.m": {"qps": 2.0, "predicted_max_qps": 10.0,
                        "headroom_frac": 0.05, "binding_phase": "reply"}}
    fleet_view = capacity.merge_states({"w0": w0, "w1": w1})
    agg = fleet_view["serving.m"]
    assert agg["replicas"] == 2
    assert agg["headroom_frac"] == 0.05
    assert agg["binding_phase"] == "reply"
    assert agg["min_headroom_worker"] == "w1"
    assert agg["predicted_max_qps"] == pytest.approx(
        w0["serving.m"]["predicted_max_qps"] + 10.0)

    # tenants through the full STATS_PULL payload + merge
    tenant.account("acme", requests=3, rows=6, device_ms=30.0)
    tenant.account("beta", requests=1, rows=1, device_ms=5.0)
    payload = json.loads(aggregate.local_snapshot_payload())
    assert "capacity" in payload and "tenants" in payload
    merged = aggregate.merge_snapshots({"w0": payload, "w1": payload})
    assert merged["capacity"]["fleet"]["serving.m"]["replicas"] == 2
    assert set(merged["capacity"]["per_worker"]) == {"w0", "w1"}
    acme = merged["tenants"]["tenants"]["acme"]
    assert acme["requests"] == 6
    assert acme["device_ms"] == pytest.approx(60.0)


def test_tenant_merge_retrim_folds_overflow_into_other():
    _flags.set_flags({"tenant_accounting": True, "tenant_top_k": 2})
    try:
        w = {"top_k": 2, "tracked": 3, "evictions": 0,
             "tenants": {"a": {"requests": 10, "device_ms": 1.0},
                         "b": {"requests": 5, "device_ms": 2.0},
                         "c": {"requests": 1, "device_ms": 3.0}}}
        merged = tenant.merge_states({"w0": w, "w1": w})
        assert set(merged["tenants"]) == {"a", "b"}   # re-trim to top-K
        assert merged["tenants"]["a"]["requests"] == 20
        assert merged[tenant.OTHER]["requests"] == 2  # c folded
        assert merged[tenant.OTHER]["device_ms"] == pytest.approx(6.0)
    finally:
        _flags.set_flags({"tenant_accounting": False, "tenant_top_k": 20})
        tenant.reset()


# -- the headroom -> lease data -> elastic/supervisor chain ------------------

def test_headroom_rides_lease_data_to_elastic_and_supervisor(cap_flag):
    """The self-sizing chain: a replica's heartbeat publishes the
    compact headroom rider as lease data; the ElasticController filters
    it per role and carries it on decide() informationally (HOLD-safe);
    a supervisor folds the tightest replica's headroom into its status
    card — and takes NO action on it."""
    from paddle_tpu.checkpoint.elastic import ElasticController
    from paddle_tpu.distributed.registry import Heartbeat, RegistryServer
    from paddle_tpu.distributed.supervisor import FleetSpec, RoleSpec, \
        Supervisor

    reg = RegistryServer("127.0.0.1:0")
    reg.start()
    ep = f"127.0.0.1:{reg.port}"
    rider = {"qps": 12.0, "headroom_frac": 0.25, "binding_phase": "device",
             "predicted_max_qps": 48.0}
    hb = Heartbeat(ep, "serving/t_cap/r0", "127.0.0.1:9200", ttl=0.2,
                   role="SERVING", data_fn=lambda: rider)
    hb.start()
    try:
        ctrl = ElasticController(ep, poll_ttl=0.05)
        deadline = time.monotonic() + 10
        while True:
            hr = ctrl.headroom("SERVING")
            if "serving/t_cap/r0" in hr:
                break
            assert time.monotonic() < deadline
            time.sleep(0.05)
        ent = hr["serving/t_cap/r0"]
        assert ent["headroom_frac"] == 0.25
        assert ent["binding_phase"] == "device"
        assert ent["predicted_max_qps"] == 48.0
        # role filtering: a DECODE view excludes the serving lease
        assert ctrl.headroom("DECODE") == {}
        # decide() carries capacity informationally; action unchanged
        d = ctrl.decide("SERVING", 1)
        assert d["action"] == "hold"
        assert d["capacity"]["serving/t_cap/r0"]["headroom_frac"] == 0.25

        spec = FleetSpec(roles={"serving": RoleSpec(
            count=0, argv=["true"], health_role="SERVING")},
            registry=ep, name="t_cap")
        sup = Supervisor(spec, poll_s=0.05, registry_poll_s=0.05)
        sup.start()
        try:
            deadline = time.monotonic() + 10
            while True:
                st = sup.status()
                if st.get("headroom", {}).get("serving/t_cap/r0"):
                    break
                assert time.monotonic() < deadline
                time.sleep(0.05)
            assert st["roles"]["serving"]["headroom_frac"] == 0.25
            assert st["state"] == "RUNNING"       # HOLD-safe: no action
        finally:
            sup.stop()
    finally:
        hb.stop(bye=True)
        reg.stop()


# -- operator surfaces -------------------------------------------------------

def test_dump_metrics_capacityz_tenantz_modes(capsys, cap_flag,
                                              tenant_flag):
    import sys
    sys.path.insert(0, "tools")
    try:
        import dump_metrics
    finally:
        sys.path.pop(0)
    t = capacity.tracker("serving.t_cli", ("device",))
    t.note("device", 5.0, bucket=8, work=8)
    t.note_done(4)
    tenant.account("acme", requests=2, rows=4, device_ms=5.0)
    srv = debug_server.start(port=0)
    try:
        rc = dump_metrics.main([str(srv.port), "--capacityz"])
        assert rc == 0
        page = json.loads(capsys.readouterr().out)
        assert page["pipelines"]["serving.t_cli"][
            "binding_phase"] == "device"
        rc = dump_metrics.main([str(srv.port), "--capacityz", "--text"])
        assert rc == 0
        assert "binding=device" in capsys.readouterr().out
        rc = dump_metrics.main([str(srv.port), "--tenantz"])
        assert rc == 0
        page = json.loads(capsys.readouterr().out)
        assert page["tenants"]["acme"]["requests"] == 2
        rc = dump_metrics.main([str(srv.port), "--tenantz", "--text"])
        assert rc == 0
        text = capsys.readouterr().out
        assert "acme" in text and "device_ms" in text
    finally:
        debug_server.stop()


def test_fleet_status_role_table_renders_headroom(capsys):
    import sys
    sys.path.insert(0, "tools")
    try:
        import fleet as fleet_cli
    finally:
        sys.path.pop(0)
    status = {"fleet": "f", "state": "RUNNING",
              "roles": {"serving": {"count": 2, "target": 2, "hold": False,
                                    "headroom_frac": 0.125}},
              "slo_breaches": {"serving-0": ["lat"]}}
    fleet_cli._print_role_table({"f": status})
    out = capsys.readouterr().out
    assert "serving" in out and "12.5%" in out
    # a role without capacity data renders '-' instead of crashing
    fleet_cli._print_role_table(
        {"roles": {"trainer": {"count": 1, "target": 1}}, "state": "RUNNING"})
    assert "-" in capsys.readouterr().out


