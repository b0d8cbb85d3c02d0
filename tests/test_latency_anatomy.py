"""Latency anatomy & SLO plane (ISSUE 15): per-request phase
attribution through the serving batcher and decode engine (the
phases-sum-to-wall invariant, delay-injection naming its phase on
/servingz//decodez), TTFT/TBT decode histograms + goodput, metric
history rings (wraparound, downsampling, skew-proof fleet merge), the
SLO watchdog (grammar, sustain/clear hysteresis, flight notes, /sloz,
the heartbeat slo dimension through the registry into
ElasticController + supervisor), the /healthz inference-liveness fix,
and the shared percentile helpers."""
import json
import time
import urllib.request

import numpy as np
import pytest

from paddle_tpu.core import flags as _flags
from paddle_tpu.distributed import faults as _faults
from paddle_tpu.observability import (aggregate, debug_server, flight,
                                      history, phase, slo, stats,
                                      step_stats)
from paddle_tpu.observability.history import HistoryStore, SeriesRing
from paddle_tpu.serving.batcher import DynamicBatcher


class _StubPredictor:
    feed_names = ["x"]
    fetch_names = ["y"]

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s

    def run(self, feed):
        if self.delay_s:
            time.sleep(self.delay_s)
        return [np.asarray(feed["x"]) * 2.0]


@pytest.fixture
def phase_flag():
    _flags.set_flags({"phase_attribution": True})
    try:
        yield
    finally:
        _flags.set_flags({"phase_attribution": False})


@pytest.fixture
def clean_faults():
    _faults.clear()
    try:
        yield
    finally:
        _faults.clear()


# -- shared percentile helpers ---------------------------------------------

def test_percentile_sorted_interpolates_and_agrees_with_step_stats():
    vals = sorted([3.0, 1.0, 9.0, 7.0, 5.0])
    # Hyndman-Fan type 7: p50 of 5 samples is the middle sample
    assert stats.percentile_sorted(vals, 0.50) == 5.0
    # p75 interpolates: pos = 0.75*4 = 3.0 -> exactly vals[3]
    assert stats.percentile_sorted(vals, 0.75) == 7.0
    # p90: pos = 3.6 -> 7 + 0.6*(9-7)
    assert stats.percentile_sorted(vals, 0.90) == pytest.approx(8.2)
    assert stats.percentile_sorted([], 0.99) == 0.0
    assert stats.percentile_sorted([4.2], 0.99) == 4.2
    # the StepStats summary routes through the SAME helper
    assert step_stats._percentile is stats.percentile_sorted


def test_histogram_percentile_interpolates_inside_bucket():
    h = stats.Histogram("t_anat.h", buckets=(10.0, 20.0, 40.0))
    for v in (5.0, 12.0, 15.0, 18.0, 35.0):
        h.observe(v)
    snap = h.snapshot()
    # p50 target rank 2.5 lands in (10, 20] which holds ranks 2..4:
    # interpolate 10 + (2.5-1)/3 * 10 = 15.0 — INSIDE the bucket, not
    # snapped to its 20.0 edge (the old estimator's answer)
    assert stats.histogram_percentile(snap, 0.50) == pytest.approx(15.0)
    # a quantile landing in +Inf reports the largest finite edge
    h2 = stats.Histogram("t_anat.h2", buckets=(1.0,))
    h2.observe(50.0)
    assert h2.percentile(0.99) == 1.0
    # string "+Inf" keys (the fleet-merge wire form) parse too
    wire = {"buckets": {"10": 1, "20": 2, "+Inf": 2}, "count": 2}
    assert stats.histogram_percentile(wire, 0.50) == pytest.approx(10.0)


def test_servingz_pct_uses_shared_percentile(phase_flag):
    b = DynamicBatcher(_StubPredictor(), name="t_pct", buckets=(1, 2),
                       max_delay_ms=1.0)
    try:
        for _ in range(5):
            b.infer({"x": np.ones((1, 3), "float32")}, timeout=10)
        snap = b.stats.snapshot()
        lats = sorted(lat for _, lat in b.stats._recent)
        assert snap["p99_ms"] == pytest.approx(
            round(stats.percentile_sorted(lats, 0.99), 3))
        assert snap["p50_ms"] == pytest.approx(
            round(stats.percentile_sorted(lats, 0.50), 3))
    finally:
        b.close()


# -- serving phase attribution ---------------------------------------------

def test_serving_phase_invariant_and_delay_attribution(phase_flag,
                                                       clean_faults):
    """The acceptance pin (serving half): under load, recorded phase
    durations sum to the measured end-to-end wall within 5%, and a
    fault-injected dispatch delay is NAMED by the slowest-phase
    attribution on /servingz."""
    b = DynamicBatcher(_StubPredictor(delay_s=0.005), name="t_anat_m",
                       buckets=(1, 2, 4, 8), max_delay_ms=2.0)
    try:
        # a small load burst so batches coalesce
        t0 = time.monotonic()
        futs = [b.submit({"x": np.ones((1, 3), "float32")})
                for _ in range(12)]
        [f.result(timeout=30) for f in futs]
        rec = b.stats.phases()
        assert rec is not None
        snap = rec.snapshot()
        assert snap["observed"] == 12
        # invariant: each sample's phases sum to its recorded total
        for s in snap["recent"]:
            assert sum(s["phases"].values()) == pytest.approx(
                s["total_ms"], abs=0.01)
        # ... and the recorded total tracks an externally measured wall
        wall_ms = (time.monotonic() - t0) * 1e3
        slowest = snap["slowest_requests"][0]
        assert slowest["total_ms"] <= wall_ms * 1.05
        assert set(snap["phases"]) == {"queue", "assemble", "dispatch",
                                       "device", "reply"}

        # inject a 120 ms dispatch delay (the PR-6 `delay` rule): the
        # dispatch phase must dominate and be NAMED
        _faults.inject("delay:serving_dispatch:ms=120")
        t1 = time.monotonic()
        fut = b.submit({"x": np.ones((1, 3), "float32")})
        fut.result(timeout=30)
        wall2 = (time.monotonic() - t1) * 1e3
        assert wall2 >= 110.0
        snap2 = b.stats.phases().snapshot()
        worst = snap2["slowest_requests"][0]
        assert max(worst["phases"], key=worst["phases"].get) == "dispatch"
        assert sum(worst["phases"].values()) == pytest.approx(
            worst["total_ms"], abs=0.01)
        assert worst["total_ms"] == pytest.approx(wall2, rel=0.05)

        # /servingz (via the manager payload shape): phases ride the
        # batcher stats snapshot
        full = b.stats.snapshot()
        assert full["phases"]["slowest_phase"] == "dispatch"
    finally:
        b.close()


def test_phase_flag_off_no_series_no_timelines(clean_faults):
    assert not phase.enabled()
    b = DynamicBatcher(_StubPredictor(), name="t_anat_off", buckets=(1, 2),
                       max_delay_ms=1.0)
    try:
        fut = b.submit({"x": np.ones((1, 3), "float32")})
        fut.result(timeout=10)
        assert b.stats.phases() is None
        snap = b.stats.snapshot()
        assert "phases" not in snap
        assert not any(".phase." in n
                       for n in stats.default_registry().names()
                       if n.startswith("serving.t_anat_off"))
    finally:
        b.close()


# -- decode TTFT/TBT, goodput, phases --------------------------------------

@pytest.fixture(scope="module")
def tiny_engine_cls():
    from paddle_tpu.decode import (DecodeEngine, LMConfig, SamplingParams,
                                   TransformerLM)
    cfg = LMConfig(vocab=64, d_model=32, n_head=2, d_ffn=64, n_layer=1,
                   max_seq_len=64)
    lm = TransformerLM(cfg)
    params = lm.init_params(seed=3)
    return DecodeEngine, SamplingParams, lm, params


def test_decode_ttft_tbt_goodput_and_phase_invariant(
        tiny_engine_cls, phase_flag, clean_faults):
    """The acceptance pin (decode half): a streaming request's three
    phases sum to its end-to-end wall within 5%; TTFT/TBT histograms
    populate; goodput accounts useful vs pad work; an injected prefill
    delay is named by the attribution on /decodez; the histograms ride
    the fleet merge."""
    DecodeEngine, SamplingParams, lm, params = tiny_engine_cls
    eng = DecodeEngine(lm, params, name="t_anat", max_slots=2,
                       block_tokens=8, prefill_buckets=(16, 32),
                       max_queue=8)
    try:
        t0 = time.monotonic()
        h = eng.submit(np.arange(6, dtype="int32"),
                       SamplingParams(max_new_tokens=5))
        toks = list(h)              # stream it
        wall_ms = (time.monotonic() - t0) * 1e3
        assert len(toks) == 5
        z = eng.decodez()
        assert z["ttft_p99_ms"] > 0
        assert z["tbt_p99_ms"] > 0
        # goodput: 6 real prompt tokens padded to the 16 bucket; 4
        # decode steps with 1 of 2 slots live
        g = z["goodput"]
        assert g["prefill_tokens"] == 6 and g["pad_prefill_tokens"] == 10
        assert g["live_slot_steps"] == 4 and g["pad_slot_steps"] == 4
        assert g["slot_utilization"] == pytest.approx(0.5)
        # the invariant: queue + prefill + decode == end-to-end wall
        sample = z["phases"]["recent"][-1]
        assert set(sample["phases"]) == {"queue", "prefill", "decode"}
        assert sum(sample["phases"].values()) == pytest.approx(
            sample["total_ms"], abs=0.01)
        assert sample["total_ms"] == pytest.approx(wall_ms, rel=0.05)
        assert sample["finish"] == "length" and sample["tokens"] == 5

        # injected prefill delay (warm executables now: the delay
        # dominates) -> TTFT inflates and 'prefill' is the named phase;
        # an SLO rule armed on the ttft_ms p99 trips off the SAME
        # injected delay (the acceptance chain's trigger)
        wd = slo.SloWatchdog("ttft=decode.t_anat.ttft_ms:p99>100:for=0")
        wd.evaluate()                         # baseline window
        _faults.inject("delay:decode_prefill:ms=150")
        h2 = eng.submit(np.arange(4, dtype="int32"),
                        SamplingParams(max_new_tokens=2))
        h2.result(timeout=60)
        ev = wd.evaluate()
        assert ev and ev[0]["event"] == "breach" and ev[0]["value"] >= 150
        assert any(e["msg"] == "slo_breach" and e.get("rule") == "ttft"
                   for e in flight.events())
        z2 = eng.decodez()
        # the delayed request is the newest sample (the first request's
        # cold-compile walls still own the all-time slowest exemplar)
        delayed = z2["phases"]["recent"][-1]
        assert max(delayed["phases"], key=delayed["phases"].get) == \
            "prefill"
        assert delayed["phases"]["prefill"] >= 150.0
        assert z2["ttft_p99_ms"] >= 150.0

        # fleet merge: the TTFT/TBT histograms ride export_state like
        # any histogram — bucket-merged under their metric names
        merged = aggregate.merge_snapshots(
            {"w0": stats.export_state(), "w1": stats.export_state()})
        hh = merged["histograms"]["decode.t_anat.ttft_ms"]
        assert hh["count"] == 2 * eng.stats.lat.ttft_ms.count
        assert "decode.t_anat.tbt_ms" in merged["histograms"]
    finally:
        eng.close()


def test_decode_cancel_counts_into_goodput(tiny_engine_cls, phase_flag):
    DecodeEngine, SamplingParams, lm, params = tiny_engine_cls
    eng = DecodeEngine(lm, params, name="t_anat_c", max_slots=1,
                       block_tokens=8, prefill_buckets=(16,),
                       max_queue=8)
    try:
        h = eng.submit(np.arange(3, dtype="int32"),
                       SamplingParams(max_new_tokens=40))
        assert h.next_token(timeout=60) is not None
        h.cancel()
        h.result(timeout=60)
        deadline = time.monotonic() + 10
        while eng.stats.lat.cancelled.value < 1:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert eng.stats.lat.cancelled_tokens.value >= 1
    finally:
        eng.close()


# -- metric history rings ---------------------------------------------------

def test_history_ring_wraparound_downsampling_bounded():
    r = SeriesRing(16)
    for i in range(1000):
        r.append(float(i), float(i))
    # bounded memory: never past capacity; resolution doubled instead
    assert len(r) <= 16 and r.stride in (64, 128)
    pts = r.points(now=1000.0)
    ages = [a for a, _ in pts]
    # monotonic timestamps: oldest-first, ages strictly decreasing
    assert all(ages[i] > ages[i + 1] for i in range(len(ages) - 1))
    # correct downsampled means: a stored point covering raw samples
    # [k, k+stride) has value mean == k + (stride-1)/2, and its
    # timestamp is the window end (k + stride - 1)
    for age, v in pts:
        t = 1000.0 - age
        k = t - (r.stride - 1)
        assert v == pytest.approx(k + (r.stride - 1) / 2.0)


def test_history_store_sampling_and_window_query():
    reg = stats.StatsRegistry()
    c = reg.counter("steps")
    g = reg.gauge("depth")
    reg.histogram("lat_ms").observe(1.0)   # histograms are skipped
    st = HistoryStore(reg, points=32)
    for i in range(50):
        c.inc()
        g.set(i)
        st.sample(now=float(i))
    q = st.query(window_s=10.0, now=49.0)
    assert set(q) == {"steps", "depth"}
    for name, pts in q.items():
        assert all(age <= 10.0 for age, _ in pts)
    # the counter series is monotonic in value
    vals = [v for _, v in st.query(now=49.0)["steps"]]
    assert vals == sorted(vals)
    assert st.stats()["points"] <= 2 * 32


def test_history_fleet_merge_with_skewed_worker_clocks():
    """Two workers whose monotonic clocks disagree by hours still merge
    into comparable series: the wire form is ages-at-pull, never wall
    clocks."""
    regs, stores, states = [], [], {}
    for w, base in (("w0", 1_000.0), ("w1", 500_000.0)):  # wild skew
        reg = stats.StatsRegistry()
        g = reg.gauge("qps")
        st = HistoryStore(reg, points=64)
        for i in range(20):
            g.set(i)
            st.sample(now=base + i)
        state = reg.export_state()
        state["history"] = st.export_state(now=base + 19)
        states[w] = state
        regs.append(reg)
        stores.append(st)
    merged = aggregate.merge_snapshots(states)
    assert set(merged["history"]) == {"w0", "w1"}
    s0 = merged["history"]["w0"]["series"]["qps"]
    s1 = merged["history"]["w1"]["series"]["qps"]
    # identical sampling cadence => identical ages despite the skew
    assert [a for a, _ in s0] == [a for a, _ in s1]
    assert [v for _, v in s0] == [v for _, v in s1]
    # flags-off wire byte-identity: no history key without the plane
    payload = json.loads(aggregate.local_snapshot_payload())
    assert "history" not in payload
    plain = aggregate.merge_snapshots({"w0": regs[0].export_state()})
    assert "history" not in plain


def test_history_varz_disabled_and_enabled():
    assert "disabled" in history.varz()["history"]
    st = history.store(create=True)
    try:
        stats.counter("t_anat.varz_probe").inc()
        st.sample()
        out = history.varz(window_s=60.0, pattern="t_anat.varz_probe")
        assert "t_anat.varz_probe" in out["series_points"]
    finally:
        history.stop()


# -- SLO watchdog -----------------------------------------------------------

def test_slo_rule_grammar():
    rules = slo.parse_rules(
        "ttft=decode.lm.ttft_ms:p99>250:for=5;"
        "err=rpc.client.errors:rate>0.5:for=10;"
        "q=decode.lm.queue_depth:value>48")
    assert [r.name for r in rules] == ["ttft", "err", "q"]
    assert rules[0].stat == "p99" and rules[0].sustain_s == 5.0
    assert rules[2].op == ">" and rules[2].threshold == 48.0
    with pytest.raises(ValueError):
        slo.parse_rules("garbage")
    with pytest.raises(ValueError):
        slo.parse_rules("a=m:p42>1")
    with pytest.raises(ValueError):
        slo.parse_rules("a=m:value>1;a=m:value>2")   # duplicate name


def test_slo_breach_sustain_and_clear():
    wd = slo.SloWatchdog("lag=t_anat.slo_ms:p99>100:for=0.1")
    h = stats.histogram("t_anat.slo_ms")
    for _ in range(10):
        h.observe(500.0)
    assert wd.evaluate() == []        # first sighting: baseline only
    for _ in range(10):
        h.observe(500.0)
    assert wd.evaluate() == []        # pending (sustain window open)
    assert wd.rules[0].state == slo.PENDING
    time.sleep(0.12)
    for _ in range(10):
        h.observe(500.0)
    ev = wd.evaluate()
    assert ev and ev[0]["event"] == "breach"
    assert wd.breached() == ["lag"]
    assert wd.health_dimension() == {"slo": "breach", "slo_rules": ["lag"]}
    assert stats.counter("slo.lag.breaches").value == 1
    # flight note landed
    assert any(e["msg"] == "slo_breach" for e in flight.events())
    # windowed percentile: good recent traffic CLEARS after the window
    for _ in range(200):
        h.observe(1.0)
    assert wd.evaluate() == []        # clear window opens
    time.sleep(0.12)
    for _ in range(200):
        h.observe(1.0)
    ev = wd.evaluate()
    assert ev and ev[0]["event"] == "clear"
    assert wd.health_dimension() == {"slo": "ok"}
    assert any(e["msg"] == "slo_clear" for e in flight.events())


def test_slo_heartbeat_dimension_elastic_and_supervisor():
    """The acceptance chain: an armed rule trips -> /sloz renders ->
    the heartbeat slo dimension flips at the registry -> the
    ElasticController reports it (decisions HOLD-safe) -> a supervisor
    observes a damped confirmed breach in its status."""
    from paddle_tpu.checkpoint.elastic import ElasticController
    from paddle_tpu.distributed.registry import Heartbeat, RegistryServer
    from paddle_tpu.distributed.supervisor import FleetSpec, RoleSpec, \
        Supervisor

    wd = slo.SloWatchdog("ttft=decode.t_slo.ttft_ms:p99>100:for=0")
    slo.install(wd)
    reg = RegistryServer("127.0.0.1:0")
    reg.start()
    ep = f"127.0.0.1:{reg.port}"
    hb = Heartbeat(ep, "decode/t_slo/r0", "127.0.0.1:9100", ttl=0.2,
                   role="DECODE")
    hb.start()
    srv = debug_server.start(port=0)
    try:
        h = stats.histogram("decode.t_slo.ttft_ms")
        for _ in range(5):
            h.observe(400.0)
        wd.evaluate()                 # baseline
        for _ in range(5):
            h.observe(400.0)
        ev = wd.evaluate()
        assert ev and ev[0]["event"] == "breach"

        # /sloz over HTTP
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/sloz", timeout=5).read()
        page = json.loads(body)
        assert page["breached"] == ["ttft"]

        # the registry health table sees the flipped dimension within
        # one lease refresh
        ctrl = ElasticController(ep, poll_ttl=0.05)
        deadline = time.monotonic() + 10
        while True:
            br = ctrl.slo_breaches("DECODE")
            if "decode/t_slo/r0" in br:
                assert br["decode/t_slo/r0"] == ["ttft"]
                break
            assert time.monotonic() < deadline
            time.sleep(0.05)
        # breach rides decide() informationally; action is liveness-only
        d = ctrl.decide("DECODE", 1)
        assert d["action"] == "hold" and "slo_breaches" in d

        # a supervisor against the same registry confirms the breach
        # after `hysteresis` fresh polls — and takes NO action
        spec = FleetSpec(roles={"decode": RoleSpec(count=0, argv=["true"],
                                                   health_role="DECODE")},
                         registry=ep, hysteresis=2, name="t_slo")
        sup = Supervisor(spec, poll_s=0.05, registry_poll_s=0.05)
        sup.start()
        try:
            deadline = time.monotonic() + 10
            while True:
                st = sup.status()
                if "decode/t_slo/r0" in st.get("slo_breaches", {}):
                    break
                assert time.monotonic() < deadline
                time.sleep(0.05)
            assert stats.counter("supervisor.slo_breaches").value >= 1
            assert any(e["msg"] == "supervisor_slo_breach"
                       for e in flight.events())
            assert st["state"] == "RUNNING"      # HOLD-safe: no action
        finally:
            sup.stop()
    finally:
        debug_server.stop()
        hb.stop(bye=True)
        reg.stop()
        slo.install(None)


def test_slo_flag_off_heartbeat_payload_unchanged():
    """No watchdog armed: the heartbeat health payload carries no slo
    key — the wire is byte-identical to the pre-slo build."""
    from paddle_tpu.distributed.registry import Heartbeat
    assert slo.health_dimension() == {}
    hb = Heartbeat("127.0.0.1:1", "t/anat", "127.0.0.1:2", role="X")
    payload = hb._health_payload()
    assert "slo" not in payload and "slo_rules" not in payload


# -- /healthz liveness for inference-only processes -------------------------

def test_healthz_folds_serving_decode_activity(phase_flag):
    """A pure-inference process (no StepStats) must report a bounded
    last-step age once its serving/decode planes dispatch."""
    base = debug_server._healthz()
    # dispatch one serving batch: the activity mark lands
    b = DynamicBatcher(_StubPredictor(), name="t_anat_hz", buckets=(1,),
                       max_delay_ms=1.0)
    try:
        b.infer({"x": np.ones((1, 2), "float32")}, timeout=10)
    finally:
        b.close()
    hz = debug_server._healthz()
    assert "serving" in hz["activity_age_s"]
    assert hz["last_step_age_s"] is not None
    assert hz["last_step_age_s"] <= hz["activity_age_s"]["serving"] + 0.001
    assert hz["last_step_age_s"] < 60.0
    assert base["uptime_s"] <= hz["uptime_s"]


# -- operator CLI -----------------------------------------------------------

def test_dump_metrics_sloz_and_varz_modes(capsys):
    import sys
    sys.path.insert(0, "tools")
    try:
        import dump_metrics
    finally:
        sys.path.pop(0)
    st = history.store(create=True)
    stats.counter("t_anat.cli_probe").inc(3)
    st.sample()
    wd = slo.SloWatchdog("cli=t_anat.cli_probe:rate>1e9")
    slo.install(wd)
    srv = debug_server.start(port=0)
    try:
        rc = dump_metrics.main([str(srv.port), "--sloz"])
        assert rc == 0
        page = json.loads(capsys.readouterr().out)
        assert page["rules"][0]["name"] == "cli"
        rc = dump_metrics.main([str(srv.port), "--varz", "--window", "60"])
        assert rc == 0
        page = json.loads(capsys.readouterr().out)
        assert page["window_s"] == 60.0
        assert "t_anat.cli_probe" in page["series_points"]
    finally:
        debug_server.stop()
        history.stop()
        slo.install(None)
