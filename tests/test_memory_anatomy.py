"""Memory anatomy (ISSUE 19): the per-pool attribution ledger and its
reconciliation identity against live PJRT bytes, the allocation event
ring and its Chrome-trace counter lanes, the leak sentinel's health
dimension, OOM forensics + recovery on the decode plane, the chaos
``oom`` rule, per-tenant resident KV bytes, the flags-off byte-identity
guarantees (no pools, no series, no threads, no rider bytes), the
lease-data memory-headroom chain into ElasticController and the
supervisor, and the operator surfaces (/allocz, dump_metrics --allocz,
fleet status mem column)."""
import json
import threading
import time

import numpy as np
import pytest

from paddle_tpu.core import flags as _flags
from paddle_tpu.distributed import faults as _faults
from paddle_tpu.observability import (aggregate, debug_server, memory,
                                      stats, tenant, trace)
from paddle_tpu.serving.batcher import DynamicBatcher


class _StubPredictor:
    feed_names = ["x"]
    fetch_names = ["y"]

    def run(self, feed):
        return [np.asarray(feed["x"]) * 2.0]


@pytest.fixture
def mem_flag():
    _flags.set_flags({"memory_attribution": True})
    memory.reset()
    try:
        yield
    finally:
        _flags.set_flags({"memory_attribution": False})
        memory.reset()


@pytest.fixture
def clean_faults():
    _faults.clear()
    try:
        yield
    finally:
        _faults.clear()


def _mk_engine(name, **kw):
    from paddle_tpu.decode import (DecodeEngine, LMConfig, SamplingParams,
                                   TransformerLM)
    cfg = LMConfig(vocab=64, d_model=32, n_head=2, d_ffn=64, n_layer=2,
                   max_seq_len=128)
    lm = TransformerLM(cfg)
    params = lm.init_params(seed=0)
    kw.setdefault("max_slots", 4)
    kw.setdefault("block_tokens", 8)
    kw.setdefault("prefill_buckets", (16, 32))
    kw.setdefault("max_queue", 16)
    kw.setdefault("attn_impl", "xla")
    return DecodeEngine(lm, params, name=name, **kw), SamplingParams


def _prompts(n, rng=None):
    rng = rng or np.random.RandomState(0)
    return [rng.randint(0, 64, 12).astype("int32") for _ in range(n)]


# -- flags-off byte identity (FIRST: a later flag-on test registers
#    memory.* series that persist in the process-global registry) ----------

def test_flags_off_no_pools_no_series_no_threads_no_riders():
    """Default build: no pool registers anywhere (engine, batcher), no
    ``memory.*`` series, no sentinel thread, and every rider returns
    its absent form — STATS_PULL, heartbeat, lease and trace payloads
    stay byte-identical to the pre-memory wire."""
    assert not memory.enabled()
    eng, SP = _mk_engine("t_mem_off")
    b = DynamicBatcher(_StubPredictor(), name="t_mem_off_srv",
                       buckets=(1, 2), max_delay_ms=1.0)
    try:
        eng.submit(_prompts(1)[0], SP(max_new_tokens=4)).result(timeout=60)
        b.submit({"x": np.ones((1, 3), "float32")}).result(timeout=10)
        assert eng._mem_pool is None
        assert b._mem_pool is None
        assert memory.pools() == {}
        assert memory.events() == []
    finally:
        b.close()
        eng.close()
    assert memory.export_state() is None
    assert memory.lease_rider() is None
    assert memory.health_dimension() == {}
    assert not memory.maybe_start_sentinel()
    assert not any("memory-leak-sentinel" in t.name
                   for t in threading.enumerate())
    assert not any(n.startswith("memory.")
                   for n in stats.default_registry().names())
    payload = json.loads(aggregate.local_snapshot_payload())
    assert "memory" not in payload
    merged = aggregate.merge_snapshots({"w0": stats.export_state()})
    assert "memory" not in merged
    assert "counters" not in trace.local_trace_snapshot()
    assert "disabled" in str(memory.allocz())
    # the perf page carries no attribution fold when unarmed
    from paddle_tpu.observability import perf
    assert "attribution" not in perf.memz()
    # the heartbeat payload carries no memory dimension when unarmed
    from paddle_tpu.distributed.registry import Heartbeat
    hb = Heartbeat("127.0.0.1:1", "t/off", "127.0.0.1:2", ttl=1.0)
    assert "memory" not in hb._health_payload()


# -- the ledger + reconciliation pin ---------------------------------------

def test_reconciliation_identity_live_decode_under_load(mem_flag):
    """The acceptance pin: with attribution on, per device the sum of
    registered device-pool bytes plus the ``unattributed_bytes``
    residual equals the live ``bytes_in_use`` EXACTLY, read while a
    decode engine is mid-flight; the KV pool reports the paged cache's
    full reservation."""
    eng, SP = _mk_engine("t_mem_rec")
    try:
        handles = [eng.submit(p, SP(max_new_tokens=12))
                   for p in _prompts(8)]
        led = memory.ledger()          # mid-flight snapshot
        for dev, rec in led["devices"].items():
            assert rec["attributed"] + rec["unattributed_bytes"] \
                == rec["bytes_in_use"], (dev, rec)
        kv = led["pools"]["decode_kv.t_mem_rec"]
        assert kv["reserved"] == eng.cache.nbytes
        assert kv["kind"] == "device"
        for h in handles:
            h.result(timeout=120)
        # drained: every block released, alloc/free events filed
        kv = memory.ledger()["pools"]["decode_kv.t_mem_rec"]
        assert kv["used"] == 0
        kinds = {e["kind"] for e in memory.events()}
        assert {"alloc", "free"} <= kinds
        # the STATS_PULL rider carries the ledger and the fleet merge
        # sums pool bytes while keeping the residual per worker
        payload = json.loads(aggregate.local_snapshot_payload())
        assert "decode_kv.t_mem_rec" in payload["memory"]["pools"]
        merged = aggregate.merge_snapshots({"w0": payload, "w1": payload})
        fleet = merged["memory"]["fleet"]
        assert fleet["pools"]["decode_kv.t_mem_rec"]["workers"] == 2
        assert set(fleet["unattributed"]) == {"w0", "w1"}
        # /allocz both renderings
        page = memory.allocz()
        assert "decode_kv.t_mem_rec" in page["ledger"]["pools"]
        assert "decode_kv.t_mem_rec" in memory.allocz_text()
        # /memz folds the same ledger in
        from paddle_tpu.observability import perf
        assert "decode_kv.t_mem_rec" in perf.memz()["attribution"]["pools"]
        assert "attribution" in perf.memz_text()
    finally:
        eng.close()
    assert memory.get("decode_kv.t_mem_rec") is None   # close unregisters


def test_serving_staging_pool_and_checkpoint_pool(mem_flag, tmp_path):
    """The host-side pools: the batcher's staging pool reports queued +
    in-flight feed bytes, the snapshotter's pool reports in-flight
    write buffers (both 0 at rest)."""
    b = DynamicBatcher(_StubPredictor(), name="t_mem_srv",
                       buckets=(1, 2), max_delay_ms=1.0)
    try:
        assert b._mem_pool == "serving_staging.t_mem_srv"
        b.submit({"x": np.ones((1, 3), "float32")}).result(timeout=10)
        snap = memory.get(b._mem_pool).snapshot()
        assert snap["kind"] == "host" and snap["used"] == 0
    finally:
        b.close()
    assert memory.get("serving_staging.t_mem_srv") is None
    from paddle_tpu.checkpoint.snapshot import AsyncSnapshotter
    snapper = AsyncSnapshotter(
        str(tmp_path), "w0",
        lambda step: {"v": np.zeros(1024, "float32")})
    assert snapper.snapshot(1, wait=True)
    assert snapper._inflight_bytes == 0
    pool = memory.get("checkpoint_staging")
    assert pool is not None and pool.snapshot()["used"] == 0
    kinds = [e for e in memory.events()
             if e["pool"] == "checkpoint_staging"]
    assert [e["kind"] for e in kinds] == ["alloc", "free"]
    assert kinds[0]["bytes"] == 4096
    snapper.close()


# -- event ring + counter lanes --------------------------------------------

def test_counter_series_and_chrome_stitch(mem_flag):
    memory.note_event("alloc", "p0", 100)
    memory.note_event("alloc", "p0", 50)
    memory.note_event("park", "p0", 30)
    memory.note_event("reclaim", "p0", 30)
    memory.note_event("free", "p0", 120)
    series = memory.counter_series()
    assert [s["resident"] for s in series] == [100, 150, 120, 120, 0]
    assert [s["parked"] for s in series] == [0, 0, 30, 0, 0]
    snap = trace.local_trace_snapshot()
    assert len(snap["counters"]) == 5
    doc = trace.stitch_chrome_trace({"w0": snap})
    lanes = [e for e in doc["traceEvents"]
             if e["ph"] == "C" and e["name"] == "mem:p0"]
    assert len(lanes) == 5
    assert lanes[-1]["args"] == {"resident": 0, "parked": 0}


def test_event_ring_is_bounded(mem_flag):
    _flags.set_flags({"memory_event_ring": 16})
    try:
        for i in range(100):
            memory.note_event("alloc", "p", 1, i=i)
        evs = memory.events()
        assert len(evs) == 16 and evs[-1]["i"] == 99
    finally:
        _flags.set_flags({"memory_event_ring": 1024})


# -- leak sentinel + health dimension --------------------------------------

def test_leak_audit_promotes_memory_health_dimension(mem_flag):
    memory.pool("ok_pool", "device", lambda: {"used": 1},
                audit=lambda: 0)
    memory.run_audit()
    assert memory.health_dimension() == {"memory": "ok"}
    memory.pool("leaky", "device", lambda: {"used": 1}, audit=lambda: 3)
    leaks = memory.run_audit()
    assert leaks == {"leaky": 3}
    dim = memory.health_dimension()
    assert dim == {"memory": "leak", "memory_pools": ["leaky"]}
    rider = memory.lease_rider()
    assert rider["memory_leak"] == 3
    # the heartbeat payload carries the dimension; the health table
    # files and re-exports it like the canary dimension
    from paddle_tpu.distributed.registry import Heartbeat
    from paddle_tpu.observability.health import HealthTable
    hb = Heartbeat("127.0.0.1:1", "t/leak", "127.0.0.1:2", ttl=1.0)
    payload = hb._health_payload()
    assert payload["memory"] == "leak"
    table = HealthTable()
    table.observe("w0", ttl=1.0, role="DECODE",
                  memory=payload["memory"],
                  memory_pools=payload["memory_pools"])
    ent = table.snapshot()["w0"]
    assert ent["memory"] == "leak" and ent["memory_pools"] == ["leaky"]


def test_sentinel_thread_audits_periodically(mem_flag):
    _flags.set_flags({"memory_audit_interval_s": 0.05})
    try:
        memory.pool("leaky", "device", lambda: {}, audit=lambda: 1)
        assert memory.maybe_start_sentinel()
        assert memory.maybe_start_sentinel()      # idempotent
        deadline = time.monotonic() + 10
        while memory.last_audit() is None:
            assert time.monotonic() < deadline
            time.sleep(0.02)
        assert memory.last_audit()["leaks"] == {"leaky": 1}
    finally:
        _flags.set_flags({"memory_audit_interval_s": 5.0})


# -- OOM: chaos rule, forensics, recovery ----------------------------------

def test_oom_rule_is_site_only_and_realistic(clean_faults):
    _faults.inject("oom:decode_step:times=1")
    # the generic event dispatcher skips site-only kinds (no budget burn)
    _faults.event("decode_step")
    with pytest.raises(RuntimeError) as ei:
        _faults.oom_fault("decode_step")
    assert "RESOURCE_EXHAUSTED" in str(ei.value)
    assert memory.is_oom(ei.value)
    _faults.oom_fault("decode_step")      # times=1: spent


def test_oom_forensics_unarmed_or_not_oom_is_none(mem_flag):
    assert memory.oom_forensics(ValueError("boom"), "x") is None
    _flags.set_flags({"memory_attribution": False})
    err = RuntimeError("RESOURCE_EXHAUSTED: oom")
    assert memory.oom_forensics(err, "x") is None


def test_injected_decode_oom_dumps_forensics_and_recovers(
        mem_flag, clean_faults):
    """The acceptance drill: an injected ``oom:decode_step`` under an
    overcommitted engine produces a forensic record naming the block
    pool as top holder with preempt events in the tail, while the
    engine recovers through the existing preemption path — every
    stream completes, the recovery is counted, nothing crashes."""
    _faults.inject("oom:decode_step:n=3,times=2")
    eng, SP = _mk_engine("t_mem_oom", num_blocks=24, overcommit=True)
    try:
        handles = [eng.submit(p, SP(max_new_tokens=16))
                   for p in _prompts(8)]
        results = [h.result(timeout=120) for h in handles]
        assert all(r["finish"] == "length" for r in results)
        rec = memory.last_oom()
        assert rec is not None and rec["site"] == "decode_step"
        assert rec["top_holders"][0]["pool"] == "decode_kv.t_mem_oom"
        assert any(e["kind"] == "preempt" for e in rec["events"])
        snap = stats.export_state()["metrics"]
        assert snap["decode.t_mem_oom.oom_recovered"]["value"] >= 1
        assert snap["memory.oom_dumps"]["value"] >= 1
        assert eng._mem_pool_audit() == 0
    finally:
        eng.close()


def test_injected_serving_oom_dumps_forensics(mem_flag, clean_faults):
    _faults.inject("oom:serving_dispatch:times=1")
    b = DynamicBatcher(_StubPredictor(), name="t_mem_soom",
                       buckets=(1,), max_delay_ms=0.5)
    try:
        with pytest.raises(RuntimeError):
            b.submit({"x": np.ones((1, 3), "float32")}).result(timeout=10)
        rec = memory.last_oom()
        assert rec is not None and rec["site"] == "serving_dispatch"
        # the batcher recovered: the next request serves normally
        out = b.submit({"x": np.ones((1, 3), "float32")}).result(timeout=10)
        assert np.allclose(out[0], 2.0)
    finally:
        b.close()


# -- per-tenant resident KV bytes ------------------------------------------

def test_tenant_resident_kv_bytes_nets_to_zero(mem_flag):
    _flags.set_flags({"tenant_accounting": True})
    tenant.reset()
    try:
        eng, SP = _mk_engine("t_mem_ten")
        try:
            hs = [eng.submit(p, SP(max_new_tokens=12), tenant="acme")
                  for p in _prompts(4)]
            for h in hs:
                h.result(timeout=120)
        finally:
            eng.close()
        rec = tenant.tenantz()["tenants"]["acme"]
        assert rec["requests"] == 4
        # admission/growth added, retire subtracted: current footprint 0
        assert rec["resident_kv_bytes"] == 0
        assert "kv_bytes" in tenant.tenantz_text()
    finally:
        _flags.set_flags({"tenant_accounting": False})
        tenant.reset()


# -- lease-data chain: elastic + supervisor --------------------------------

def test_memory_rides_lease_to_elastic_and_supervisor(mem_flag):
    """The headroom chain: a replica's lease data carries the compact
    memory rider; ElasticController.memory_headroom filters per role
    and decide() carries it informationally (HOLD-safe); the
    supervisor folds the tightest replica's byte headroom + leak flag
    into its status card — and takes NO action on it."""
    from paddle_tpu.checkpoint.elastic import ElasticController
    from paddle_tpu.distributed.registry import Heartbeat, RegistryServer
    from paddle_tpu.distributed.supervisor import FleetSpec, RoleSpec, \
        Supervisor

    memory.pool("decode_kv.t", "device",
                lambda: {"reserved": 1000, "used": 750, "parked": 100},
                audit=lambda: 2)
    memory.run_audit()
    rider = memory.lease_rider()
    assert rider == {"memory_bytes": 750, "memory_parked_bytes": 100,
                     "memory_headroom_frac": 0.25, "memory_leak": 2}
    reg = RegistryServer("127.0.0.1:0")
    reg.start()
    ep = f"127.0.0.1:{reg.port}"
    hb = Heartbeat(ep, "decode/t_mem/r0", "127.0.0.1:9301", ttl=0.2,
                   role="DECODE", data_fn=memory.lease_rider)
    hb.start()
    try:
        ctrl = ElasticController(ep, poll_ttl=0.05)
        deadline = time.monotonic() + 10
        while True:
            mh = ctrl.memory_headroom("DECODE")
            if "decode/t_mem/r0" in mh:
                break
            assert time.monotonic() < deadline
            time.sleep(0.05)
        ent = mh["decode/t_mem/r0"]
        assert ent["memory_headroom_frac"] == 0.25
        assert ent["memory_bytes"] == 750 and ent["memory_leak"] == 2
        assert ctrl.memory_headroom("SERVING") == {}
        d = ctrl.decide("DECODE", 1)
        assert d["action"] == "hold"
        assert d["memory"]["decode/t_mem/r0"][
            "memory_headroom_frac"] == 0.25
        # the heartbeat's memory health dimension reached the table
        deadline = time.monotonic() + 10
        while True:
            view = ctrl.fleet_view(refresh=True)
            if view.get("decode/t_mem/r0", {}).get("memory") == "leak":
                break
            assert time.monotonic() < deadline
            time.sleep(0.05)
        assert view["decode/t_mem/r0"]["memory_pools"] == ["decode_kv.t"]

        spec = FleetSpec(roles={"decode": RoleSpec(
            count=0, argv=["true"], health_role="DECODE")},
            registry=ep, name="t_mem")
        sup = Supervisor(spec, poll_s=0.05, registry_poll_s=0.05)
        sup.start()
        try:
            deadline = time.monotonic() + 10
            while True:
                st = sup.status()
                if st.get("memory_headroom", {}).get("decode/t_mem/r0"):
                    break
                assert time.monotonic() < deadline
                time.sleep(0.05)
            assert st["roles"]["decode"]["memory_headroom_frac"] == 0.25
            assert st["roles"]["decode"]["memory_leak"] is True
            assert st["state"] == "RUNNING"       # HOLD-safe: no action
        finally:
            sup.stop()
    finally:
        hb.stop(bye=True)
        reg.stop()


# -- operator surfaces -------------------------------------------------------

def test_dump_metrics_allocz_modes(capsys, mem_flag):
    import sys
    sys.path.insert(0, "tools")
    try:
        import dump_metrics
    finally:
        sys.path.pop(0)
    memory.pool("t_cli_pool", "host",
                lambda: {"used": 2048, "reserved": 4096})
    memory.note_event("alloc", "t_cli_pool", 2048)
    srv = debug_server.start(port=0)
    try:
        rc = dump_metrics.main([str(srv.port), "--allocz"])
        assert rc == 0
        page = json.loads(capsys.readouterr().out)
        assert page["ledger"]["pools"]["t_cli_pool"]["used"] == 2048
        assert page["events"][-1]["kind"] == "alloc"
        rc = dump_metrics.main([str(srv.port), "--allocz", "--text"])
        assert rc == 0
        text = capsys.readouterr().out
        assert "memory ledger" in text and "t_cli_pool" in text
    finally:
        debug_server.stop()


def test_fleet_status_role_table_renders_mem_column(capsys):
    import sys
    sys.path.insert(0, "tools")
    try:
        import fleet as fleet_cli
    finally:
        sys.path.pop(0)
    status = {"fleet": "f", "state": "RUNNING",
              "roles": {"decode": {"count": 2, "target": 2, "hold": False,
                                   "memory_headroom_frac": 0.4},
                        "serving": {"count": 1, "target": 1,
                                    "memory_leak": True}},
              "slo_breaches": {}}
    fleet_cli._print_role_table({"f": status})
    out = capsys.readouterr().out
    assert "mem" in out and "40.0%" in out and "leak!" in out
    # a role without memory data renders '-' instead of crashing
    fleet_cli._print_role_table(
        {"roles": {"trainer": {"count": 1, "target": 1}},
         "state": "RUNNING"})
    assert "-" in capsys.readouterr().out


