"""Distributed tracing + crash flight recorder (ISSUE 4): trace-context
propagation over the RPC wire (old-peer interop preserved, sampling=0
adds zero bytes), server spans parenting under inbound contexts across
striped connections, fleet trace stitching (TRACE_PULL + /tracez +
tools/stitch_trace.py), the 2-process trainer+pserver stitched-trace
acceptance scenario, and flight-recorder dumps on unhandled exceptions /
SIGTERM / Heartbeat dirty exits — plus the satellites (profiler lane
ids + metadata, tools/timeline.py pid preservation, dump_metrics
--tracez/--flight)."""
import importlib.util
import json
import os
import signal
import struct
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.core import flags as core_flags
from paddle_tpu.distributed import transport
from paddle_tpu.distributed.registry import Heartbeat, RegistryServer
from paddle_tpu.observability import aggregate, debug_server, flight
from paddle_tpu.observability import trace as trace_mod

from dist_model import batches, build, free_ports, retry_flaky

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def _trace_hygiene():
    """Every test starts unsampled with empty rings and leaves no
    debug server, no flight dir, and default transport flags behind."""
    saved = fluid.get_flags(["trace_sample_rate", "flight_record_dir",
                             "rpc_transport", "rpc_conns_per_endpoint"])
    trace_mod.clear_spans()
    flight.clear_events()
    yield
    fluid.set_flags(saved)
    trace_mod.clear_spans()
    flight.clear_events()
    debug_server.stop()
    core_flags.set_flags({"debug_server_port": 0})


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_under_test", os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Echo:
    """Echoes the payload back; records what the service layer saw."""

    def __init__(self):
        self.seen = []

    def handle(self, msg_type, tid, name, payload):
        self.seen.append((msg_type, tid, name, bytes(payload)))
        return transport.OK, bytes(payload)


def _start_server(service=None):
    fluid.set_flags({"rpc_transport": "python"})
    svc = service or _Echo()
    srv = transport.RPCServer("127.0.0.1:0", svc)
    srv.start()
    return srv, svc, f"127.0.0.1:{srv.port}"


# ---------------------------------------------------------------------------
# wire interop
# ---------------------------------------------------------------------------

def test_ctx_wire_roundtrip_and_old_format_parse():
    ctx = trace_mod.SpanContext(0x1234ABCD5678, 0x9F, True)
    wire = trace_mod.ctx_to_wire(ctx)
    assert len(wire) == trace_mod.WIRE_CTX_SIZE
    assert trace_mod.ctx_from_wire(wire) == ctx
    assert trace_mod.ctx_from_wire(None) is None
    assert trace_mod.ctx_from_wire(b"short") is None

    # a frame WITHOUT the extension is byte-identical to the PR-3 wire
    # format (old peers parse it exactly as before)
    old = struct.pack("<BiH", transport.SEND_VARS, 3, 1) + b"w" + b"payload"
    new = transport._pack_body(transport.SEND_VARS, 3, "w", b"payload")
    assert old == new
    mt, tid, name, payload, got_ctx = transport._unpack_body_ext(old)
    assert (mt, tid, name, bytes(payload), got_ctx) == (
        transport.SEND_VARS, 3, "w", b"payload", None)
    # 4-tuple compatibility form unchanged
    assert transport._unpack_body(old)[:3] == (transport.SEND_VARS, 3, "w")

    # a frame WITH the extension round-trips: flag bit set, ctx between
    # name and payload, payload byte-identical after stripping
    flagged = transport._pack_body(transport.SEND_VARS, 3, "w", b"payload",
                                   ctx=wire)
    assert flagged[0] == transport.SEND_VARS | transport.TRACE_CTX_FLAG
    mt, tid, name, payload, got_ctx = transport._unpack_body_ext(flagged)
    assert (mt, tid, name, bytes(payload)) == (
        transport.SEND_VARS, 3, "w", b"payload")
    assert trace_mod.ctx_from_wire(got_ctx) == ctx
    # ERR responses (0xFF) must never be mistaken for a flagged frame
    err = transport._pack_body(transport.ERR, 0, "", b"boom")
    mt, _, _, payload, got_ctx = transport._unpack_body_ext(err)
    assert mt == transport.ERR and bytes(payload) == b"boom"
    assert got_ctx is None


def test_sampling_zero_sends_zero_extra_bytes(monkeypatch):
    """With FLAGS_trace_sample_rate=0 (the default) a real request's
    frame is byte-for-byte the pre-trace format."""
    fluid.set_flags({"trace_sample_rate": 0.0})
    srv, svc, ep = _start_server()
    captured = []
    real = transport._pack_body_vec

    def spy(msg_type, trainer_id, name, payload_bufs, ctx=None):
        bufs = real(msg_type, trainer_id, name, payload_bufs, ctx=ctx)
        captured.append((ctx, b"".join(bytes(b) for b in bufs)))
        return bufs

    monkeypatch.setattr(transport, "_pack_body_vec", spy)
    try:
        client = transport.RPCClient(7)
        out = client._raw_request(ep, transport.GET_VAR, "v", b"abc")
        assert bytes(out) == b"abc"
    finally:
        srv.stop()
    req = [c for c in captured if c[1][0] != transport.OK]
    assert req and req[0][0] is None  # no ctx injected
    assert req[0][1] == transport._pack_body(transport.GET_VAR, 7, "v",
                                             b"abc")
    # the service layer saw the identical payload
    assert svc.seen[-1] == (transport.GET_VAR, 7, "v", b"abc")
    # and nothing landed in the span ring
    assert trace_mod.spans() == []


@retry_flaky()
def test_old_format_peer_frames_against_new_server():
    """A PR-3-era peer (no trace extension, raw socket speak) works
    against the new server unchanged — request and response frames both
    carry no extension bytes."""
    import socket as socket_mod

    srv, svc, ep = _start_server()
    try:
        host, port = ep.rsplit(":", 1)
        s = socket_mod.create_connection((host, int(port)), timeout=10)
        body = struct.pack("<BiH", transport.GET_VAR, 1, 1) + b"k" + b"old!"
        s.sendall(struct.pack("<I", len(body)) + body)
        raw = b""
        while len(raw) < 4:
            raw += s.recv(4 - len(raw))
        (blen,) = struct.unpack("<I", raw)
        resp = b""
        while len(resp) < blen:
            resp += s.recv(blen - len(resp))
        s.close()
        mt, tid, name, payload, ctx = transport._unpack_body_ext(resp)
        assert mt == transport.OK and bytes(payload) == b"old!"
        assert ctx is None
        assert resp[0] == transport.OK  # no flag bit on the response
    finally:
        srv.stop()


@retry_flaky()
def test_server_spans_parent_correctly_under_striped_concurrency():
    """N concurrent client threads, each under its own root span, over
    striped connections to ONE server: every server span's parent must
    be ITS request's client span (no cross-wiring), one trace id per
    thread."""
    fluid.set_flags({"trace_sample_rate": 1.0,
                     "rpc_conns_per_endpoint": 4})
    srv, svc, ep = _start_server()
    client = transport.RPCClient(0)
    roots = {}
    errs = []

    def one(i):
        try:
            with trace_mod.start_span(f"step-{i}") as root:
                roots[i] = (root.trace_id, root.span_id)
                for _ in range(3):
                    client._raw_request(ep, transport.GET_VAR, f"v{i}",
                                        str(i).encode())
        except Exception as e:  # pragma: no cover
            errs.append(e)

    try:
        threads = [threading.Thread(target=one, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        srv.stop()
    assert not errs
    spans = trace_mod.spans()
    clients = [s for s in spans if s["name"] == "rpc.client::get_var"]
    servers = [s for s in spans if s["name"] == "rpc.server::get_var"]
    assert len(clients) == 18 and len(servers) == 18
    by_id = {s["span_id"]: s for s in spans}
    for sv in servers:
        parent = by_id.get(sv["parent_id"])
        assert parent is not None and parent["name"] == "rpc.client::get_var"
        assert parent["trace_id"] == sv["trace_id"]
    # each thread's requests stayed inside its own trace
    trace_ids = {r[0] for r in roots.values()}
    assert len(trace_ids) == 6
    assert {s["trace_id"] for s in servers} == trace_ids


def test_trace_pull_rpc_and_ring_bound():
    fluid.set_flags({"trace_sample_rate": 1.0})
    core_flags.set_flags({"trace_ring_spans": 32})
    try:
        for i in range(80):
            with trace_mod.start_span(f"s{i}"):
                pass
        assert len(trace_mod.spans()) == 32  # bounded ring
        assert trace_mod.total_spans_recorded() == 80
        srv, svc, ep = _start_server()
        try:
            client = transport.RPCClient(0)
            payload = client._raw_request(ep, transport.TRACE_PULL)
            snap = aggregate.parse_trace_snapshot(payload)
        finally:
            srv.stop()
        assert snap["pid"] == os.getpid()
        assert any(s["name"] == "s79" for s in snap["spans"])
        # bad version rejected
        bad = dict(snap, version=99)
        with pytest.raises(ValueError):
            aggregate.parse_trace_snapshot(json.dumps(bad).encode())
    finally:
        core_flags.set_flags({"trace_ring_spans": 4096})


def test_stitch_chrome_trace_pids_and_metadata():
    snap_a = {"version": 1, "pid": 4242, "role": "TRAINER", "host": "h1",
              "lanes": {"0": "MainThread"},
              "spans": [{"name": "executor::step", "cat": "executor",
                         "trace_id": 7, "span_id": 1, "parent_id": 0,
                         "tid": 0, "ts_us": 10.0, "dur_us": 5.0}]}
    snap_b = {"version": 1, "pid": 4242, "role": "PSERVER", "host": "h2",
              "lanes": {},
              "spans": [{"name": "rpc.server::send_vars", "cat": "rpc",
                         "trace_id": 7, "span_id": 2, "parent_id": 1,
                         "tid": 3, "ts_us": 11.0, "dur_us": 1.0,
                         "tags": {"trainer_id": 0}}]}
    doc = trace_mod.stitch_chrome_trace({"trainer": snap_a, "ps": snap_b})
    evs = doc["traceEvents"]
    pnames = [e for e in evs if e.get("ph") == "M"
              and e["name"] == "process_name"]
    assert len(pnames) == 2
    # same-pid workers (different hosts) get distinct display pids
    assert len({e["pid"] for e in pnames}) == 2
    spans = [e for e in evs if e.get("ph") == "X"]
    assert {e["name"] for e in spans} == {"executor::step",
                                          "rpc.server::send_vars"}
    assert len({e["pid"] for e in spans}) == 2
    # trace/span ids ride as hex args; tags merge in
    sv = next(e for e in spans if e["name"] == "rpc.server::send_vars")
    assert sv["args"]["trace_id"] == f"{7:016x}"
    assert sv["args"]["parent_id"] == f"{1:016x}"
    assert sv["args"]["trainer_id"] == 0
    # thread_name metadata from lanes
    tn = [e for e in evs if e.get("ph") == "M" and e["name"] == "thread_name"]
    assert any(e["args"]["name"] == "MainThread" for e in tn)


# ---------------------------------------------------------------------------
# the 2-process acceptance scenario
# ---------------------------------------------------------------------------

@retry_flaky()
def test_two_process_trainer_pserver_stitched_trace(tmp_path):
    """Trainer (this process) + pserver (subprocess) over the in-repo
    transport: the stitched Chrome trace shows client send_vars spans
    and the pserver's server/apply spans under ONE trace id with
    distinct pids."""
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.executor import Executor, Scope

    (port,) = free_ports(1)
    ep = f"127.0.0.1:{port}"
    ready_dir = str(tmp_path / "ready")
    env = dict(os.environ,
               PADDLE_TRAINING_ROLE="PSERVER",
               PADDLE_PSERVER_ENDPOINTS=ep,
               PADDLE_CURRENT_ENDPOINT=ep,
               PADDLE_TRAINERS_NUM="1",
               PADDLE_READY_DIR=ready_dir,
               JAX_PLATFORMS="cpu",
               FLAGS_rpc_transport="python",
               FLAGS_flight_record_dir=str(tmp_path / "flight"),
               PYTHONPATH=os.pathsep.join(
                   [REPO, os.environ.get("PYTHONPATH", "")]))
    ps = subprocess.Popen([sys.executable,
                           os.path.join(TESTS, "dist_runner.py")],
                          env=env, cwd=TESTS)
    try:
        with unique_name.guard():
            prog, startup, loss = build()
            t = fluid.DistributeTranspiler()
            t.transpile(trainer_id=0, program=prog, pservers=ep,
                        trainers=1, sync_mode=True,
                        startup_program=startup)
            tp = t.get_trainer_program()
        fluid.set_flags({"rpc_transport": "python"})
        fluid.distributed.wait_server_ready([ep], timeout=120.0,
                                            ready_dir=ready_dir)
        scope = Scope()
        exe = Executor()
        exe.run(startup, scope=scope)
        fluid.set_flags({"trace_sample_rate": 1.0})
        trace_mod.clear_spans()
        for x, y in batches(2):
            exe.run(tp, feed={"x": x, "y": y}, fetch_list=[loss],
                    scope=scope)
        fluid.set_flags({"trace_sample_rate": 0.0})
        # pull the pserver's span ring over its OWN var-RPC port
        client = transport.get_client(0)
        payload = client._raw_request(ep, transport.TRACE_PULL)
        ps_snap = aggregate.parse_trace_snapshot(payload)
        local_snap = trace_mod.local_trace_snapshot()
        doc = trace_mod.stitch_chrome_trace({"trainer-0": local_snap,
                                             "ps-0": ps_snap})
        out = tmp_path / "stitched.json"
        out.write_text(json.dumps(doc))
        fluid.distributed.notify_complete([ep], trainer_id=0)
        assert ps.wait(timeout=120) == 0
    finally:
        if ps.poll() is None:
            ps.kill()
            ps.wait()

    assert ps_snap["pid"] != os.getpid()
    local = {s["name"]: s for s in local_snap["spans"]}
    assert "rpc.client::send_vars" in local, sorted(local)
    ps_names = [s["name"] for s in ps_snap["spans"]]
    assert "rpc.server::send_vars" in ps_names, sorted(set(ps_names))
    assert "pserver::apply_round" in ps_names, sorted(set(ps_names))
    # ONE trace id spans both processes: the client send_vars span and
    # the server-side spans it parented
    send_cl = local["rpc.client::send_vars"]
    ps_send = [s for s in ps_snap["spans"]
               if s["name"] == "rpc.server::send_vars"]
    assert any(s["trace_id"] == send_cl["trace_id"] for s in ps_send)
    applies = [s for s in ps_snap["spans"]
               if s["name"] == "pserver::apply_round"]
    trainer_traces = {s["trace_id"] for s in local_snap["spans"]}
    assert any(s["trace_id"] in trainer_traces for s in applies)
    # the stitched doc renders both processes distinctly
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    pids_by_name = {}
    for e in spans:
        pids_by_name.setdefault(e["name"], set()).add(e["pid"])
    assert pids_by_name["rpc.client::send_vars"].isdisjoint(
        pids_by_name["rpc.server::send_vars"])
    # and carries matching trace ids across those pids
    cl_tids = {e["args"]["trace_id"] for e in spans
               if e["name"] == "rpc.client::send_vars"}
    sv_tids = {e["args"]["trace_id"] for e in spans
               if e["name"] == "rpc.server::send_vars"}
    assert cl_tids & sv_tids


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

_FLIGHT_CHILD = r"""
import os, sys, time
import paddle_tpu as fluid
from paddle_tpu.observability import flight, trace
fluid.set_flags({"trace_sample_rate": 1.0})
assert flight.arm_from_flags(), "hooks must install when the dir is set"
span = trace.start_span("executor::step", cat="executor",
                        tags={"step": 3})
span.__enter__()   # in-flight on purpose: we die mid-step
flight.note("mid_step", step=3)
print("READY", flush=True)
MODE = sys.argv[1]
if MODE == "raise":
    raise RuntimeError("boom mid-step")
time.sleep(120)
"""


def _run_flight_child(tmp_path, mode):
    rec_dir = str(tmp_path / "rec")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               FLAGS_flight_record_dir=rec_dir,
               PYTHONPATH=os.pathsep.join(
                   [REPO, os.environ.get("PYTHONPATH", "")]))
    child = subprocess.Popen([sys.executable, "-c", _FLIGHT_CHILD, mode],
                             env=env, cwd=TESTS,
                             stdout=subprocess.PIPE, text=True)
    assert child.stdout.readline().strip() == "READY"
    return child, rec_dir


def _read_dump(rec_dir):
    deadline = time.time() + 60
    while time.time() < deadline:
        files = sorted(os.listdir(rec_dir)) if os.path.isdir(rec_dir) else []
        if files:
            try:
                return json.load(open(os.path.join(rec_dir, files[0])))
            except ValueError:
                pass  # mid-write (should not happen: atomic replace)
        time.sleep(0.1)
    raise AssertionError(f"no flight dump appeared in {rec_dir}")


def test_flight_dump_on_unhandled_exception(tmp_path):
    child, rec_dir = _run_flight_child(tmp_path, "raise")
    assert child.wait(timeout=60) != 0
    dump = _read_dump(rec_dir)
    assert dump["reason"] == "unhandled_exception"
    assert "boom mid-step" in dump["exception"]
    open_names = [s["name"] for s in dump["open_spans"]]
    assert "executor::step" in open_names  # the in-flight span survived
    flight_span = next(s for s in dump["open_spans"]
                       if s["name"] == "executor::step")
    assert flight_span["in_flight"] and flight_span["tags"]["step"] == 3
    assert any(e["msg"] == "mid_step" for e in dump["events"])
    assert "step_stats" in dump


def test_flight_dump_on_sigterm_kill_mid_step(tmp_path):
    """Killing the worker mid-step (SIGTERM) leaves a post-mortem with
    the in-flight span — the acceptance scenario's black box."""
    child, rec_dir = _run_flight_child(tmp_path, "sleep")
    child.send_signal(signal.SIGTERM)
    rc = child.wait(timeout=60)
    assert rc != 0  # still died
    dump = _read_dump(rec_dir)
    assert dump["reason"] == "sigterm"
    assert any(s["name"] == "executor::step" and s.get("in_flight")
               for s in dump["open_spans"])


def test_flight_dirty_exit_on_heartbeat_stop(tmp_path):
    fluid.set_flags({"rpc_transport": "python"})
    reg = RegistryServer("127.0.0.1:0")
    reg.start()
    rec_dir = str(tmp_path / "rec")
    try:
        hb = Heartbeat(f"127.0.0.1:{reg.port}", "ps-0", "127.0.0.1:9999",
                       ttl=5.0, role="PSERVER")
        hb.start()
        core_flags.set_flags({"flight_record_dir": rec_dir})
        hb.stop(bye=False)  # dirty: no goodbye → post-mortem
    finally:
        core_flags.set_flags({"flight_record_dir": ""})
        reg.stop()
    dump = _read_dump(rec_dir)
    assert dump["reason"].startswith("heartbeat_stop")
    assert any(e["msg"] == "dirty_exit" for e in dump["events"])
    # a CLEAN goodbye must not dump
    reg2 = RegistryServer("127.0.0.1:0")
    reg2.start()
    rec2 = str(tmp_path / "rec2")
    try:
        hb2 = Heartbeat(f"127.0.0.1:{reg2.port}", "ps-1", "127.0.0.1:9998",
                        ttl=5.0, role="PSERVER")
        hb2.start()
        core_flags.set_flags({"flight_record_dir": rec2})
        hb2.stop(bye=True)
    finally:
        core_flags.set_flags({"flight_record_dir": ""})
        reg2.stop()
    assert not os.path.isdir(rec2) or not os.listdir(rec2)


# ---------------------------------------------------------------------------
# satellites: profiler lanes, timeline pid preservation, tools
# ---------------------------------------------------------------------------

def test_profiler_lane_ids_stable_and_metadata(tmp_path):
    profiler.reset_profiler()
    profiler.start_profiler("All")
    try:
        with profiler.RecordEvent("main-span"):
            pass

        def other():
            with profiler.RecordEvent("worker-span"):
                pass

        t = threading.Thread(target=other, name="lane-test-worker")
        t.start()
        t.join()
    finally:
        profiler._state["enabled"] = False
    evs = {e["name"]: e for e in profiler.events()}
    main_lane = evs["main-span"]["tid"]
    worker_lane = evs["worker-span"]["tid"]
    assert main_lane != worker_lane  # no aliasing into one lane
    names = profiler.lane_names()
    assert names[worker_lane] == "lane-test-worker"
    path = str(tmp_path / "prof.json")
    profiler.chrome_trace(path)
    doc = json.load(open(path))
    meta = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    assert any(e["name"] == "process_name" for e in meta)
    thread_meta = {e["tid"]: e["args"]["name"] for e in meta
                   if e["name"] == "thread_name"}
    assert thread_meta.get(worker_lane) == "lane-test-worker"
    # real events carry the process pid now (multi-process merges need it)
    ev = next(e for e in doc["traceEvents"] if e.get("name") == "main-span")
    assert ev["pid"] == os.getpid()


def test_timeline_merge_preserves_stitched_pids(tmp_path):
    stitched = {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 100,
         "args": {"name": "trainer"}},
        {"ph": "M", "name": "process_name", "pid": 200,
         "args": {"name": "ps"}},
        {"name": "a", "ph": "X", "pid": 100, "tid": 0, "ts": 1, "dur": 2},
        {"name": "b", "ph": "X", "pid": 200, "tid": 1, "ts": 2, "dur": 2},
    ]}
    p1 = tmp_path / "stitched.json"
    p1.write_text(json.dumps(stitched))
    foreign = tmp_path / "foreign.json"
    foreign.write_text(json.dumps({"traceEvents": [
        {"name": "xla", "ph": "X", "ts": 1, "dur": 1}]}))
    tl = _load_tool("timeline")
    merged = tl.merge([str(p1), str(foreign)])
    by_name = {e["name"]: e for e in merged["traceEvents"]
               if e.get("ph") == "X"}
    assert by_name["a"]["pid"] == 100 and by_name["b"]["pid"] == 200
    assert by_name["xla"]["pid"] not in (100, 200)
    assert by_name["xla"]["tid"] == 0
    # the stitched file's own process_name metadata survived (not
    # replaced by a synthetic "profile <path>" row)
    meta_names = {e["args"]["name"] for e in merged["traceEvents"]
                  if e.get("ph") == "M" and e["name"] == "process_name"}
    assert {"trainer", "ps"} <= meta_names


def test_stitch_trace_tool_files_and_chrome_passthrough(tmp_path, capsys):
    snap = {"version": 1, "pid": 11, "role": "TRAINER", "host": "h",
            "lanes": {"0": "MainThread"},
            "spans": [{"name": "executor::step", "cat": "executor",
                       "trace_id": 5, "span_id": 9, "parent_id": 0,
                       "tid": 0, "ts_us": 1.0, "dur_us": 2.0}]}
    chrome = {"traceEvents": [
        {"name": "c", "ph": "X", "pid": 11, "tid": 0, "ts": 3, "dur": 1}]}
    f1 = tmp_path / "worker.json"
    f1.write_text(json.dumps(snap))
    f2 = tmp_path / "extra.json"
    f2.write_text(json.dumps(chrome))
    out = tmp_path / "out.json"
    st = _load_tool("stitch_trace")
    assert st.main([str(f1), str(f2), "-o", str(out)]) == 0
    doc = json.load(open(out))
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in spans} == {"executor::step", "c"}
    # pid collision between inputs got bumped, not merged
    assert len({e["pid"] for e in spans}) == 2


@retry_flaky()
def test_stitch_trace_tool_pulls_endpoints(tmp_path):
    fluid.set_flags({"trace_sample_rate": 1.0})
    with trace_mod.start_span("pull-me"):
        pass
    srv, svc, ep = _start_server()
    out = tmp_path / "out.json"
    try:
        st = _load_tool("stitch_trace")
        assert st.main(["--endpoints", ep, "-o", str(out)]) == 0
    finally:
        srv.stop()
    doc = json.load(open(out))
    assert any(e.get("name") == "pull-me" for e in doc["traceEvents"])


def test_dump_metrics_tracez_and_flight_modes(capsys):
    fluid.set_flags({"trace_sample_rate": 1.0})
    with trace_mod.start_span("visible-span"):
        pass
    srv = debug_server.start(port=0)
    dm = _load_tool("dump_metrics")
    assert dm.main(["--tracez", str(srv.port)]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert any(e.get("name") == "visible-span" for e in doc["traceEvents"])
    assert dm.main(["--tracez", "--raw", str(srv.port)]) == 0
    snap = json.loads(capsys.readouterr().out)
    assert snap["pid"] == os.getpid()
    assert any(s["name"] == "visible-span" for s in snap["spans"])
    assert dm.main(["--flight", str(srv.port)]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["reason"] == "tracez" and "open_spans" in rec


def test_fleet_aggregator_pull_traces_and_stitch():
    fluid.set_flags({"trace_sample_rate": 1.0})
    with trace_mod.start_span("fleet-span"):
        pass
    srv, svc, ep = _start_server()
    try:
        agg = aggregate.FleetAggregator({"w0": ep, "dead": "127.0.0.1:1"})
        snaps = agg.pull_traces()
        assert "w0" in snaps and "dead" not in snaps
        assert agg.last_errors.get("dead")
        doc = agg.stitched_trace(include_self="me")
        assert any(e.get("name") == "fleet-span"
                   for e in doc["traceEvents"])
    finally:
        srv.stop()


