"""Performance & numerics observability plane (ISSUE 7): XLA
cost/memory attribution with roofline positions (/profilez), live
device-memory telemetry (/memz), the run-scalar JSONL log +
tools/runlog_report.py, the NaN/Inf post-step sentinel
(FLAGS_numerics_check) — plus the satellite coverage (StepStats ring
percentile edge cases, fleet histogram merge with mismatched bucket
layouts, /statusz device inventory, dump_metrics --memz/--profilez)."""
import json
import os
import socket
import sys
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.core import flags as core_flags
from paddle_tpu.core import unique_name
from paddle_tpu.core.executor import Executor, Scope, scope_guard
from paddle_tpu.core.program import Program, program_guard
from paddle_tpu.observability import aggregate, debug_server, flight
from paddle_tpu.observability import perf, runlog
from paddle_tpu.observability import stats as stats_mod
from paddle_tpu.observability.step_stats import StepStats, StepStatsRecorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import runlog_report  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_perf_plane():
    """Every test leaves the perf flags off and the module state empty."""
    yield
    core_flags.set_flags({"perf_attribution": False, "run_log_dir": "",
                          "numerics_check": "", "debug_server_port": 0})
    perf.reset()
    runlog.reset()
    flight.clear_events()
    debug_server.stop()


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _get(port: int, page: str) -> str:
    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}{page}", timeout=10).read().decode("utf-8")


def _lenet_programs():
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        from paddle_tpu.models import mnist
        _, loss, acc = mnist.build()
    return prog, startup, loss


def _lenet_feed(batch=16, seed=0, poison=None):
    rng = np.random.RandomState(seed)
    pixel = rng.randn(batch, 1, 28, 28).astype("float32")
    if poison is not None:
        pixel[0, 0, 0, 0] = poison
    return {"pixel": pixel,
            "label": rng.randint(0, 10, (batch, 1)).astype("int64")}


def _fc_programs(feature=6):
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [feature])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.05).minimize(loss)
    return prog, startup, loss


def _fc_feed(batch=8, feature=6, seed=0, poison=None):
    rng = np.random.RandomState(seed)
    x = rng.randn(batch, feature).astype("float32")
    if poison is not None:
        x[0, 0] = poison
    return {"x": x, "y": rng.randn(batch, 1).astype("float32")}


# ---------------------------------------------------------------------------
# (a) cost/memory attribution + rooflines
# ---------------------------------------------------------------------------

def test_lenet_step_yields_perf_record_with_rooflines():
    """THE acceptance path: one LeNet train step under
    FLAGS_perf_attribution=1 produces a /profilez record with nonzero
    flops and bytes from XLA cost_analysis, memory_analysis numbers, a
    computed roofline position, and live device-memory gauges."""
    perf.reset()
    core_flags.set_flags({"perf_attribution": True})
    prog, startup, loss = _lenet_programs()
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
        for i in range(2):
            (lv,) = exe.run(prog, feed=_lenet_feed(seed=i),
                            fetch_list=[loss], sync=True)
    assert np.isfinite(float(lv))

    recs = [r for r in perf.records() if r.steps > 0]
    assert recs, "no perf record observed a step"
    rec = max(recs, key=lambda r: r.flops)
    # a conv net's train step is far beyond a few kFLOP — cost_analysis
    # really ran (these are XLA's numbers, not wall-clock guesses)
    assert rec.flops > 1e6
    assert rec.bytes_accessed > 1e4
    assert rec.source == "compile"
    assert rec.memory.get("argument_bytes", 0) > 0
    assert rec.memory.get("peak_bytes", 0) > 0

    s = rec.summary()
    assert s["intensity_flops_per_byte"] == pytest.approx(
        rec.flops / rec.bytes_accessed, rel=1e-3)
    # CPU backend: the nominal host envelope still yields a full
    # roofline position (labeled nominal, relative not absolute)
    assert s["achieved_gflops"] > 0
    assert s["achieved_gbps"] > 0
    assert 0 < s["roofline_frac"]
    assert s["bound"] in ("compute", "memory")
    assert s["peaks_nominal"] is True

    # live device-memory gauges landed on the registry (host RSS always;
    # per-device bytes_in_use only on backends that report)
    snap = stats_mod.to_dict()
    assert snap.get("device_mem.host_rss_bytes", 0) > 0
    # perf.* summary gauges track the most recent step
    assert "perf.last_achieved_gflops" in snap
    assert snap["perf.executables"] >= 1


def test_perf_record_key_joins_step_stats_ring():
    """After the first observed step the /profilez record is keyed by
    the StepStats program_key, so the two planes share an identity."""
    perf.reset()
    core_flags.set_flags({"perf_attribution": True})
    prog, startup, loss = _fc_programs()
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
        exe.run(prog, feed=_fc_feed(), fetch_list=[loss], sync=True)
    ring_keys = {s.program_key for s in obs.step_stats.last_n(8)}
    rec_keys = {r.key for r in perf.records() if r.steps > 0}
    assert rec_keys and rec_keys <= ring_keys


def test_run_steps_perf_attribution():
    """run_steps (K steps in one dispatch): the record's flops cover K
    steps and its wall covers the same K — rates stay consistent."""
    perf.reset()
    core_flags.set_flags({"perf_attribution": True})
    prog, startup, loss = _fc_programs()
    K, B = 4, 8
    rng = np.random.RandomState(1)
    feed = {"x": rng.randn(K, B, 6).astype("float32"),
            "y": rng.randn(K, B, 1).astype("float32")}
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
        (stacked,) = exe.run_steps(prog, feed=feed, fetch_list=[loss])
    assert stacked.shape[0] == K
    recs = [r for r in perf.records() if r.mode == "run_steps"
            and r.steps > 0]
    assert recs and recs[0].flops > 0


def test_profilez_memz_served_over_http():
    perf.reset()
    core_flags.set_flags({"perf_attribution": True})
    prog, startup, loss = _fc_programs()
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
        exe.run(prog, feed=_fc_feed(), fetch_list=[loss], sync=True)

    port = _free_port()
    debug_server.start(port)
    try:
        pz = json.loads(_get(port, "/profilez"))
        assert pz["enabled"] is True
        assert pz["platform_peaks"]["platform"] == "cpu"
        assert any(r["flops"] > 0 for r in pz["records"])
        observed = [r for r in pz["records"] if r["steps"] > 0]
        assert observed and "roofline_frac" in observed[0]

        mz = json.loads(_get(port, "/memz"))
        assert len(mz["devices"]) >= 1
        assert mz["host_rss_bytes"] > 0

        # human renderings
        assert "perf attribution (on)" in _get(port, "/profilez?text=1")
        assert "host rss" in _get(port, "/memz?text=1")
        # the index advertises the new pages
        assert "/memz" in _get(port, "/")
    finally:
        debug_server.stop()


def test_statusz_includes_device_inventory():
    """Satellite: /statusz carries the hardware card (platform, device
    kind/count, per-device memory limit) for dashboard labeling."""
    port = _free_port()
    debug_server.start(port)
    try:
        st = json.loads(_get(port, "/statusz"))
        inv = st["platform"]
        assert inv["platform"] == "cpu"
        assert inv["device_count"] >= 1
        assert inv["local_device_count"] == len(inv["devices"])
        d0 = inv["devices"][0]
        assert "kind" in d0 and "memory_limit_bytes" in d0
    finally:
        debug_server.stop()


def test_dump_metrics_memz_profilez_modes(capsys):
    """Satellite: the operator CLI pulls the perf pages without curl."""
    import dump_metrics
    port = _free_port()
    debug_server.start(port)
    try:
        rc = dump_metrics.main(["--memz", "--profilez", str(port)])
        out = capsys.readouterr().out
        assert rc == 0
        assert '"devices"' in out and '"platform_peaks"' in out
        rc = dump_metrics.main(["--memz", "--text", str(port)])
        out = capsys.readouterr().out
        assert rc == 0 and "host rss" in out
    finally:
        debug_server.stop()


def test_flags_off_zero_io_and_lazy_jit(tmp_path):
    """Flags unset (default): no perf records, no run-log I/O, and the
    executor still builds the LAZY jit (no eager AOT compile) — the
    pre-PR dispatch path, byte-identical."""
    perf.reset()
    runlog.reset()
    assert not perf.enabled() and not runlog.enabled()
    prog, startup, loss = _fc_programs()
    scope, exe = Scope(), Executor()
    before = set(os.listdir(str(tmp_path)))
    with scope_guard(scope):
        exe.run(startup)
        exe.run(prog, feed=_fc_feed(), fetch_list=[loss], sync=True)
    assert perf.records() == []
    assert set(os.listdir(str(tmp_path))) == before
    entries = list(exe._cache.values())
    assert entries
    for e in entries:
        assert e.perf is None
        # aot_ms set only by warm-start/disk/perf paths — all off here
        assert e.aot_ms is None


# ---------------------------------------------------------------------------
# (b) run-scalar log + tools/runlog_report.py
# ---------------------------------------------------------------------------

def test_runlog_roundtrips_through_report_tool(tmp_path, capsys):
    d = str(tmp_path / "rl")
    core_flags.set_flags({"run_log_dir": d})
    prog, startup, loss = _fc_programs()
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
        for i in range(5):
            exe.run(prog, feed=_fc_feed(seed=i), fetch_list=[loss],
                    sync=True)
    runlog.reset()  # close the writer

    files = [f for f in os.listdir(d) if f.endswith(".jsonl")]
    assert len(files) == 1
    path = os.path.join(d, files[0])

    records = runlog_report.load(path)
    # 5 training runs logged (the startup run carries no scalar fetches
    # but still logs a step record)
    scalar_recs = [r for r in records if r.get("scalars")]
    assert len(scalar_recs) == 5
    r = scalar_recs[-1]
    assert "step" in r and "ts" in r and r["step_ms"] > 0
    assert r["samples_per_sec"] > 0
    [(name, val)] = list(r["scalars"].items())
    assert np.isfinite(val)

    summary = runlog_report.summarize(records)
    assert summary["records"] == len(records)
    st = summary["scalars"][name]
    assert st["n"] == 5 and st["nonfinite"] == 0
    assert st["min"] <= st["mean"] <= st["max"]

    # the CLI renders text, CSV and JSON from the same file
    assert runlog_report.main([path]) == 0
    text = capsys.readouterr().out
    assert f"scalar {name}" in text
    assert runlog_report.main([path, "--csv"]) == 0
    csv_out = capsys.readouterr().out
    assert name in csv_out.splitlines()[0]
    assert len(csv_out.strip().splitlines()) == len(records) + 1
    assert runlog_report.main([path, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["records"] == len(records)


def test_runlog_compare_two_runs(tmp_path, capsys):
    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, final in ((a, 1.0), (b, 0.25)):
        log = runlog.RunLog(path)
        for i in range(4):
            log.log({"scalars": {"loss": final + (3 - i) * 0.5},
                     "step_ms": 10.0 if path == a else 5.0})
        log.close()
    cmp = runlog_report.compare(runlog_report.load(a),
                                runlog_report.load(b))
    assert cmp["scalars"]["loss"]["delta"] == pytest.approx(-0.75)
    assert cmp["step_ms_ratio"] == pytest.approx(0.5)
    assert runlog_report.main([a, "--compare", b]) == 0
    assert "loss" in capsys.readouterr().out


def test_runlog_grad_norm_and_nonfinite_visibility(tmp_path, capsys):
    """Fetched @GRAD vars fold into grad_global_norm; a NaN'd loss in
    the log is loud in the report even without the sentinel armed."""
    path = str(tmp_path / "r.jsonl")
    log = runlog.RunLog(path)
    log.log({"scalars": {"loss": 1.0}})
    log.log({"scalars": {"loss": float("nan")}})
    log.close()
    summary = runlog_report.summarize(runlog_report.load(path))
    assert summary["scalars"]["loss"]["nonfinite"] == 1

    # grad folding straight through the executor-facing entry point
    core_flags.set_flags({"run_log_dir": str(path) + ".d"})
    runlog.log_run(["w@GRAD", "loss"],
                   [np.full((2, 2), 3.0), np.float32(0.5)], wall_ms=1.0)
    lg = runlog.default_log()
    recs = runlog.RunLog.read(lg.path)
    runlog.reset()
    assert recs[-1]["grad_global_norm"] == pytest.approx(6.0)
    assert recs[-1]["scalars"] == {"loss": 0.5}


class _FakeDeferred:
    """LazyFetch stand-in: reading it before materialize() is the
    device sync the deferred-log contract forbids."""

    def __init__(self, val):
        import threading
        self._np = None
        self._err = None
        self._done = threading.Event()
        self._val = val
        self.shape = ()
        self.dtype = np.dtype("float32")

    def materialize(self):
        self._np = np.asarray(self._val, dtype="float32")
        self._done.set()

    def __array__(self, dtype=None, copy=None):
        assert self._np is not None, "deferred fetch forced a device sync"
        return self._np if dtype is None else self._np.astype(dtype)


def test_runlog_defers_pending_fetches_without_sync(tmp_path):
    """A record whose fetches are still on device is queued, never
    forced: it lands (in order) once the values materialize, and
    flush()/reset() writes the tail."""
    d = str(tmp_path / "rl")
    core_flags.set_flags({"run_log_dir": d})
    f1, f2 = _FakeDeferred(1.5), _FakeDeferred(2.5)
    runlog.log_run(["loss"], [f1], wall_ms=1.0)   # queued: would sync
    lg = runlog.default_log()
    assert runlog.RunLog.read(lg.path) == []
    f1.materialize()                               # user read the loss
    runlog.log_run(["loss"], [f2], wall_ms=1.0)   # drains #1, queues #2
    recs = runlog.RunLog.read(lg.path)
    assert [r["scalars"]["loss"] for r in recs] == [1.5]
    f2.materialize()
    runlog.flush()
    recs = runlog.RunLog.read(lg.path)
    assert [r["scalars"]["loss"] for r in recs] == [1.5, 2.5]


def test_runlog_defers_unready_raw_device_arrays(tmp_path):
    """run(return_numpy=False) hands raw jax.Arrays to the log: their
    sync-free is_ready() gates the write the same way LazyFetch does."""
    class _Arr:
        def __init__(self):
            self.ready = False
            self.shape = ()
            self.dtype = np.dtype("float32")

        def is_ready(self):
            return self.ready

        def __array__(self, dtype=None, copy=None):
            assert self.ready, "blocked on an unready device array"
            return np.asarray(7.0, dtype="float32")

    d = str(tmp_path / "rl")
    core_flags.set_flags({"run_log_dir": d})
    a = _Arr()
    runlog.log_run(["loss"], [a], wall_ms=1.0)     # queued, not forced
    lg = runlog.default_log()
    assert runlog.RunLog.read(lg.path) == []
    a.ready = True                                  # dispatch finished
    runlog.flush()
    assert [r["scalars"]["loss"]
            for r in runlog.RunLog.read(lg.path)] == [7.0]


def test_runlog_async_executor_path_drains_on_reset(tmp_path):
    """End to end on the default async fetch path (sync=False →
    LazyFetch): no record is forced mid-loop, reset() lands them all."""
    d = str(tmp_path / "rl")
    core_flags.set_flags({"run_log_dir": d})
    prog, startup, loss = _fc_programs()
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
        outs = [exe.run(prog, feed=_fc_feed(seed=i), fetch_list=[loss])
                for i in range(3)]
    runlog.reset()  # force-drains the queue, then closes
    files = [f for f in os.listdir(d) if f.endswith(".jsonl")]
    recs = [r for r in runlog.RunLog.read(os.path.join(d, files[0]))
            if r.get("scalars")]
    assert len(recs) == 3
    assert all(np.isfinite(list(r["scalars"].values())[0]) for r in recs)
    del outs


def test_runlog_batch_of_picks_largest_feed():
    """samples/sec uses the batch-major (largest) feed's leading dim,
    not whichever feed sorts first alphabetically."""
    aux = np.zeros((1,), dtype="float32")          # sorts first
    img = np.zeros((256, 3, 8, 8), dtype="float32")
    assert runlog.batch_of([aux, img]) == 256
    assert runlog.batch_of([np.zeros((4, 256, 7), dtype="float32")],
                           axis=1) == 256
    assert runlog.batch_of([np.float32(1.0)]) is None
    assert runlog.batch_of([]) is None


def test_runlog_rotation_atomic_and_watch(tmp_path):
    path = str(tmp_path / "rot.jsonl")
    log = runlog.RunLog(path, max_bytes=400)
    for i in range(40):
        log.log({"scalars": {"loss": float(i)}})
    log.close()
    # rotation happened; the generation chain (.1 newest .. .8 oldest)
    # preserved the WHOLE history, every file parses cleanly (no torn
    # lines), and nothing leaked past the chain cap
    assert os.path.exists(path + ".1")
    main = runlog.RunLog.read(path)
    gens = []
    for k in range(1, runlog.RunLog.KEEP_ROTATIONS + 1):
        gens.append(runlog.RunLog.read(f"{path}.{k}"))
    assert main and gens[0]
    assert not os.path.exists(f"{path}.{runlog.RunLog.KEEP_ROTATIONS + 1}")
    every = sorted(r["step"] for recs in [main] + gens for r in recs)
    assert every == list(range(1, 41))  # all 40 records survived
    steps = [r["step"] for r in main]
    assert steps == sorted(steps)

    # watch() replays the current file then times out quietly
    got = list(runlog.RunLog(path).watch(poll_interval=0.01, timeout=0.2))
    assert [r["step"] for r in got] == steps


def test_watch_survives_fast_rotation_without_loss(tmp_path):
    """A burst of appends that rotates the log several times between
    two watcher polls loses nothing: on inode change watch() finds the
    generation it was on (by inode) and yields its unread tail plus
    every newer generation before restarting on the fresh file."""
    import threading
    import time as _time
    path = str(tmp_path / "rw.jsonl")
    log = runlog.RunLog(path, max_bytes=500)
    got = []
    t = threading.Thread(target=lambda: got.extend(
        r["scalars"]["v"]
        for r in log.watch(poll_interval=0.03, timeout=1.0)))
    t.start()
    _time.sleep(0.15)  # let the watcher take its first (empty) poll
    for i in range(40):
        log.log({"scalars": {"v": float(i)}})
        _time.sleep(0.005)  # paced: rotations land between polls
    log.close()
    t.join()
    assert got == [float(i) for i in range(40)]


def test_runlog_unreadable_fetch_is_stamped(tmp_path):
    """A deferred fetch whose buffer died before the drain (e.g.
    donated by a later dispatch) is counted on the record — the data
    loss is visible in the log, never silent."""
    class _Dead:
        shape = ()
        dtype = np.dtype("float32")

        def is_ready(self):
            return True

        def __array__(self, dtype=None, copy=None):
            raise RuntimeError("buffer was donated")

    core_flags.set_flags({"run_log_dir": str(tmp_path / "rl")})
    runlog.log_run(["loss"], [_Dead()], wall_ms=1.0)
    lg = runlog.default_log()
    recs = runlog.RunLog.read(lg.path)
    assert recs and recs[-1]["unreadable_fetches"] == 1
    assert recs[-1]["scalars"] == {}


def test_run_steps_emits_k_records(tmp_path):
    d = str(tmp_path / "rl")
    core_flags.set_flags({"run_log_dir": d})
    prog, startup, loss = _fc_programs()
    K, B = 3, 8
    rng = np.random.RandomState(2)
    feed = {"x": rng.randn(K, B, 6).astype("float32"),
            "y": rng.randn(K, B, 1).astype("float32")}
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
        (stacked,) = exe.run_steps(prog, feed=feed, fetch_list=[loss])
    runlog.reset()
    files = [f for f in os.listdir(d) if f.endswith(".jsonl")]
    recs = [r for r in runlog.RunLog.read(os.path.join(d, files[0]))
            if r.get("scalars")]
    assert len(recs) == K
    logged = [list(r["scalars"].values())[0] for r in recs]
    np.testing.assert_allclose(logged, np.asarray(stacked).reshape(K),
                               rtol=1e-6)
    assert all(r["k_steps"] == K for r in recs)


# ---------------------------------------------------------------------------
# (c) numerics sentinel
# ---------------------------------------------------------------------------

def test_sentinel_warn_names_variables_and_counts():
    core_flags.set_flags({"numerics_check": "warn"})
    flight.clear_events()
    obs.reset()
    prog, startup, loss = _fc_programs()
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
        # NaN in the feed poisons loss AND the updated params
        (lv,) = exe.run(prog, feed=_fc_feed(poison=np.nan),
                        fetch_list=[loss], sync=True)
    assert np.isnan(float(lv))  # warn mode let the step land
    snap = stats_mod.to_dict()
    assert snap["numerics.nan"] >= 1
    assert snap["numerics.checked_steps"] >= 1
    assert snap.get("numerics.inf", 0) == 0
    evs = [e for e in flight.events() if e["msg"] == "numerics_sentinel"]
    assert evs, "no flight-recorder note"
    assert loss.name in evs[-1]["nan_vars"]
    assert evs[-1]["mode"] == "warn"


def test_sentinel_inf_detection():
    core_flags.set_flags({"numerics_check": "warn"})
    obs.reset()
    prog, startup, loss = _fc_programs()
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
        exe.run(prog, feed=_fc_feed(poison=np.inf),
                fetch_list=[loss], sync=True)
    snap = stats_mod.to_dict()
    assert snap["numerics.inf"] >= 1


def test_sentinel_fatal_raises_before_apply(tmp_path):
    """fatal mode: the poisoned step raises, the scope still holds the
    PRE-step parameters (finite, exactly the pre-poison values), and a
    flight record lands on disk."""
    core_flags.set_flags({"numerics_check": "fatal",
                          "flight_record_dir": str(tmp_path / "fl")})
    flight.clear_events()
    prog, startup, loss = _fc_programs()
    scope, exe = Scope(), Executor()
    try:
        with scope_guard(scope):
            exe.run(startup)
            exe.run(prog, feed=_fc_feed(seed=3), fetch_list=[loss],
                    sync=True)
            w_names = [n for n in ("fc_0.w_0", "fc_0.b_0")
                       if scope.find_var(n) is not None]
            assert w_names
            before = {n: np.asarray(scope.find_var(n)).copy()
                      for n in w_names}
            with pytest.raises(FloatingPointError) as ei:
                exe.run(prog, feed=_fc_feed(seed=3, poison=np.nan),
                        fetch_list=[loss], sync=True)
            assert loss.name in str(ei.value)
            for n in w_names:
                after = np.asarray(scope.find_var(n))
                assert np.isfinite(after).all()
                np.testing.assert_array_equal(before[n], after)
            # training continues cleanly from the restored state
            (lv,) = exe.run(prog, feed=_fc_feed(seed=4),
                            fetch_list=[loss], sync=True)
            assert np.isfinite(float(lv))
    finally:
        core_flags.set_flags({"flight_record_dir": ""})
    dumps = os.listdir(str(tmp_path / "fl"))
    assert any("numerics_fatal" in f for f in dumps)


def test_sentinel_fatal_run_steps():
    core_flags.set_flags({"numerics_check": "fatal"})
    prog, startup, loss = _fc_programs()
    K, B = 3, 8
    rng = np.random.RandomState(5)
    x = rng.randn(K, B, 6).astype("float32")
    x[1, 0, 0] = np.nan  # poison step 2 of the scan
    feed = {"x": x, "y": rng.randn(K, B, 1).astype("float32")}
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
        w0 = np.asarray(scope.find_var("fc_0.w_0")).copy()
        with pytest.raises(FloatingPointError):
            exe.run_steps(prog, feed=feed, fetch_list=[loss])
        np.testing.assert_array_equal(
            w0, np.asarray(scope.find_var("fc_0.w_0")))


def test_sentinel_off_keeps_counters_quiet():
    obs.reset()
    prog, startup, loss = _fc_programs()
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
        (lv,) = exe.run(prog, feed=_fc_feed(poison=np.nan),
                        fetch_list=[loss], sync=True)
    assert np.isnan(float(lv))  # NaN sails through, as before this PR
    snap = stats_mod.to_dict()
    assert snap.get("numerics.checked_steps", 0) == 0


def test_run_steps_grad_norm_folds(tmp_path):
    """run_steps records carry grad_global_norm too: [K, ...]-shaped
    @GRAD fetches fold into a per-step norm, like run()'s do."""
    path = str(tmp_path / "g.jsonl")
    log = runlog.RunLog(path)
    K = 3
    grads = np.arange(K * 4, dtype="float32").reshape(K, 2, 2)
    losses = np.array([1.0, 2.0, 3.0], dtype="float32")
    log.defer(("steps", ["loss", "w@GRAD"], [losses, grads], K, 30.0, 8))
    log.close()
    recs = runlog.RunLog.read(path)
    assert len(recs) == K
    for i, r in enumerate(recs):
        expect = float(np.sqrt((grads[i].astype("float64") ** 2).sum()))
        assert r["grad_global_norm"] == pytest.approx(expect, rel=1e-6)
        assert r["scalars"]["loss"] == losses[i]


def test_roofline_numbers_shared_arithmetic():
    """The one roofline arithmetic (executor records use it too): peaks
    fixed, bound classification from arithmetic intensity."""
    peaks = {"flops": 100e9, "hbm_bytes_per_s": 10e9}
    # intensity 100 f/B >> balance 10 → compute-bound
    r = perf.roofline_numbers(1e9, 1e7, 0.1, peaks=peaks)
    assert r["bound"] == "compute"
    assert r["achieved_gflops"] == pytest.approx(10.0)
    assert r["frac_of_peak_flops"] == pytest.approx(0.1)
    assert r["roofline_frac"] == pytest.approx(0.1)
    # intensity 0.1 f/B << balance → memory-bound, HBM axis dominates
    r = perf.roofline_numbers(1e6, 1e7, 0.001, peaks=peaks)
    assert r["bound"] == "memory"
    assert r["roofline_frac"] == pytest.approx(r["frac_of_peak_hbm"])
    # no wall time yet: intensity/bound only, no achieved rates
    r = perf.roofline_numbers(1e6, 1e7, None, peaks=peaks)
    assert "achieved_gflops" not in r and "bound" in r


# ---------------------------------------------------------------------------
# satellites: StepStats ring + fleet histogram merge edge cases
# ---------------------------------------------------------------------------

def test_step_stats_summary_empty_ring():
    rec = StepStatsRecorder(capacity=4)
    s = rec.summary()
    assert s["window"] == 0 and s["total_recorded"] == 0
    assert s["hit_rate"] == 0.0
    assert s["wall_ms"] == {"p50": 0.0, "p90": 0.0, "p99": 0.0,
                            "mean": 0.0, "max": 0.0}


def test_step_stats_summary_single_sample():
    rec = StepStatsRecorder(capacity=4)
    rec.record(StepStats("k", True, wall_ms=7.5))
    s = rec.summary()
    assert s["window"] == 1 and s["hit_rate"] == 1.0
    # one sample: every percentile IS the sample
    assert s["wall_ms"]["p50"] == s["wall_ms"]["p99"] == 7.5
    assert s["wall_ms"]["mean"] == s["wall_ms"]["max"] == 7.5


def test_step_stats_ring_wraparound_window_vs_lifetime():
    """Percentiles cover the RETAINED window only; total_recorded keeps
    the lifetime count after the ring wraps."""
    rec = StepStatsRecorder(capacity=8)
    for i in range(20):  # walls 0..19; ring retains 12..19
        rec.record(StepStats("k", i % 2 == 0, wall_ms=float(i)))
    s = rec.summary()
    assert s["window"] == 8 and s["total_recorded"] == 20
    assert len(rec) == 8
    assert [st.wall_ms for st in rec.last_n(100)] == \
        [float(i) for i in range(12, 20)]
    assert s["wall_ms"]["max"] == 19.0
    assert s["wall_ms"]["p50"] == pytest.approx(15.5)
    assert s["wall_ms"]["p90"] == pytest.approx(18.3)
    # interpolated percentile stays inside the window's range
    assert 12.0 <= s["wall_ms"]["p50"] <= 19.0


def _hist_state(name, buckets, total, count):
    return {"labels": {}, "metrics": {
        name: {"kind": "histogram", "buckets": buckets,
               "sum": total, "count": count}}}


def test_fleet_histogram_merge_mismatched_bucket_layouts():
    """Workers built at different versions can export the same family
    with DIFFERENT bucket layouts: the merge unions the boundaries
    (cumulative counts stay per-boundary correct), sums sum/count, and
    keeps per-worker counts."""
    a = _hist_state("rpc.latency_ms", {"1": 2, "10": 5, "+Inf": 6},
                    30.0, 6)
    b = _hist_state("rpc.latency_ms", {"5": 1, "10": 3, "50": 4,
                                       "+Inf": 4}, 40.0, 4)
    merged = aggregate.merge_snapshots({"w0": a, "w1": b})
    h = merged["histograms"]["rpc.latency_ms"]
    assert h["count"] == 10 and h["sum"] == pytest.approx(70.0)
    assert h["per_worker_count"] == {"w0": 6, "w1": 4}
    # union of both layouts; boundaries present in one worker only
    # carry that worker's cumulative count
    assert h["buckets"] == {"1": 2, "5": 1, "10": 8, "50": 4, "+Inf": 10}
    # the prometheus rendering sorts the union numerically, +Inf last
    text = aggregate.fleet_prometheus_text(merged)
    les = [line.split('le="')[1].split('"')[0]
           for line in text.splitlines() if 'le="' in line]
    assert les == ["1", "5", "10", "50", "+Inf"]


def test_fleet_merge_includes_perf_gauges():
    """device_mem/perf gauges ride the existing STATS_PULL merge shape
    like any other gauge — labeled per worker."""
    a = {"labels": {}, "metrics": {"device_mem.host_rss_bytes": {
        "kind": "gauge", "value": 111.0}}}
    b = {"labels": {}, "metrics": {"device_mem.host_rss_bytes": {
        "kind": "gauge", "value": 222.0}}}
    merged = aggregate.merge_snapshots({"w0": a, "w1": b})
    g = merged["gauges"]["device_mem.host_rss_bytes"]
    assert g["per_worker"] == {"w0": 111.0, "w1": 222.0}
