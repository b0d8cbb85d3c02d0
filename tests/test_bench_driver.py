"""The bench.py scan driver must be a faithful steady-state training loop:
K scanned steps == K eager steps (same program, same donated state).

The orchestrator (partial flushed JSON per config, per-config deadlines
with worker restart, a wall-clock budget, and a device check that fails
chip configs when JAX finds no TPU) is exercised here via a fake config
table (PADDLE_TPU_BENCH_TEST_TABLE) so no TPU is needed."""
import json
import os
import subprocess
import sys

import numpy as np

sys.path.insert(0, ".")  # repo root: bench.py lives beside tests/

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")

FAKE_TABLE = """
import time


def ok1():
    return {"v": 1}


def hang():
    time.sleep(300)
    return {"v": "never"}


def ok2():
    return {"v": 2}


CONFIG_TABLE = [
    ("ok1", ok1, 60, False),
    ("hang", hang, 3, False),
    ("ok2", ok2, 60, False),
]
"""


def _run_bench(tmp_path, table_src, env_extra, timeout=180):
    table = tmp_path / "fake_table.py"
    table.write_text(table_src)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PADDLE_TPU_BENCH_TEST_TABLE"] = str(table)
    # keep the telemetry artifact out of the repo root (test hygiene)
    env.setdefault("PADDLE_TPU_BENCH_STATS_PATH",
                   str(tmp_path / "step_stats.json"))
    env.update(env_extra)
    out = subprocess.run([sys.executable, BENCH], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=timeout)
    # contract: stdout is EXACTLY one JSON line (the driver parses it);
    # incremental partials stream to stderr
    stdout_lines = out.stdout.strip().splitlines()
    assert len(stdout_lines) == 1, (
        f"stdout not one line:\n{out.stdout}\nstderr:\n{out.stderr}")
    finals = [json.loads(stdout_lines[0])]
    assert "metric" in finals[0], out.stdout
    partials = [json.loads(l) for l in out.stderr.splitlines()
                if l.startswith('{"partial"')]
    assert partials, f"no partial lines on stderr:\n{out.stderr}"
    return partials, finals[0]


def test_orchestrator_timeout_restarts_worker(tmp_path):
    """A hung config is killed at its deadline, marked, and the worker
    is restarted on the remaining configs — finished results survive."""
    partials, final = _run_bench(tmp_path, FAKE_TABLE, {})
    cfg = final["configs"]
    assert cfg["ok1"] == {"v": 1}
    assert cfg["hang"]["error"] == "timeout"
    assert cfg["ok2"] == {"v": 2}, "worker was not restarted past the hang"
    assert final["device_check"]["ok"] is True
    assert final["device_check"]["platform"] == "cpu"
    assert final["device_check"]["device_kind"]
    assert final["device_check"]["device_count"] >= 1
    # every config got its own flushed partial line before the final line
    names = [p["config"] for p in partials]
    for n in ("ok1", "hang", "ok2"):
        assert n in names


def test_orchestrator_failed_device_check_and_budget(tmp_path):
    """A device check that cannot finish fails the chip configs
    explicitly; an exhausted budget skips the rest explicitly — the
    final line still prints."""
    table = """
def cpu_ok():
    return {"v": 3}


CONFIG_TABLE = [
    ("needs_chip", cpu_ok, 60, True),
    ("cpu_only", cpu_ok, 60, False),
]
"""
    partials, final = _run_bench(
        tmp_path, table,
        {"PADDLE_TPU_BENCH_PROBE_TIMEOUT_S": "0",
         "PADDLE_TPU_BENCH_BUDGET_S": "5"})
    cfg = final["configs"]
    assert final["device_check"]["ok"] is False
    assert cfg["needs_chip"] == {
        "error": "device check failed (timeout)"}
    assert cfg["cpu_only"] == {"skipped": "budget"}


def test_orchestrator_chip_configs_fail_without_a_tpu(tmp_path):
    """On a machine where JAX finds no TPU a chip config FAILS with the
    platform named — it is neither run on the CPU nor silently skipped —
    while CPU-only configs still run so the artifact is never empty.
    Analysis-only entries (scaling_dp8) carry an explicit analysis:
    true tag and do not count as measured."""
    table = """
def chip():
    raise AssertionError("a chip config must not run without a TPU")


def cpu_ok():
    return {"v": 4}


def scaling():
    return {"eff_flops": 1.0}


CONFIG_TABLE = [
    ("needs_chip", chip, 60, True),
    ("cpu_only", cpu_ok, 60, False),
    ("scaling_dp8", scaling, 60, False),
]
"""
    partials, final = _run_bench(tmp_path, table, {})
    cfg = final["configs"]
    assert final["device_check"]["platform"] == "cpu"
    assert cfg["needs_chip"] == {
        "error": "no TPU: device check found platform 'cpu'"}
    assert cfg["cpu_only"] == {"v": 4}
    assert cfg["scaling_dp8"]["analysis"] is True
    assert final["measured_configs"] == 1        # scaling is analysis-only
    names = [p["config"] for p in partials]
    assert "_device_check" in names


def test_step_stats_artifact_written(tmp_path):
    """Every completed config dumps its runtime telemetry (stats snapshot
    + StepStats summary/tail) into the step_stats.json artifact, so a
    BENCH_r*.json regression carries cache/compile/transfer context."""
    table = """
def ok():
    return {"v": 1}


CONFIG_TABLE = [
    ("ok", ok, 120, False),
]
"""
    partials, final = _run_bench(tmp_path, table, {})
    path = tmp_path / "step_stats.json"
    assert final["step_stats_path"] == str(path)
    data = json.loads(path.read_text())
    rec = data["configs"]["ok"]
    assert "stats" in rec and "step_stats" in rec
    summ = rec["step_stats"]["summary"]
    for key in ("cache_hits", "cache_misses", "compile_ms_total",
                "feed_bytes_total", "wall_ms"):
        assert key in summ


def test_auto_compare_records_verdict_in_summary(tmp_path):
    """A completed round auto-compares against the pinned (or newest
    measured) baseline via tools/bench_compare.py and records the
    per-config deltas + verdict under ``comparison`` in the summary
    JSON — the regression gate rides every future BENCH_r*.json."""
    table = """
def fast():
    return {"images_per_sec": 80.0}


def steady():
    return {"tokens_per_sec": 1010.0}


CONFIG_TABLE = [
    ("fast", fast, 60, False),
    ("steady", steady, 60, False),
]
"""
    baseline = {
        "metric": "x", "value": 1.0,
        "configs": {"fast": {"images_per_sec": 100.0},
                    "steady": {"tokens_per_sec": 1000.0}}}
    base = tmp_path / "BENCH_prev.json"
    base.write_text(json.dumps(baseline))
    partials, final = _run_bench(
        tmp_path, table, {"PADDLE_TPU_BENCH_COMPARE_PREV": str(base)})
    cmp = final["comparison"]
    assert cmp["baseline"] == "BENCH_prev.json"
    assert cmp["verdict"] == "regression"          # fast fell 20%
    assert cmp["configs"]["fast"]["status"] == "regression"
    assert cmp["configs"]["fast"]["delta"] == -0.2
    assert cmp["configs"]["steady"]["status"] == "within_noise"


def test_auto_compare_empty_env_disables(tmp_path):
    """PADDLE_TPU_BENCH_COMPARE_PREV= (empty) opts the round out of the
    auto-comparison entirely — comparison is null, never an error."""
    table = """
def ok():
    return {"images_per_sec": 5.0}


CONFIG_TABLE = [
    ("ok", ok, 60, False),
]
"""
    partials, final = _run_bench(
        tmp_path, table, {"PADDLE_TPU_BENCH_COMPARE_PREV": ""})
    assert final["comparison"] is None


def test_scan_driver_matches_eager_steps():
    import bench
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.program import Program, program_guard

    def build():
        x = fluid.layers.data("x", [6])
        y = fluid.layers.data("y", [1])
        h = fluid.layers.fc(x, 16, act="tanh")
        p = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
        fluid.optimizer.Momentum(0.05, 0.9).minimize(loss)
        return loss

    rng = np.random.RandomState(0)
    xb = rng.randn(16, 6).astype("float32")
    feed = {"x": xb, "y": xb.sum(1, keepdims=True).astype("float32")}

    def run(scan_steps):
        prog, startup = Program(), Program()
        prog.random_seed = 3
        with program_guard(prog, startup), unique_name.guard():
            loss = build()
        # bench_program returns steps/sec; to compare *states* we re-time
        # tiny step counts and rely on its internal loop for execution
        sps = bench.bench_program(prog, startup, feed, [loss.name],
                                  steps=6, warmup=0 if scan_steps else 0,
                                  scan_steps=scan_steps)
        return sps

    # Both drivers must run without error and yield positive throughput;
    # loss equivalence is covered by the trajectory check below.
    assert run(None) > 0
    assert run(6) > 0


def test_scan_driver_loss_trajectory_matches():
    """Drive the same jitted block fn both ways and compare final loss."""
    import jax
    import numpy as np
    from jax import lax

    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.executor import (Executor, Scope, _as_device_array,
                                          scope_guard)
    from paddle_tpu.core.lowering import analyze_block, build_block_fn
    from paddle_tpu.core.program import Program, program_guard

    prog, startup = Program(), Program()
    prog.random_seed = 3
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [6])
        y = fluid.layers.data("y", [1])
        p = fluid.layers.fc(x, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
        fluid.optimizer.SGD(0.1).minimize(loss)

    rng = np.random.RandomState(0)
    xb = rng.randn(16, 6).astype("float32")
    feed = {"x": xb, "y": xb.sum(1, keepdims=True).astype("float32")}

    def final_loss(use_scan):
        scope = Scope()
        exe = Executor()
        with scope_guard(scope):
            exe.run(startup)
            ordered = sorted(feed)
            plan = analyze_block(prog, 0, ordered, [loss.name])
            fn = build_block_fn(prog, plan)
            refeed = plan.donated_write_indices
            block = prog.global_block
            feeds = [jax.device_put(_as_device_array(
                feed[n], block.var_or_none(n))) for n in ordered]
            donated = [jax.device_put(np.asarray(scope.find_var(n)))
                       for n in plan.donated_reads]
            const = [jax.device_put(np.asarray(scope.find_var(n)))
                     for n in plan.const_reads]
            rngk = jax.random.PRNGKey(0)
            if use_scan:
                def multi(feeds, donated, const, rngk):
                    def one(carry, _):
                        donated, rngk = carry
                        fetches, new_state, rngk = fn(feeds, donated,
                                                      const, rngk)
                        return ([new_state[i] for i in refeed], rngk), \
                            fetches[0]
                    (donated, rngk), ls = lax.scan(one, (donated, rngk),
                                                   None, length=5)
                    return ls[-1]
                return float(np.asarray(jax.jit(multi)(
                    feeds, donated, const, rngk)))
            jitted = jax.jit(fn)
            for _ in range(5):
                fetches, new_state, rngk = jitted(feeds, donated, const,
                                                  rngk)
                donated = [new_state[i] for i in refeed]
            return float(np.asarray(fetches[0]))

    a, b = final_loss(False), final_loss(True)
    np.testing.assert_allclose(a, b, rtol=1e-5)


def test_deepfm_fused_headline_wired_into_compare_gate():
    """ISSUE 10 satellite: the deepfm_fused config's headline metric must
    be a bench_compare METRIC_KEY (so the regression gate and the
    measured-configs accounting see the fused capture), and the config
    must be registered with the orchestrator."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import bench_compare

    import bench

    assert "fused_samples_per_sec" in bench_compare.METRIC_KEYS
    names = [n for n, _, _, _ in bench.CONFIG_TABLE]
    assert "deepfm_fused" in names


def test_recovery_headline_wired_into_compare_gate():
    """ISSUE 14 satellite: the recovery config's MTTR headline is a
    bench_compare METRIC_KEY with lower-is-better RELATIVE semantics
    (seconds, not a fraction: 3 s -> 4 s must classify as a regression,
    3.0 -> 2.9 as within-noise), and the config is registered with the
    orchestrator."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import bench_compare

    import bench

    assert "recovery_mttr_s" in bench_compare.METRIC_KEYS
    assert "recovery_mttr_s" in bench_compare.LOWER_BETTER_KEYS
    names = [n for n, _, _, _ in bench.CONFIG_TABLE]
    assert "recovery" in names

    def rnd(v):
        return {"configs": {"recovery": {"recovery_mttr_s": v}}}

    worse = bench_compare.compare(rnd(3.0), rnd(4.0))
    assert worse["configs"]["recovery"]["status"] == "regression"
    better = bench_compare.compare(rnd(3.0), rnd(2.0))
    assert better["configs"]["recovery"]["status"] == "improvement"
    noise = bench_compare.compare(rnd(3.0), rnd(2.9))
    assert noise["configs"]["recovery"]["status"] == "within_noise"
    # the fraction key keeps its absolute-delta discipline (a 0.0
    # baseline stays legitimate and comparable)
    frac = bench_compare.compare(
        {"configs": {"checkpoint": {"ckpt_overhead_frac": 0.0}}},
        {"configs": {"checkpoint": {"ckpt_overhead_frac": 0.02}}})
    assert frac["configs"]["checkpoint"]["status"] == "within_noise"
