"""The reader of ``step_off_cpu_ms.*`` (``benchmark/metrics/engine_off_cpu.py``):
a window worked out by hand, traces that carry nothing for it, its cost on a
long trace, and its two entries in the manifest."""
import copy
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402
from benchmark.metrics import engine_off_cpu, program_spans  # noqa: E402

DATA = os.path.join(REPO, "benchmark", "testdata")
MANIFEST = harness.load_manifest(REPO)
ENTRIES = ("step_off_cpu_ms.served", "step_off_cpu_ms.tbt50")

# A window of 10,000 µs, written in µs.  Thread 1 is the engine's, thread 2 a
# trainer's whose spans count for nothing.  off = duration - cpu_ns, never
# floored a span; only a launch and its wait are read, whatever else carries
# the argument.  Device operations less than 200 µs apart are one stretch.
#
#  step A [1000,4000): cpu 1120 -> off 1880; wait [2200,3700) cpu 100 -> 1400;
#   queued 480.  device [1900,2400) [2500,3100): it ends BEFORE the wait does,
#   so hand-back = 3700 - max(2200, 3100) - 100 = 500; the gap between the two
#   operations is 100; the first starts 200 after executor::dispatch opens
#  prefill P [4100,4900): cpu 300 -> off 500; wait [4400,4800) cpu 50 -> 350;
#   queued 150; device [4300,4650): hand-back 4800 - 4650 - 50 = 100; no
#   executor::dispatch among its spans, so no first-op reading
#  step B [5000,8000): cpu 1200 -> off 1800; wait [6100,7300) cpu 200 -> 1000;
#   queued 800.  device [5800,6400) [6500,7500): the second starts before the
#   wait ends, so it is this launch's, and ends AFTER it (a device clock that
#   lags): the device's end is taken as the wait's, hand-back = 0 - 200; the
#   gap between the two operations is 100; first op 500 after dispatch
#  step C [9500,10500) is cut by the window's edge, the step at -500 ended
#   before it opened: both left out
#
#  means over A and B, µs: queued 640 + handback 150 = total 790; gaps 100; on
#   CPU 1160, of it 150 in the wait; first op: median of 200 and 500 = 350;
#   four spans read, none over; the smallest cpu_ns read is 100, so the
#   steps' 2320 are 23 ticks (the prefill's: 50, 6 ticks)
def _scaled(raw, k):
    return {"window": [t * k for t in raw["window"]],
            "device_ops": [[s * k, d * k] for s, d in raw["device_ops"]],
            "spans": [[n, t, s * k, d * k,
                       {a: v * k if a == "cpu_ns" else v
                        for a, v in args.items()}]
                      for n, t, s, d, args in raw["spans"]]}


HAND_US = {
    "window": [0.0, 10000.0],
    "device_ops": [[1900, 500], [2500, 600], [4300, 350], [5800, 600],
                   [6500, 1000], [9700, 100]],
    "spans": [
        ["decode::step", 1, -500, 300, {"live": 1, "cpu_ns": 100}],
        ["decode::step", 1, 1000, 3000, {"live": 2, "cpu_ns": 1120}],
        ["decode::step.retire", 1, 1000, 100, {"cpu_ns": 100}],
        ["decode::step.feed", 1, 1100, 200, {"cpu_ns": 150}],
        ["executor::run_callable", 1, 1300, 700,
         {"key": "decode/lm/step", "cpu_ns": 400}],
        ["executor::feed", 1, 1350, 300, {"cpu_ns": 200}],
        ["executor::dispatch", 1, 1700, 250,
         {"key": "decode/lm/step", "cpu_ns": 100}],
        ["decode::step.emit", 1, 2000, 200, {"cpu_ns": 120}],
        ["decode::step.wait", 1, 2200, 1500, {"cpu_ns": 100}],
        ["decode::step.observe", 1, 3500, 100, {"cpu_ns": 90}],
        ["decode::step.book", 1, 3700, 250, {}],
        ["decode::prefill", 1, 4100, 800,
         {"rid": 1, "bucket": 8, "cpu_ns": 300}],
        ["decode::prefill.feed", 1, 4100, 100, {"cpu_ns": 100}],
        ["executor::run_callable", 1, 4200, 200, {"cpu_ns": 150}],
        ["decode::prefill.wait", 1, 4400, 400, {"cpu_ns": 50}],
        ["decode::prefill.emit", 1, 4800, 100, {"cpu_ns": 50}],
        ["decode::step", 1, 5000, 3000, {"live": 2, "cpu_ns": 1200}],
        ["decode::step.feed", 1, 5000, 100, {"cpu_ns": 130}],
        ["executor::run_callable", 1, 5200, 600, {"cpu_ns": 450}],
        ["executor::dispatch", 1, 5300, 400, {"cpu_ns": 300}],
        ["decode::step.wait", 1, 6100, 1200, {"cpu_ns": 200}],
        ["decode::step.book", 1, 7300, 300, {"cpu_ns": 250}],
        ["decode::step.emit", 1, 7650, 300, {"cpu_ns": 100}],
        ["decode::step", 1, 9500, 1000, {"live": 1, "cpu_ns": 10}],
        ["executor::run_steps", 2, 900, 7000, {"cpu_ns": 1}],
        ["executor::dispatch", 2, 1650, 500, {"cpu_ns": 1}],
        ["decode::step.wait", 2, 2000, 4000, {"cpu_ns": 1}],
    ],
}
HAND = _scaled(HAND_US, 1e3)        # in ns, as a trace has them
MS = 1e-3                           # ms a µs


def test_the_split_worked_out_by_hand():
    found = engine_off_cpu.split(copy.deepcopy(HAND))
    step, prefill = found["decode::step"], found["decode::prefill"]
    assert step["total"] == pytest.approx(790 * MS)
    assert step["queued"] == pytest.approx(640 * MS)
    assert step["handback"] == pytest.approx(150 * MS)
    assert step["gaps"] == pytest.approx(100 * MS)
    assert step["on_cpu"] == pytest.approx(1160 * MS)
    assert step["wait_cpu"] == pytest.approx(150 * MS)
    assert step["first_op"] == pytest.approx(350 * MS)
    assert (step["launches"], step["spans"], step["over"]) == (2, 4, 0)
    assert (step["clock_step"], step["ticks"]) == (pytest.approx(100 * MS), 23)
    assert prefill["total"] == pytest.approx(250 * MS)
    assert prefill["queued"] == pytest.approx(150 * MS)
    assert prefill["handback"] == pytest.approx(100 * MS)
    assert (prefill["launches"], prefill["spans"], prefill["over"]) \
        == (1, 2, 0)
    assert (prefill["clock_step"], prefill["ticks"]) \
        == (pytest.approx(50 * MS), 6)
    # what the time outside the wait may not pass: the same steps' host time
    host = ((3000 - 1500) + (3000 - 1200)) / 2
    assert step["queued"] <= host * MS


def test_the_metric_is_the_steps_total_and_the_line_is_printed_once(
        monkeypatch, capsys):
    # the same window a thousand times longer: µs by hand read as ms
    raw = _scaled(HAND_US, 1e6)
    monkeypatch.setattr(program_spans, "load", lambda: raw)
    assert engine_off_cpu.read({}) == pytest.approx(790.0)
    assert engine_off_cpu.read({}) == pytest.approx(790.0)
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("bench spans: engine thread off the CPU")]
    assert lines == [
        "bench spans: engine thread off the CPU, ms a step: total=790.0000 "
        "queued=640.0000 handback=150.0000 | in-launch gaps=100.0000 | on "
        "CPU=1160.0000 (in the wait 150.0000) | dispatch to first "
        "op=350.0000 | 2 launches, cpu_ns over the duration in 0 of 4 spans, "
        "23 ticks of 100.0000",
        "bench spans: engine thread off the CPU, ms a prefill: total=250.0000 "
        "queued=150.0000 handback=100.0000 | in-launch gaps=0.0000 | on "
        "CPU=300.0000 (in the wait 50.0000) | dispatch to first op=nan | 1 "
        "launches, cpu_ns over the duration in 0 of 2 spans, 6 ticks of "
        "50.0000"]


def test_a_clock_that_ticks_coarsely_is_summed_before_it_is_subtracted():
    """The v5e hosts' thread clock advances 10 ms at a time: of ten steps of
    2,000 µs that each ran 1,000, one reads a tick of 10,000 and nine read 0.
    off(step) sums to 20,000 - 10,000, a mean of 1,000; the waits' 800 are
    all hand-back (no device operation), so queued 200 and the total 1,000 —
    where a floor a span would have read 1,800."""
    spans = []
    for i in range(10):
        t = 3000.0 * i
        spans.append(["decode::step", 1, t, 2000.0,
                      {"cpu_ns": 10000 if i == 4 else 0}])
        spans.append(["decode::step.wait", 1, t + 1000, 800.0, {"cpu_ns": 0}])
    step = engine_off_cpu.split(_scaled(
        {"window": [0.0, 30000.0], "spans": spans, "device_ops": []}, 1e3)
    )["decode::step"]
    assert step["total"] == pytest.approx(1000 * MS)
    assert step["queued"] == pytest.approx(200 * MS)
    assert step["handback"] == pytest.approx(800 * MS)
    assert (step["over"], step["ticks"]) == (1, 1)
    assert step["clock_step"] == pytest.approx(10000 * MS)


def test_a_launch_keeps_its_whole_stretch_when_the_device_clock_lags():
    """µs.  Two steps; a program is three operations 50 apart, and the
    device's clock lags: a program's last operation STARTS after its wait
    has ended.  It stays that launch's (one stretch), so the second launch's
    first operation is its own, 900 after its dispatch opens, and neither
    holds the 1,850 between the programs as a gap of its own."""
    spans, ops = [], []
    for t in (0.0, 3000.0):
        spans += [["decode::step", 1, t, 2500.0, {"cpu_ns": 500}],
                  ["executor::dispatch", 1, t + 200, 300.0, {}],
                  ["decode::step.wait", 1, t + 600, 1500.0, {"cpu_ns": 0}]]
        ops += [[t + 1100, 300.0], [t + 1450, 650.0], [t + 2150, 100.0]]
    step = engine_off_cpu.split(_scaled(
        {"window": [0.0, 6000.0], "spans": spans, "device_ops": ops}, 1e3)
    )["decode::step"]
    assert step["first_op"] == pytest.approx(900 * MS)
    assert step["gaps"] == pytest.approx(100 * MS)
    assert step["handback"] == pytest.approx(0.0)     # both outlast the wait


def test_a_wait_that_carries_no_cpu_time_leaves_its_launch_out():
    raw = copy.deepcopy(HAND)
    (wait,) = [s for s in raw["spans"] if s[0] == "decode::step.wait"
               and s[1] == 1 and s[2] == 6100e3]
    del wait[4]["cpu_ns"]
    step = engine_off_cpu.split(raw)["decode::step"]
    assert step["launches"] == 1 and step["total"] == pytest.approx(980 * MS)
    assert step["gaps"] == pytest.approx(100 * MS)


def test_traces_that_carry_nothing_for_it_read_as_nothing(monkeypatch):
    """The parent of the PR that added ``cpu_ns`` (the recorded v5e trace is
    such a program's), a trace with no spans, and no trace at all."""
    recorded = program_spans.extract(
        os.path.join(DATA, "tiny_v5e_engine.xplane.pb"))
    assert program_spans.inside(recorded, "decode::step")
    bare = {"window": [0.0, 1000.0], "spans": [],
            "device_ops": HAND["device_ops"]}
    for raw in (recorded, bare, dict(HAND, window=None), None):
        monkeypatch.setattr(program_spans, "load", lambda raw=raw: raw)
        assert engine_off_cpu.read({}) is None
        if raw is not None:
            assert engine_off_cpu.split(raw) == {}


def test_a_long_trace_is_read_in_seconds():
    """20,000 steps of nine children each and 300,000 device operations: a
    faster program puts more of both in the same window (in µs)."""
    spans, ops = [], []
    for i in range(20_000):
        t = 1000.0 * i
        spans.append(["decode::step", 1, t, 900.0, {"cpu_ns": 300}])
        for k, name in enumerate(
                ("decode::step.retire", "decode::step.feed",
                 "executor::run_callable", "decode::step.emit")):
            spans.append([name, 1, t + 50 * k, 40.0 + 100 * (k == 2), {}])
        spans.append(["executor::feed", 1, t + 105, 30.0, {}])
        spans.append(["executor::dispatch", 1, t + 140, 90.0, {}])
        spans.append(["decode::step.wait", 1, t + 300, 400.0, {"cpu_ns": 30}])
        spans.append(["decode::step.observe", 1, t + 650, 40.0, {}])
        spans.append(["decode::step.book", 1, t + 700, 100.0, {}])
        ops.extend([t + 250 + 20 * j, 15.0] for j in range(15))
    raw = _scaled({"window": [0.0, 2.0e7], "spans": spans,
                   "device_ops": ops}, 1e3)
    t0 = time.perf_counter()
    step = engine_off_cpu.split(raw)["decode::step"]
    took = time.perf_counter() - t0
    assert step["launches"] == 20_000 and step["spans"] == 2 * 20_000
    # hand-back 700 - 545 - 30; queued (900 - 300) - (400 - 30)
    assert step["handback"] == pytest.approx(125 * MS)
    assert step["total"] == pytest.approx((230 + 125) * MS)
    assert step["gaps"] == pytest.approx(14 * 5 * MS)
    assert step["first_op"] == pytest.approx(110 * MS)
    assert took < 30.0, took


@pytest.mark.parametrize("metric", ENTRIES)
def test_an_entry_is_sound_and_names_the_reader(metric):
    assert harness.check_manifest(REPO, MANIFEST) == []
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    assert "workloads" not in entry and entry["unit"] == "ms" \
        and entry["better"] == "lower" and entry["layer"] == "decode plane"
    # span arguments (`cpu_ns`) and the launches' ends, from one trace file:
    # entered under the spans' label since PR 58 (device_trace before it)
    assert entry["source"] == "program_span"
    moved = {"served": "served_tokens_per_s", "tbt50": "tbt_p50_ms"}
    assert entry["moves"] == moved[metric.rsplit(".", 1)[1]]
    cells = [w["name"] for w in MANIFEST["workloads"]
             if harness.metric_applies(entry, w["name"],
                                       MANIFEST["end_to_end"])]
    assert cells
    for cell in cells:
        spec = harness.Cell(REPO, MANIFEST, cell).metric_file(metric)
        assert spec["reader"] == "benchmark/metrics/engine_off_cpu.py"
        assert spec.get("args", {}) == {}


def test_the_twin_reads_as_the_served_entry_does():
    served, twin = (
        harness._read_json(os.path.join(
            REPO, "benchmark", "metrics", name + ".json")) for name in ENTRIES)
    assert (served["reader"], served.get("args", {})) \
        == (twin["reader"], twin.get("args", {}))


def test_a_cell_listed_under_the_rate_alone_reads_the_served_entry():
    manifest = copy.deepcopy(MANIFEST)
    (rate,) = [m for m in manifest["end_to_end"]
               if m["name"] == "served_tokens_per_s"]
    rate["workloads"].append("a_later_cell")
    read = {m["name"] for m in harness.cell_metrics(manifest, "a_later_cell")[1]}
    assert ENTRIES[0] in read and ENTRIES[1] not in read


@pytest.mark.parametrize("metric", ENTRIES)
def test_an_entry_reads_nothing_from_a_checkout_with_no_trace(
        metric, tmp_path, monkeypatch):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    (cell,) = [w["name"] for w in MANIFEST["workloads"]
               if harness.metric_applies(entry, w["name"],
                                         MANIFEST["end_to_end"])][:1]
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    assert harness.Cell(REPO, MANIFEST, cell).reader(metric)({}) is None
