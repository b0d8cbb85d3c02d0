"""The readers of the ``program_span`` and ``program_counter`` metrics, on a
window small enough to work out by hand and on a trace of a tiny decode
engine recorded on the v5e (``benchmark/testdata/tiny_v5e_engine.xplane.pb``)."""
import importlib.util
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402
from benchmark.metrics import (idle_under_spans, program_spans,  # noqa: E402
                               span_arg_percentile, span_mean)

DATA = os.path.join(REPO, "benchmark", "testdata")
MANIFEST = harness.load_manifest(REPO)

# A window of 1000 ns.  Thread 1 is the engine's (it holds decode::step),
# thread 2 a trainer's.  By hand:
#  device busy   [130,270) [330,550) [650,850)             = 560, idle 440
#  idle gaps     [0,130) [270,330) [550,650) [850,1000)
#  thread 1 is in decode::wait_work  [0,100)
#              in a *.wait span      [140,280) [340,560) [660,850)
#              in no decode:: span   [600,620) [900,950)
#              in other decode:: spans everywhere else up to the window's end
#  idle and host     [100,130) [280,330) [560,600) [620,650) [850,900)
#                    [950,1000)                             = 250 -> 25.0 %
#  idle and no_work  [0,100)                                = 100 -> 10.0 %
#  idle and wait     [270,280) [550,560)                    =  20 ->  2.0 %
#  idle and none     [600,620) [900,950)                    =  70 ->  7.0 %
#  decode::step less its .wait: 300 - 220 = 80 and 280 - 190 = 90 -> mean 85
#   (thread 2's span of the same name covers nothing of thread 1's steps; the
#   step that ended before the window opened is left out)
#  decode::step.emit: 40 and 30 -> mean 35
#  decode::prefill less its .wait: 190 - 140 = 50; the second prefill is cut
#   by the window's end and left out of the mean, but it STARTS in the window,
#   so its queue_ms counts: median of 12 and 40 = 26
#  trainer calls: run_steps 300 - fetch 50 = 250; parallel_executor::run 200 -
#   its executor::fetch grandchild 80 = 120 -> mean 185; the run_steps that
#   ends after the window is left out
SMALL = {
    "window": [0.0, 1000.0],
    "device_ops": [[130, 20], [150, 120], [330, 220], [650, 200], [1040, 30]],
    "spans": [
        ["decode::step", 1, -40, 35, {"live": 1}],
        ["decode::wait_work", 1, 0, 100, {}],
        ["decode::admit", 1, 100, 10, {"admitted": 1, "pending": 1}],
        ["decode::prefill", 1, 110, 190,
         {"rid": 1, "bucket": 8, "prompt": 5, "queue_ms": 12.0}],
        ["decode::prefill.feed", 1, 110, 10, {}],
        ["executor::dispatch", 1, 120, 20, {"key": "decode/lm/prefill/8"}],
        ["decode::prefill.wait", 1, 140, 140, {}],
        ["decode::prefill.emit", 1, 280, 20, {}],
        ["decode::step", 1, 300, 300, {"live": 2}],
        ["decode::step.retire", 1, 300, 5, {}],
        ["decode::step.feed", 1, 305, 15, {}],
        ["executor::dispatch", 1, 320, 20, {"key": "decode/lm/step"}],
        ["decode::step.wait", 1, 340, 220, {}],
        ["decode::step.emit", 1, 560, 40, {}],
        ["decode::step", 1, 620, 280, {"live": 2}],
        ["decode::step.feed", 1, 625, 15, {}],
        ["executor::dispatch", 1, 640, 20, {"key": "decode/lm/step"}],
        ["decode::step.wait", 1, 660, 190, {}],
        ["decode::step.emit", 1, 870, 30, {}],
        ["decode::prefill", 1, 950, 100,
         {"rid": 2, "bucket": 16, "prompt": 12, "queue_ms": 40.0}],
        ["decode::prefill.feed", 1, 950, 10, {}],
        ["decode::step.wait", 2, 300, 300, {}],
        ["executor::run_steps", 2, 100, 300, {}],
        ["executor::feed", 2, 100, 40, {}],
        ["executor::fetch", 2, 350, 50, {}],
        ["parallel_executor::run", 2, 500, 200, {}],
        ["executor::run", 2, 510, 180, {}],
        ["executor::fetch", 2, 600, 80, {}],
        ["executor::run_steps", 2, 900, 200, {}],
    ],
}


def test_idle_time_by_the_engine_threads_span_worked_out_by_hand():
    parts = idle_under_spans.partition(SMALL)
    assert parts == pytest.approx(
        {"host": 25.0, "no_work": 10.0, "wait": 2.0, "none": 7.0})
    idle = program_spans.device_idle(SMALL)
    assert idle == [(0, 130), (270, 330), (550, 650), (850, 1000)]
    assert sum(parts.values()) == pytest.approx(
        100.0 * sum(e - s for s, e in idle) / 1000.0)


def test_self_time_is_the_span_less_its_children_on_its_own_thread():
    ns = 1e-6                                           # ms per ns
    assert span_mean.mean_ms(SMALL, "decode::step", r"decode::step\.wait") \
        == pytest.approx(85 * ns)
    assert span_mean.mean_ms(SMALL, r"decode::step\.emit") \
        == pytest.approx(35 * ns)
    assert span_mean.mean_ms(SMALL, "decode::prefill",
                             r"decode::prefill\.wait") == pytest.approx(50 * ns)
    assert span_mean.mean_ms(
        SMALL, "executor::run_steps|parallel_executor::run",
        "executor::fetch") == pytest.approx(185 * ns)
    assert span_mean.mean_ms(SMALL, "decode::copy_block") is None


def test_a_span_cut_by_the_windows_edge_is_left_out_of_a_mean():
    whole = program_spans.inside(SMALL, "decode::prefill")
    assert [s[4]["rid"] for s in whole] == [1]
    started = program_spans.inside(SMALL, "decode::prefill", "start")
    assert [s[4]["rid"] for s in started] == [1, 2]
    assert len(program_spans.inside(SMALL, "decode::step")) == 2
    assert span_arg_percentile.percentile(
        SMALL, "decode::prefill", "queue_ms", 0.5) == pytest.approx(26.0)
    assert span_arg_percentile.percentile(
        SMALL, "decode::step", "queue_ms", 0.5) is None


def test_the_recorded_v5e_engine_trace_reads_as_worked_out_by_hand():
    """``testdata/tiny_v5e_engine.xplane.pb``: a one-layer ``DecodeEngine``
    (3 slots, rungs 8 and 16, ``attn_impl="xla"``) serving two requests of
    three tokens, resting, then one of two, under a 32.8 ms ``bench.window``,
    recorded on the TPU v5e with the benchmark's profiler options (my chip
    run, PR 25, call 2).  The ``/host:metadata`` plane's HLO protos, which no
    reader opens, were dropped to keep the file small; nothing else.

    By hand, from a direct walk of the file, in ns after the window's opening
    (45,597,037; the window lasts 32,795,837).  The engine's thread holds, in
    order: admit [2,628,429 +79,880), prefill rid 3 [2,867,939 +5,605,430)
    with its wait +1,654,520, prefill rid 4 [8,494,959 +4,272,449) wait
    +841,040, step [12,792,198 +3,426,450) wait +908,190 emit +35,540, admit
    +15,130, step [16,248,598 +3,496,420) wait +742,770 emit +99,290,
    wait_work [19,756,198 +3,353,029), admit +38,849, prefill rid 5
    [23,179,198 +3,919,409) wait +898,410, step [27,113,607 +3,526,950) wait
    +929,380 emit +118,170.
     step less wait: 2,518,260 + 2,753,650 + 2,597,570 = 7,869,480 / 3
     emit: 253,000 / 3;  prefill less wait: 3,950,910 + 3,431,409 + 3,020,999
     = 10,403,318 / 3;  queue_ms 0.518, 5.988, 0.136 -> median 0.518
    The device ran six programs, 63,863 ns of operations in all, each while
    the thread was inside ``executor::feed`` of the call that launched it: this
    recording shows the device's clock 0.57-0.75 ms AHEAD of the host's (a
    program starts that much before its ``executor::dispatch`` opens), so all
    the busy time falls in ``host`` territory.  Spans cover 27,733,996 of the
    window: none = 32,795,837 - 27,733,996 = 5,061,841; no_work 3,353,029;
    wait 5,974,310; host = 27,733,996 - 3,353,029 - 5,974,310 - 63,863 =
    18,342,794; together 32,731,974 = the window less the busy time."""
    path = os.path.join(DATA, "tiny_v5e_engine.xplane.pb")
    assert os.path.getsize(path) < 300_000
    raw = program_spans.extract(path)
    assert raw["window"] == [45597037.0, 32795837.0]
    assert program_spans.thread_of(raw, "decode::step") is not None
    ns = 1e-6
    assert span_mean.mean_ms(raw, "decode::step", r"decode::step\.wait") \
        == pytest.approx(7869480 / 3 * ns, rel=1e-9)
    assert span_mean.mean_ms(raw, r"decode::step\.emit") \
        == pytest.approx(253000 / 3 * ns, rel=1e-9)
    assert span_mean.mean_ms(raw, "decode::prefill",
                             r"decode::prefill\.wait") \
        == pytest.approx(10403318 / 3 * ns, rel=1e-9)
    assert span_arg_percentile.percentile(
        raw, "decode::prefill", "queue_ms", 0.5) == pytest.approx(0.51776)
    w = 32795837
    assert idle_under_spans.partition(raw) == pytest.approx(
        {"host": 100 * 18342794 / w, "no_work": 100 * 3353029 / w,
         "wait": 100 * 5974310 / w, "none": 100 * 5061841 / w}, rel=1e-9)
    # the four terms are the device's idle share, as trace_reduce has it
    from benchmark import trace_reduce as tr
    summary = tr.reduce(tr.extract(path), ())
    assert sum(idle_under_spans.partition(raw).values()) == pytest.approx(
        100 * summary["idle_share"], rel=1e-9)
    # every program under its own name, launched once per span of its kind
    launches = {k.split("(")[0]: v["launches"]
                for k, v in summary["modules"].items() if "decode" in k}
    assert launches == {"jit_fn_decode_lm_step": 3.0,
                        "jit_fn_decode_lm_prefill_8": 2.0,
                        "jit_fn_decode_lm_prefill_16": 1.0}
    assert len(program_spans.inside(raw, "decode::step")) == 3
    assert len(program_spans.inside(raw, "decode::prefill")) == 3
    keys = sorted(s[4]["key"] for s in
                  program_spans.inside(raw, "executor::dispatch"))
    assert keys == ["decode/lm/prefill/16", "decode/lm/prefill/8",
                    "decode/lm/prefill/8", "decode/lm/step",
                    "decode/lm/step", "decode/lm/step"]


def test_a_trace_without_program_spans_reads_as_nothing():
    """The parent of the PR that added the spans: the readers report nothing
    and do not raise."""
    bare = {"window": [0.0, 1000.0], "spans": [],
            "device_ops": SMALL["device_ops"]}
    assert idle_under_spans.partition(bare) is None
    assert span_mean.mean_ms(bare, "decode::step") is None
    assert span_arg_percentile.percentile(
        bare, "decode::prefill", "queue_ms", 0.5) is None
    no_window = dict(SMALL, window=None)
    assert idle_under_spans.partition(no_window) is None
    assert span_mean.mean_ms(no_window, "decode::step") is None


def test_the_run_s_own_trace_is_the_newest_under_bench_trace(tmp_path):
    assert program_spans.find_trace(str(tmp_path)) is None
    old = tmp_path / ".bench_trace" / "cell" / "plugins" / "profile" / "a"
    new = tmp_path / ".bench_trace" / "cell" / "plugins" / "profile" / "b"
    for d, t in ((old, 1_000), (new, 2_000)):
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
        os.utime(d / "host.xplane.pb", (t, t))
    assert program_spans.find_trace(str(tmp_path)) == str(new / "host.xplane.pb")


def test_the_counter_reader_scales_a_counter_and_skips_a_missing_one():
    from paddle_tpu import observability as obs
    spec = importlib.util.spec_from_file_location(
        "program_counter_under_test",
        os.path.join(REPO, "benchmark", "metrics", "program_counter.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    obs.stats.counter("benchtest.some_ms").inc(1500)
    assert reader.read({}, "benchtest.some_ms", 0.001) == pytest.approx(1.5)
    assert reader.read({}, "benchtest.no_such_counter") is None


SPAN_READERS = ("span_mean.py", "span_arg_percentile.py",
                "idle_under_spans.py", "engine_off_cpu.py")
# every (span metric, cell that reads it)
SPAN_PAIRS = [(m["name"], w["name"]) for w in MANIFEST["workloads"]
              for m in harness.cell_metrics(MANIFEST, w["name"])[1]
              if m["source"] == "program_span"]


@pytest.mark.parametrize("metric, workload", SPAN_PAIRS)
def test_a_span_metric_reads_nothing_from_a_checkout_with_no_trace(
        metric, workload, tmp_path, monkeypatch):
    """No ``.bench_trace`` in the checkout: the metric's reader, found
    through its own file by each cell that reads it, reports nothing rather
    than raising."""
    cell = harness.Cell(REPO, MANIFEST, workload)
    assert os.path.basename(cell.metric_file(metric)["reader"]) in SPAN_READERS
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    assert cell.reader(metric)({}) is None
