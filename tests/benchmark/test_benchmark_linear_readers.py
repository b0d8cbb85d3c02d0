"""The trace readers must survive a decode step that is six times faster: a
traced window then holds hundreds of thousands of device operations and gaps
(PR 26's ``lm_chat_open`` trace: 337,752 and 268,934 in 5 s), and a reader
quadratic in them does not end inside the driver's 360 s.  So: the one-pass
``subtract`` and the bisecting ``covered`` against the old loops (kept here,
and only here, as the oracle), a synthetic trace of that size read inside a
fixed time with its sums closed, the decode programs picked by name on a
``modules`` table shaped like PR 26's, and the order of a traced run —
``stop_trace`` inside the window, the reading after it."""
import io
import os
import random
import re
import sys
import time
from contextlib import redirect_stdout

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, trace_reduce as tr  # noqa: E402
from benchmark.metrics import (idle_under_spans, program_spans,  # noqa: E402
                               span_mean)

DATA = os.path.join(REPO, "benchmark", "testdata")
MANIFEST = harness.load_manifest(REPO)


def old_subtract(a, b):
    """``trace_reduce.subtract`` as it stood before this file existed: for
    every interval of ``a`` a scan of ``b`` from its start."""
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def old_covered(raw, span, pattern):
    """``program_spans.covered`` as it stood: every span of the trace matched
    and scanned for every span of interest."""
    rx = re.compile(pattern)
    lo, hi = span[2], span[2] + span[3]
    kids = ((s[2], s[2] + s[3]) for s in raw["spans"]
            if s[1] == span[1] and s is not span and rx.fullmatch(s[0]))
    return tr.total(tr.union(tr.clip(kids, lo, hi)))


def random_union(rng, n, span):
    return tr.union((s, s + rng.choice((0, 1, 1, 2, 5, 40)))
                    for s in (rng.randrange(span) for _ in range(n)))


@pytest.mark.parametrize("seed", range(6))
def test_subtract_in_one_pass_gives_the_old_loops_intervals(seed):
    rng = random.Random(seed)
    for _ in range(60):                     # 360 pairs over the six seeds
        span = rng.choice((20, 200, 2000))
        a = random_union(rng, rng.randrange(0, 40), span)
        b = random_union(rng, rng.randrange(0, 40), span)
        assert tr.subtract(a, b) == old_subtract(a, b)
        assert tr.subtract(a, a) == [] and tr.subtract(a, []) == list(a)
        # what reduce() and partition() do with it: peel one cover after another
        took = tr.intersect(a, b)
        assert tr.subtract(a, took) == old_subtract(a, took)
        assert tr.total(tr.subtract(a, took)) + tr.total(took) == tr.total(a)


@pytest.mark.parametrize("seed", range(4))
def test_children_found_by_bisection_cover_what_the_old_scan_covered(seed):
    """Spans that nest, overlap and touch, on three threads, the span of
    interest matching its own ``minus`` pattern among them."""
    rng = random.Random(100 + seed)
    names = ("decode::step", "decode::step.wait", "decode::step.emit",
             "executor::fetch")
    raw = {"window": [0.0, 5000.0], "device_ops": [], "spans": [
        [rng.choice(names), rng.randrange(1, 4), float(rng.randrange(5000)),
         float(rng.choice((0, 3, 30, 300, 3000))), {}] for _ in range(400)]}
    for pattern in (r"decode::step\.wait", r"decode::step\..*",
                    "decode::step|executor::fetch"):
        for span in raw["spans"]:
            assert program_spans.covered(raw, span, pattern) \
                == old_covered(raw, span, pattern)
    got = span_mean.mean_ms(raw, "decode::step", r"decode::step\.wait")
    inside = program_spans.inside(raw, "decode::step")
    assert got == sum(s[3] - old_covered(raw, s, r"decode::step\.wait")
                      for s in inside) / len(inside) / 1e6


# A traced window as a fast decode step leaves it: 300,000 device operations
# 3 ns long and 1 ns apart, [4i, 4i+3), so 300,000 idle gaps [4i+3, 4i+4) in a
# window of 1,200,000 ns: idle 25%.  The engine's thread works in 2,000 slots
# of 600 ns.  Nine slots of ten hold a decode::step [0, 590) with .retire
# [0, 10), .feed [10, 50), an executor::dispatch [60, 90), .wait [100, 500)
# and .emit [500, 580); the tenth is a decode::wait_work [0, 600).  A slot has
# 150 gaps, at 3, 7, ..., 599.  Of a step's, 147 start before 590 (the last at
# 587) and 3 lie after the span (none); 100 of the 147 start in [100, 500)
# (103 ... 499: wait), so 47 are host.  By hand, in ns:
#   host 1,800 x 47 = 84,600   wait 1,800 x 100 = 180,000
#   none 1,800 x 3  =  5,400   no_work 200 x 150 = 30,000   sum 300,000
# decode::step less its .wait: 590 - 400 = 190 ns.
N_OPS, SLOTS, SLOT_NS = 300_000, 2_000, 600
WINDOW_NS = 4 * N_OPS
LIMIT_S = 20.0


def synthetic():
    spans = []
    for k in range(SLOTS):
        t = float(k * SLOT_NS)
        if k % 10 == 9:
            spans.append(["decode::wait_work", 1, t, 600.0, {}])
            continue
        spans += [["decode::step", 1, t, 590.0, {"live": 64}],
                  ["decode::step.retire", 1, t, 10.0, {}],
                  ["decode::step.feed", 1, t + 10, 40.0, {}],
                  ["executor::dispatch", 1, t + 60, 30.0, {}],
                  ["decode::step.wait", 1, t + 100, 400.0, {}],
                  ["decode::step.emit", 1, t + 500, 80.0, {}]]
    ops = [[float(4 * i), 3.0] for i in range(N_OPS)]
    spans_raw = {"window": [0.0, float(WINDOW_NS)], "spans": spans,
                 "device_ops": ops}
    name = "%fusion.1 = f32[64,768]{1,0} fusion(%p)"
    reduce_raw = {
        "devices": {"/device:TPU:0": {
            "ops": [[name, s, d] for s, d in ops],
            "modules": [["jit_fn_decode_lm_step(7)", float(k * SLOT_NS + 95),
                         400.0] for k in range(SLOTS) if k % 10 != 9]}},
        "host": [["bench.window", 0.0, float(WINDOW_NS)],
                 ["bench.serve.send", -50.0, 400_050.0],
                 ["bench.serve.recv", 300_000.0, 1_000_000.0]]}
    return spans_raw, reduce_raw


def test_a_window_of_300000_device_operations_is_read_in_seconds():
    spans_raw, reduce_raw = synthetic()
    t0 = time.perf_counter()
    summary = tr.reduce(reduce_raw, ("bench.serve.send", "bench.serve.recv"))
    parts = idle_under_spans.partition(spans_raw)
    self_ms = span_mean.mean_ms(spans_raw, "decode::step",
                                r"decode::step\.wait")
    emit_ms = span_mean.mean_ms(spans_raw, r"decode::step\.emit")
    took = time.perf_counter() - t0
    assert took < LIMIT_S, f"reading the synthetic trace took {took:.1f} s"
    assert summary["idle_share"] == pytest.approx(0.25, rel=1e-12)
    assert summary["busy_s"] == pytest.approx(900_000e-9, rel=1e-12)
    assert summary["op_seconds"] == pytest.approx(
        {"fusion.1 f32[64,768]": 900_000e-9}, rel=1e-9)
    assert summary["modules"] == {"jit_fn_decode_lm_step(7)": {
        "launches": 1800.0, "seconds": pytest.approx(1800 * 400e-9)}}
    # send takes the gaps it covers, [3, 400,000): 100,000; recv the rest
    assert summary["idle_gaps"] == pytest.approx(
        {"bench.serve.send": 100_000e-9, "bench.serve.recv": 200_000e-9,
         "unattributed": 0.0}, abs=1e-15)
    assert parts == pytest.approx(
        {"host": 100 * 84_600 / WINDOW_NS, "no_work": 100 * 30_000 / WINDOW_NS,
         "wait": 100 * 180_000 / WINDOW_NS, "none": 100 * 5_400 / WINDOW_NS},
        rel=1e-12)
    assert sum(parts.values()) == pytest.approx(
        100 * summary["idle_share"], abs=1e-9)
    assert self_ms == pytest.approx(190e-6, rel=1e-12)
    assert emit_ms == pytest.approx(80e-6, rel=1e-12)


def test_the_partition_is_worked_out_and_printed_once_for_a_trace(monkeypatch):
    """Two metrics read a term each (``idle_engine_host_share.*``,
    ``idle_no_work_share.*``), each through a module of its own, as the
    harness loads readers."""
    raw = {"window": [0.0, 100.0], "device_ops": [[10, 50]],
           "spans": [["decode::step", 1, 0, 80, {}],
                     ["decode::step.wait", 1, 20, 50, {}],
                     ["decode::wait_work", 1, 80, 20, {}]]}
    monkeypatch.setattr(program_spans, "load", lambda: raw)
    calls = []
    real = tr.subtract
    monkeypatch.setattr(tr, "subtract",
                        lambda a, b: calls.append(1) or real(a, b))
    cell = harness.Cell(REPO, MANIFEST, "lm_chat_open")
    out = io.StringIO()
    with redirect_stdout(out):
        host = cell.reader("idle_engine_host_share.served")({})
        n_calls = len(calls)
        no_work = cell.reader("idle_no_work_share.served")({})
    # idle [0,10) host, [60,70) wait, [70,80) host, [80,100) no_work
    assert (host, no_work) == (20.0, 20.0)
    assert len(calls) == n_calls > 0
    assert out.getvalue().count("bench spans:") == 1
    assert "host=20.000 no_work=20.000 wait=10.000 none=0.000" in out.getvalue()


# The programs of PR 26's traced lm_chat_open window (chiprun_out/pr26c/
# c_chat_t.xplane.pb, the builder's chip run): launches and device seconds of
# the launches wholly inside the traced 5 s, 2,424 decode steps in the 45 s.
PR26_CHAT_MODULES = {
    "jit_convert_element_type(15388027131515875373)":
        {"launches": 96.0, "seconds": 5.7295999999999934e-05},
    "jit_fn_decode_lm_prefill_128(4046859934788986844)":
        {"launches": 10.0, "seconds": 0.014223837999999999},
    "jit_fn_decode_lm_prefill_256(6333874144969423164)":
        {"launches": 6.0, "seconds": 0.011613537},
    "jit_fn_decode_lm_prefill_32(16921449956416324232)":
        {"launches": 2.0, "seconds": 0.0021647710000000002},
    "jit_fn_decode_lm_prefill_384(17557182387452195080)":
        {"launches": 1.0, "seconds": 0.002599857},
    "jit_fn_decode_lm_prefill_64(834102893995162208)":
        {"launches": 5.0, "seconds": 0.005978337},
    "jit_fn_decode_lm_step(17901050097464394881)":
        {"launches": 346.0, "seconds": 2.6664130890000006},
}
PROGRAM_METRICS = [("lm_chat_open", "decode_step_ms.served",
                    "prefill_ms.served"),
                   ("lm_batch_sat", "decode_step_ms.served",
                    "prefill_ms.served")]


@pytest.mark.parametrize("workload, step, prefill", PROGRAM_METRICS)
def test_the_decode_programs_are_read_by_name_whatever_the_launch_rate(
        workload, step, prefill):
    """346 step launches in the traced first 5 s against 2,424 x 5 / 45 = 269
    by the window's mean rate: the launch-count check of the old reader (a
    quarter + 2 = 69) dropped both metrics here.  By name: 2,666.413 ms / 346
    and (14.224 + 11.614 + 2.165 + 2.600 + 5.978) ms / 24."""
    cell = harness.Cell(REPO, MANIFEST, workload)
    ctx = {"trace": {"modules": PR26_CHAT_MODULES, "window_s": 5.0000924},
           "decodez": {"steps": 2424, "tokens": 18177, "prefills": 270},
           "seconds": 45.0}
    assert cell.reader(step)(ctx) == pytest.approx(7.706396, rel=1e-6)
    assert cell.reader(prefill)(ctx) == pytest.approx(1.524181, rel=1e-6)
    # no count of the program's own is needed any more, and nothing is read
    # where no launch of that name lies wholly inside the window
    assert cell.reader(step)({"trace": ctx["trace"]}) \
        == cell.reader(step)(ctx)
    only_converts = {"modules": {k: v for k, v in PR26_CHAT_MODULES.items()
                                 if "convert" in k}, "window_s": 5.0}
    assert cell.reader(step)({"trace": only_converts}) is None
    assert cell.reader(prefill)({"trace": only_converts}) is None
    assert cell.reader(step)({"trace": None}) is None


@pytest.mark.parametrize("workload, step, prefill", PROGRAM_METRICS)
def test_the_recorded_engine_trace_reads_what_the_launch_count_reader_read(
        workload, step, prefill):
    """``tiny_v5e_engine.xplane.pb``: three step launches, 34,145 ns, and
    three prefills on two rungs, 27,299 + 16,108 ns.  The reader that guessed
    the step by its launch count reported 0.01138166666666667 and
    0.014469000000000003 ms from it (parent commit, same file)."""
    path = os.path.join(DATA, "tiny_v5e_engine.xplane.pb")
    summary = tr.reduce(tr.extract(path), ())
    cell = harness.Cell(REPO, MANIFEST, workload)
    ctx = {"trace": summary, "decodez": {"steps": 3},
           "seconds": summary["window_s"]}
    assert cell.reader(step)(ctx) == 0.01138166666666667
    assert cell.reader(prefill)(ctx) == 0.014469000000000003


def test_a_traced_window_stops_the_session_and_leaves_the_reading_for_later(
        tmp_path, monkeypatch):
    import jax
    events = []
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: events.append("stop_trace"))
    monkeypatch.setattr(tr, "extract",
                        lambda path: events.append(path) or {"host": []})
    tracer = tr.Tracer(str(tmp_path))
    tracer.window(0.01)
    assert events == ["stop_trace"] and tracer.raw == {} and not tracer.xplane
    for stamp in ("2026_01_01", "2026_01_02"):
        d = tmp_path / "plugins" / "profile" / stamp
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
    tracer.read()
    newest = str(tmp_path / "plugins" / "profile" / "2026_01_02"
                 / "host.xplane.pb")
    assert events == ["stop_trace", newest]
    assert tracer.xplane == newest and tracer.raw == {"host": []}
    assert tracer.stop_s >= 0.0


def test_the_bench_time_line_names_every_phase_and_the_slow_readers():
    phases = harness.Phases(100.0)
    phases.mark("setup", at=133.5)
    phases.mark("lead_in_and_window", at=184.5)
    phases.within("stop_trace", 2.3)
    phases.mark("drain", at=186.0)
    phases.within("reader:step_host_ms.served", 1.26)
    line = phases.line()
    assert line.startswith("bench time: ")
    assert ("setup=33.5 lead_in_and_window=51.0 drain=1.5 | inside those: "
            "stop_trace=2.3 reader:step_host_ms.served=1.3") in line
    assert " | " not in harness.Phases(0.0).line()


def test_end_to_end_value_hands_on_a_number_the_driver_took_and_invents_none():
    """``served_tokens_per_s.tbt50``: the window's rate, recorded under another
    name in a cell where it is not judged."""
    from benchmark import harness
    root = os.path.join(os.path.dirname(__file__), "..", "..")
    mod = harness.load_module(os.path.join(
        root, "benchmark", "metrics", "end_to_end_value.py"), "e2e_value")
    ctx = {"end_to_end": {"served_tokens_per_s": 5555.5, "tbt_p50_ms": None}}
    assert mod.read(ctx, metric="served_tokens_per_s") == 5555.5
    assert mod.read(ctx, metric="tbt_p50_ms") is None
    assert mod.read(ctx, metric="ttft_p50_ms") is None
    assert mod.read({}, metric="served_tokens_per_s") is None


@pytest.mark.parametrize("metric, taken", [("ttft_p50_ms.open", "ttft_p50_ms"),
                                           ("tbt_p95_ms.open", "tbt_p95_ms"),
                                           ("tbt_p50_ms.open", "tbt_p50_ms")])
def test_the_open_loop_cell_records_its_latencies_and_is_judged_by_its_rate(
        metric, taken):
    """``lm_chat_open`` since PR 49: the first-token wait and the gaps are
    read from the run's own numbers under names of their own, move the one
    end-to-end metric the cell keeps beside ``setup_s``, and are left out of
    the line where the driver took none."""
    cell = harness.Cell(REPO, MANIFEST, "lm_chat_open")
    assert {m["name"] for m in cell.end_to_end} \
        == {"served_tokens_per_s", "setup_s"}
    (entry,) = [m for m in cell.per_layer if m["name"] == metric]
    assert entry["moves"] == "served_tokens_per_s"
    assert entry["workloads"] == ["lm_chat_open"]
    ctx = {"end_to_end": {"ttft_p50_ms": 11.5, "tbt_p95_ms": 7.25,
                          "tbt_p50_ms": 4.75, "served_tokens_per_s": 1140.0}}
    assert cell.reader(metric)(ctx) == ctx["end_to_end"][taken]
    assert cell.reader(metric)({"end_to_end": {"served_tokens_per_s": 1.0}}) \
        is None


@pytest.mark.parametrize("metric, q", [("tbt_p90_ms.open", 0.90),
                                       ("tbt_p99_ms.open", 0.99)])
def test_the_gaps_either_side_of_the_prefill_edge_are_read_from_the_samples(
        metric, q):
    """1,000 gaps of 5 ms and 50 that held a prefill (10 ms), 4.8% as in
    ``lm_chat_open``: the 95th percentile lies on the edge between the two,
    the 90th and the 99th well inside one kind each."""
    cell = harness.Cell(REPO, MANIFEST, "lm_chat_open")
    gaps = [5.0] * 1000 + [10.0] * 50
    assert cell.reader(metric)({"tbt_ms": gaps}) \
        == harness.percentile(gaps, q) == (5.0 if q < 0.95 else 10.0)
    assert cell.reader(metric)({"tbt_ms": []}) is None

