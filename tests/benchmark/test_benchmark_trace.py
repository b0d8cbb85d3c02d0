"""The trace reduction against values worked out by hand — on intervals small
enough to check on paper, and on a trace recorded on the v5e and kept under
``benchmark/testdata`` — and the table of peaks."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import peaks, trace_reduce as tr  # noqa: E402

DATA = os.path.join(REPO, "benchmark", "testdata")


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert tr.total([(0, 3), (5, 8)]) == 6
    assert tr.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(0, 3), (5, 8)]) == [(3, 5), (8, 10)]
    assert tr.intersect([(3, 5), (8, 10)], [(4, 9)]) == [(4, 5), (8, 9)]
    assert tr.subtract([(3, 5), (8, 10)], [(4, 9)]) == [(3, 4), (9, 10)]


def test_leaves_drops_the_events_that_contain_others():
    ops = [["while.1", 0, 100], ["a", 0, 10], ["b", 10, 20], ["c", 120, 5],
           ["conditional", 130, 10], ["d", 131, 2]]
    assert [e[0] for e in tr.leaves(ops)] == ["a", "b", "c", "d"]


def test_op_label_keeps_the_name_and_the_result_type():
    assert tr.op_label("%copy.50 = f32[12,2049,16,12,64]{4,3,2,1,0:T(8,128)} "
                       "copy(f32[12,2049,16,12,64]{4,3,2,1,0} %p)") \
        == "copy.50 f32[12,2049,16,12,64]"
    assert tr.op_label("%fusion.3 = (u32[1]{0:T(128)}, u32[1]{0}) fusion(%x)") \
        == "fusion.3 u32[1]"
    assert tr.op_label("sort") == "sort"


# A window of 100 ns on two devices, worked out by hand:
#  dev0 ops: a [10,30) b [20,40) (overlap) c [60,70), inside w [55,75) which is
#            a container and dropped from the op sums (its time IS busy time);
#            an all-reduce [90,110) is cut by the window's end to [90,100).
#            busy = [10,40) + [55,75) + [90,100) = 30 + 20 + 10 = 60
#  dev1 ops: one op [0,50)                              busy = 50
#  busy_s = (60 + 50) / 2 = 55 ns; idle share = 1 - 55/100 = 0.45
#  gaps of dev0: [0,10) [40,55) [75,90)  = 40 ns
#  spans: feed [0,12) and [74,80); fetch [5,50) and [78,95)
#   feed first:  [0,10) -> 10,  [75,80) -> 5            feed  = 15
#   then fetch on what is left: [40,50) -> 10, [80,90) -> 10   fetch = 20
#   unattributed: [50,55) -> 5
SMALL = {
    "devices": {
        "/device:TPU:0": {
            "ops": [["%a = f32[8]{0} add(%x)", 10, 20], ["%b = f32[8]{0} add(%y)", 20, 20],
                    ["%w = s32[]{:T(128)} while(%t)", 55, 20],
                    ["%c = f32[4]{0} multiply(%z)", 60, 10],
                    ["%all-reduce.1 = f32[2]{0} all-reduce(%g)", 90, 20]],
            "modules": [["jit_step(1)", 10, 30], ["jit_step(1)", 55, 20],
                        ["jit_other(2)", 90, 20]]},
        "/device:TPU:1": {"ops": [["%a = f32[8]{0} add(%x)", 0, 50]],
                          "modules": [["jit_step(1)", 0, 50]]}},
    "host": [["bench.window", 0, 100],
             ["bench.train.feed", 0, 12], ["bench.train.feed", 74, 6],
             ["bench.train.fetch", 5, 45], ["bench.train.fetch", 78, 17]],
}


def test_reduction_of_a_window_worked_out_by_hand():
    s = tr.reduce(SMALL, ("bench.train.feed", "bench.train.fetch"))
    ns = 1e-9
    assert s["window_s"] == pytest.approx(100 * ns)
    assert s["busy_s"] == pytest.approx(55 * ns)
    assert s["idle_share"] == pytest.approx(0.45)
    assert s["op_seconds"] == pytest.approx({
        "a f32[8]": (20 + 50) / 2 * ns, "b f32[8]": 20 / 2 * ns,
        "c f32[4]": 10 / 2 * ns, "all-reduce.1 f32[2]": 10 / 2 * ns})
    assert s["collective_s"] == pytest.approx(5 * ns)
    # launches cut by the window's edge are not counted
    assert s["modules"] == {"jit_step(1)": {"launches": 1.5,
                                            "seconds": pytest.approx(50 * ns)}}
    assert s["idle_gaps"] == pytest.approx({
        "bench.train.feed": 15 * ns, "bench.train.fetch": 20 * ns,
        "unattributed": 5 * ns})
    b = tr.breakdown(s, top=2)
    assert [n for n, _ in b["device_ops"]] == ["a f32[8]", "b f32[8]"]
    assert b["idle_gaps"][0][0] == "bench.train.fetch"
    assert len(b["idle_gaps"]) == 2


def test_the_recorded_v5e_trace_reduces_to_values_worked_out_by_hand():
    """``testdata/tiny_v5e.xplane.pb``: three launches of a three-op program
    (``tanh(a @ b).sum(0)`` on bf16[2048,2048]) under a 61 ms ``bench.window``,
    recorded on the TPU v5e (my chip run, PR 24, call 3), with the benchmark's
    own spans (``tiny_v5e.spans.json``, ``time.perf_counter()`` stamps).

    By hand, in ns on the trace's clock.  Window [48,396,689, 109,416,698).
    Launch 1: copy-start [52,978,215 +13), copy-done [..230 +3), fusion
    [..235 +90,882): busy 90,898.  Launch 2: 13 + 3 + 90,873 = 90,889.
    Launch 3: 13 + 2 + 90,878 = 90,893.  Busy 272,680 of 61,020,009.
    Programs: 90,906 + 90,895 + 90,901 = 272,702.  The spans lie 5.5, 15.4 and
    25.1 ms after the window's opening, each after its launch has finished on
    the device's clock (which this recording shows about a millisecond ahead of
    the host's), so they are idle throughout: feed 461,110 + 338,441 + 277,470
    = 1,077,021; fetch 599,360 + 569,709 + 591,900 = 1,760,969; the rest of the
    idle time, 60,747,329 - 2,837,990 = 57,909,339, is unattributed."""
    raw = tr.extract(os.path.join(DATA, "tiny_v5e.xplane.pb"))
    assert list(raw["devices"]) == ["/device:TPU:0"]
    dev = raw["devices"]["/device:TPU:0"]
    assert len(dev["ops"]) == 9 and len(dev["modules"]) == 3
    assert raw["host"] == [["bench.window", 48396689.0, 61020009.0]]
    with open(os.path.join(DATA, "tiny_v5e.spans.json")) as f:
        rec = json.load(f)
    tracer = tr.Tracer("unused")
    tracer.raw, tracer.t_open = raw, rec["t_open"]
    tracer.add_host_spans([tuple(s) for s in rec["spans"]])
    assert len(raw["host"]) == 7
    s = tr.reduce(raw, ("bench.train.feed", "bench.train.fetch"))
    ns = 1e-9
    assert s["window_s"] == pytest.approx(61020009 * ns, rel=1e-12)
    assert s["busy_s"] == pytest.approx(272680 * ns, rel=1e-9)
    assert s["idle_share"] == pytest.approx(1 - 272680 / 61020009, rel=1e-9)
    assert s["op_seconds"] == pytest.approx({
        "copy-start bf16[2048,2048]": 39 * ns,
        "copy-done bf16[2048,2048]": 8 * ns,
        "fusion bf16[2048]": 272633 * ns}, rel=1e-9)
    assert s["collective_s"] == 0.0
    assert s["modules"] == {"jit__lambda(3870029882665302147)": {
        "launches": 3.0, "seconds": pytest.approx(272702 * ns, rel=1e-9)}}
    assert s["idle_gaps"] == pytest.approx({
        "bench.train.feed": 1077021 * ns, "bench.train.fetch": 1760969 * ns,
        "unattributed": 57909339 * ns}, rel=1e-4)


def test_a_trace_with_no_device_plane_or_no_window_reduces_to_nothing():
    assert tr.reduce({"devices": {}, "host": [["bench.window", 0, 10]]}, ()) == {}
    assert tr.reduce({"devices": SMALL["devices"], "host": []}, ()) == {}


def test_the_peaks_table_has_the_v5e_and_refuses_an_unknown_device():
    row = peaks.peaks_for("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9 and row["hbm_bytes"] == 16e9
    assert "Google Cloud" in row["source"]
    for kind in ("TPU v4", "cpu", "comment", ""):
        with pytest.raises(KeyError):
            peaks.peaks_for(kind)


def test_flops_per_token_of_the_base_model_by_hand():
    cfg = {"d_model": 512, "d_ffn": 2048, "n_layer": 6, "tgt_vocab": 37000}
    # weights a token pair touches: encoder layer 4*512^2 + 2*512*2048 =
    # 3,145,728; decoder layer 8*512^2 + 2*512*2048 = 4,194,304; six of each
    # = 44,040,192; output projection 512*37000 = 18,944,000; 62,984,192 in all
    # attention: 6 layers * (256 + 256 + 256) query-key pairs per target
    # position = 4608, at 12 * 512 operations each = 28,311,552
    want = 6 * 62_984_192 + 28_311_552
    assert peaks.encdec_train_flops_per_token(cfg, 256, 256) == want
    # 100,000 tokens/s on one v5e: 40.62 TFLOP/s of 197
    assert peaks.model_flops_util(1e5, want, 1, "TPU v5 lite") == \
        pytest.approx(100 * 1e5 * want / 197e12)
