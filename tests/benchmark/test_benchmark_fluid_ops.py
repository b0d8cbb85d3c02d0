"""``benchmark/metrics/fluid_op_table.py`` and the nine metrics of the train
cells that read ``core/lowering.py``'s scopes, on a trace small enough to
check by hand: the shares sum as stated, an empty ``tf_op`` counts as
unscoped, a ``.remat`` clone is filed under the scope it recomputes, a trace
without a role reads nothing and says so, a run without a trace reads
nothing."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, trace_reduce  # noqa: E402
from benchmark.metrics import scope_share  # noqa: E402

DATA = os.path.join(REPO, "benchmark", "testdata")
METRICS = ["fwd_share", "bwd_share", "opt_share", "unscoped_share",
           "vocab_wide_share", "attention_share", "ffn_share",
           "top_fluid_op_share", "recompute_share"]
J = "jit(multi_s1)/while/body/closed_call/"
# instruction text -> tf_op, as scope_share.op_scopes reads them from a trace
TF_OP = {
    "%dot.1 = f32[8,8]{1,0} convolution(%a, %b)":
        J + "fwd/enc_0/ffn/mul/dot_general",
    "%dot.1.remat = f32[8,8]{1,0} convolution(%a, %b)":
        J + "fwd/enc_0/ffn/mul/dot_general",
    "%dot.2 = f32[8,8]{1,0} convolution(%g, %b)":
        J + "bwd/enc_0/ffn/mul_grad/transpose(jvp())/dot_general",
    "%dot.3 = f32[8,8]{1,0} convolution(%a, %g)":
        J + "bwd/enc_0/ffn/mul_grad/transpose(jvp())/transpose",
    "%fusion.4 = f32[8,8]{1,0} fusion(%q, %k)":
        J + "fwd/dec_1/cross_attn/fused_attention/bhqd,bhkd->bhqk/dot_general",
    "%fusion.5 = f32[8,8]{1,0} fusion(%q, %k)":
        J + "bwd/dec_1/self_attn/layer_norm_grad/mul;" + J
        + "fwd/dec_1/self_attn/layer_norm/mul",
    "%fusion.6 = f32[8,64]{1,0} fusion(%x, %w)":
        J + "fwd/out_proj/mul/dot_general",
    "%fusion.7 = f32[8,64]{1,0} fusion(%x)":
        J + "bwd/loss/softmax_with_cross_entropy_grad/sub",
    "%all-reduce.8 = f32[] all-reduce(%x)":
        J + "bwd/loss/reduce_sum_grad/broadcast_in_dim",
    "%fusion.9 = f32[64,8]{1,0} fusion(%p, %g)":
        J + "opt/adam/tgt.out_proj/sub",
    "%fusion.10 = f32[8,8]{1,0} fusion(%p, %g)":
        J + "opt/adam/enc.0.ffn.fc1.w/sub",
    "%fusion.11 = f32[] fusion(%s)": J + "opt/increment/add",
    "%copy.12 = f32[8,8]{0,1} copy(%p)": "",
    "%slice.13 = f32[8] dynamic-slice(%f)":
        "jit(multi_s1)/while/body/dynamic_slice",
}
MS = {"dot.1": 100, "dot.1.remat": 50, "dot.2": 100, "dot.3": 100,
      "fusion.4": 80, "fusion.5": 20, "fusion.6": 150, "fusion.7": 100,
      "all-reduce.8": 10, "fusion.9": 60, "fusion.10": 20, "fusion.11": 5,
      "copy.12": 15, "slice.13": 5}
BUSY = sum(MS.values())            # 815 ms, every op inside the window


def _raw(tf_op=TF_OP):
    """The ops one after another from t = 1 s inside a window that holds
    them all; a ``while`` event spans them (not a leaf); one op before the
    window."""
    by_head = {k.split(" = ")[0].lstrip("%"): k for k in tf_op}
    ops, t = [], 1e9
    for head, ms in MS.items():
        ops.append([by_head[head], t, ms * 1e6])
        t += ms * 1e6
    ops.append(["%while.1 = (f32[]) while(%t)", 1e9, t - 1e9])
    ops.append([by_head["dot.1"], 1e8, 5e7])
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": []},
                        "/device:TPU:1": {"ops": [], "modules": []}},
            "host": [[trace_reduce.WINDOW_SPAN, 9e8, 2e9]]}


def _reader():
    return harness.load_module(
        os.path.join(REPO, "benchmark", "metrics", "fluid_op_table.py"),
        "reader_under_test_fluid_op_table")


def _args(metric):
    with open(os.path.join(REPO, "benchmark", "metrics",
                           metric + ".train.json")) as f:
        return json.load(f)["args"]


@pytest.fixture()
def ctx(monkeypatch):
    monkeypatch.setattr(scope_share, "op_scopes", lambda path: TF_OP)
    return {"trace_raw": _raw(), "xplane": "a-trace.xplane.pb", "trace": None}


@pytest.fixture()
def values(ctx, capsys):
    mod = _reader()
    out = {m: mod.read(ctx, **_args(m)) for m in METRICS}
    return out, capsys.readouterr().out


@pytest.mark.parametrize("metric, ns", [
    ("fwd_share", 100 + 50 + 80 + 150),
    ("bwd_share", 100 + 100 + 20 + 100 + 10),   # fusion.5: the first name
    ("opt_share", 60 + 20 + 5),
    ("unscoped_share", 15 + 5),                 # an empty tf_op counts
    ("vocab_wide_share", 150 + 100 + 10 + 60),
    ("attention_share", 80 + 20),
    ("ffn_share", 100 + 50 + 100 + 100),
    ("top_fluid_op_share", 100 + 50 + 150),     # (fwd, mul), every layer's
    ("recompute_share", 50),
])
def test_a_metric_reads_its_share_of_the_first_devices_busy_time(
        values, metric, ns):
    assert values[0][metric] == pytest.approx(100.0 * ns / BUSY)


def test_the_shares_sum_as_stated_and_the_table_is_printed_once(values):
    v, printed = values
    assert v["fwd_share"] + v["bwd_share"] + v["opt_share"] \
        + v["unscoped_share"] == pytest.approx(100.0, abs=1e-9)
    assert v["vocab_wide_share"] + v["attention_share"] + v["ffn_share"] \
        <= v["fwd_share"] + v["bwd_share"] + v["opt_share"]
    lines = [l for l in printed.splitlines()
             if l.startswith("bench fluid ops:")]
    assert len(lines) == 4                      # nine metrics, one table
    rows = lines[0].split(": ", 2)[2].split("; ")
    assert len(rows) == 9 and "of 9 rows in the first device's 0.815" in lines[0]
    assert rows[0] == "bwd enc_0/ffn mul_grad 0.2000 (0.0000, 0.0000)"
    # the clone under the scope it recomputes, seconds then (remat, coll.)
    assert "fwd enc_0/ffn mul 0.1500 (0.0500, 0.0000)" in rows
    assert "opt - adam 0.0800 (0.0000, 0.0000)" in rows
    # which op of the program owns the scalar all-reduce
    assert lines[1].split(": ")[2].startswith(
        "fwd mul 0.3000; bwd mul_grad 0.2000; bwd softmax_with_cross_entropy")
    assert "all-reduce.8 f32[] 0.0100 bwd/loss/reduce_sum_grad" in lines[2]
    assert "0.0200 s in 2 instructions under no role, by kind: copy 0.0150 " \
        "(1); slice 0.0050 (1); the longest: copy.12 f32[8,8] 0.0150; " \
        "slice.13 f32[8] 0.0050" in lines[3]


def test_the_table_files_a_clone_and_a_collective_under_their_op(ctx):
    mod = _reader()
    table = mod.build(ctx["trace_raw"], TF_OP, frozenset(
        {"mul", "mul_grad", "fused_attention", "layer_norm_grad", "adam",
         "increment", "softmax_with_cross_entropy_grad", "reduce_sum_grad"}))
    rows = table["rows"]
    assert table["busy_s"] == pytest.approx(BUSY * 1e-3)
    sec, remat, coll, _ = rows["fwd", "enc_0/ffn", "mul"]
    assert (sec, remat, coll) == pytest.approx((0.150, 0.050, 0.0))
    sec, remat, coll, which = rows["bwd", "loss", "reduce_sum_grad"]
    assert (sec, remat, coll) == pytest.approx((0.010, 0.0, 0.010))
    assert list(which) == ["all-reduce.8 f32[]"]
    assert rows["bwd", "enc_0/ffn", "mul_grad"][0] == pytest.approx(0.200)
    assert rows["opt", "", "adam"][0] == pytest.approx(0.080)
    assert mod.file_under("", frozenset()) is None
    assert mod.file_under("jit(f)/fwd/a/b/mul/dot_general",
                          frozenset({"mul"})) == ("fwd", "a/b", "mul")
    # a sub-block's ops are filed under their control-flow op
    assert mod.file_under(
        "jit(f)/bwd/static_rnn_grad/transpose(jvp())/while/body/cell/mul/x",
        frozenset({"mul", "static_rnn_grad"})) == ("bwd", "",
                                                   "static_rnn_grad")


def test_no_clone_reads_zero_and_not_nothing(monkeypatch):
    tf_op = {k: v for k, v in TF_OP.items() if ".remat" not in k}
    monkeypatch.setattr(scope_share, "op_scopes", lambda path: tf_op)
    raw = _raw()
    dev = raw["devices"]["/device:TPU:0"]
    dev["ops"] = [e for e in dev["ops"] if ".remat" not in e[0]]
    assert _reader().read({"trace_raw": raw, "xplane": "x"},
                          stat="recompute") == 0.0


def test_a_trace_from_before_the_scopes_reads_nothing_and_says_so(
        monkeypatch, capsys):
    """As on the parent commit, or from a compile cache the parent filled."""
    bare = {k: v.replace("fwd/", "").replace("bwd/", "").replace("opt/", "")
            for k, v in TF_OP.items()}
    monkeypatch.setattr(scope_share, "op_scopes", lambda path: bare)
    mod, ctx = _reader(), {"trace_raw": _raw(), "xplane": "x"}
    assert [mod.read(ctx, **_args(m)) for m in METRICS] == [None] * 9
    said = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("bench: the trace holds")]
    assert len(said) == 1 and "no tf_op under fwd/, bwd/ or opt/" in said[0]


def test_a_run_without_a_trace_reads_nothing(monkeypatch, tmp_path):
    from benchmark.metrics import program_spans
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    program_spans.extract.cache_clear()
    mod = _reader()
    for m in METRICS:
        assert mod.read({"trace": None}, **_args(m)) is None
    assert mod.read({"trace_raw": {"devices": {}, "host": []}, "xplane": "x"},
                    stat="top") is None


def test_the_recorded_v5e_trace_goes_through_the_whole_reader():
    """``tiny_v5e_engine.xplane.pb`` is a serving engine's: real ``tf_op``s,
    none under a role."""
    path = os.path.join(DATA, "tiny_v5e_engine.xplane.pb")
    raw = trace_reduce.extract(path)
    mod = _reader()
    assert mod.read({"trace_raw": raw, "xplane": path}, stat="unscoped") is None
    table = mod.build(raw, scope_share.op_scopes(path), mod.fluid_op_types())
    assert table["rows"] == {} and table["busy_s"] > 0
    assert sum(table["unscoped"].values()) == pytest.approx(table["busy_s"])
    assert "mul_grad" in mod.fluid_op_types()


def test_the_train_drivers_ctx_has_no_raw_trace_so_it_is_extracted_once(
        monkeypatch, tmp_path):
    """``drivers/train.py`` hands the readers the summary only: the newest
    trace under ``.bench_trace/`` is found, extracted once and kept for the
    nine metrics."""
    import shutil
    from benchmark.metrics import program_spans
    where = tmp_path / ".bench_trace" / "cell" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    path = str(where / "host.xplane.pb")
    shutil.copy(os.path.join(DATA, "tiny_v5e_engine.xplane.pb"), path)
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    program_spans.extract.cache_clear()
    real = scope_share.op_scopes(path)
    monkeypatch.setattr(scope_share, "op_scopes", lambda p: {
        k: v.replace("jit(fn_decode_lm_step)/",
                     "jit(fn_s1)/fwd/enc_0/ffn/mul/") for k, v in real.items()})
    calls = []
    extract = trace_reduce.extract
    monkeypatch.setattr(trace_reduce, "extract",
                        lambda p: calls.append(p) or extract(p))
    mod = _reader()
    got = {m: mod.read({"trace": {"busy_s": 1.0}}, **_args(m))
           for m in METRICS}
    program_spans.extract.cache_clear()
    assert calls == [path]
    assert 0.0 < got["fwd_share"] <= 100.0 and got["recompute_share"] == 0.0
    assert got["fwd_share"] + got["unscoped_share"] == pytest.approx(100.0)
    assert got["ffn_share"] == pytest.approx(got["fwd_share"])
    assert got["bwd_share"] is None and got["attention_share"] is None
