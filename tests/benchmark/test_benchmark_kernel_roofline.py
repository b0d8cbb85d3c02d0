"""``benchmark/metrics/kernel_roofline.py``, the one roofline reader, through
each model's counts file: a metric's file names the reader and its ``args``,
and the counts file is named there or — where models share the kernel and
the entry (PR 58) — in the cell's configuration's ``metric_args``
(``benchmark/kernel_counts.py`` where neither names one); a cell reads its
rooflines through what ``Cell.metric_file`` resolves alone.  One case a
counts file: a hand-made trace of the model's kernels inside the launches of
its programs, the observer's spans of those launches, and the share worked
out by hand.  (The pairing of launches with spans is
``test_benchmark_mla.py``'s.)"""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, peaks  # noqa: E402
from benchmark.metrics import program_spans  # noqa: E402

MANIFEST = harness.load_manifest(REPO)
READER = "benchmark/metrics/kernel_roofline.py"
DEFAULT_COUNTS = "benchmark/kernel_counts.py"
MS = 1e6
PEAK = peaks.peaks_for("TPU v5 lite")
HBM, BF16 = PEAK["hbm_bytes_per_s"], PEAK["bf16_flops_per_s"]


def _op(kernel, at, lasts, shape="f32[8]{0}"):
    return [f"%{kernel} = {shape} custom-call()", at * MS, lasts * MS]


def _trace(modules, ops):
    return {"host": [["bench.window", 0.0, 100 * MS]],
            "devices": {"/device:TPU:0": {
                "modules": [[n, s * MS, d * MS] for n, s, d in modules],
                "ops": ops}}}


# two steps of 30 ms, the second with no span (the trace stopped): neither
# timed nor counted, however long its kernel ran
TWO_STEPS = [("jit_fn_decode_lm_step(1)", 10, 30),
             ("jit_fn_decode_lm_step(1)", 50, 30)]


def _mla():
    """``dsv2l_doc_sat``: neither its metric files nor its configuration
    name a counts file."""
    raw = _trace(TWO_STEPS + [("jit_fn_decode_lm_prefill_1024(2)", 82, 10)], [
        _op("moe_grouped_swiglu.1", 11, 2, "f32[8,8]{1,0}"),
        _op("mla_paged_decode_attn.2", 14, 1),
        _op("moe_grouped_swiglu.1", 51, 10, "f32[8,8]{1,0}"),
        _op("moe_grouped_swiglu.1", 83, 8, "f32[8,8]{1,0}")])
    spans = [["decode::step.observe", 1, 40.1 * MS, 0.1 * MS,
              {"step_experts_touched": 6 * 60, "step_routed_assignments": 0,
               "step_context_tokens": 100000}],
             ["decode::prefill.observe", 1, 92.1 * MS, 0.1 * MS,
              {"prefill_routed_assignments": 6 * 6 * 900,
               "prefill_tokens_sq": 900 ** 2}]]
    expert = 3 * 2048 * 1408
    return raw, spans, None, DEFAULT_COUNTS, {
        "moe_step_roofline.served": 100 * 6 * 60 * expert * 2 / 2e-3 / HBM,
        "mla_decode_attn_roofline.served":
            100 * 100000 * 7 * 576 * 2 / 1e-3 / HBM,
        "moe_prefill_roofline.served":
            100 * 2 * expert * 6 * 6 * 900 / 8e-3 / BF16}


def _sambay():
    raw = _trace(TWO_STEPS, [
        _op("diff_paged_decode_attn.1", 11, 2),
        _op("diff_ring_decode_attn.2", 14, 1),
        _op("diff_paged_decode_attn.3", 20, 8),
        _op("diff_paged_decode_attn.1", 51, 10)])
    spans = [["decode::step.observe", 1, 40.1 * MS, 0.1 * MS,
              {"step_context_tokens": 100000, "step_window_tokens": 30000,
               "step_streams": 64}]]
    cfg = {"hidden_size": 2560, "num_attention_heads": 40,
           "num_key_value_heads": 20, "num_hidden_layers": 32,
           "kv_dtype": "bfloat16"}
    return raw, spans, cfg, "benchmark/kernel_counts_sambay.py", {
        "shared_kv_decode_attn_roofline.served_p4f":
            100 * 100000 * 8 * 5120 / 10e-3 / HBM,
        "swa_decode_attn_roofline.served_p4f":
            100 * 30000 * 8 * 5120 / 1e-3 / HBM}


def _falconh1():
    raw = _trace(TWO_STEPS, [
        _op("ssd_state_step.1", 11, 2),
        _op("gqa_paged_decode_attn.2", 14, 1),
        _op("ssd_state_step.1", 20, 6),
        _op("ssd_state_step.1", 51, 10)])
    row = 6 * 2 * 4 * 32 * 256 * 128
    spans = [["decode::step.observe", 1, 40.1 * MS, 0.1 * MS,
              {"step_context_tokens": 100000, "step_streams": 64,
               "step_state_bytes": 64 * row}]]
    cfg = {"num_hidden_layers": 6, "num_attention_heads": 20,
           "num_key_value_heads": 4, "head_dim": 128, "mamba_d_ssm": 4096,
           "mamba_d_state": 256, "kv_dtype": "bfloat16"}
    return raw, spans, cfg, "benchmark/kernel_counts_falconh1.py", {
        "ssd_state_step_roofline.served_fh1": 100 * 64 * row / 8e-3 / HBM,
        "gqa_decode_attn_roofline.served_fh1":
            100 * 100000 * 6 * 2048 / 1e-3 / HBM}


def _smallthinker():
    raw = _trace(TWO_STEPS + [("jit_fn_decode_lm_prefill_4096(2)", 82, 10)], [
        _op("moe_grouped_reglu.1", 11, 2, "bf16[8,8]{1,0}"),
        _op("gqa_ring_decode_attn.2", 14, 1),
        _op("gqa_paged_decode_attn.3", 16, 2),
        _op("moe_grouped_reglu.1", 20, 6, "bf16[8,8]{1,0}"),
        _op("moe_grouped_reglu.1", 51, 10, "bf16[8,8]{1,0}"),
        _op("gqa_window_flash_fwd.4", 83, 3),
        _op("gqa_group_flash_fwd.5", 86, 1),
        _op("moe_grouped_reglu.6", 88, 4, "bf16[8,8]{1,0}")])
    spans = [["decode::step.observe", 1, 40.1 * MS, 0.1 * MS,
              {"step_context_tokens": 200000, "step_ring_rows_live": 150000,
               "step_streams": 64, "step_routed_assignments": 3072,
               "step_experts_touched": 500}],
             ["decode::prefill.observe", 1, 92.1 * MS, 0.1 * MS,
              {"prefill_real_tokens": 4000, "prefill_tokens_sq": 16000000,
               "prefill_window_pairs": 8002000,
               "prefill_routed_assignments": 4000 * 6 * 8}]]
    expert, pair = 3 * 2560 * 768, 28 * 4 * 128
    return raw, spans, None, "benchmark/kernel_counts_smallthinker.py", {
        "moe_step_roofline.served":
            100 * (500 * expert * 2 + 3072 * 2560 * 4) / 8e-3 / HBM,
        "ring_decode_attn_roofline.served_st":
            100 * 150000 * 6 * 2048 / 1e-3 / HBM,
        "full_decode_attn_roofline.served_st":
            100 * 200000 * 2 * 2048 / 2e-3 / HBM,
        "moe_prefill_roofline.served":
            100 * 2 * expert * 4000 * 48 / 4e-3 / BF16,
        "window_prefill_attn_roofline.served_st":
            100 * pair * 8002000 * 6 / 3e-3 / BF16,
        "full_prefill_attn_roofline.served_st":
            100 * pair * 8002000 * 2 / 1e-3 / BF16}


FAMILIES = {"dsv2l_doc_sat": _mla, "p4flash_reason_sat": _sambay,
            "fh1_chat_sat": _falconh1, "st21b_mixed_sat": _smallthinker}


@pytest.mark.parametrize("workload", sorted(FAMILIES))
def test_the_one_reader_counts_each_family_by_its_own_file(workload,
                                                           monkeypatch):
    raw, spans, cfg, counts, expect = FAMILIES[workload]()
    cell = harness.Cell(REPO, MANIFEST, workload)
    ctx = {"trace_raw": raw, "config": cfg or cell.config,
           "memory": {"kind": "TPU v5 lite"}}
    monkeypatch.setattr(program_spans, "load", lambda: {"spans": spans})
    for metric, share in expect.items():
        spec = cell.metric_file(metric)
        assert spec["reader"] == READER
        assert spec["args"].get("counts", DEFAULT_COUNTS) == counts
        assert cell.reader(metric)(ctx) == pytest.approx(share), metric
    # the parent of the PR that brought a kernel: no such kernel, no such
    # count, no trace — nothing, never 0
    mod = harness.load_module(os.path.join(REPO, READER), "roofline_under_test")
    args = cell.metric_file(next(iter(expect)))["args"]
    assert mod.read(ctx, **args) == pytest.approx(next(iter(expect.values())))
    assert mod.read(ctx, **dict(args, kernel="^absent")) is None
    assert mod.read(ctx, **dict(args, count="absent")) is None
    assert mod.read(dict(ctx, trace_raw=None), **args) is None
    # a counts file that is not there is a fault of the configuration, said
    # before a number is printed, not a metric that is silently missing
    # and so is one outside the benchmark's own directories: the operations
    # and bytes are the yardstick's to count, never the program's
    for counts in ("benchmark/kernel_counts_none.py",
                   "paddle_tpu/kernels/moe.py",
                   "benchmark/../paddle_tpu/kernels/moe.py"):
        with pytest.raises(harness.ConfigurationError):
            mod.read(ctx, **dict(args, counts=counts))


ROOFLINES = [(m["name"], w["name"]) for w in MANIFEST["workloads"]
             for m in harness.cell_metrics(MANIFEST, w["name"])[1]
             if m["name"].split(".")[0].endswith("_roofline")]


@pytest.mark.parametrize("metric, workload", ROOFLINES)
def test_a_roofline_names_a_count_that_its_counts_file_has(metric, workload):
    """A misnamed ``count`` reads as nothing on the chip (the reader cannot
    tell it from the parent of the kernel's PR), so it is caught here."""
    cell = harness.Cell(REPO, MANIFEST, workload)
    spec = cell.metric_file(metric)
    assert spec["reader"] == READER
    (entry,) = [m for m in cell.per_layer if m["name"] == metric]
    assert entry["unit"] == "%" and entry["better"] == "higher"
    assert entry["source"] == "device_trace"
    mod = harness.load_module(os.path.join(REPO, READER), "roofline_under_test")
    counts = mod.counting(spec["args"].get("counts", DEFAULT_COUNTS))
    assert callable(counts[spec["args"]["count"]])
    assert set(spec["args"]) <= {"program", "kernel", "count", "span",
                                 "counts"}


def test_there_is_one_roofline_reader():
    metrics = os.listdir(os.path.join(REPO, "benchmark", "metrics"))
    assert [f for f in metrics if f.startswith("kernel_roofline")] == [
        "kernel_roofline.py"]
    # every roofline of every cell is read by it (the cases above), and
    # every cell that has a counts file of its own has rooflines
    assert {w for _, w in ROOFLINES} >= set(FAMILIES)
