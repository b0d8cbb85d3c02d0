"""One entry a metric that several models share (PR 58): the twenty-five
entries that went, each held here with the cell that read it and the reader
and arguments it was read with, against what that cell resolves today under
the surviving name — the metric's file overlaid with the cell's
configuration's ``metric_args``.  The ledger's lines before PR 58 carry the
old names; this table is how they map.  And the readings that waited for the
fold: cells whose programs bear a sibling's scopes and counters read the
sibling's entry by their name in its list."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402

MANIFEST = harness.load_manifest(REPO)
METRICS = "benchmark/metrics/"
_PREFILL = {"program": "^jit_fn_decode_lm_prefill_",
            "span": "decode::prefill\\.observe"}
_STEP = {"program": "^jit_fn_decode_lm_step\\b",
         "span": "decode::step\\.observe"}


def _roofline(launch, kernel, count, counts=None):
    args = dict(launch, kernel=kernel, count=count)
    if counts:
        args["counts"] = f"benchmark/kernel_counts_{counts}.py"
    return "kernel_roofline.py", args


def _moe(suffix, kernel="^moe_grouped_swiglu", counts=None):
    return {
        "moe_prefill_roofline" + suffix: _roofline(
            _PREFILL, kernel, "moe_prefill", counts),
        "moe_step_roofline" + suffix: _roofline(
            _STEP, kernel, "moe_step", counts)}


def _mla(suffix, counts=None):
    return {
        "mla_prefill_attn_roofline" + suffix: _roofline(
            _PREFILL, "^flash_fwd", "mla_prefill_attn", counts),
        "mla_decode_attn_roofline" + suffix: _roofline(
            _STEP, "^mla_paged_decode_attn", "mla_decode_attn", counts)}


def _load(experts, suffix):
    return {"expert_load_max_over_mean" + suffix: ("counter_ratio.py", {
        "num": ["step_expert_load_max_sum"],
        "den": ["step_routed_assignments"], "times_config": experts})}


def _touched(den, suffix):
    return {"experts_touched_per_step" + suffix: ("counter_ratio.py", {
        "num": ["step_experts_touched"], "den": [den]})}


def _scopes(name, *scopes):
    return {name: ("scope_share.py", {"scopes": [f"/{s}/" for s in scopes]})}


MLA_SCOPES = ("mla_wq", "mla_wkva", "mla_wkvb", "mla_wo", "mla_rope",
              "mla_cache_write", "mla_attn")
MOE_SCOPES = ("moe_router", "moe_routed", "moe_shared")
# {cell: {the entry it read before PR 58: (reader, args)}}
WAS = {
    "dsv2l_doc_sat": {
        **_scopes("moe_share.served_ds", *MOE_SCOPES),
        **_scopes("mla_share.served_ds", *MLA_SCOPES),
        **_moe(".served_ds"), **_mla(".served_ds"),
        **_load("n_routed_experts", ".served_ds"),
        **_touched("step_moe_dispatches", ".served_ds")},
    "st21b_mixed_sat": {
        **_scopes("moe_share.served_st", "route", "experts"),
        **_moe(".served_st", "^moe_grouped_reglu", "smallthinker"),
        **_load("moe_num_primary_experts", ".served_st")},
    "lfm2_topic_sat": {
        **_scopes("moe_share.served_lfm2", "moe"),
        **_moe(".served_lfm2", counts="lfm2"),
        **_load("num_experts", ".served_lfm2"),
        **_touched("steps", ".served_lfm2")},
    "kl48b_longdoc_sat": {**_moe(".served_kl", counts="kimi_linear"),
                          **_mla(".served_kl", "kimi_linear")},
    "xg29b_doc_sat": {**_moe(".served_xg", counts="xing"),
                      **_mla(".served_xg", "xing")},
}
FOLDED = [(old, cell) for cell, entries in WAS.items() for old in entries]


def _now(old):
    return old.split(".")[0] + ".served"


@pytest.mark.parametrize("old, workload", FOLDED)
def test_a_cell_reads_the_surviving_entry_as_it_read_the_one_that_went(
        old, workload):
    reader, args = WAS[workload][old]
    cell = harness.Cell(REPO, MANIFEST, workload)
    assert old not in {m["name"] for m in MANIFEST["per_layer"]}
    assert not os.path.exists(os.path.join(REPO, METRICS, old + ".json"))
    (entry,) = [m for m in cell.per_layer if m["name"] == _now(old)]
    assert workload in entry["workloads"]
    spec = cell.metric_file(_now(old))
    assert spec["reader"] == METRICS + reader
    assert spec["args"] == args
    # and what made it a copy is gone: one entry, one file a metric
    assert [m["name"] for m in MANIFEST["per_layer"]].count(_now(old)) == 1
    assert (entry["moves"], entry["source"]) == (
        "served_tokens_per_s",
        "program_counter" if reader == "counter_ratio.py" else "device_trace")


# the readings that waited for the fold (PERF.md section 7 until PR 58): each
# cell's program bears decode/mla.py's scopes and the routed-load series under
# the siblings' names, so it reads the sibling's entry with the file's
# defaults and its configuration's own key of the experts it holds
JOINS = {
    "kl48b_longdoc_sat": {
        **_scopes("moe_share.served", *MOE_SCOPES),
        **_scopes("mla_share.served", *MLA_SCOPES),
        **_load("num_experts", ".served"),
        **_touched("step_moe_dispatches", ".served")},
    "xg29b_doc_sat": {
        **_scopes("moe_share.served", *MOE_SCOPES),
        **_scopes("mla_share.served", *MLA_SCOPES),
        **_load("n_routed_experts", ".served"),
        **_touched("step_moe_dispatches", ".served"),
        "prefill_pad_share.served": ("counter_ratio.py", {
            "num": ["prefill_pad_tokens"],
            "den": ["prefill_pad_tokens", "prefill_real_tokens"],
            "scale": 100.0})},
}
JOINED = [(name, cell) for cell, entries in JOINS.items() for name in entries]


@pytest.mark.parametrize("metric, workload", JOINED)
def test_a_cell_joins_a_siblings_entry_by_its_name_in_the_list(metric,
                                                               workload):
    reader, args = JOINS[workload][metric]
    cell = harness.Cell(REPO, MANIFEST, workload)
    (entry,) = [m for m in cell.per_layer if m["name"] == metric]
    assert workload in entry["workloads"]
    spec = cell.metric_file(metric)
    assert (spec["reader"], spec["args"]) == (METRICS + reader, args)
    if "times_config" in args:      # 64 held or routed experts in both
        assert cell.config[args["times_config"]] == 64
    # the driver hands the reader every counter it divides: the window's
    # deltas carry them (a name that is not there reads as nothing)
    if reader == "counter_ratio.py":
        driver = cell.driver()
        assert set(args["num"]) | set(args["den"]) <= set(
            driver.WINDOW_COUNTERS)
