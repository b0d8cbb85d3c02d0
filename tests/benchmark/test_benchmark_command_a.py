"""The ``command_a_serve`` driver at a toy size on the CPU: the new cell's
entries and the manifest with it — the cell IN each joined entry's list, its
``times_config`` and counts resolved through the configuration's
``metric_args``; the replay through the engine's own executables (pool and
rings that wrap), the plain reference's full forward, the readings, and the
controls of ``benchmark/command_a_controls.py`` through the same functions;
the counting functions against hand-worked numbers at the published
widths."""
import importlib.util
import json
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import command_a_controls, harness  # noqa: E402
from benchmark import kernel_counts_command_a  # noqa: E402

CFG = {
    "vocab_size": 96, "hidden_size": 64, "intermediate_size": 32,
    "num_hidden_layers": 4, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 4,
    "num_experts_per_tok": 4, "num_shared_experts": 4,
    "norm_topk_prob": True, "expert_selection_fn": "sigmoid",
    "shared_expert_combination_strategy": "average", "layer_norm_eps": 1e-5,
    "rope_theta": 50000, "position_embedding_type": "rope_gptj",
    "rotary_pct": 1, "layer_types": ["sliding_attention"] * 3
    + ["full_attention"], "sliding_window": 32, "logit_scale": 1,
    "tie_word_embeddings": True, "use_parallel_block": True,
    "use_qk_norm": False, "use_gated_activation": True,
    "attention_bias": False, "hidden_act": "silu",
    "first_k_dense_replace": 0, "router_experts": 16, "first_expert": 8,
    "max_seq_len": 192, "dtype": "float32", "kv_dtype": "float32"}
MIX = {"engine": {"max_slots": 3, "max_queue": 8, "block_tokens": 16,
                  "num_blocks": 40, "prefill_buckets": [64, 128]},
       "prompt_tokens": {"max": 100}}
CELL = "cap_rag_sat"
CONFIG = "command-a-plus-218b-ep8-pp8s0"
SOURCE = "https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/" \
    "main/config.json"
MANIFEST = harness.load_manifest(REPO)
# the accepted entries the cell joins by its name in their lists (PR 58's
# way in; the kernels' names, `gqa16_*` at a group of sixteen, are words of
# the configuration's `metric_args`), and the two it brings
JOINED = {n + ".served" for n in (
    "moe_share", "moe_prefill_roofline", "moe_step_roofline",
    "expert_load_max_over_mean", "experts_touched_per_step",
    "prefill_pad_share", "live_context_tokens")} | {
    n + ".served_st" for n in (
        "window_attn_share", "ring_live_share",
        "window_prefill_attn_roofline", "full_prefill_attn_roofline",
        "ring_decode_attn_roofline", "full_decode_attn_roofline")} | {
    "held_choice_share.served_kl"}
OWN = {"attn_proj_share.served_ca", "shared_ffn_share.served_ca"}


@pytest.fixture(scope="module")
def driver():
    path = os.path.join(REPO, "benchmark", "drivers", "command_a_serve.py")
    spec = importlib.util.spec_from_file_location(
        "command_a_serve_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(driver):
    from paddle_tpu.decode import SamplingParams
    # four held of sixteen at top-4 and prompts of tens of positions: the
    # reference's own readings lie wider than at the published sizes, and
    # float32 on both sides reads a thousandth of what bf16 activations do
    driver.REFERENCE_RANGES = dict(
        driver.REFERENCE_RANGES, ref_held_choice_share=(0.02, 0.7),
        ref_top1_weight=(0.25, 0.6), ref_attn_logit_std=(0.3, 4.0),
        ref_routed_rms=(0.02, 1.5), ref_attn_rms=(0.1, 2.0),
        ref_shared_rms=(0.1, 2.0))
    driver.LIMITS = dict(driver.LIMITS, ring_err_max=1e-3,
                         logit_err_prefill_max=1e-3, norm_unit_err_max=5e-5)
    params = driver.make_params(CFG)
    engine, server, _ = driver.build_server(CFG, MIX, params)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, size=n).astype(np.int32)
               for n in (5, 64, 100)]       # inside the window; past it twice
    handles = [engine.submit(p, SamplingParams(temperature=0.0,
                                               max_new_tokens=m))
               for p, m in zip(prompts, (20, 24, 21))]
    asks = [(p, h.result(timeout=900.0)["tokens"])
            for p, h in zip(prompts, handles)]
    yield params, engine, asks
    server.stop()


def test_the_manifest_is_sound_and_names_the_cell_and_its_configuration_once():
    assert harness.check_manifest(REPO, MANIFEST) == []
    assert [w["name"] for w in MANIFEST["workloads"]].count(CELL) == 1
    assert [c["name"] for c in MANIFEST["configs"]].count(CONFIG) == 1
    assert OWN <= {m["name"] for m in MANIFEST["per_layer"]}


def test_every_line_of_the_manifest_keeps_the_contracts_form():
    # what the driver holds the file to before any run, and check_manifest
    # does not: PR 59 was refused once for a `why` of 209 characters
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
    for part in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[part]:
            assert name.match(entry["name"]), entry["name"]
            for key in ("why", "layer", "source"):
                said = entry.get(key)
                if said is not None:
                    assert 1 <= len(said) <= 200 and said.isprintable(), \
                        (entry["name"], key, len(said))
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10


def test_the_new_cell_is_the_one_the_issue_names():
    cell = harness.Cell(REPO, MANIFEST, CELL)
    assert (cell.config_name, cell.mix_name, cell.chips, cell.kind) == \
        (CONFIG, "rag_sat", 1, "command_a_serve")
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    cut = ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["reduced"] == cut == cell.config["reduced"]
    assert entry["source"] == cell.config["source"] == SOURCE
    # every key of the source under its name, none changed but the three
    src = cell.config["source_keys"]
    assert {k: src[k] for k in cut} == cell.config["published"] == \
        {"num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144}
    for k, v in src.items():
        if k not in cut:
            assert cell.config[k] == v, k
    assert {k: cell.config[k] for k in cut} == \
        {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 32768}
    assert (cell.config["router_experts"], cell.config["first_expert"]) == \
        (128, 0)
    assert (cell.config["hidden_size"], cell.config["intermediate_size"],
            cell.config["num_attention_heads"],
            cell.config["num_key_value_heads"], cell.config["head_dim"],
            cell.config["num_experts_per_tok"],
            cell.config["num_shared_experts"], cell.config["sliding_window"],
            cell.config["rope_theta"], cell.config["layer_norm_eps"],
            cell.config["logit_scale"], cell.config["max_seq_len"]) == \
        (4096, 4096, 128, 8, 128, 8, 4, 4096, 50000, 1e-5, 1, 8832)
    assert cell.config["layer_types"][:4] == ["sliding_attention"] * 3 \
        + ["full_attention"] and len(cell.config["layer_types"]) == 32
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "command-a-plus-05-2026"]
    assert src == row["config"] and entry["source"] == row["source_url"]
    for key in ("deployment", "assumed", "what"):
        assert cell.config[key]
    said = " ".join(cell.config["assumed"])
    # every reading the config leaves open, the one not taken beside it
    for word in ("ONE LayerNorm", "not taken: a second norm", "GPT-J layout",
                 "not taken: rotate-half", "no selection bias", "width of "
                 "ONE expert", "MEAN over the four", "not taken: averaging "
                 "the shared and the routed", "read by nothing", "text only",
                 "float32 norm statistics", "8,832"):
        assert word in said, word
    assert "eight pipeline stages" in cell.config["deployment"] \
        and "EIGHT chips a stage" in cell.config["deployment"] \
        and "stage 0, chip 0" in cell.config["deployment"] \
        and "4,733,292,544" in cell.config["deployment"] \
        and "EIGHTH" in cell.config["deployment"]
    mix = cell.mix
    assert (mix["loop"], mix["callers"], mix["lead_s"], mix["trace_seconds"],
            mix["trace_after_s"]) == ("closed", 48, 8.0, 5.0, 10.0)
    assert mix["engine"] == {
        "max_slots": 32, "max_queue": 64, "block_tokens": 16,
        "num_blocks": 16385,
        "prefill_buckets": [1024, 2048, 3072, 4096, 6144, 8192]}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 4096,
                                    "sigma": 0.5, "min": 1024, "max": 8192}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 160,
                                    "sigma": 0.6, "min": 32, "max": 640}
    assert "prompt_ids" not in mix and mix["sampling"] == "greedy"
    assert mix["request_block"] * mix["cycle_blocks"] == 192
    assert mix["who"] and len(mix["why"]) > 500


def test_the_cell_is_in_every_joined_entrys_list_and_its_words_resolve():
    cell = harness.Cell(REPO, MANIFEST, CELL)
    assert {m["name"] for m in cell.end_to_end} == \
        {"served_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    family = {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m
              and m["moves"] in {e["name"] for e in cell.end_to_end}}
    assert names == family | JOINED | OWN
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in JOINED:         # appended to an accepted entry's list
        assert CELL in by_name[name]["workloads"] \
            and len(by_name[name]["workloads"]) >= 2
    for name in OWN:
        assert by_name[name]["workloads"] == [CELL] \
            and by_name[name]["layer"] == by_name["moe_share.served"]["layer"]
    assert set(cell.config["metric_args"]) <= JOINED
    # the 16 held, not the 128; the scopes and kernels bear the accepted
    # names, so nothing but the counts file differs
    assert cell.metric_file("expert_load_max_over_mean.served")["args"][
        "times_config"] == "num_experts"
    assert cell.metric_file("moe_share.served")["args"]["scopes"] == \
        ["/moe_router/", "/moe_routed/", "/moe_shared/"]
    assert cell.metric_file("attn_proj_share.served_ca")["args"] == \
        {"scopes": ["/attn_proj/"]}
    assert cell.metric_file("shared_ffn_share.served_ca")["args"] == \
        {"scopes": ["/moe_shared/"]}
    kernels = {}
    for m in cell.per_layer:
        cell.reader(m["name"])              # every reader is found by name
        if m["name"] in JOINED | OWN:
            assert m["moves"] == "served_tokens_per_s"
        if m["name"].split(".")[0].endswith("_roofline"):
            spec = cell.metric_file(m["name"])
            assert spec["args"]["counts"] == \
                "benchmark/kernel_counts_command_a.py"
            assert spec["args"]["count"] in kernel_counts_command_a.COUNTS
            assert m["unit"] == "%"
            kernels[spec["args"]["count"]] = spec["args"]["kernel"]
    assert kernels == {
        "moe_prefill": "^moe_grouped_swiglu", "moe_step": "^moe_grouped_swiglu",
        "window_prefill_attn": "^gqa16_window_flash_fwd",
        "full_prefill_attn": "^gqa16_group_flash_fwd",
        "ring_decode_attn": "^gqa16_ring_decode_attn",
        "full_decode_attn": "^gqa16_paged_decode_attn"}
    # full_attn_share.served_st is NOT joined: an accepted control
    # (test_benchmark_manifest.py) needs its cell to keep one share of its own
    assert CELL not in by_name["full_attn_share.served_st"]["workloads"]
    # the accepted cells read their own counts as before
    st = harness.Cell(REPO, MANIFEST, "st21b_mixed_sat")
    assert st.metric_file("window_prefill_attn_roofline.served_st")["args"][
        "counts"] == "benchmark/kernel_counts_smallthinker.py"


def test_a_checkout_without_the_model_is_refused_before_a_device(driver,
                                                                 monkeypatch):
    cell = harness.Cell(REPO, MANIFEST, CELL)
    driver.validate(cell, 45.0)
    monkeypatch.setitem(sys.modules, "paddle_tpu.decode.command_a", None)
    import paddle_tpu.decode as plane
    monkeypatch.delattr(plane, "command_a")
    with pytest.raises(harness.ConfigurationError, match="cannot run"):
        driver.validate(cell, 45.0)
    monkeypatch.undo()
    bad = harness.Cell(REPO, MANIFEST, CELL)
    bad.mix = dict(bad.mix, prompt_ids={"dist": "zipf", "s": 1.0})
    with pytest.raises(harness.ConfigurationError, match="uniformly"):
        driver.validate(bad, 45.0)


def test_the_counts_are_the_hand_count_at_the_published_widths():
    cfg = harness.Cell(REPO, MANIFEST, CELL).config
    c = kernel_counts_command_a.COUNTS
    pair = 128 * 4.0 * 128              # 65,536 operations a pair
    # three window layers: m (m + 1) / 2 + (n - m) W pairs, the observer's
    assert c["window_prefill_attn"](cfg, {"prefill_window_pairs": 1e6}) == \
        (pair * 1e6 * 3, 0.0)
    # one full layer: n (n + 1) / 2
    assert c["full_prefill_attn"](
        cfg, {"prefill_tokens_sq": 100.0 ** 2, "prefill_real_tokens": 100.0}
    ) == (pair * 5050, 0.0)
    # a cached row: 8 K/V heads x 128 x (k and v) x 2 B = 4,096 B
    assert c["ring_decode_attn"](cfg, {"step_ring_rows_live": 1000.0}) == \
        (pair * 3000, 3000 * 4096.0)
    assert c["full_decode_attn"](cfg, {"step_context_tokens": 1000.0}) == \
        (pair * 1000, 1000 * 4096.0)
    expert = 3 * 4096 * 4096            # 50,331,648 numbers
    assert c["moe_prefill"](cfg, {"prefill_routed_assignments": 32}) == \
        (2.0 * expert * 32, 0.0)
    ops, byts = c["moe_step"](cfg, {"step_experts_touched": 55,
                                    "step_routed_assignments": 128})
    assert ops == 2.0 * expert * 128
    assert byts == 55 * expert * 2 + 128 * 4096 * 2 * 2
    assert set(c) == {"moe_prefill", "moe_step", "window_prefill_attn",
                      "full_prefill_attn", "ring_decode_attn",
                      "full_decode_attn"}


@pytest.mark.parametrize("tokens,rows", [
    (65, [0] + list(range(2, 65, 2))),      # 33 a request, 528 of sixteen
    (20, [0] + list(range(2, 20, 2))), (3, [0, 2]), (2, [0, 1]), (1, [0])])
def test_the_judged_rows_of_a_replay(driver, tokens, rows):
    assert driver.judged_steps(tokens) == rows


def test_every_control_names_the_limit_that_guards_it(driver):
    guards = command_a_controls.GUARDS
    assert set(guards.values()) <= set(driver.LIMITS)
    assert set(guards) == {
        "fp8_kv", "bf16_router_scores", "bf16_norm_stats", "rotate_half",
        "shared_sum", "no_renorm", "ring_off_by_a_row",
        "another_streams_token"}
    assert set(command_a_controls.OTHER_MODELS) == \
        set(driver.reference.FAULTS)
    assert set(command_a_controls.REPORTED) == \
        set(command_a_controls.OTHER_MODELS) - set(guards)
    # every limit but the median's twin and the routing's share guards
    # something
    assert set(driver.LIMITS) - set(guards.values()) == \
        {"logit_err_decode_p90", "route_differs_share"}
    assert (driver.SAMPLE, driver.PAST_WINDOW, driver.REPLAY_TOKENS) == \
        (16, 4, 65)
    assert driver.reference_lengths({"prompt_tokens": {"max": 8192}},
                                    {"sliding_window": 4096}) == [4160, 8256]


def test_the_draw_follows_the_rules_the_configuration_states(driver):
    cfg = harness.Cell(REPO, MANIFEST, CELL).config
    assert driver.draw_rule(cfg, "ln", (1, 3, 4096)) == "norm"
    assert driver.draw_rule(cfg, "emb", (32768, 4096)) == ((4096, 1.0),)
    assert driver.draw_rule(cfg, "wqkv", (1, 4096, 18432)) == \
        ((16384, 4096 ** -0.5 * 2.0), (2048, 4096 ** -0.5))
    assert driver.draw_rule(cfg, "wo", (1, 16384, 4096)) == \
        ((4096, 16384 ** -0.5 * 2.0),)
    assert driver.draw_rule(cfg, "router", (1, 4096, 128)) == \
        ((128, 4096 ** -0.5),)
    assert driver.draw_rule(cfg, "e_down", (1, 16, 4096, 4096)) == \
        ((4096, 4096 ** -0.5 * 4.0),)
    # the four shared experts side by side: each at ITS fan-in
    assert driver.draw_rule(cfg, "s_down", (1, 16384, 4096)) == \
        ((4096, 4096 ** -0.5 * 1.5),)
    assert driver.draw_rule(cfg, "s_gate", (1, 4096, 16384)) == \
        ((16384, 4096 ** -0.5),)
    model = driver.model_config(cfg)
    assert (model.periods, model.period, model.window_layers,
            model.first_expert, model.router_experts, model.num_experts,
            model.shared_width) == (1, 4, 3, 0, 128, 16, 16384)


def test_the_pick_puts_prompts_past_the_window_first(driver):
    class R:
        def __init__(self, n, out):
            self.prompt, self.tokens = np.zeros((n,), np.int32), [0] * out
    done = [R(100 + i, 70) for i in range(30)] + [R(5000 + i, 70)
                                                  for i in range(6)] \
        + [R(6000, 10)] + [R(100 + i, 70) for i in range(30)]   # sent twice
    got = driver.pick(done, 7, 4096)
    assert len(got) == 16 and len({r.prompt.size for r in got}) == 16
    assert sum(r.prompt.size > 4096 for r in got[:4]) == 4
    assert all(len(r.tokens) >= 65 for r in got)


def test_the_sound_program_is_correct_and_every_control_is_not(driver,
                                                               served):
    params, engine, asks = served
    # every sample padded to ONE length: a reference compiles once a model
    out = command_a_controls.run_controls(driver, CFG, MIX, params, engine,
                                          asks, lengths=[128])
    verdicts = {k: v[0] for k, v in out.items()}
    assert verdicts.pop("sound"), out["sound"][1]
    for name in command_a_controls.REPORTED:
        verdicts.pop(name)
    assert not any(verdicts.values()), verdicts
    assert set(verdicts) == set(command_a_controls.GUARDS)
    for name, guard in command_a_controls.GUARDS.items():
        assert command_a_controls.over(out[name][1][guard],
                                       driver.LIMITS[guard]), (name, guard)
    sound = out["sound"][1]
    # float32 on both sides: the program IS the reference
    assert sound["logit_err_prefill_max"] < 1e-4 \
        and sound["logit_err_decode_p90"] < 1e-4 \
        and sound["ring_err_max"] < 1e-4 \
        and sound["route_differs_share"] == 0.0 \
        and sound["route_weight_err_max"] < 1e-6 \
        and sound["norm_unit_err_max"] < 5e-5
    assert sound["prompts"] == [5, 64, 100] \
        and sound["steps_replayed"] == 19 and sound["finite"]
    # a ring off by a row reads near 1: every row is another position's
    assert out["ring_off_by_a_row"][1]["ring_err_max"] > 0.5
