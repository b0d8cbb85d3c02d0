"""The ``nemotron_h_serve`` driver at a toy size on the CPU: the new cell's
entries and the manifest with it — the cell IN each joined entry's list, its
``times_config``, kernels and counts resolved through the configuration's
``metric_args``; the replay through the engine's own executables (pool,
paired recurrent rows and tails), the plain reference's full forward, the
readings, and the controls of ``benchmark/nemotron_h_controls.py`` through the
same functions; the counting functions against hand-worked numbers at the
published widths."""
import importlib.util
import json
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, nemotron_h_controls  # noqa: E402
from benchmark import kernel_counts_nemotron_h  # noqa: E402

CFG = {
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 13,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*E",
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 64, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "use_conv_bias": True, "mamba_hidden_act": "silu",
    "mamba_proj_bias": False, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 48, "n_routed_experts": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "n_group": 1, "topk_group": 1,
    "mlp_hidden_act": "relu2", "mlp_bias": False, "attention_bias": False,
    "use_bias": False, "layer_norm_epsilon": 1e-5,
    "tie_word_embeddings": False, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 0.0001, "rope_theta": 10000,
    "router_experts": 8, "first_expert": 0, "max_seq_len": 192,
    "dtype": "float32", "kv_dtype": "float32"}
MIX = {"engine": {"max_slots": 3, "max_queue": 8, "block_tokens": 16,
                  "num_blocks": 40, "prefill_buckets": [64, 128]},
       "prompt_tokens": {"max": 100}}
CELL = "n3n_agent_sat"
CONFIG = "nemotron-3-nano-30b-a3b-ep2-pp4s0"
SOURCE = "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/" \
    "blob/main/config.json"
COUNTS = "benchmark/kernel_counts_nemotron_h.py"
MANIFEST = harness.load_manifest(REPO)
# the accepted entries the cell joins by its name in their lists (PR 58's way
# in; its kernels' names are words of the configuration's `metric_args`); it
# brings none of its own.  (The shared expert's share is NOT among them:
# tests/benchmark/test_benchmark_command_a.py holds `shared_ffn_share.
# served_ca` to ONE workload, and check_manifest refuses a second entry of the
# same reader, scope and `moves` as a copy; `moe_share.served` counts the
# shared expert with the routed ones.)
JOINED = {n + ".served" for n in (
    "moe_share", "moe_prefill_roofline", "moe_step_roofline",
    "expert_load_max_over_mean", "experts_touched_per_step",
    "prefill_pad_share", "live_context_tokens")} | {
    n + ".served_fh1" for n in (
        "ssd_share", "ssd_scan_prefill_roofline", "ssd_state_step_roofline",
        "gqa_attn_share")} | {
    "full_prefill_attn_roofline.served_st",
    "full_decode_attn_roofline.served_st", "held_choice_share.served_kl"}
OWN = set()


@pytest.fixture(scope="module")
def driver():
    path = os.path.join(REPO, "benchmark", "drivers", "nemotron_h_serve.py")
    spec = importlib.util.spec_from_file_location(
        "nemotron_h_serve_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(driver):
    from paddle_tpu.decode import SamplingParams
    # four held of eight at top-3, sixteen state numbers a head and prompts of
    # tens of positions: the reference's own readings lie wider than at the
    # published sizes, and float32 on both sides reads a thousandth of what
    # bf16 activations do
    driver.REFERENCE_RANGES = dict(
        driver.REFERENCE_RANGES, ref_held_choice_share=(0.1, 0.9),
        ref_top1_weight=(0.3, 0.8), ref_attn_logit_std=(0.2, 4.0),
        ref_bias_turns_share=(0.0, 0.7), ref_mamba_rms=(0.02, 2.0),
        ref_experts_rms=(0.02, 2.0), ref_attn_rms=(0.02, 2.0),
        ref_step_size_max=(1e-2, 5.0), ref_decay_weakest=(0.9, 1.0))
    driver.LIMITS = dict(driver.LIMITS, logit_err_prefill_max=1e-3,
                         logit_err_decode_p90=1e-3, state_err_p50=1e-3,
                         tail_err_max=1e-3, pool_err_max=1e-3,
                         expert_out_err_p90=1e-3)
    params = driver.make_params(CFG)
    engine, server, _ = driver.build_server(CFG, MIX, params)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, size=n).astype(np.int32)
               for n in (5, 64, 100)]
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
    assert len(MANIFEST["workloads"]) <= 24 and len(MANIFEST["configs"]) <= 24
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    assert len(MANIFEST["per_layer"]) <= 116


def test_every_line_of_the_manifest_keeps_the_contracts_form():
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
        (CONFIG, "agent_sat", 1, "nemotron_h_serve")
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    cut = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["reduced"] == cut == cell.config["reduced"]
    assert entry["source"] == cell.config["source"] == SOURCE
    # every key of the source under its name, none changed but the three
    src = cell.config["source_keys"]
    assert {k: src[k] for k in cut} == cell.config["published"] == \
        {"num_hidden_layers": 52, "n_routed_experts": 128,
         "vocab_size": 131072}
    for k, v in src.items():
        if k not in cut:
            assert cell.config[k] == v, k
    assert {k: cell.config[k] for k in cut} == \
        {"num_hidden_layers": 13, "n_routed_experts": 64, "vocab_size": 65536}
    assert (cell.config["router_experts"], cell.config["first_expert"]) == \
        (128, 0)
    assert (cell.config["hidden_size"], cell.config["moe_intermediate_size"],
            cell.config["moe_shared_expert_intermediate_size"],
            cell.config["mamba_num_heads"], cell.config["mamba_head_dim"],
            cell.config["n_groups"], cell.config["ssm_state_size"],
            cell.config["num_attention_heads"],
            cell.config["num_key_value_heads"], cell.config["head_dim"],
            cell.config["num_experts_per_tok"],
            cell.config["routed_scaling_factor"],
            cell.config["max_seq_len"]) == \
        (2688, 1856, 3712, 64, 64, 8, 128, 32, 2, 128, 6, 2.5, 14336)
    assert cell.config["hybrid_override_pattern"][:13] == "MEMEM*EMEMEM*" \
        and len(cell.config["hybrid_override_pattern"]) == 52
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows
              if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"]
    assert src == row["config"] and entry["source"] == row["source_url"]
    for key in ("deployment", "assumed", "what"):
        assert cell.config[key]
    said = " ".join(cell.config["assumed"])
    # every reading the config leaves open, the one not taken beside it
    for word in ("ONE mixer", "not taken: a mixer AND a feed-forward",
                 "expand 2", "not taken: d_inner = expand", "NO clamp",
                 "not taken: a clamp", "gate BEFORE the norm",
                 "not taken: the norm before the gate", "UNGATED",
                 "not taken: a gated unit", "NO rotation",
                 "not taken: rotate-half", "read by nothing", "14,336",
                 "float32 accumulation"):
        assert word in said, word
    assert "four pipeline stages" in cell.config["deployment"] \
        and "TWO chips a stage" in cell.config["deployment"] \
        and "stage 0, chip 0" in cell.config["deployment"] \
        and "3,926,018,560" in cell.config["deployment"] \
        and "HALF" in cell.config["deployment"]
    mix = cell.mix
    assert (mix["loop"], mix["callers"], mix["lead_s"], mix["trace_seconds"],
            mix["trace_after_s"]) == ("closed", 192, 8.0, 5.0, 10.0)
    assert mix["engine"] == {
        "max_slots": 128, "max_queue": 256, "block_tokens": 16,
        "num_blocks": 49153,
        "prefill_buckets": [512, 1024, 2048, 3072, 4096, 6144, 8192, 12288]}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 2048,
                                    "sigma": 0.8, "min": 256, "max": 12288}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.7, "min": 128, "max": 2048}
    assert "prompt_ids" not in mix and mix["sampling"] == "greedy"
    assert mix["who"] and len(mix["why"]) > 500 and "HALF" in mix["why"]


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
        assert CELL in by_name[name]["workloads"] \
            and by_name[name]["layer"] == by_name["moe_share.served"]["layer"]
    assert set(cell.config["metric_args"]) <= JOINED
    # the 64 held, not the 128; the scopes bear the accepted names, so only
    # kernels and counts differ
    assert cell.metric_file("expert_load_max_over_mean.served")["args"][
        "times_config"] == "n_routed_experts"
    assert cell.metric_file("moe_share.served")["args"]["scopes"] == \
        ["/moe_router/", "/moe_routed/", "/moe_shared/"]
    assert cell.metric_file("ssd_share.served_fh1")["args"]["scopes"] == \
        ["/ssd_in/", "/ssd_conv/", "/ssd_scan/", "/ssd_out/"]
    assert CELL not in by_name["shared_ffn_share.served_ca"]["workloads"]
    kernels = {}
    for m in cell.per_layer:
        cell.reader(m["name"])              # every reader is found by name
        if m["name"] in JOINED | OWN:
            assert m["moves"] == "served_tokens_per_s"
        if m["name"].split(".")[0].endswith("_roofline"):
            spec = cell.metric_file(m["name"])
            assert spec["args"]["counts"] == COUNTS
            assert spec["args"]["count"] in kernel_counts_nemotron_h.COUNTS
            assert m["unit"] == "%"
            kernels[spec["args"]["count"]] = spec["args"]["kernel"]
    assert kernels == {
        "moe_prefill": "^moe_grouped_relu2", "moe_step": "^moe_grouped_relu2",
        "ssd_scan_prefill": "^ssd64_chunk_scan",
        "ssd_state_step": "^ssd64_state_step",
        "full_prefill_attn": "^gqa16_group_flash_fwd",
        "full_decode_attn": "^gqa16_paged_decode_attn"}
    # the accepted cells read their own kernels and counts as before
    fh1 = harness.Cell(REPO, MANIFEST, "fh1_chat_sat")
    assert fh1.metric_file("ssd_state_step_roofline.served_fh1")["args"][
        "kernel"] == "^ssd_state_step"
    assert fh1.metric_file("ssd_scan_prefill_roofline.served_fh1")["args"][
        "counts"] == "benchmark/kernel_counts_falconh1.py"
    cap = harness.Cell(REPO, MANIFEST, "cap_rag_sat")
    assert cap.metric_file("moe_step_roofline.served")["args"]["kernel"] == \
        "^moe_grouped_swiglu"


def test_a_checkout_without_the_model_is_refused_before_a_device(driver,
                                                                 monkeypatch):
    cell = harness.Cell(REPO, MANIFEST, CELL)
    driver.validate(cell, 45.0)
    monkeypatch.setitem(sys.modules, "paddle_tpu.decode.nemotron_h", None)
    import paddle_tpu.decode as plane
    monkeypatch.delattr(plane, "nemotron_h")
    with pytest.raises(harness.ConfigurationError, match="cannot run"):
        driver.validate(cell, 45.0)
    monkeypatch.undo()
    bad = harness.Cell(REPO, MANIFEST, CELL)
    bad.mix = dict(bad.mix, prompt_ids={"dist": "zipf", "s": 1.0})
    with pytest.raises(harness.ConfigurationError, match="uniformly"):
        driver.validate(bad, 45.0)


def test_the_counts_are_the_hand_count_at_the_published_widths():
    cfg = harness.Cell(REPO, MANIFEST, CELL).config
    c = kernel_counts_nemotron_h.COUNTS
    pair = 32 * 4.0 * 128               # 16,384 operations a pair
    # two attention layers: n (n + 1) / 2
    assert c["full_prefill_attn"](
        cfg, {"prefill_tokens_sq": 100.0 ** 2, "prefill_real_tokens": 100.0}
    ) == (pair * 5050 * 2, 0.0)
    # a cached row: 2 K/V heads x 128 x (k and v) x 2 B = 1,024 B
    assert c["full_decode_attn"](cfg, {"step_context_tokens": 1000.0}) == \
        (pair * 2000, 2000 * 1024.0)
    # TWO matrices of 2,688 x 1,856 an expert, not three, no padded column
    expert = 2 * 2688 * 1856
    assert expert == 9_977_856
    assert c["moe_prefill"](cfg, {"prefill_routed_assignments": 32}) == \
        (2.0 * expert * 32, 0.0)
    ops, byts = c["moe_step"](cfg, {"step_experts_touched": 55,
                                    "step_routed_assignments": 128})
    assert ops == 2.0 * expert * 128
    assert byts == 55 * expert * 2 + 128 * 2688 * 2 * 2
    # a live stream's row of a Mamba layer: 64 x 128 x 64 float32 = 2 MB,
    # read and written; five operations a state number a position
    state = 64 * 128 * 64
    assert state * 4 == 2_097_152
    moved = 100 * 6 * 2 * state * 4.0
    assert c["ssd_state_step"](cfg, {"step_state_bytes": moved}) == \
        (5.0 * moved / 8.0, moved)
    ops, byts = c["ssd_scan_prefill"](cfg, {"prefill_real_tokens": 1000.0})
    assert ops == 5.0 * state * 1000 * 6
    assert byts == 6000 * ((2 * 4096 + 2 * 8 * 128) * 2 + 4 * 64)
    assert set(c) == {"moe_prefill", "moe_step", "ssd_scan_prefill",
                      "ssd_state_step", "full_prefill_attn",
                      "full_decode_attn"}


@pytest.mark.parametrize("tokens,rows", [
    (65, [0, 1, 2, 3] + list(range(4, 65, 2))),
    (20, [0, 1, 2, 3] + list(range(4, 20, 2))), (3, [0, 1, 2]), (2, [0, 1]),
    (1, [0])])
def test_the_judged_rows_of_a_replay(driver, tokens, rows):
    assert driver.judged_steps(tokens) == rows


def test_every_control_names_the_limit_that_guards_it(driver):
    guards = nemotron_h_controls.GUARDS
    assert set(guards.values()) <= set(driver.LIMITS)
    assert set(guards) == {
        "bf16_recurrent_rows", "bf16_step", "bf16_router_scores", "fp8_pool",
        "fp8_tails", "silu_unit", "rotate_attention", "norm_before_gate",
        "one_norm_group", "no_skip", "another_streams_token"}
    assert set(nemotron_h_controls.OTHER_MODELS) == \
        set(driver.reference.FAULTS)
    # every limit but the medians' twins, the routing's share and the
    # weights' equations (exact on both sides) guards something
    assert set(driver.LIMITS) - set(guards.values()) == \
        {"logit_err_decode_p50", "logit_err_decode_p90", "logit_err_join_max",
         "route_differs_share", "route_weight_err_max"}
    assert (driver.SAMPLE, driver.REPLAY_TOKENS) == (16, 65)
    assert driver.reference_lengths({"prompt_tokens": {"max": 12288}}, {}) \
        == [3136, 6208, 12352]


def test_the_draw_follows_the_rules_the_configuration_states(driver):
    cfg = harness.Cell(REPO, MANIFEST, CELL).config
    assert driver.draw_rule("ln", (6, 2688)) == "norm"
    assert driver.draw_rule("ssm_norm", (6, 4096)) == "norm"
    assert driver.draw_rule("emb", (65536, 2688)) == ((2688, 1.0),)
    assert driver.draw_rule("wqkv", (2, 2688, 4608), 4096) == \
        ((4096, 2688 ** -0.5 * 2.0), (512, 2688 ** -0.5))
    assert driver.draw_rule("wo", (2, 4096, 2688)) == \
        ((2688, 4096 ** -0.5 * 2.0),)
    assert driver.draw_rule("router", (5, 2688, 128)) == \
        ((128, 2688 ** -0.5),)
    # an expert's first matrix lies [out, in]: its fan-in is the last axis
    assert driver.draw_rule("e_up", (5, 64, 1856, 2688)) == \
        ((2688, 2688 ** -0.5),)
    assert driver.draw_rule("e_down", (5, 64, 1856, 2688)) == \
        ((2688, 1856 ** -0.5),)
    assert driver.draw_rule("s_up", (5, 2688, 3712)) == \
        ((3712, 2688 ** -0.5),)
    for leaf in ("a_log", "dt_bias", "d_skip"):
        assert driver.draw_rule(leaf, (6, 64)) == leaf
    model = driver.model_config(cfg)
    assert (model.hybrid_override_pattern, model.first_expert,
            model.router_experts, model.n_routed_experts,
            model.state_shape) == \
        ("MEMEM*EMEMEM*", 0, 128, 64, (32, 128, 128))
    params = driver.make_params(CFG)
    dt = np.log1p(np.exp(np.asarray(params["m.dt_bias"], np.float64)))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 0.1 * 1.01
    a = np.exp(np.asarray(params["m.a_log"], np.float64))
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert (np.asarray(params["m.d_skip"]) == 1.0).all()


def test_the_rows_are_unpaired_as_the_program_pairs_them(driver):
    from paddle_tpu.kernels import ssd
    S = np.random.default_rng(0).standard_normal((2, 8, 16, 64)).astype(
        np.float32)
    kept = np.asarray(ssd.pack_state(S))
    assert kept.shape == (2, 4, 16, 128)
    np.testing.assert_array_equal(driver.unpack_rows(kept, 64), S)
    wide = np.zeros((2, 8, 16, 128), np.float32)
    assert driver.unpack_rows(wide, 128) is wide


def test_the_sound_program_is_correct_and_every_control_is_not(driver,
                                                               served):
    params, engine, asks = served
    # every sample padded to ONE length: a reference compiles once a model
    out = nemotron_h_controls.run_controls(driver, CFG, MIX, params, engine,
                                           asks, lengths=[128])
    verdicts = {k: v[0] for k, v in out.items()}
    assert verdicts.pop("sound"), out["sound"][1]
    assert not any(verdicts.values()), verdicts
    assert set(verdicts) == set(nemotron_h_controls.GUARDS)
    for name, guard in nemotron_h_controls.GUARDS.items():
        assert nemotron_h_controls.over(out[name][1][guard],
                                        driver.LIMITS[guard]), (name, guard)
    assert out["bf16_recurrent_rows"][1]["state_bf16_share"] == 1.0
    sound = out["sound"][1]
    # float32 on both sides: the program IS the reference
    assert sound["logit_err_prefill_max"] < 1e-4 \
        and sound["logit_err_decode_p90"] < 1e-4 \
        and sound["state_err_max"] < 1e-4 \
        and sound["state_bf16_share"] < 1e-3 \
        and sound["tail_err_max"] < 1e-5 \
        and sound["pool_err_max"] < 1e-5 \
        and sound["expert_out_err_max"] < 1e-4 \
        and sound["route_differs_share"] == 0.0 \
        and sound["route_weight_err_max"] < 1e-6
    assert sound["prompts"] == [5, 64, 100] \
        and sound["steps_replayed"] == 19 and sound["finite"]
