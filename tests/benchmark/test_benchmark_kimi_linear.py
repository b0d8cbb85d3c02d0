"""The ``kimi_linear_serve`` driver at a toy size on the CPU: the new cell's
entries and the manifest with it; the replay through the engine's own
executables (latent pool, recurrent rows and tails), the plain reference's
full forward, the readings, and the controls of
``benchmark/kimi_linear_controls.py`` through the same functions; the
counting functions against hand-worked numbers at the published widths."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, kernel_counts_kimi_linear  # noqa: E402
from benchmark import kimi_linear_controls  # noqa: E402

CFG = {
    "vocab_size": 96, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 9,
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 32, "q_lora_rank": None,
    "mla_use_nope": True, "first_k_dense_replace": 1, "num_experts": 4,
    "num_experts_per_token": 4, "num_shared_experts": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "routed_scaling_factor": 2.446, "num_expert_group": 1, "topk_group": 1,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False, "rope_theta": 10000,
    "linear_attn_config": {
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11],
        "full_attn_layers": [4, 8, 12], "head_dim": 16, "num_heads": 2,
        "short_conv_kernel_size": 4},
    "router_experts": 16, "first_expert": 8,
    "max_seq_len": 192, "dtype": "float32", "kv_dtype": "float32"}
MIX = {"engine": {"max_slots": 3, "max_queue": 8, "block_tokens": 16,
                  "num_blocks": 40, "prefill_buckets": [64, 128]},
       "prompt_tokens": {"max": 100}}
CELL = "kl48b_longdoc_sat"
CONFIG = "kimi-linear-48b-a3b-ep4-pp3s0"
MANIFEST = harness.load_manifest(REPO)


@pytest.fixture(scope="module")
def driver():
    path = os.path.join(REPO, "benchmark", "drivers", "kimi_linear_serve.py")
    spec = importlib.util.spec_from_file_location(
        "kimi_linear_serve_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(driver):
    from paddle_tpu.decode import SamplingParams
    # sixteen experts lie further apart than 256, a prompt of one token reads
    # its shares over twenty positions, and float32 on both sides reads a
    # bf16 state at a thousandth where bf16 activations read a hundredth
    driver.BIAS_STD = 0.05
    driver.REFERENCE_RANGES = dict(
        driver.REFERENCE_RANGES, ref_bias_turns_share=(0.02, 0.95),
        ref_held_choice_share=(0.05, 0.6), ref_decay_strongest=(-200.0, -0.3),
        ref_attn_logit_std=(0.3, 4.0), ref_top1_weight=(0.3, 1.2),
        ref_decay_weakest=(-0.01, -1e-7))
    driver.LIMITS = dict(driver.LIMITS, state_err_p50=5e-4)
    params = driver.make_params(CFG)
    engine, server, _ = driver.build_server(CFG, MIX, params)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, size=n).astype(np.int32)
               for n in (1, 64, 100)]   # shorter than a tail; a chunk's edge
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


def test_the_new_cell_is_the_one_the_issue_names():
    cell = harness.Cell(REPO, MANIFEST, CELL)
    assert (cell.config_name, cell.mix_name, cell.chips, cell.kind) == \
        (CONFIG, "longdoc_sat", 1, "kimi_linear_serve")
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    cut = ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["reduced"] == cut == cell.config["reduced"]
    assert entry["source"] == cell.config["source"] == \
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/" \
        "blob/main/config.json"
    # every key of the source under its name, none changed but the three
    src = cell.config["source_keys"]
    assert {k: src[k] for k in cut} == cell.config["published"] == \
        {"num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840}
    for k, v in src.items():
        if k not in cut:
            assert cell.config[k] == v, k
    assert {k: cell.config[k] for k in cut} == \
        {"num_hidden_layers": 9, "num_experts": 64, "vocab_size": 40960}
    assert (cell.config["router_experts"], cell.config["first_expert"]) == \
        (256, 0)
    assert (cell.config["hidden_size"], cell.config["intermediate_size"],
            cell.config["moe_intermediate_size"],
            cell.config["num_experts_per_token"],
            cell.config["num_shared_experts"], cell.config["kv_lora_rank"],
            cell.config["qk_nope_head_dim"], cell.config["qk_rope_head_dim"],
            cell.config["v_head_dim"], cell.config["num_attention_heads"],
            cell.config["routed_scaling_factor"],
            cell.config["max_seq_len"]) == \
        (2304, 9216, 1024, 8, 1, 512, 128, 64, 128, 32, 2.446, 17408)
    lac = cell.config["linear_attn_config"]
    assert (lac["num_heads"], lac["head_dim"],
            lac["short_conv_kernel_size"]) == (32, 128, 4)
    assert len(lac["kda_layers"]) == 20 and lac["full_attn_layers"] == \
        [4, 8, 12, 16, 20, 24, 27]              # kept whole: the cut reads 9
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
    assert src == row["config"] and entry["source"] == row["source_url"]
    for key in ("deployment", "assumed", "what"):
        assert cell.config[key]
    said = " ".join(cell.config["assumed"])
    for word in ("recalled without a network", "head_dim = 128", "WITHOUT a "
                 "bias", "A uniform in [1, 16]", "SIGMOID", "shared by the "
                 "heads", "1e-6", "float32 recurrent state", "NO rotation",
                 "17,408", "value-major"):
        assert word in said, word
    assert "three pipeline stages" in cell.config["deployment"] \
        and "FOUR chips a stage" in cell.config["deployment"] \
        and "stage 0, chip 0" in cell.config["deployment"] \
        and "4,272,540,512" in cell.config["deployment"] \
        and "QUARTER" in cell.config["deployment"]
    mix = cell.mix
    assert (mix["loop"], mix["callers"], mix["lead_s"], mix["trace_seconds"],
            mix["trace_after_s"]) == ("closed", 96, 8.0, 5.0, 10.0)
    assert mix["engine"] == {
        "max_slots": 64, "max_queue": 128, "block_tokens": 16,
        "num_blocks": 40961,
        "prefill_buckets": [1024, 2048, 4096, 6144, 8192, 12288, 16384]}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 6144,
                                    "sigma": 0.7, "min": 1024, "max": 16384}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.6, "min": 64, "max": 1024}
    assert "prompt_ids" not in mix and mix["sampling"] == "greedy"
    assert mix["request_block"] * mix["cycle_blocks"] == 192
    assert mix["who"] and len(mix["why"]) > 500
    assert {m["name"] for m in cell.end_to_end} == \
        {"served_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    family = {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m
              and m["moves"] in {e["name"] for e in cell.end_to_end}}
    own = {n + ".served_kl" for n in (
        "kda_share", "kda_chunk_prefill_roofline", "kda_state_step_roofline",
        "held_choice_share")}
    # what it shares with the other models of latent attention and of
    # experts since PR 58: one entry a metric, this cell in its list, the
    # counts file and the configuration's key of the experts HELD in this
    # configuration's `metric_args`; the scopes and counters bear
    # decode/mla.py's names, the defaults
    shared = {n + ".served" for n in (
        "mla_prefill_attn_roofline", "mla_decode_attn_roofline",
        "moe_prefill_roofline", "moe_step_roofline", "mla_share", "moe_share",
        "expert_load_max_over_mean", "experts_touched_per_step",
        "prefill_pad_share", "live_context_tokens")}
    assert names == family | own | shared
    assert set(cell.config["metric_args"]) <= shared
    assert cell.metric_file("expert_load_max_over_mean.served")["args"][
        "times_config"] == "num_experts"        # the 64 held, not the 256
    for m in cell.per_layer:
        cell.reader(m["name"])              # every reader is found by name
        if m["name"] in own | shared:
            assert CELL in m["workloads"] \
                and m["moves"] == "served_tokens_per_s"
        if m["name"].split(".")[0].endswith("_roofline"):
            spec = cell.metric_file(m["name"])
            assert spec["args"]["counts"] == \
                "benchmark/kernel_counts_kimi_linear.py"
            assert spec["args"]["count"] in kernel_counts_kimi_linear.COUNTS
            assert m["unit"] == "%"


def test_a_checkout_without_the_model_is_refused_before_a_device(driver,
                                                                 monkeypatch):
    cell = harness.Cell(REPO, MANIFEST, CELL)
    driver.validate(cell, 45.0)
    monkeypatch.setitem(sys.modules, "paddle_tpu.decode.kimi_linear", None)
    import paddle_tpu.decode as plane
    monkeypatch.delattr(plane, "kimi_linear")
    with pytest.raises(harness.ConfigurationError, match="cannot run"):
        driver.validate(cell, 45.0)
    monkeypatch.undo()
    bad = harness.Cell(REPO, MANIFEST, CELL)
    bad.mix = dict(bad.mix, prompt_ids={"dist": "zipf", "s": 1.0})
    with pytest.raises(harness.ConfigurationError, match="uniformly"):
        driver.validate(bad, 45.0)


def test_the_counts_are_the_hand_count_at_the_published_widths():
    cfg = harness.Cell(REPO, MANIFEST, CELL).config
    c = kernel_counts_kimi_linear.COUNTS
    # seven KDA layers of the nine; a position's recurrence is 7 operations
    # an element of 32 states of 128 x 128; its rows: q, k, v and o in bf16
    # and the decay in float32 a channel (4,096 of them), the step size a head
    ops, byts = c["kda_chunk_prefill"](cfg, {"prefill_real_tokens": 1000.0})
    assert ops == 7.0 * 32 * 128 * 128 * 1000 * 7
    assert byts == 1000 * 7 * (4096 * (4 * 2 + 4) + 4 * 32)
    # a stream's rows of a layer: 32 x 128 x 128 x 4 B, read and written
    moved = 60 * 7 * 2 * 2_097_152
    assert c["kda_state_step"](cfg, {"step_state_bytes": float(moved)}) == \
        (7.0 * moved / 8, float(moved))
    # 32 heads x (2 x 192 for the score + 2 x 128 for the value) a pair, two
    # latent layers; n (n + 1) / 2 pairs a prompt
    ops, byts = c["mla_prefill_attn"](
        cfg, {"prefill_tokens_sq": 100.0 ** 2 + 10.0 ** 2,
              "prefill_real_tokens": 110.0})
    assert (ops, byts) == (2.0 * 32 * 320 * (5050 + 55) * 2, 0.0)
    # a cached token's row: 576 numbers in bf16 a layer, two layers
    ops, byts = c["mla_decode_attn"](cfg, {"step_context_tokens": 1000.0})
    assert byts == 1000 * 2 * 576 * 2 \
        and ops == 2.0 * 32 * (576 + 512) * 1000 * 2
    expert = 3 * 2304 * 1024            # 7,077,888 numbers
    assert c["moe_prefill"](cfg, {"prefill_routed_assignments": 32}) == \
        (2.0 * expert * 32, 0.0)
    ops, byts = c["moe_step"](cfg, {"step_experts_touched": 440,
                                    "step_routed_assignments": 1024})
    assert ops == 2.0 * expert * 1024
    assert byts == 440 * expert * 2 + 1024 * 2304 * 2 * 2
    assert set(c) == {"kda_chunk_prefill", "kda_state_step",
                      "mla_prefill_attn", "mla_decode_attn", "moe_prefill",
                      "moe_step"}


@pytest.mark.parametrize("tokens,rows", [
    (65, [0, 1, 2, 3] + list(range(4, 65, 2))),     # 35 a request, 560 of 16
    (20, [0, 1, 2, 3] + list(range(4, 20, 2))),
    (5, [0, 1, 2, 3, 4]), (4, [0, 1, 2, 3]),        # ends at the join
    (2, [0, 1]), (1, [0])])                         # nothing decoded
def test_the_judged_rows_of_a_replay(driver, tokens, rows):
    """The prefill's token, the three steps that read its tail, then every
    second step; a replay shorter than the join judges what it has."""
    assert driver.judged_steps(tokens) == rows


@pytest.mark.parametrize("fault", ["tail_of_zeros", "tail_from_rung_end"])
def test_a_wrong_tail_moves_the_join_and_leaves_the_prefill(driver, served,
                                                            fault):
    """The replay's two hooks at the prefill -> decode join: the prefill's
    own logits are the sound replay's, the three steps that read the tail
    are not — but for the prompt that fills its rung, whose tail the rung's
    end IS."""
    _, engine, asks = served
    sound = driver.replay(engine, asks)
    how = {"after_prefill": kimi_linear_controls.zero_tails} \
        if fault == "tail_of_zeros" else {"tail_from_rung_end": True}
    other = driver.replay(engine, asks, **how)
    for (prompt, _), a, b in zip(asks, sound, other):
        np.testing.assert_array_equal(a.logits[0], b.logits[0])
        same = fault == "tail_from_rung_end" and prompt.size == 64
        moved = np.abs(a.logits[1:4] - b.logits[1:4]).max()
        assert (moved == 0) if same else (moved > 1e-3), (prompt.size, moved)


def test_every_control_names_the_limit_that_guards_it(driver):
    guards = kimi_linear_controls.GUARDS
    assert set(guards.values()) <= set(driver.LIMITS)
    assert set(guards) == {
        "bf16_recurrent_state", "fp8_pool", "fp8_tails", "tail_of_zeros",
        "tail_from_rung_end", "bf16_router_scores", "scalar_decay",
        "no_delta", "no_qk_norm", "rotate_keys", "renorm_held",
        "another_streams_token"}
    assert set(kimi_linear_controls.OTHER_MODELS) == \
        set(driver.reference.FAULTS)
    # every limit but the median's twin and the routing's share guards
    # something
    assert set(driver.LIMITS) - set(guards.values()) == \
        {"logit_err_decode_p90", "route_differs_share"}
    assert kimi_linear_controls.over(float("nan"), 0.08) \
        and kimi_linear_controls.over(0.09, 0.08) \
        and not kimi_linear_controls.over(0.08, 0.08)
    assert (driver.SAMPLE, driver.REPLAY_TOKENS) == (16, 65)
    assert driver.reference_lengths({"prompt_tokens": {"max": 16384}}, {}) \
        == [5525, 16448]


def test_the_draw_follows_the_rules_the_configuration_states(driver):
    cfg = harness.Cell(REPO, MANIFEST, CELL).config
    assert driver.draw_rule("kda", "o_norm", (2, 128)) == "norm"
    assert driver.draw_rule("kda", "a_log", (2, 32)) == "a_log"
    assert driver.draw_rule("kda", "dt_bias", (2, 4096)) == "dt_bias"
    assert driver.draw_rule(None, "emb", (40960, 2304)) == ((2304, 1.0),)
    assert driver.draw_rule("mla", "router_bias", (2, 256)) == \
        ((256, driver.BIAS_STD),)
    assert driver.draw_rule("kda", "conv_w", (2, 4, 12288)) == \
        ((12288, 4 ** -0.5),)
    assert driver.draw_rule("kda", "wo", (2, 4096, 2304)) == \
        ((2304, 4096 ** -0.5),)
    assert driver.draw_rule("mla", "wo", (2, 4096, 2304)) == \
        ((2304, 4096 ** -0.5 * 2.0),)
    assert driver.draw_rule("mla", "wq", (2, 2304, 6144)) == \
        ((6144, 2304 ** -0.5 * 2.5),)
    assert driver.draw_rule("kda", "e_down", (2, 64, 1024, 2304)) == \
        ((2304, 1024 ** -0.5 * 2.5),)
    model = driver.model_config(cfg)
    assert (model.periods, model.pattern, model.kda_layers, model.mla_layers,
            model.first_expert, model.router_experts) == \
        (2, ("kda", "kda", "mla", "kda"), 7, 2, 0, 256)
    import jax
    a = np.exp(np.asarray(driver.draw_decay(
        jax.random.PRNGKey(0), "a_log", (2, 32), "float32")))
    assert (a >= 1).all() and (a <= 16).all()
    dt = np.log1p(np.exp(np.asarray(driver.draw_decay(
        jax.random.PRNGKey(1), "dt_bias", (2, 4096), "float32"))))
    assert dt.min() > 0.99e-3 and dt.max() < 0.101


def test_the_sound_program_is_correct_and_every_control_is_not(driver,
                                                               served):
    params, engine, asks = served
    # every sample padded to ONE length: a reference compiles once a model
    out = kimi_linear_controls.run_controls(driver, CFG, MIX, params, engine,
                                            asks, lengths=[128])
    verdicts = {k: v[0] for k, v in out.items()}
    assert verdicts.pop("sound"), out["sound"][1]
    assert not any(verdicts.values()), verdicts
    assert set(verdicts) == set(kimi_linear_controls.GUARDS)
    for name, guard in kimi_linear_controls.GUARDS.items():
        assert kimi_linear_controls.over(out[name][1][guard],
                                         driver.LIMITS[guard]), (name, guard)
    sound = out["sound"][1]
    # float32 on both sides: the program IS the reference
    assert sound["logit_err_prefill_max"] < 1e-4 \
        and sound["logit_err_join_max"] < 1e-4 \
        and sound["state_err_max"] < 1e-4 \
        and sound["route_differs_share"] == 0.0 \
        and sound["route_weight_err_max"] < 1e-6
    assert sound["prompts"] == [1, 64, 100] \
        and sound["steps_replayed"] == 19 and sound["finite"]
