"""The ``lfm2_serve`` driver at a toy size on the CPU: the new cell's entries
and the manifest with it; the Zipf redraw of the prompts' ids; the replay
through the engine's own executables (pool and tails), the plain reference's
full forward, the readings, and the controls of ``benchmark/lfm2_controls.py``
through the same functions; the counting functions against hand-worked
numbers at the published widths."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, kernel_counts_lfm2, lfm2_controls, loadgen  # noqa: E402

CFG = {
    "vocab_size": 96, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 9,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "norm_eps": 1e-5, "conv_L_cache": 3,
    "conv_bias": False,
    "layer_types": ["conv"] + ["full_attention", "conv", "conv", "conv"] * 3,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "max_seq_len": 160, "dtype": "float32", "kv_dtype": "float32"}
MIX = {"engine": {"max_slots": 3, "max_queue": 8, "block_tokens": 16,
                  "num_blocks": 40, "prefill_buckets": [16, 32, 64, 128]},
       "prompt_tokens": {"max": 100}}
CELL = "lfm2_topic_sat"
MANIFEST = harness.load_manifest(REPO)


@pytest.fixture(scope="module")
def driver():
    path = os.path.join(REPO, "benchmark", "drivers", "lfm2_serve.py")
    spec = importlib.util.spec_from_file_location("lfm2_serve_under_test",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(driver):
    from paddle_tpu.decode import SamplingParams
    # eight experts lie further apart than sixty-four: the bias that turns a
    # fifth of the choices among 64 turns none among 8; and a prompt of one
    # token reads its share over twenty positions
    driver.BIAS_STD = 0.05
    driver.REFERENCE_RANGES = dict(driver.REFERENCE_RANGES,
                                   ref_bias_turns_share=(0.02, 0.9))
    params = driver.make_params(CFG)
    engine, server, _ = driver.build_server(CFG, MIX, params)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, size=n).astype(np.int32)
               for n in (1, 30, 100)]   # shorter than a tail; inside rungs
    handles = [engine.submit(p, SamplingParams(temperature=0.0,
                                               max_new_tokens=m))
               for p, m in zip(prompts, (20, 24, 21))]
    asks = [(p, h.result(timeout=900.0)["tokens"])
            for p, h in zip(prompts, handles)]
    yield params, engine, asks
    server.stop()


def test_the_manifest_is_sound_and_names_the_cell_and_its_configuration_once():
    # (how much room the lists have is the contract's to say, and
    # test_benchmark_manifest.py says it; where in them these two lie, no one's)
    assert harness.check_manifest(REPO, MANIFEST) == []
    assert [w["name"] for w in MANIFEST["workloads"]].count(CELL) == 1
    assert [c["name"] for c in MANIFEST["configs"]].count(
        "lfm2-24b-a2b-pp5s0") == 1


def test_the_new_cell_is_the_one_the_issue_names():
    cell = harness.Cell(REPO, MANIFEST, CELL)
    assert (cell.config_name, cell.mix_name, cell.chips, cell.kind) == \
        ("lfm2-24b-a2b-pp5s0", "topic_sat", 1, "lfm2_serve")
    (entry,) = [c for c in MANIFEST["configs"]
                if c["name"] == cell.config_name]
    assert entry["reduced"] == ["num_hidden_layers"] == cell.config["reduced"]
    assert entry["source"] == cell.config["source"] == \
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    # every key of the source under its name, none changed but the depth
    src = cell.config["source_keys"]
    assert src["num_hidden_layers"] == 40
    for k, v in src.items():
        if k != "num_hidden_layers":
            assert cell.config[k] == v, k
    assert cell.config["num_hidden_layers"] == 10
    assert len(cell.config["layer_types"]) == 40
    assert (cell.config["vocab_size"], cell.config["hidden_size"],
            cell.config["intermediate_size"],
            cell.config["moe_intermediate_size"],
            cell.config["num_experts"], cell.config["num_experts_per_tok"],
            cell.config["num_attention_heads"],
            cell.config["num_key_value_heads"], cell.config["conv_L_cache"],
            cell.config["rope_parameters"]["rope_theta"],
            cell.config["max_seq_len"]) == \
        (65536, 2048, 11776, 1536, 64, 4, 32, 8, 3, 1000000, 16384)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "LFM2-24B-A2B"]
    assert src == row["config"] and entry["source"] == row["source_url"]
    for key in ("deployment", "assumed", "what"):
        assert cell.config[key]
    said = " ".join(cell.config["assumed"])
    for word in ("tie_word_embeddings", "1e-6", "[B | C | x]", "BEFORE",
                 "cache's dtype", "weights seed 44", "16,384"):
        assert word in said, word
    assert "five pipeline stages" in cell.config["deployment"] \
        and "5,267,090,176" in cell.config["deployment"]
    mix = cell.mix
    assert (mix["loop"], mix["callers"], mix["lead_s"], mix["cycle_seed"],
            mix["drain_timeout_s"], mix["trace_seconds"],
            mix["trace_after_s"]) == ("closed", 96, 8.0, 44, 120.0, 5.0, 10.0)
    assert mix["engine"] == {
        "max_slots": 64, "max_queue": 128, "block_tokens": 16,
        "num_blocks": 24577,
        "prefill_buckets": [1024, 2048, 3072, 4096, 6144, 8192, 12288]}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 3072,
                                    "sigma": 0.7, "min": 512, "max": 12288}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 96,
                                    "sigma": 0.6, "min": 16, "max": 384}
    assert mix["prompt_ids"] == {"dist": "zipf", "s": 1.0}
    assert mix["sampling"] == "greedy"
    assert mix["request_block"] * mix["cycle_blocks"] == 192
    assert {m["name"] for m in cell.end_to_end} == \
        {"served_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    family = {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m
              and m["moves"] in {e["name"] for e in cell.end_to_end}}
    # the fourteen it was accepted with, by name: a later PR may add to them
    assert family >= {
        "decode_step_ms.served", "prefill_ms.served", "step_host_ms.served",
        "step_emit_ms.served", "tokens_per_decode_step.served",
        "device_idle_share.served", "idle_engine_host_share.served",
        "idle_no_work_share.served", "top_device_op_share.served",
        "hbm_peak_gb.served", "hbm_temp_gb.served", "warm_cache_hits",
        "window_compiles", "program_build_s"}
    own = {n + ".served_lfm2" for n in (
        "conv_mixer_share", "attn_share", "gqa64_prefill_attn_roofline",
        "gqa64_decode_attn_roofline", "prefill_expert_load_max_over_mean",
        "moe_tile_pad_share")}
    # what it shares with the other models of experts since PR 58: one entry
    # a metric, this cell in its list, the words that differ (the counts
    # file, the scope, the configuration's key of the experts held, a step's
    # layers counted together) in this configuration's `metric_args`
    shared = {n + ".served" for n in (
        "moe_share", "moe_prefill_roofline", "moe_step_roofline",
        "expert_load_max_over_mean", "experts_touched_per_step",
        "prefill_pad_share", "live_context_tokens")}
    assert names == family | own | shared
    assert set(cell.config["metric_args"]) <= shared
    for m in cell.per_layer:
        cell.reader(m["name"])              # every reader is found by name
        if m["name"] in own | shared:       # this cell is IN its list
            assert CELL in m["workloads"]
        if m["name"].split(".")[0].endswith("_roofline"):
            spec = cell.metric_file(m["name"])
            assert spec["args"]["counts"] == "benchmark/kernel_counts_lfm2.py"
            assert spec["args"]["count"] in kernel_counts_lfm2.COUNTS
            assert m["unit"] == "%"
    assert cell.metric_file("moe_share.served")["args"] == {
        "scopes": ["/moe/"]}
    assert cell.metric_file("expert_load_max_over_mean.served")["args"][
        "times_config"] == "num_experts"
    assert cell.metric_file("experts_touched_per_step.served")["args"][
        "den"] == ["steps"]


def test_a_checkout_without_the_model_is_refused_before_a_device(driver,
                                                                 monkeypatch):
    cell = harness.Cell(REPO, MANIFEST, CELL)
    driver.validate(cell, 45.0)
    monkeypatch.setitem(sys.modules, "paddle_tpu.decode.lfm2", None)
    import paddle_tpu.decode as plane
    monkeypatch.delattr(plane, "lfm2")
    with pytest.raises(harness.ConfigurationError, match="cannot run"):
        driver.validate(cell, 45.0)
    monkeypatch.undo()
    bad = harness.Cell(REPO, MANIFEST, CELL)
    bad.mix = dict(bad.mix, prompt_ids={"dist": "zipf", "s": 0})
    with pytest.raises(harness.ConfigurationError, match="prompt_ids"):
        driver.validate(bad, 45.0)


def test_the_redraw_keeps_every_length_and_draws_zipf_ranks_by_seed(driver):
    mix = harness.Cell(REPO, MANIFEST, CELL).mix
    vocab = 65536
    perm = np.random.default_rng(mix["cycle_seed"]).permutation(vocab)
    rank_of = np.empty(vocab, np.int64)
    rank_of[perm] = np.arange(vocab)
    hists = []
    for seed in (7, 3_000_000_019):     # one past 32 signed bits
        plain = loadgen.build_requests(mix, vocab, seed, 45.0)[:200]
        lengths = [r.prompt.size for r in plain]
        before = np.concatenate([r.prompt for r in plain])
        again = driver.redraw_ids(plain, mix, vocab, seed)
        assert [r.prompt.size for r in again] == lengths
        ids = np.concatenate([r.prompt for r in again])
        assert ids.dtype == np.int32 and 0 <= ids.min() \
            and ids.max() < vocab and (ids != before).mean() > 0.9
        ranks = rank_of[ids]            # 0 is the first rank
        share = np.bincount(ranks, minlength=vocab) / ranks.size
        harmonic = (1.0 / np.arange(1, vocab + 1)).sum()
        # P(rank r) = r^-1 / H: 8.6% of the tokens are the first rank's,
        # half as many the second's, and the first hundred hold 44%
        assert abs(share[0] - 1 / harmonic) < 0.004
        assert abs(share[1] - 0.5 / harmonic) < 0.003
        assert abs(share[:100].sum() - 0.444) < 0.01
        hists.append(share)
        # the same seed draws the same ids
        same = driver.redraw_ids(loadgen.build_requests(
            mix, vocab, seed, 45.0)[:200], mix, vocab, seed)
        np.testing.assert_array_equal(
            np.concatenate([r.prompt for r in same]), ids)
    # two seeds: the same subject (the permutation is the mix's), other ids
    assert np.abs(hists[0][:50] - hists[1][:50]).max() < 0.004
    uniform = dict(mix, prompt_ids={"dist": "uniform"})
    plain = loadgen.build_requests(mix, vocab, 7, 45.0)[:4]
    kept = [r.prompt.copy() for r in plain]
    for r, k in zip(driver.redraw_ids(plain, uniform, vocab, 7), kept):
        np.testing.assert_array_equal(r.prompt, k)


def test_the_counts_are_the_hand_count_at_the_published_widths():
    cfg = harness.Cell(REPO, MANIFEST, CELL).config
    c = kernel_counts_lfm2.COUNTS
    expert = 3 * 2048 * 1536            # 9,437,184 numbers
    assert c["moe_prefill"](cfg, {"prefill_routed_assignments": 32}) == \
        (2.0 * expert * 32, 0.0)
    ops, byts = c["moe_step"](cfg, {"step_experts_touched": 40,
                                    "step_routed_assignments": 256})
    assert ops == 2.0 * expert * 256
    assert byts == 40 * expert * 2 + 256 * 2048 * 2 * 2
    # 32 heads x (2 x 64 for the score + 2 x 64 for the value) a pair, two
    # attention layers; n (n + 1) / 2 pairs a prompt
    ops, byts = c["gqa64_prefill_attn"](
        cfg, {"prefill_tokens_sq": 100.0 ** 2 + 10.0 ** 2,
              "prefill_real_tokens": 110.0})
    assert (ops, byts) == (32 * 4 * 64 * (5050 + 55) * 2, 0.0)
    # a cached token's row: keys and values of 8 heads of 64 in bf16 =
    # 2,048 B a layer, 4,096 B over the two
    ops, byts = c["gqa64_decode_attn"](cfg, {"step_context_tokens": 1000.0})
    assert byts == 1000 * 4096 and ops == 32 * 4 * 64 * 1000 * 2
    assert set(c) == {"moe_prefill", "moe_step", "gqa64_prefill_attn",
                      "gqa64_decode_attn"}


def test_every_control_names_the_limit_that_guards_it(driver):
    assert set(lfm2_controls.GUARDS.values()) <= set(driver.LIMITS)
    assert set(lfm2_controls.GUARDS) == {
        "fp8_state", "bf16_router_scores", "bias_in_weights", "no_qk_norm",
        "tail_of_zeros", "tail_from_rung_end", "another_streams_token"}
    # every limit but the percentile's twin guards something
    assert set(driver.LIMITS) - set(lfm2_controls.GUARDS.values()) == \
        {"logit_err_decode_p90", "route_differs_share"}
    assert driver.judged_steps(65) == [0, 1, 2, 32, 40, 48, 56, 64]
    assert driver.judged_steps(2) == [0, 1] and driver.judged_steps(1) == [0]
    assert driver.reference_lengths({"prompt_tokens": {"max": 12288}}, {}) \
        == [4160, 12352]


def test_the_draw_follows_the_rules_the_configuration_states(driver):
    cfg = harness.Cell(REPO, MANIFEST, CELL).config
    assert driver.draw_rule(cfg, "q_norm", (2, 64)) == ("norm", 2.5)
    assert driver.draw_rule(cfg, "ln1", (2, 2048)) == ("norm", 1.0)
    assert driver.draw_rule(cfg, "emb", (65536, 2048)) == ((2048, 1.0),)
    assert driver.draw_rule(cfg, "router_bias", (2, 64)) == \
        ((64, driver.BIAS_STD),)
    assert driver.draw_rule(cfg, "conv_out", (2, 2048, 2048)) == \
        ((2048, 2048 ** -0.5 * 0.7),)
    assert driver.draw_rule(cfg, "conv_w", (2, 3, 2048)) == \
        ((2048, 3 ** -0.5),)
    assert driver.draw_rule(cfg, "e_down", (2, 64, 1536, 2048)) == \
        ((2048, 1536 ** -0.5 * 2.5),)
    model = driver.model_config(cfg)
    assert (model.head_dim, model.rope_theta, model.periods) == (64, 1e6, 2)


def test_the_sound_program_is_correct_and_every_control_is_not(driver,
                                                               served):
    params, engine, asks = served
    out = lfm2_controls.run_controls(driver, CFG, MIX, params, engine, asks)
    verdicts = {k: v[0] for k, v in out.items()}
    assert verdicts.pop("sound"), out["sound"][1]
    assert not any(verdicts.values()), verdicts
    assert set(verdicts) == set(lfm2_controls.GUARDS)
    for name, guard in lfm2_controls.GUARDS.items():
        assert out[name][1][guard] > driver.LIMITS[guard], (name, guard)
    sound = out["sound"][1]
    # float32 on both sides: the program IS the reference
    assert sound["logit_err_prefill_max"] < 1e-4 \
        and sound["logit_err_join_max"] < 1e-4 \
        and sound["route_differs_share"] == 0.0 \
        and sound["route_weight_err_max"] < 1e-6
    assert sound["prompts"] == [1, 30, 100] \
        and sound["judged_steps"][:3] == [0, 1, 2]
    # a wrong tail moves the join and nothing before it
    for name in ("tail_of_zeros", "tail_from_rung_end"):
        got = out[name][1]
        assert got["logit_err_prefill_max"] == sound["logit_err_prefill_max"]
        assert got["logit_err_join_max"] > 0.1
    # the bias in the weights: the logits barely move, the weights do
    assert out["bias_in_weights"][1]["route_weight_err_max"] > 1e-3
