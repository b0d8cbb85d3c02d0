"""The ``smallthinker_serve`` driver's reference comparison at a toy size on
the CPU: the replay through the engine's own executables (pool and rings,
one stream past the window), the plain reference's full forward, the
readings, the weights the driver draws, and the controls of
``benchmark/smallthinker_controls.py`` through the same functions; the new
cell's entries; the counting functions against hand-worked numbers; the new
reader on a recorded trace."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, kernel_counts_smallthinker  # noqa: E402
from benchmark import smallthinker_controls  # noqa: E402

CFG = {
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 3,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 1500000,
    "rope_layout": [0, 1, 1, 1] * 13, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 32, "max_seq_len": 160, "dtype": "float32",
    "kv_dtype": "float32"}
MIX = {"engine": {"max_slots": 3, "max_queue": 8, "block_tokens": 16,
                  "num_blocks": 40, "prefill_buckets": [16, 32, 64, 128]},
       "prompt_tokens": {"max": 100}}
CELL = "st21b_mixed_sat"
MANIFEST = harness.load_manifest(REPO)


@pytest.fixture(scope="module")
def driver():
    path = os.path.join(REPO, "benchmark", "drivers", "smallthinker_serve.py")
    spec = importlib.util.spec_from_file_location(
        "smallthinker_serve_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(driver):
    from paddle_tpu.decode import SamplingParams
    params = driver.make_params(CFG)
    engine, server, _ = driver.build_server(CFG, MIX, params)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, size=n).astype(np.int32)
               for n in (11, 30, 100)]      # inside, across and past a window
    handles = [engine.submit(p, SamplingParams(temperature=0.0,
                                               max_new_tokens=m))
               for p, m in zip(prompts, (20, 24, 21))]
    asks = [(p, h.result(timeout=900.0)["tokens"])
            for p, h in zip(prompts, handles)]
    yield params, engine, asks
    server.stop()


def test_the_new_cell_is_the_one_the_issue_names():
    cell = harness.Cell(REPO, MANIFEST, CELL)
    assert (cell.config_name, cell.mix_name, cell.chips, cell.kind) == \
        ("smallthinker-21b-pp7s0", "mixed_sat", 1, "smallthinker_serve")
    (entry,) = [c for c in MANIFEST["configs"]
                if c["name"] == cell.config_name]
    assert entry["reduced"] == ["num_hidden_layers"] == cell.config["reduced"]
    assert entry["source"] == cell.config["source"] == \
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/" \
        "blob/main/config.json"
    # every key of the source under its name, none changed but the depth
    src = cell.config["source_keys"]
    assert src["num_hidden_layers"] == 52
    for k, v in src.items():
        if k != "num_hidden_layers":
            assert cell.config[k] == v, k
    assert cell.config["num_hidden_layers"] == 8
    assert (cell.config["vocab_size"], cell.config["hidden_size"],
            cell.config["moe_ffn_hidden_size"],
            cell.config["moe_num_primary_experts"],
            cell.config["moe_num_active_primary_experts"],
            cell.config["sliding_window_size"], cell.config["rope_theta"],
            cell.config["max_seq_len"],
            cell.config["max_position_embeddings"]) == \
        (151936, 2560, 768, 64, 6, 4096, 1500000, 16384, 16384)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct"]
    assert src == row["config"] and entry["source"] == row["source_url"]
    for key in ("deployment", "assumed", "what"):
        assert cell.config[key]
    said = " ".join(cell.config["assumed"])
    for word in ("normed INPUT", "rotate-half", "no QK norm",
                 "no secondary experts", "weight seed 41", "kv_dtype"):
        assert word in said, word
    mix = cell.mix
    assert (mix["loop"], mix["callers"], mix["lead_s"], mix["cycle_seed"],
            mix["drain_timeout_s"], mix["trace_seconds"]) == \
        ("closed", 96, 8.0, 41, 120.0, 5.0)
    eng = mix["engine"]
    assert (eng["max_slots"], eng["max_queue"], eng["block_tokens"]) == \
        (64, 128, 16)
    assert eng["prefill_buckets"][-1] == 12288
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 2048,
                                    "sigma": 1.0, "min": 256, "max": 12288}
    assert mix["output_tokens"]["dist"] == "lognormal" \
        and mix["output_tokens"]["sigma"] == 0.6
    assert (mix["output_tokens"]["median"], mix["output_tokens"]["min"],
            mix["output_tokens"]["max"]) in ((128, 32, 512), (64, 32, 256))
    assert mix["sampling"] == "greedy"
    assert mix["request_block"] * mix["cycle_blocks"] == 192
    assert {m["name"] for m in cell.end_to_end} == \
        {"served_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    # the decode-plane and device family under its one name, joined through
    # served_tokens_per_s (step_emit_ms and idle_no_work_share with it, which
    # this cell's own PR had no room for), and the model's own (a later PR
    # may add to either)
    family = {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m
              and m["moves"] in {e["name"] for e in cell.end_to_end}}
    assert "decode_step_ms.served" in family
    assert names >= family | {
        "prefill_pad_share.served", "live_context_tokens.served",
        "moe_share.served", "window_attn_share.served_st",
        "full_attn_share.served_st", "expert_load_max_over_mean.served",
        "ring_live_share.served_st", "moe_prefill_roofline.served",
        "moe_step_roofline.served",
        "window_prefill_attn_roofline.served_st",
        "full_prefill_attn_roofline.served_st",
        "ring_decode_attn_roofline.served_st",
        "full_decode_attn_roofline.served_st"}
    for m in cell.per_layer:
        cell.reader(m["name"])              # every reader is found by name
        if "workloads" in m:                # listed: this cell is IN the list
            assert CELL in m["workloads"]
    # the four it shares with the other models of experts since PR 58, by the
    # words of its own in the configuration's `metric_args`: the ReGLU kernel,
    # its counts file, its two scopes, its key of the experts held
    assert set(cell.config["metric_args"]) == {n + ".served" for n in (
        "moe_share", "moe_prefill_roofline", "moe_step_roofline",
        "expert_load_max_over_mean")}
    for n in ("moe_prefill_roofline.served", "moe_step_roofline.served"):
        args = cell.metric_file(n)["args"]
        assert (args["kernel"], args["counts"]) == (
            "^moe_grouped_reglu", "benchmark/kernel_counts_smallthinker.py")
    assert cell.metric_file("moe_share.served")["args"] == {
        "scopes": ["/route/", "/experts/"]}
    # four chips where the measured thing exists only across chips: at most
    # a quarter of the cells, and one always may (the contract)
    assert 1 <= sum(w["chips"] == 4 for w in MANIFEST["workloads"]) \
        <= max(1, len(MANIFEST["workloads"]) // 4)
    assert len(MANIFEST["per_layer"]) <= 128
    from paddle_tpu.decode.smallthinker import param_shapes
    shapes = param_shapes(cell.driver().model_config(cell.config))
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) == \
        8 * 398_627_840 + 777_914_880 == 3_966_937_600   # 7.93 GB of bf16
    # the pool: never under 1.5 x the mean reservation of 64 streams
    pool = (eng["num_blocks"] - 1) * eng["block_tokens"]
    from benchmark import loadgen
    mean = np.mean(loadgen._lengths(mix["prompt_tokens"], 4096)) \
        + np.mean(loadgen._lengths(mix["output_tokens"], 4096))
    assert pool >= 1.5 * 64 * mean


def test_no_accepted_metric_starts_to_match_a_new_kernel():
    import re
    new = ("gqa_window_flash_fwd", "gqa_group_flash_fwd",
           "gqa_ring_decode_attn", "moe_grouped_reglu")
    # as every OTHER cell reads its entries (a shared entry's kernel is a
    # configuration's word since PR 58: this one's names `moe_grouped_reglu`)
    for w in MANIFEST["workloads"]:
        if w["name"] == CELL:
            continue
        cell = harness.Cell(REPO, MANIFEST, w["name"])
        for m in cell.per_layer:
            args = cell.metric_file(m["name"])["args"]
            if "kernel" in args:
                assert not any(re.search(args["kernel"], k) for k in new), \
                    (w["name"], m["name"])


def test_the_replay_agrees_with_the_reference(driver, served):
    params, engine, asks = served
    samples = driver.replay(engine, asks)
    n = min(len(t) for _, t in asks)
    at = driver.judged_steps(n)
    assert at[0] == 0 and at[-1] == n - 1
    for s, (prompt, tokens) in zip(samples, asks):
        # every stream is replayed for the shortest one's tokens,
        # teacher-forced with the engine's own: float32, the same argmax
        assert len(s.produced) == n and list(s.at) == at
        assert s.logits.argmax(-1).tolist() == [tokens[j] for j in at]
        assert s.ids.shape == (8, prompt.size + n - 1, 3)
        assert s.router_u.shape == (len(at), 8, 64)
        assert s.router_r.shape == (len(at), 8, 8)
    refs = driver.run_reference(params, CFG, samples,
                                driver.reference_lengths(MIX, CFG))
    got = driver.readings(samples, refs,
                          driver.router_errors(params, CFG, samples))
    assert got["logit_err_decode_p90"] < 1e-4
    assert got["logit_err_prefill_max"] < 1e-4
    assert got["route_differs_share"] == 0.0
    assert got["router_score_err_max"] < 1e-5
    assert got["token_gap_p99"] == 0.0
    assert got["positions"] == 3 * len(at) and got["steps_replayed"] == n - 1
    assert got["routed_pairs"] == 8 * sum(p.size + n - 1 for p, _ in asks)
    assert got["prompts"] == [11, 30, 100]
    checks = harness.Checks()
    driver.judge(checks, got)
    assert len(checks.items) == len(driver.LIMITS) \
        + len(driver.REFERENCE_RANGES)
    # the toy's streams are short and its experts few, so the reference's own
    # ranges, which are set for the cell's sizes, may be left; the limits hold
    failed = [name for name, ok, _ in checks.items if not ok]
    assert all("the reference's own" in name for name in failed), failed
    assert engine.cache.allocator.referenced_blocks == 0
    assert driver.judged_steps(65) == [0] + list(range(32, 65, 8))
    assert driver.reference_lengths(
        {"prompt_tokens": {"max": 12288}}, {"sliding_window_size": 4096}) == \
        [4096 + 64, 12288 + 64]


def test_the_sample_holds_streams_past_the_window_where_there_are_some(
        driver):
    class R:
        def __init__(self, p, n):
            self.prompt, self.tokens = np.zeros((p,), np.int32), [0] * n

    done = [R(p, n) for p, n in ((100, 70), (5000, 70), (300, 20), (9000, 80),
                                 (700, 90), (4500, 66), (50, 65), (2000, 64))]
    got = driver.pick(done, 7, 4096)
    assert len(got) == driver.SAMPLE == 4
    assert sum(r.prompt.size > 4096 for r in got) == driver.PAST_WINDOW == 2
    assert all(len(r.tokens) >= driver.REPLAY_TOKENS for r in got)
    # too few long outputs: the longest ones
    short = driver.pick(done[:3], 7, 4096)
    assert sorted(len(r.tokens) for r in short) == [20, 70, 70]


def test_the_driver_draws_the_weights_the_configuration_assumes(driver):
    """By the driver's own rules, from names and shapes: nothing of the
    program's initialiser is on either side of the comparison."""
    import inspect
    for fn in (driver.make_params, driver.draw, driver.draw_rule):
        assert "init_tensor" not in inspect.getsource(fn)
    params = {k: np.asarray(v, np.float64)
              for k, v in driver.make_params(CFG).items()}
    D, g = 64, driver.GAINS
    assert params["emb"].std() == pytest.approx(1.0, rel=0.05)
    assert params["head"].std() == pytest.approx(D ** -0.5, rel=0.05)
    for prefix in ("pf.", "pw."):
        w = params[prefix + "wqkv"]
        assert w[..., :64].std() == pytest.approx(g["q"] * D ** -0.5,
                                                  rel=0.05)
        assert w[..., 64:].std() == pytest.approx(D ** -0.5, rel=0.05)
        assert params[prefix + "wo"].std() == pytest.approx(
            g["wo"] * 64 ** -0.5, rel=0.05)
        assert params[prefix + "router"].std() == pytest.approx(
            g["router"] * D ** -0.5, rel=0.1)
        assert params[prefix + "e_gate"].std() == pytest.approx(D ** -0.5,
                                                                rel=0.05)
        assert params[prefix + "e_up"].std() == pytest.approx(D ** -0.5,
                                                              rel=0.05)
        assert params[prefix + "e_down"].std() == pytest.approx(
            g["e_down"] * 32 ** -0.5, rel=0.05)
        assert params[prefix + "ln1"].std() == pytest.approx(0.1, rel=0.2)
    assert abs(params["final_norm"].mean() - 1.0) < 0.05
    again = driver.make_params(CFG)
    assert all(np.array_equal(np.asarray(again[k], np.float64), params[k])
               for k in params)


def test_a_replay_that_would_compile_is_an_error(driver, served):
    params, engine, asks = served
    long = np.arange(40, dtype=np.int32) % 96       # no rung of 48 was run
    engine.prefill_ladder = type(engine.prefill_ladder)([16, 32, 48])
    try:
        with pytest.raises(RuntimeError, match="executable cache"):
            driver.replay(engine, [(long, [1, 2])])
    finally:
        engine.prefill_ladder = type(engine.prefill_ladder)([16, 32, 64, 128])
        engine.cache.allocator.release(
            [b for b in list(engine.cache.allocator._ref)])


def test_a_judged_fault_fails_its_limit(driver):
    got = dict({name: 0.0 for name in driver.LIMITS}, positions=1,
               routed_pairs=1, prompts=[1], steps_replayed=1, finite=True,
               **{name: [low, high]
                  for name, (low, high) in driver.REFERENCE_RANGES.items()})
    for name, limit in driver.LIMITS.items():
        checks = harness.Checks()
        driver.judge(checks, dict(got, **{name: limit * 1.01}))
        assert [ok for _, ok, _ in checks.items].count(False) == 1
    for name, (low, high) in driver.REFERENCE_RANGES.items():
        for bad in ([low * 0.99, high], [low, high * 1.01]):
            checks = harness.Checks()
            driver.judge(checks, dict(got, **{name: bad}))
            assert [ok for _, ok, _ in checks.items].count(False) == 1
    checks = harness.Checks()
    driver.judge(checks, dict(got, finite=False))
    assert not checks.ok


@pytest.fixture(scope="module")
def controls(driver, served):
    params, engine, asks = served
    return smallthinker_controls.run_controls(
        driver, CFG, MIX, params, engine, asks,
        driver.reference_lengths(MIX, CFG))


def test_every_control_names_a_limit_the_driver_has(driver):
    assert set(smallthinker_controls.GUARDS.values()) <= set(driver.LIMITS)
    assert set(smallthinker_controls.GUARDS) == {
        "fp8_kv", "bf16_router_scores", "another_streams_token",
        *smallthinker_controls.other_models(CFG)}


@pytest.mark.parametrize("control", sorted(smallthinker_controls.GUARDS))
def test_a_control_fails_the_limit_that_guards_it(driver, controls, control):
    """A precision below the stated one, or another mechanism, reads over
    the limit that guards it, and the sound program far under: at this toy
    float32 size the sound readings are rounding noise, so the guard is held
    to the committed limit AND to a hundred times the toy's own sound
    reading."""
    limit = smallthinker_controls.GUARDS[control]
    ok, got = controls[control]
    sound = controls["sound"][1]
    assert not ok
    assert got[limit] > driver.LIMITS[limit]
    assert got[limit] > 100 * sound[limit]
    assert sound[limit] < driver.LIMITS[limit] / 3


def test_the_controls_leave_nothing_behind(driver, served, controls):
    """A join overwrites everything a control left in a slot's rings and in
    the blocks it is given: the sound program, replayed after all of them,
    reads as before."""
    params, engine, asks = served
    samples = driver.replay(engine, asks)
    again = driver.readings(
        samples, driver.run_reference(params, CFG, samples,
                                      driver.reference_lengths(MIX, CFG)),
        driver.router_errors(params, CFG, samples))
    assert again["logit_err_decode_p90"] < 1e-4
    assert again["route_differs_share"] == 0.0


def test_the_counting_functions_against_hand_worked_numbers():
    cfg = {"num_hidden_layers": 8, "num_attention_heads": 28,
           "num_key_value_heads": 4, "head_dim": 128, "hidden_size": 2560,
           "moe_ffn_hidden_size": 768,
           "sliding_window_layout": [0, 1, 1, 1] * 13,
           "dtype": "bfloat16", "kv_dtype": "bfloat16"}
    expert = 3 * 2560 * 768                     # 5,898,240 numbers
    ops, moved = kernel_counts_smallthinker.moe_prefill(
        cfg, {"prefill_routed_assignments": 48.0})
    assert (ops, moved) == (2 * expert * 48, 0.0)
    ops, moved = kernel_counts_smallthinker.moe_step(
        cfg, {"step_experts_touched": 500.0,
              "step_routed_assignments": 3072.0})
    assert ops == 2 * expert * 3072
    assert moved == 500 * expert * 2 + 3072 * 2560 * 4
    pair = 28 * 4 * 128
    ops, moved = kernel_counts_smallthinker.window_prefill_attn(
        cfg, {"prefill_window_pairs": 1000.0})
    assert (ops, moved) == (pair * 1000 * 6, 0.0)
    ops, moved = kernel_counts_smallthinker.full_prefill_attn(
        cfg, {"prefill_tokens_sq": 100.0, "prefill_real_tokens": 10.0})
    assert (ops, moved) == (pair * 55 * 2, 0.0)
    ops, moved = kernel_counts_smallthinker.ring_decode_attn(
        cfg, {"step_ring_rows_live": 10.0})
    assert (ops, moved) == (pair * 60, 60 * 2048)
    ops, moved = kernel_counts_smallthinker.full_decode_attn(
        cfg, {"step_context_tokens": 10.0})
    assert (ops, moved) == (pair * 20, 20 * 2048)
    assert set(kernel_counts_smallthinker.COUNTS) == {
        "moe_prefill", "moe_step", "window_prefill_attn",
        "full_prefill_attn", "ring_decode_attn", "full_decode_attn"}


def test_the_counter_readers_read_the_window_s_deltas():
    cell = harness.Cell(REPO, MANIFEST, CELL)
    ctx = {"config": cell.config, "window_counters": {
        "step_ring_rows_live": 300.0, "step_ring_rows_held": 1200.0,
        "step_expert_load_max_sum": 90.0, "step_routed_assignments": 1920.0,
        "prefill_pad_tokens": 10.0, "prefill_real_tokens": 90.0,
        "step_context_tokens": 640.0, "step_streams": 64.0}}
    assert cell.reader("ring_live_share.served_st")(ctx) == 25.0
    assert cell.reader("expert_load_max_over_mean.served")(ctx) == 3.0
    assert cell.reader("prefill_pad_share.served")(ctx) == 10.0
    assert cell.reader("live_context_tokens.served")(ctx) == 10.0
    # the parent has no such counter: nothing, and no error
    assert cell.reader("ring_live_share.served_st")(
        {"config": cell.config, "window_counters": {}}) is None
