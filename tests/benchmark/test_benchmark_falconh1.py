"""The ``falconh1_serve`` driver's reference comparison at a toy size on the
CPU: the replay through the engine's own executables, the plain reference's
full forward, the readings, the weights the driver draws, and the two
lower-precision controls and five planted faults of
``benchmark/falconh1_controls.py`` through the same functions; the new cell's
entries; the counting functions against hand-worked numbers."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import falconh1_controls, harness, kernel_counts_falconh1  # noqa: E402

CFG = {
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "rms_norm_eps": 1e-5, "rope_theta": 1e11,
    "mamba_d_ssm": 64, "mamba_n_heads": 4, "mamba_d_head": 16,
    "mamba_n_groups": 2, "mamba_d_state": 32, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "embedding_multiplier": 5.656854249492381,
    "lm_head_multiplier": 0.0078125, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375,
    "key_multiplier": 0.011048543456039804, "ssm_in_multiplier": 0.25,
    "ssm_out_multiplier": 0.08838834764831845,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "max_seq_len": 96, "dtype": "float32", "kv_dtype": "float32"}
MIX = {"engine": {"max_slots": 3, "max_queue": 8, "block_tokens": 16,
                  "num_blocks": 24, "prefill_buckets": [16, 32]}}
CELL = "fh1_chat_sat"
MANIFEST = harness.load_manifest(REPO)


@pytest.fixture(scope="module")
def driver():
    path = os.path.join(REPO, "benchmark", "drivers", "falconh1_serve.py")
    spec = importlib.util.spec_from_file_location("falconh1_serve_under_test",
                                                  path)
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
               for n in (11, 20, 30)]
    handles = [engine.submit(p, SamplingParams(temperature=0.0,
                                               max_new_tokens=m))
               for p, m in zip(prompts, (40, 44, 41))]
    asks = [(p, h.result(timeout=600.0)["tokens"])
            for p, h in zip(prompts, handles)]
    yield params, engine, asks
    server.stop()


def test_the_new_cell_is_the_one_the_issue_names():
    cell = harness.Cell(REPO, MANIFEST, CELL)
    assert (cell.config_name, cell.mix_name, cell.chips, cell.kind) == \
        ("falcon-h1-34b-pp12s0", "chat_sat", 1, "falconh1_serve")
    (entry,) = [c for c in MANIFEST["configs"]
                if c["name"] == cell.config_name]
    assert entry["reduced"] == ["num_hidden_layers"] == cell.config["reduced"]
    assert entry["source"] == cell.config["source"] == \
        "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/" \
        "config.json"
    # every key of the source under its name, none changed but the depth
    src = cell.config["source_keys"]
    assert src["num_hidden_layers"] == 72
    for k, v in src.items():
        if k != "num_hidden_layers":
            assert cell.config[k] == v, k
    assert cell.config["num_hidden_layers"] == 6
    assert (cell.config["vocab_size"], cell.config["hidden_size"],
            cell.config["intermediate_size"], cell.config["mamba_d_ssm"],
            cell.config["mamba_d_state"], cell.config["max_seq_len"]) == \
        (261120, 5120, 21504, 4096, 256, 4096)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Falcon-H1-34B-Instruct"]
    assert src == row["config"] and entry["source"] == row["source_url"]
    mix = cell.mix
    assert (mix["loop"], mix["callers"], mix["lead_s"], mix["cycle_seed"],
            mix["drain_timeout_s"], mix["trace_seconds"]) == \
        ("closed", 96, 8.0, 36, 120.0, 5.0)
    assert mix["engine"] == {
        "max_slots": 64, "max_queue": 128, "block_tokens": 16,
        "num_blocks": 8193, "prefill_buckets": [512, 1024, 1536, 2048, 3072]}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.6, "min": 256, "max": 3072}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.6, "min": 64, "max": 1024}
    assert mix["sampling"] == "greedy"
    assert mix["request_block"] * mix["cycle_blocks"] == 192
    assert {m["name"] for m in cell.end_to_end} == \
        {"served_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    # the decode-plane and device family under its one name, joined through
    # served_tokens_per_s, and the model's own (a later PR may add to either)
    family = {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m
              and m["moves"] in {e["name"] for e in cell.end_to_end}}
    assert "decode_step_ms.served" in family
    assert names >= family | {
        "prefill_pad_share.served", "live_context_tokens.served",
        "ssd_share.served_fh1", "gqa_attn_share.served_fh1",
        "ssd_scan_prefill_roofline.served_fh1",
        "ssd_state_step_roofline.served_fh1",
        "gqa_decode_attn_roofline.served_fh1",
        "gqa_prefill_attn_roofline.served_fh1"}
    for m in cell.per_layer:
        cell.reader(m["name"])              # every reader is found by name
        if m["name"].endswith("fh1"):       # the model's own: this cell is
            assert CELL in m["workloads"]   # IN its list (another may join)
    # four chips where the measured thing exists only across chips: at most
    # a quarter of the cells, and one always may (the contract)
    assert 1 <= sum(w["chips"] == 4 for w in MANIFEST["workloads"]) \
        <= max(1, len(MANIFEST["workloads"]) // 4)
    assert cell.config_name in {c["name"] for c in MANIFEST["configs"]}
    from paddle_tpu.decode.falcon_h1 import param_shapes
    params = sum(int(np.prod(s)) for s, _ in param_shapes(
        cell.driver().model_config(cell.config)).values())
    assert params == 6 * 430_120_032 + 2_673_873_920    # 10.51 GB of bf16
    # the mix's reservations at its means: 74% of the pool
    pool = (mix["engine"]["num_blocks"] - 1) * mix["engine"]["block_tokens"]
    assert pool == 131072


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
        assert s.h.shape == (3, 4, 32, 16)
    got = driver.readings(samples, driver.run_reference(params, CFG, samples))
    assert got["logit_err_decode_p90"] < 1e-4 and got["state_err_p50"] < 1e-4
    assert got["logit_err_prefill_max"] < 1e-4
    assert got["token_gap_p99"] == 0.0
    assert got["positions"] == 3 * len(at) and got["steps_replayed"] == n - 1
    checks = harness.Checks()
    driver.judge(checks, got)
    assert len(checks.items) == len(driver.LIMITS) \
        + len(driver.REFERENCE_RANGES)
    # the toy's streams are 50 to 70 tokens long: its attention averages over
    # few keys and its states are young, so two of the reference's own ranges,
    # which are set for the cell's lengths, may be left; the limits hold
    failed = [name for name, ok, _ in checks.items if not ok]
    assert all("the reference's own" in name for name in failed), failed
    assert engine.cache.allocator.referenced_blocks == 0
    assert driver.judged_steps(257) == [0] + list(range(192, 257, 8))


def test_the_driver_draws_the_weights_the_configuration_assumes(driver):
    """By the driver's own rules, from names and shapes: nothing of the
    program's initialiser is on either side of the comparison."""
    import inspect
    for fn in (driver.make_params, driver.draw, driver.draw_rule):
        assert "init_tensor" not in inspect.getsource(fn)
    params = {k: np.asarray(v, np.float64)
              for k, v in driver.make_params(CFG).items()}
    assert (driver.STEP_SIZE, driver.DECAY) == ((1e-3, 1e-1), (1.0, 16.0))
    lo, hi = driver.STEP_SIZE
    step = np.log1p(np.exp(params["lay.dt_bias"]))              # softplus
    assert lo * 0.999 <= step.min() and step.max() <= hi * 1.001
    decay = np.exp(params["lay.a_log"])
    assert 1.0 <= decay.min() and decay.max() <= 16.0
    assert (params["lay.d_skip"] == 1.0).all()
    D, g = 64, driver.GAINS
    assert params["emb"].std() == pytest.approx(
        1 / CFG["embedding_multiplier"], rel=0.05)
    assert params["head"].std() == pytest.approx(
        D ** -0.5 / CFG["lm_head_multiplier"], rel=0.05)
    w = params["lay.in_proj"]
    base = D ** -0.5 / CFG["ssm_in_multiplier"]
    for (a, b), gain, m in zip(((0, 64), (64, 128), (128, 192), (192, 256),
                                (256, 260)), (1, 1, 1, 1, g["dt"]),
                               CFG["ssm_multipliers"]):
        assert w[..., a:b].std() == pytest.approx(base * gain / m, rel=0.12)
    w = params["lay.wqkv"]
    assert w[..., :64].std() == pytest.approx(g["q"] * D ** -0.5, rel=0.05)
    assert w[..., 64:96].std() == pytest.approx(
        D ** -0.5 / CFG["key_multiplier"], rel=0.05)
    assert w[..., 96:].std() == pytest.approx(D ** -0.5, rel=0.05)
    assert params["lay.wo"].std() == pytest.approx(
        g["wo"] * 64 ** -0.5 / CFG["attention_out_multiplier"], rel=0.05)
    assert params["lay.out_proj"].std() == pytest.approx(
        g["out_proj"] * 64 ** -0.5 / CFG["ssm_out_multiplier"], rel=0.05)
    assert params["lay.mlp_gate"].std() == pytest.approx(
        D ** -0.5 / CFG["mlp_multipliers"][0], rel=0.05)
    assert params["lay.mlp_up"].std() == pytest.approx(D ** -0.5, rel=0.05)
    assert params["lay.mlp_down"].std() == pytest.approx(
        g["mlp_down"] * 128 ** -0.5 / CFG["mlp_multipliers"][1], rel=0.05)
    assert params["lay.conv_w"].std() == pytest.approx(0.5, rel=0.1)
    assert params["lay.conv_b"].std() == pytest.approx(0.02, rel=0.2)
    assert abs(params["final_norm"].mean() - 1.0) < 0.05
    assert params["lay.ssm_norm"].std() == pytest.approx(0.1, rel=0.2)
    again = driver.make_params(CFG)
    assert all(np.array_equal(np.asarray(again[k], np.float64), params[k])
               for k in params)


@pytest.mark.parametrize("shift,fails", [
    (-4.0, "ref_step_size_max"), (4.0, "ref_step_size_in_range_share")])
def test_a_shifted_dt_bias_fails_the_reference_s_own_check(
        driver, served, shift, fails):
    """Weights whose step sizes left the trained range (both sides of the
    comparison would agree on them): the reference's own readings say so."""
    params, engine, asks = served
    samples = driver.replay(engine, asks)
    off = dict(params)
    off["lay.dt_bias"] = params["lay.dt_bias"] + shift
    got = driver.readings(samples, driver.run_reference(off, CFG, samples))
    checks = harness.Checks()
    driver.judge(checks, got)
    failed = [name for name, ok, _ in checks.items if not ok]
    assert any(fails in name for name in failed), failed
    sound = driver.readings(samples,
                            driver.run_reference(params, CFG, samples))
    # (the toy has 4 heads a layer: a head is a quarter of a layer's share)
    low, high = driver.REFERENCE_RANGES[fails]
    assert 0.85 * low <= sound[fails][0] and sound[fails][1] <= high


def test_a_replay_that_would_compile_is_an_error(driver, served):
    params, engine, asks = served
    long = np.arange(40, dtype=np.int32) % 96       # no rung of 40 was run
    engine.prefill_ladder = type(engine.prefill_ladder)([16, 32, 48])
    try:
        with pytest.raises(RuntimeError, match="executable cache"):
            driver.replay(engine, [(long, [1, 2])])
    finally:
        engine.prefill_ladder = type(engine.prefill_ladder)([16, 32])
        engine.cache.allocator.release(
            [b for b in list(engine.cache.allocator._ref)])


def test_a_judged_fault_fails_its_limit(driver):
    got = dict({name: 0.0 for name in driver.LIMITS}, positions=1,
               steps_replayed=1, finite=True,
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
    return falconh1_controls.run_controls(driver, CFG, MIX, params, engine,
                                          asks)


def test_both_lower_precision_controls_fail_and_each_by_its_own_limit(
        driver, served, controls):
    """The recurrent rows see a state kept in bf16; the logits see an 8-bit
    pool.  At this toy float32 size the sound program reads rounding noise
    and a stream lives 39 steps, so bf16's rounding of the state is held to
    a limit a decade above the toy's own sound reading; on the chip, at the
    real size and bf16, both controls cross the committed limits (PERF.md
    section 6 has those readings)."""
    params, engine, asks = served
    sound = controls["sound"][1]
    bf16 = controls["bf16_recurrent_state"][1]
    assert falconh1_controls.GUARDS["bf16_recurrent_state"] == "state_err_p50"
    assert bf16["state_err_p50"] > 100 * sound["state_err_p50"]
    assert bf16["state_err_p50"] > 1e-3 > 10 * sound["state_err_p50"]
    fp8 = controls["fp8_pool"][1]
    guard = falconh1_controls.GUARDS["fp8_pool"]
    assert fp8[guard] > 100 * sound[guard]
    assert fp8[guard] > 1e-3 > 10 * sound[guard]
    # the 8-bit pool does not reach the recurrent rows' own reading by much,
    # nor a bf16 state the prefill's logits (a prefill reads no state)
    assert bf16["logit_err_prefill_max"] < 1e-4
    # a join overwrites everything a control left in a slot's rows and in
    # the blocks it is given: the sound program, replayed after all of them,
    # reads as before
    samples = driver.replay(engine, asks)
    again = driver.readings(samples,
                            driver.run_reference(params, CFG, samples))
    assert again["logit_err_decode_p90"] < 1e-4
    assert again["state_err_p50"] < 1e-4


@pytest.mark.parametrize("fault", [
    "attention_dropped", "ssm_dropped", "mu_left_out", "rotary_off_by_one",
    "another_streams_token"])
def test_a_planted_fault_of_logic_fails_the_limit_that_guards_it(
        driver, controls, fault):
    limit = falconh1_controls.GUARDS[fault]
    ok, got = controls[fault]
    assert not ok
    assert got[limit] > 2 * driver.LIMITS[limit]
    assert controls["sound"][1][limit] < driver.LIMITS[limit] / 3


def test_the_counting_functions_against_hand_worked_numbers():
    cfg = {"num_hidden_layers": 6, "num_attention_heads": 20,
           "num_key_value_heads": 4, "head_dim": 128, "mamba_d_ssm": 4096,
           "mamba_n_heads": 32, "mamba_n_groups": 2, "mamba_d_state": 256,
           "dtype": "bfloat16", "kv_dtype": "bfloat16"}
    ops, moved = kernel_counts_falconh1.ssd_scan_prefill(
        cfg, {"prefill_real_tokens": 10.0})
    assert ops == 5 * 4096 * 256 * 60
    assert moved == 60 * ((2 * 4096 + 2 * 512) * 2 + 32 * 4)
    row = 6 * 2 * 4 * 32 * 256 * 128            # a stream: 50,331,648 B
    ops, moved = kernel_counts_falconh1.ssd_state_step(
        cfg, {"step_state_bytes": 3.0 * row})
    assert moved == 3 * row and ops == 5 * 3 * 6 * 32 * 256 * 128
    ops, moved = kernel_counts_falconh1.gqa_decode_attn(
        cfg, {"step_context_tokens": 10.0})
    assert (ops, moved) == (20 * 512 * 60, 60 * 2048)
    ops, moved = kernel_counts_falconh1.gqa_prefill_attn(
        cfg, {"prefill_tokens_sq": 100.0, "prefill_real_tokens": 10.0})
    assert (ops, moved) == (20 * 512 * 55 * 6, 0.0)
    assert set(kernel_counts_falconh1.COUNTS) == {
        "ssd_scan_prefill", "ssd_state_step", "gqa_decode_attn",
        "gqa_prefill_attn"}
