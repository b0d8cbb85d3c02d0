"""The ``sambay_serve`` driver's reference comparison at a toy size on the
CPU: the replay through the engine's own executables, the plain reference's
full forward, the readings, the weights the driver draws, and the two
lower-precision controls and three planted faults of
``benchmark/sambay_controls.py`` through the same functions; the new cell's
entries; the counting functions against hand-worked numbers."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, kernel_counts_sambay, sambay_controls  # noqa: E402

CFG = {
    "vocab_size": 96, "hidden_size": 256, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 384, "sliding_window": 8, "mb_per_layer": 2,
    "layer_norm_eps": 1e-5, "d_state": 16, "d_conv": 4, "expand": 2,
    "dt_rank": 16, "max_seq_len": 96, "dtype": "float32",
    "kv_dtype": "float32", "attn_impl": "pallas"}
MIX = {"engine": {"max_slots": 3, "max_queue": 8, "block_tokens": 16,
                  "num_blocks": 24, "prefill_buckets": [16, 32]}}
CELL = "p4flash_reason_sat"
MANIFEST = harness.load_manifest(REPO)


@pytest.fixture(scope="module")
def driver():
    path = os.path.join(REPO, "benchmark", "drivers", "sambay_serve.py")
    spec = importlib.util.spec_from_file_location("sambay_serve_under_test",
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
        ("phi4-mini-flash-reasoning", "reason_sat", 1, "sambay_serve")
    (entry,) = [c for c in MANIFEST["configs"]
                if c["name"] == cell.config_name]
    assert entry["reduced"] == [] and cell.config["reduced"] == []
    # every key of the source under its name, none changed
    for k, v in cell.config["source_keys"].items():
        assert cell.config[k] == v, k
    assert cell.config["num_hidden_layers"] == 32
    assert cell.config["vocab_size"] == 200064
    mix = cell.mix
    assert (mix["loop"], mix["callers"], mix["engine"]["max_slots"],
            mix["engine"]["max_queue"]) == ("closed", 96, 64, 128)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1536,
                                    "sigma": 0.35, "min": 768, "max": 3072}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 768,
                                    "sigma": 0.5, "min": 192, "max": 1536}
    assert (mix["request_block"], mix["cycle_blocks"]) == (16, 4)
    assert mix["engine"]["prefill_buckets"][-1] == 3072
    assert (mix["engine"]["num_blocks"] - 1) \
        * mix["engine"]["block_tokens"] >= 262144
    # judged by the median gap between a stream's tokens: the window's rate
    # swings with a pause of the machine by more than any bound here (PR 43)
    # and is recorded per layer under another name
    assert {m["name"] for m in cell.end_to_end} == {"tbt_p50_ms", "setup_s"}
    (rate,) = [m for m in MANIFEST["end_to_end"]
               if m["name"] == "served_tokens_per_s"]
    assert CELL not in rate["workloads"]
    names = {m["name"] for m in cell.per_layer}
    # the decode-plane and device family under its one name, joined through
    # tbt_p50_ms, and the model's own (a later PR may add to either)
    family = {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m
              and m["moves"] in ("tbt_p50_ms", "setup_s")}
    assert "decode_step_ms.tbt50" in family
    assert names >= family | {
        "served_tokens_per_s.tbt50", "prefill_pad_share.tbt50",
        "live_context_tokens.tbt50", "shared_kv_attn_share.served_p4f",
        "swa_share.served_p4f", "ssm_share.served_p4f",
        "shared_kv_decode_attn_roofline.served_p4f",
        "swa_decode_attn_roofline.served_p4f",
        "ssm_scan_prefill_roofline.served_p4f",
        "swa_prefill_attn_roofline.served_p4f"}
    # every reading EVERY saturated serve cell takes under `.served` (the
    # list-less engine family) and the two counters every drawn model's
    # observer keeps, this cell takes under `.tbt50` from the same reader with
    # the same arguments; an entry that lists the cells of the models that
    # have its kernel, scope or router (`moe_share.served`, a roofline) is not
    # this model's to twin
    served = {m["name"] for m in MANIFEST["per_layer"]
              if m["moves"] == "served_tokens_per_s"
              and m["name"].endswith(".served") and "workloads" not in m}
    served |= {"prefill_pad_share.served", "live_context_tokens.served"}
    for name in served:
        twin = name[:-len("served")] + "tbt50"
        assert twin in names, twin
        a = json.load(open(os.path.join(REPO, "benchmark", "metrics",
                                        name + ".json")))
        b = cell.metric_file(twin)
        assert (a["reader"], a.get("args") or {}) == (b["reader"], b["args"])
    for m in cell.per_layer:
        cell.reader(m["name"])              # every reader is found by name
        if m["name"].endswith("p4f"):       # the model's own: this cell is
            assert CELL in m["workloads"]   # IN its list (another may join)
    from paddle_tpu.decode.sambay import param_shapes
    params = sum(int(np.prod(s)) for s, _ in param_shapes(
        cell.driver().model_config(cell.config)).values())
    assert params == 3_852_562_944          # 7.71 GB of bf16


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
        assert s.h.shape == (3, 16, 512)
    got = driver.readings(samples, driver.run_reference(params, CFG, samples))
    checks = harness.Checks()
    driver.judge(checks, got)
    assert checks.ok, checks.lines()
    assert len(checks.items) == len(driver.LIMITS) \
        + len(driver.REFERENCE_RANGES)
    assert got["logit_err_decode_p90"] < 1e-4 and got["state_err_p50"] < 1e-4
    assert got["token_gap_p99"] == 0.0
    assert got["positions"] == 3 * len(at) and got["steps_replayed"] == n - 1
    assert engine.cache.allocator.referenced_blocks == 0
    assert driver.judged_steps(577) == [0] + list(range(512, 577, 8))


def test_the_driver_draws_the_weights_the_configuration_assumes(driver):
    """By the driver's own rules, from names and shapes: nothing of the
    program's initialiser is on either side of the comparison."""
    import inspect
    assert "init_tensor" not in inspect.getsource(driver.make_params)
    assert "init_tensor" not in inspect.getsource(driver.draw)
    params = {k: np.asarray(v, np.float64)
              for k, v in driver.make_params(CFG).items()}
    lo, hi = driver.STEP_SIZE
    assert (lo, hi) == (1e-3, 1e-1)
    for prefix in ("sp.s.", "ms."):
        a = np.exp(params[prefix + "a_log"])
        want = np.broadcast_to(np.arange(1, 17.0)[:, None], a.shape[-2:])
        np.testing.assert_allclose(a, np.broadcast_to(want, a.shape),
                                   rtol=1e-6)
        step = np.log1p(np.exp(params[prefix + "dt_b"]))    # softplus
        assert lo * 0.999 <= step.min() and step.max() <= hi * 1.001
        # log-uniform: a third of the channels a third of the way, in logs
        third = np.exp(np.log(lo) + (np.log(hi) - np.log(lo)) / 3)
        assert 0.25 < (step < third).mean() < 0.42
        assert (params[prefix + "skip"] == 1.0).all()
        x = params[prefix + "x_proj"]
        assert x.std() == pytest.approx((3 * x.shape[-2]) ** -0.5, rel=0.05)
        d = params[prefix + "dt_w"]
        assert d.std() == pytest.approx((3 * d.shape[-2]) ** -0.5, rel=0.05)
    assert params["emb"].std() == pytest.approx(256 ** -0.5, rel=0.05)
    assert params["mf.wqkv"].std() == pytest.approx(256 ** -0.5, rel=0.05)
    assert params["cp.g.w2"].std() == pytest.approx(512 ** -0.5, rel=0.05)
    assert abs(params["final_g"].mean() - 1.0) < 0.03
    assert params["final_g"].std() == pytest.approx(0.1, rel=0.2)
    assert params["mf.bo"].std() == pytest.approx(0.02, rel=0.2)
    assert params["mf.lam_q1"].std() == pytest.approx(0.1, rel=0.4)
    again = driver.make_params(CFG)
    assert all(np.array_equal(np.asarray(again[k], np.float64), params[k])
               for k in params)


@pytest.mark.parametrize("shift,fails", [
    (-4.0, "ref_step_size_max"), (4.0, "ref_step_size_in_range_share")])
def test_a_step_size_outside_its_range_fails_the_reference_s_own_check(
        driver, served, shift, fails):
    """Weights whose step sizes left the trained range (both sides of the
    comparison would agree on them): the reference's own readings say so."""
    params, engine, asks = served
    samples = driver.replay(engine, asks)
    off = dict(params)
    for k in ("sp.s.dt_b", "ms.dt_b"):
        off[k] = params[k] + shift
    got = driver.readings(samples, driver.run_reference(off, CFG, samples))
    checks = harness.Checks()
    driver.judge(checks, got)
    failed = [name for name, ok, _ in checks.items if not ok]
    assert any(fails in name for name in failed), failed


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
    return sambay_controls.run_controls(driver, CFG, MIX, params, engine,
                                        asks)


def test_both_lower_precision_controls_fail_and_each_by_its_own_limit(
        driver, served, controls, monkeypatch):
    """The recurrent states see a state kept in bf16; the logits see an
    8-bit pool and rings.  At this toy float32 size the sound program reads
    rounding noise and a stream lives 39 steps, so bf16's rounding of the
    state has reached 0.4%, not the chip's 4% after 576: the toy holds it to
    a limit a decade above the toy's own sound reading; on the chip, at the
    real size and bf16, both controls cross the committed limits (PERF.md
    section 6 has those readings)."""
    params, engine, asks = served
    ok, sound = controls["sound"]
    assert ok
    ok, bf16 = controls["bf16_recurrent_state"]
    assert bf16["state_err_p50"] > 1000 * sound["state_err_p50"]
    monkeypatch.setitem(driver.LIMITS, "state_err_p50", 1e-3)
    assert sambay_controls.verdict("sound, toy limit", sound, driver)
    assert not sambay_controls.verdict("bf16 state, toy limit", bf16, driver)
    monkeypatch.undo()
    ok, fp8 = controls["fp8_pool_and_rings"]
    assert not ok
    assert fp8["logit_err_decode_p90"] > driver.LIMITS["logit_err_decode_p90"]
    assert fp8["logit_err_decode_p50"] > 100 * sound["logit_err_decode_p50"]
    # a join overwrites everything a control left in a slot's rows and in
    # the blocks it is given: the sound program, replayed after all of them,
    # reads as before
    samples = driver.replay(engine, asks)
    again = driver.readings(samples,
                            driver.run_reference(params, CFG, samples))
    assert again["logit_err_decode_p90"] < 1e-4
    assert again["state_err_p50"] < 1e-4


@pytest.mark.parametrize("fault,limit", [
    ("full_layer_dropped", "logit_err_prefill_max"),
    ("window_short_a_tile", "logit_err_prefill_max"),
    ("another_streams_token", "token_gap_p99")])
def test_a_planted_fault_of_logic_fails_the_limit_that_guards_it(
        driver, controls, fault, limit):
    ok, got = controls[fault]
    assert not ok
    assert got[limit] > 3 * driver.LIMITS[limit]
    assert controls["sound"][1][limit] < driver.LIMITS[limit] / 3


def test_the_counting_functions_against_hand_worked_numbers():
    cfg = {"hidden_size": 2560, "num_attention_heads": 40,
           "num_key_value_heads": 20, "num_hidden_layers": 32, "expand": 2,
           "d_state": 16, "dtype": "bfloat16", "kv_dtype": "bfloat16"}
    pair = 20 * 2 * (2 * 64 + 4 * 64)           # 15,360 operations a pair
    ops, moved = kernel_counts_sambay.shared_kv_decode_attn(
        cfg, {"step_context_tokens": 10.0})
    assert (ops, moved) == (pair * 80, 80 * 5120)
    ops, moved = kernel_counts_sambay.swa_decode_attn(
        cfg, {"step_window_tokens": 3.0})
    assert (ops, moved) == (pair * 24, 24 * 5120)
    ops, moved = kernel_counts_sambay.ssm_scan_prefill(
        cfg, {"prefill_scan_tokens": 9.0})
    assert ops == 7 * 5120 * 16 * 9
    assert moved == 9 * (5120 * (2 + 2 + 4) + 2 * 16 * 4)
    ops, moved = kernel_counts_sambay.swa_prefill_attn(
        cfg, {"prefill_window_pairs": 100.0})
    assert (ops, moved) == (pair * 100 * 8, 0.0)
    assert set(kernel_counts_sambay.COUNTS) == {
        "shared_kv_decode_attn", "swa_decode_attn", "ssm_scan_prefill",
        "swa_prefill_attn"}
