"""The ``mla_serve`` driver's reference comparison at a toy size on the CPU:
the replay through the engine's own executables, the plain reference given
the program's expert choices, the readings, and the two lower-precision
controls of ``benchmark/mla_controls.py`` through the same functions."""
import importlib.util
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, mla_controls  # noqa: E402

CFG = {
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 32, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_routed_experts": 8,
    "num_experts_per_tok": 3, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "norm_topk_prob": False,
    "routed_scaling_factor": 1, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"factor": 4, "original_max_position_embeddings": 16,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "type": "yarn"},
    "max_seq_len": 64, "dtype": "float32", "kv_dtype": "float32",
    "attn_impl": "pallas"}
MIX = {"engine": {"max_slots": 4, "max_queue": 8, "block_tokens": 4,
                  "num_blocks": 65, "prefill_buckets": [16, 32]}}


@pytest.fixture(scope="module")
def driver():
    path = os.path.join(REPO, "benchmark", "drivers", "mla_serve.py")
    spec = importlib.util.spec_from_file_location("mla_serve_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(driver):
    from paddle_tpu.decode import SamplingParams
    params = driver.make_params(CFG)
    engine, server, _ = driver.build_server(CFG, MIX, params)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 128, size=n).astype(np.int32)
               for n in (9, 20, 31)]
    handles = [engine.submit(p, SamplingParams(temperature=0.0,
                                               max_new_tokens=m))
               for p, m in zip(prompts, (12, 6, 20))]
    asks = [(p, h.result(timeout=300.0)["tokens"])
            for p, h in zip(prompts, handles)]
    yield params, engine, asks
    server.stop()


def test_the_replay_reads_the_window_s_own_tokens_and_agrees_with_the_reference(
        driver, served):
    params, engine, asks = served
    samples = driver.replay(engine, asks)
    for s, (prompt, tokens) in zip(samples, asks):
        # teacher-forced with the engine's tokens, float32: the same argmax
        assert s.logits.argmax(-1).tolist() == list(tokens)
        assert s.ids.shape == (2, prompt.size + len(tokens) - 1, 3)
        # the first expert layer's experts at the judged positions: row 0
        # from the prefill program, the others from the step program
        assert s.expert_x.shape == s.expert_y.shape == (len(tokens), 64)
    experts = driver.reference_experts(params, CFG, samples)
    assert [e.shape for e in experts] == [s.expert_y.shape for s in samples]
    got = driver.readings(
        samples, driver.run_reference(params, CFG, samples), experts)
    checks = harness.Checks()
    driver.judge(checks, got)
    assert checks.ok, checks.lines()
    assert got["route_differs_share"] == 0.0 and got["expert_err_p50"] < 1e-5
    assert got["logit_err_p90"] < 1e-4 and got["token_gap_p99"] == 0.0
    assert got["positions"] == 12 + 6 + 20
    assert got["expert_rows"] == [3, 12 + 6 + 20 - 3]
    # the reading is the larger of the two programs' medians: a fault in
    # the prefill's three rows alone shows
    for s in samples:
        s.expert_y[0] *= 1.5
    worse = driver.readings(
        samples, driver.run_reference(params, CFG, samples), experts)
    assert worse["expert_err_p50"] == worse["expert_err_p50_prefill_rows"] \
        == pytest.approx(0.5, rel=1e-3)
    assert worse["expert_err_p50_step_rows"] == got["expert_err_p50_step_rows"]


def test_a_replay_that_would_compile_is_an_error(driver, served):
    params, engine, asks = served
    long = np.arange(40, dtype=np.int32) % 128      # no rung of 40 was run
    engine.prefill_ladder = type(engine.prefill_ladder)([16, 32, 48])
    try:
        with pytest.raises(RuntimeError, match="executable cache"):
            driver.replay(engine, [(long, 2)])
    finally:
        engine.prefill_ladder = type(engine.prefill_ladder)([16, 32])
        engine.cache.allocator.release(
            [b for b in list(engine.cache.allocator._ref)])


def test_a_judged_fault_fails_its_limit(driver):
    got = {"route_differs_share": 0.0, "expert_err_p50": 0.0,
           "logit_err_p50": 0.0,
           "logit_err_p90": 0.0, "token_gap_p99": 0.0, "positions": 1,
           "routings": 1, "finite": True}
    for name, limit in driver.LIMITS.items():
        checks = harness.Checks()
        driver.judge(checks, dict(got, **{name: limit * 1.01}))
        assert [ok for _, ok, _ in checks.items].count(False) == 1
    checks = harness.Checks()
    driver.judge(checks, dict(got, finite=False))
    assert not checks.ok


def test_both_lower_precision_controls_read_far_above_the_sound_program(
        driver, served):
    """At this toy float32 size the sound program reads rounding noise; the
    controls must read orders of magnitude above it through the very same
    functions (at the real size and bf16, on the chip, they must cross the
    committed limits: PERF.md section 6 has those readings)."""
    params, engine, asks = served
    out = mla_controls.run_controls(driver, CFG, params, engine, asks)
    sound = out["sound"][1]
    assert out["sound"][0]
    for name in ("fp8_latent_pool", "int8_experts"):
        got = out[name][1]
        assert got["logit_err_p90"] > 100 * max(sound["logit_err_p90"], 1e-7)
    planted = out["planted_faults"]
    assert not planted[0]
    assert planted[1]["route_differs_share"] > 0.02     # 5% of 190 pairs
    assert planted[1]["token_gap_p99"] > 0.02
    # the experts alone, read from the replayed programs on their own input
    # rows, see the int8 weights (in the prefill's rows and in the steps')
    # and nothing of the pool
    got = out["int8_experts"][1]
    assert min(got["expert_err_p50_prefill_rows"],
               got["expert_err_p50_step_rows"]) > 2e-3
    assert out["fp8_latent_pool"][1]["expert_err_p50"] < 1e-5
    assert sound["expert_err_p50"] < 1e-5
    # the int8 control left original weights behind, made anew from the seed
    again = driver.make_params(CFG)
    assert all(np.array_equal(np.asarray(params[k]), np.asarray(again[k]))
               for k in again)


# -- the new readers --------------------------------------------------------

DATA = os.path.join(REPO, "benchmark", "testdata")


def _reader(name):
    return harness.load_module(
        os.path.join(REPO, "benchmark", "metrics", name + ".py"),
        "reader_under_test_" + name)


def test_scope_share_reads_the_scopes_of_the_recorded_v5e_trace():
    """``tiny_v5e_engine.xplane.pb`` has no ``moe_``/``mla_`` scope (a
    reader of those reports nothing, as on the parent), but every
    instruction's ``tf_op`` is there: the matmuls' share is a share."""
    from benchmark import trace_reduce
    path = os.path.join(DATA, "tiny_v5e_engine.xplane.pb")
    raw = trace_reduce.extract(path)
    summary = trace_reduce.reduce(raw, ())
    ctx = {"trace_raw": raw, "trace": summary, "xplane": path}
    mod = _reader("scope_share")
    assert mod.read(ctx, scopes=["/moe_routed/", "/mla_attn/"]) is None
    share = mod.read(ctx, scopes=["/dot_general"])
    everything = mod.read(ctx, scopes=["jit\\("])
    assert 0.0 < share < everything <= 100.0 + 1e-6
    assert mod.read({"trace_raw": None, "trace": None}, scopes=["x"]) is None


def test_kernel_roofline_counts_work_over_the_very_launches_it_times():
    from benchmark import kernel_counts, peaks
    ms = 1e6
    raw = {"host": [["bench.window", 0.0, 100 * ms]], "devices": {"/device:TPU:0": {
        "modules": [["jit_fn_decode_lm_step(1)", 10 * ms, 10 * ms],
                    ["jit_fn_decode_lm_prefill_1024(2)", 30 * ms, 20 * ms],
                    ["jit_fn_decode_lm_step(1)", 60 * ms, 10 * ms],
                    ["jit_fn_decode_lm_prefill_2048(3)", 75 * ms, 20 * ms]],
        "ops": [["%moe_grouped_swiglu.1 = f32[8,8]{1,0} custom-call()", 11 * ms, 2 * ms],
                ["%moe_grouped_swiglu.1 = f32[8,8]{1,0} custom-call()", 31 * ms, 8 * ms],
                ["%moe_grouped_swiglu.1 = f32[8,8]{1,0} custom-call()", 61 * ms, 4 * ms],
                ["%fusion.3 = f32[8]{0} fusion()", 66 * ms, 1 * ms],
                ["%moe_grouped_swiglu.1 = f32[8,8]{1,0} custom-call()", 76 * ms, 16 * ms]]}}}

    def step(at, touched, assignments):
        return ["decode::step.observe", 1, at * ms, 0.1 * ms,
                {"step_experts_touched": touched,
                 "step_routed_assignments": str(assignments),
                 "step_context_tokens": 1000}]
    # the device's clock runs a little ahead of the host's: the first step's
    # span starts just "before" its launch ends.  The last prefill's span
    # never came (the trace stopped): that launch is neither timed nor
    # counted, however long its kernel ran.
    spans = {"spans": [
        step(19.5, 6 * 60, 6 * 384),
        ["decode::prefill.observe", 1, 50.3 * ms, 0.1 * ms,
         {"prefill_routed_assignments": 6 * 6 * 900, "prefill_tokens_sq": 900 ** 2}],
        step(70.2, 6 * 64, 6 * 380),
        ["decode::step", 1, 59 * ms, 12 * ms, {"live": 64}]]}
    cfg = {"hidden_size": 2048, "moe_intermediate_size": 1408,
           "dtype": "bfloat16", "num_hidden_layers": 7,
           "first_k_dense_replace": 1}
    ctx = {"trace_raw": raw, "config": cfg, "memory": {"kind": "TPU v5 lite"}}
    mod = _reader("kernel_roofline")
    peak = peaks.peaks_for("TPU v5 lite")
    step_args = dict(program="^jit_fn_decode_lm_step\\b",
                     kernel="^moe_grouped_swiglu", count="moe_step",
                     span="decode::step\\.observe")
    seconds, work, launches = mod.timed(raw, spans, step_args["program"],
                                        step_args["kernel"], step_args["span"])
    assert launches == 2 and seconds == pytest.approx(6e-3)
    assert work == {"step_experts_touched": 6 * 124.0,
                    "step_routed_assignments": 6 * 764.0,
                    "step_context_tokens": 2000.0}
    ops, moved = kernel_counts.moe_step(cfg, work)
    assert mod.share(ctx, spans, **step_args) == pytest.approx(
        100 * max(ops / 6e-3 / peak["bf16_flops_per_s"],
                  moved / 6e-3 / peak["hbm_bytes_per_s"]))
    got = mod.share(ctx, spans, program="^jit_fn_decode_lm_prefill_",
                    kernel="^moe_grouped_swiglu", count="moe_prefill",
                    span="decode::prefill\\.observe")
    assert got == pytest.approx(
        100 * 2 * 3 * 2048 * 1408 * 6 * 6 * 900 / 8e-3
        / peak["bf16_flops_per_s"])
    # the parent: no such span, no such kernel, no trace
    assert mod.share(ctx, {"spans": spans["spans"][3:]}, **step_args) is None
    assert mod.share(ctx, spans, **dict(step_args, kernel="^absent")) is None
    assert mod.read(dict(ctx, trace_raw=None), **step_args) is None


def test_counter_ratio_and_the_attention_counts():
    from benchmark import kernel_counts
    mod = _reader("counter_ratio")
    ctx = {"window_counters": {"a": 30.0, "b": 70.0, "z": 0.0},
           "config": {"n": 64}}
    assert mod.read(ctx, num=["a"], den=["a", "b"], scale=100.0) == 30.0
    assert mod.read(ctx, num=["a"], den=["b"], times_config="n") == \
        pytest.approx(30 / 70 * 64)
    assert mod.read(ctx, num=["a"], den=["z"]) is None
    assert mod.read(ctx, num=["missing"], den=["a"]) is None
    assert mod.read({}, num=["a"], den=["b"]) is None
    cfg = {"num_attention_heads": 16, "qk_nope_head_dim": 128,
           "qk_rope_head_dim": 64, "v_head_dim": 128, "kv_lora_rank": 512,
           "num_hidden_layers": 7, "kv_dtype": "bfloat16"}
    ops, moved = kernel_counts.mla_prefill_attn(cfg, {"prefill_tokens_sq": 4.0})
    assert (ops, moved) == (2 * 16 * 320 * 2.0 * 7, 0.0)
    ops, moved = kernel_counts.mla_decode_attn(cfg, {"step_context_tokens": 10.0})
    assert ops == 2 * 16 * (576 + 512) * 70 and moved == 70 * 576 * 2


def test_the_cell_reads_the_serve_family_and_its_own_metrics():
    manifest = harness.load_manifest(REPO)
    cell = harness.Cell(REPO, manifest, "dsv2l_doc_sat")
    assert {m["name"] for m in cell.end_to_end} == \
        {"served_tokens_per_s", "setup_s"}
    # the decode-plane and device family under its one name, joined through
    # served_tokens_per_s, and the model's own (a later PR may add to either)
    family = {m["name"] for m in manifest["per_layer"] if "workloads" not in m
              and m["moves"] in {e["name"] for e in cell.end_to_end}}
    assert "decode_step_ms.served" in family
    assert {m["name"] for m in cell.per_layer} >= family | {
        "prefill_pad_share.served",
        "moe_share.served", "mla_share.served",
        "moe_prefill_roofline.served", "moe_step_roofline.served",
        "mla_prefill_attn_roofline.served",
        "mla_decode_attn_roofline.served",
        "expert_load_max_over_mean.served",
        "experts_touched_per_step.served"}
    # the eight it shares with the other models of latent attention and of
    # experts since PR 58: the metric files' defaults are this model's words
    # (decode/mla.py's scopes, benchmark/kernel_counts.py), so its
    # configuration gives one alone, its key of the experts held
    assert cell.config["metric_args"] == {
        "expert_load_max_over_mean.served": {
            "times_config": "n_routed_experts"}}
    for m in cell.per_layer:
        cell.reader(m["name"])              # every reader is found by name
        if "workloads" in m:                # listed: this cell is IN the list
            assert cell.name in m["workloads"]
