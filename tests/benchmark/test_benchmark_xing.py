"""The ``xing_serve`` driver at a toy size on the CPU: the new cell's entries
and the manifest with it (read by name only); the configuration file against
the catalog's keys; the replay through the engine's own executables, the plain
reference's full forward, the readings, and the two controls of
``benchmark/xing_controls.py`` through the same functions; the counting
functions against hand-worked numbers at the published widths."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, kernel_counts, kernel_counts_xing  # noqa: E402
from benchmark import xing_controls  # noqa: E402

CFG = {
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 32, "q_lora_rank": 24,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "num_experts_per_tok": 3, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"factor": 4.0, "original_max_position_embeddings": 16,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                     "mscale_all_dim": 1, "type": "yarn"},
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30,
    "max_seq_len": 128, "dtype": "float32", "kv_dtype": "float32",
    "attn_impl": "pallas"}
MIX = {"engine": {"max_slots": 3, "max_queue": 8, "block_tokens": 16,
                  "num_blocks": 40, "prefill_buckets": [64, 128]}}
CELL = "xg29b_doc_sat"
CONFIG = "xing4-29b-a4b-s0"
MANIFEST = harness.load_manifest(REPO)
OWN = {n + ".served_xg" for n in (
    "mhc_share", "dense_ffn_share", "mhc_pre_prefill_roofline",
    "mhc_post_prefill_roofline")}
# what it shares with the other models of latent attention and of experts
# since PR 58: one entry a metric, this cell in its list, the counts file and
# the configuration's key of the experts held in this configuration's
# `metric_args`; the scopes and counters are decode/mla.py's, the defaults
SHARED = {n + ".served" for n in (
    "mla_prefill_attn_roofline", "mla_decode_attn_roofline",
    "moe_prefill_roofline", "moe_step_roofline", "mla_share", "moe_share",
    "expert_load_max_over_mean", "experts_touched_per_step",
    "prefill_pad_share")}


@pytest.fixture(scope="module")
def driver():
    path = os.path.join(REPO, "benchmark", "drivers", "xing_serve.py")
    spec = importlib.util.spec_from_file_location("xing_serve_under_test",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(driver):
    from paddle_tpu.decode import SamplingParams
    # float32 on both sides reads an e4m3 pool at a hundredth where the
    # sound program reads a millionth and chooses the reference's experts at
    # every pair (a random one of eight is often the one replaced); a branch
    # of a 64-wide toy weighs otherwise
    driver.LIMITS = dict(driver.LIMITS, logit_err_p50=1e-3,
                         logit_err_p90=2e-3, route_differs_share=0.01)
    driver.REFERENCE_RANGES = dict(
        driver.REFERENCE_RANGES, ref_attn_rms=(0.05, 3.0),
        ref_ffn_rms=(0.05, 3.0), ref_stream_growth=(0.3, 4.0),
        ref_hres_token_std=(0.01, 0.5))
    params = driver.make_params(CFG)
    engine, server, _ = driver.build_server(CFG, MIX, params)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, size=n).astype(np.int32)
               for n in (5, 64, 100)]
    handles = [engine.submit(p, SamplingParams(temperature=0.0,
                                               max_new_tokens=m))
               for p, m in zip(prompts, (9, 12, 7))]
    asks = [(p, h.result(timeout=900.0)["tokens"])
            for p, h in zip(prompts, handles)]
    yield params, engine, asks
    server.stop()


def test_the_manifest_is_sound_and_names_the_cell_and_its_configuration_once():
    assert harness.check_manifest(REPO, MANIFEST) == []
    assert [w["name"] for w in MANIFEST["workloads"]].count(CELL) == 1
    assert [c["name"] for c in MANIFEST["configs"]].count(CONFIG) == 1
    # the contract's limit on every string of an entry (check_manifest holds
    # a cell's `why` to it, and nothing a configuration's)
    mine = [e for key in ("configs", "workloads", "per_layer")
            for e in MANIFEST[key]
            if e["name"] in (CONFIG, CELL) or e["name"] in OWN]
    assert len(mine) == 2 + len(OWN)
    for e in mine:
        for value in e.values():
            if isinstance(value, str):
                assert 1 <= len(value) <= 200 and "\t" not in value \
                    and "\n" not in value, (e["name"], value)


def test_the_new_cell_is_the_one_the_issue_names():
    cell = harness.Cell(REPO, MANIFEST, CELL)
    assert (cell.config_name, cell.mix_name, cell.chips, cell.kind) == \
        (CONFIG, "doc_sat", 1, "xing_serve")
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    cut = ["num_hidden_layers", "num_nextn_predict_layers"]
    assert entry["reduced"] == cut == cell.config["reduced"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Xing4.0-29B-A4B"]
    src = cell.config["source_keys"]
    assert src == row["config"]
    assert entry["source"] == cell.config["source"] == row["source_url"]
    # every key of the source under its name, none changed but the two
    for k, v in src.items():
        if k not in cut:
            assert cell.config[k] == v, k
    assert {k: cell.config[k] for k in cut} == \
        {"num_hidden_layers": 7, "num_nextn_predict_layers": 0}
    assert {k: (v["published"], v["here"])
            for k, v in cell.config["reduced_from"].items()} == \
        {"num_hidden_layers": (40, 7), "num_nextn_predict_layers": (1, 0)}
    assert all(v["which"] for v in cell.config["reduced_from"].values())
    # no width is cut, every expert and the whole vocabulary are held
    assert (cell.config["hidden_size"], cell.config["intermediate_size"],
            cell.config["moe_intermediate_size"],
            cell.config["n_routed_experts"],
            cell.config["num_experts_per_tok"], cell.config["vocab_size"],
            cell.config["q_lora_rank"], cell.config["kv_lora_rank"],
            cell.config["hc_mult"], cell.config["first_k_dense_replace"],
            cell.config["max_seq_len"]) == \
        (3584, 9216, 1024, 64, 4, 131072, 768, 512, 4, 2, 8192)
    for key in ("deployment", "assumed", "what"):
        assert cell.config[key]
    said = " ".join(cell.config["assumed"])
    for word in ("recalled without a network", "replicated", "no gain",
                 "rows then columns", "OUTPUT streams", "2 x sigmoid",
                 "float32", "q_norm", "noaux_tc", "1e-20", "8192",
                 "de-interleaves", "weight seed 54", "0.008"):
        assert word in said, word
    assert "six pipeline stages" in cell.config["deployment"] \
        and "4,921 M" in cell.config["deployment"] \
        and "four residual streams" in cell.config["deployment"]
    # the accepted mix, read and not edited
    mix = cell.mix
    assert (mix["loop"], mix["callers"], mix["lead_s"]) == ("closed", 96, 8.0)
    assert mix["engine"] == {
        "max_slots": 64, "max_queue": 128, "block_tokens": 16,
        "num_blocks": 16385,
        "prefill_buckets": [1024, 2048, 3072, 4096, 6144, 8192]}
    assert {m["name"] for m in cell.end_to_end} == \
        {"served_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    family = {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m
              and m["moves"] in {e["name"] for e in cell.end_to_end}}
    assert names == family | OWN | SHARED
    assert set(cell.config["metric_args"]) <= SHARED
    for m in cell.per_layer:
        cell.reader(m["name"])              # every reader is found by name
        if m["name"] in OWN | SHARED:       # this cell is IN its list
            assert CELL in m["workloads"] \
                and m["moves"] == "served_tokens_per_s"
        if m["name"] in OWN:
            assert m["unit"] == "%"
        if m["name"].split(".")[0].endswith("_roofline"):
            spec = cell.metric_file(m["name"])
            assert spec["args"]["counts"] == "benchmark/kernel_counts_xing.py"
            assert spec["args"]["count"] in kernel_counts_xing.COUNTS
    assert cell.metric_file("expert_load_max_over_mean.served")["args"][
        "times_config"] == "n_routed_experts"
    assert cell.config["n_routed_experts"] == 64


def test_a_checkout_without_the_model_is_refused_before_a_device(driver,
                                                                 monkeypatch):
    cell = harness.Cell(REPO, MANIFEST, CELL)
    driver.validate(cell, 45.0)
    from paddle_tpu.decode import mla
    monkeypatch.delattr(mla, "HyperMLATransformerLM")
    with pytest.raises(harness.ConfigurationError, match="cannot run"):
        driver.validate(cell, 45.0)


def test_the_counts_are_the_hand_count_at_the_published_widths():
    cfg = harness.Cell(REPO, MANIFEST, CELL).config
    c = kernel_counts_xing.COUNTS
    # a real row a sub-layer: 4 x 3584 bf16 read, 3584 bf16 written, H_post
    # and H_res (4 + 16 float32) written; 2 x 14336 x 24 for the product, a
    # multiply and an add an element for the squares and for h
    ops, byts = c["mhc_pre_prefill"](cfg, {"prefill_mhc_rows": 14000.0})
    assert byts == 14000 * (28672 + 7168 + 80)
    assert ops == 14000 * (2.0 * 14336 * 24 + 4.0 * 14336)
    # ... 4 x 3584 + 3584 bf16 and the 20 float32 read, 4 x 3584 written; 5
    # multiplies and 4 adds an element written
    ops, byts = c["mhc_post_prefill"](cfg, {"prefill_mhc_rows": 14000.0})
    assert byts == 14000 * (2 * 28672 + 7168 + 80)
    assert ops == 14000 * 9.0 * 14336
    # the siblings' kernels by kernel_counts.py's own functions, from this
    # configuration's keys: 32 heads x (2 x 192 + 2 x 128) a pair, 7 layers
    for name in ("mla_prefill_attn", "mla_decode_attn", "moe_prefill",
                 "moe_step"):
        assert c[name] is kernel_counts.COUNTS[name]
    ops, _ = c["mla_prefill_attn"](cfg, {"prefill_tokens_sq": 100.0 ** 2})
    assert ops == 2.0 * 32 * 320 * 5000 * 7
    ops, byts = c["mla_decode_attn"](cfg, {"step_context_tokens": 1000.0})
    assert byts == 1000 * 7 * 576 * 2 \
        and ops == 2.0 * 32 * (576 + 512) * 1000 * 7
    expert = 3 * 3584 * 1024
    assert c["moe_prefill"](cfg, {"prefill_routed_assignments": 32}) == \
        (2.0 * expert * 32, 0.0)
    ops, byts = c["moe_step"](cfg, {"step_experts_touched": 300,
                                    "step_routed_assignments": 1280})
    assert ops == 2.0 * expert * 1280
    assert byts == 300 * expert * 2 + 1280 * 3584 * (2 + 4)
    assert set(c) == {"mhc_pre_prefill", "mhc_post_prefill",
                      "mla_prefill_attn", "mla_decode_attn", "moe_prefill",
                      "moe_step"}


def test_the_draw_follows_the_rules_the_configuration_states(driver):
    import jax
    assert driver.draw_rule("q_norm", (768,)) == "norm"
    assert driver.draw_rule("emb", (131072, 3584)) == ((3584, 1.0),)
    assert driver.draw_rule("router_bias", (64,)) == ((64, 0.008),)
    assert driver.draw_rule("wq_a", (3584, 768)) == ((768, 3584 ** -0.5),)
    assert driver.draw_rule("wq_b", (768, 6144)) == \
        ((6144, 768 ** -0.5 * 1.5),)
    assert driver.draw_rule("e_down", (64, 1024, 3584)) == \
        ((3584, 1024 ** -0.5 * 0.7),)
    key = jax.random.PRNGKey(0)
    phi = np.asarray(driver.draw_hc(key, "hc_phi", (24, 14336)))
    assert phi.dtype == np.float32 and abs(phi.std() * 14336 ** 0.5 - 1) < 0.02
    alpha = np.asarray(driver.draw_hc(key, "hc_alpha", (3,)))
    assert np.all(np.abs(alpha / np.asarray(driver.ALPHA) - 1) < 0.5)
    b = np.stack([np.asarray(driver.draw_hc(jax.random.PRNGKey(i), "hc_b",
                                            (24,))) for i in range(200)])
    mean = b.mean(0)
    assert np.abs(mean[:4]).max() < 0.1 \
        and np.abs(mean[4:8] - driver.B_POST).max() < 0.1
    assert np.abs(mean[8:].reshape(4, 4)
                  - driver.B_DIAG * np.eye(4)).max() < 0.1
    params = driver.make_params(CFG)
    assert str(params["l1.ffn_hc_phi"].dtype) == "float32" \
        and str(params["l1.attn_hc_alpha"].dtype) == "float32" \
        and params["l1.ffn_hc_phi"].shape == (24, 256)
    from paddle_tpu.decode.mla import param_shapes
    assert set(params) == set(param_shapes(driver.model_config(CFG)))


def test_the_replay_reads_the_mixing_s_probe_from_both_programs(driver,
                                                                served):
    params, engine, asks = served
    samples = driver.replay(engine, asks)
    for (prompt, tokens), s in zip(asks, samples):
        n = len(tokens)
        assert s.logits.shape == (n, 96) and s.hc_x.shape == (n, 256) \
            and s.hc_maps.shape == (n, 24) and s.expert_x.shape == (n, 64)
        assert s.ids.shape == (2, prompt.size + n - 1, 3)
        assert list(s.produced) == list(tokens)
        res = s.hc_maps[:, 8:].reshape(n, 4, 4)
        np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-3)
        assert (s.hc_maps[:, :4] > 0).all() and (s.hc_maps[:, :4] < 1).all()
    err = driver.map_errors(params, CFG, samples)
    assert err.shape == (sum(len(t) for _, t in asks),) and err.max() < 1e-5


def test_the_sound_program_is_correct_and_every_control_is_not(driver,
                                                               served):
    params, engine, asks = served
    out = xing_controls.run_controls(driver, CFG, params, engine, asks)
    verdicts = {k: v[0] for k, v in out.items()}
    assert verdicts.pop("sound"), out["sound"][1]
    assert not any(verdicts.values()), verdicts
    assert set(verdicts) == set(xing_controls.GUARDS)
    # every limit guards something
    assert {g for gs in xing_controls.GUARDS.values() for g in gs} == \
        set(driver.LIMITS)
    for name, guards in xing_controls.GUARDS.items():
        for guard in guards:
            assert out[name][1][guard] > driver.LIMITS[guard], (name, guard)
    sound = out["sound"][1]
    # float32 on both sides: the program IS the reference
    assert sound["logit_err_p90"] < 1e-4 and sound["hc_map_err_max"] < 1e-5 \
        and sound["route_differs_share"] == 0.0 \
        and sound["expert_err_p50"] < 1e-5 and sound["finite"]
    # maps from bf16 products leave the pool's control alone, and the other
    # way round
    assert out["fp8_latent_pool"][1]["hc_map_err_p50"] < 1e-5
    for name in driver.REFERENCE_RANGES:
        low, high = sound[name]
        assert low <= high


def test_the_engine_s_phi_goes_through_bfloat16_and_nothing_else(served):
    _, engine, _ = served
    names = engine.model.param_names()
    const = xing_controls.bf16_phi(engine)
    for name, was, now in zip(names, engine._plist, const):
        if name.endswith("_hc_phi"):
            assert now.dtype == was.dtype and not np.array_equal(
                np.asarray(now), np.asarray(was))
            assert np.abs(np.asarray(now) - np.asarray(was)).max() \
                < 2.0 ** -8 * np.abs(np.asarray(was)).max()
        else:
            assert now is was
