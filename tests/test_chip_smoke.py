"""chip_smoke.py: it must refuse to run without a TPU, and its phase
functions must work — rehearsed here at tiny size on the CPU mesh (the
script's ``__main__`` has no size switch; the phases take their sizes as
arguments)."""
import json
import os
import subprocess
import sys
import time

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dict(vocab=64, d_model=32, n_head=2, d_ffn=64, n_layer=1)


@pytest.fixture(autouse=True)
def compile_log():
    if chip_smoke.LOG is None:
        chip_smoke.LOG = chip_smoke.CompileLog()
    return chip_smoke.LOG


def test_exits_nonzero_at_once_without_a_tpu():
    """``JAX_PLATFORMS=cpu python chip_smoke.py``: non-zero exit within
    seconds, the platform found named on stderr, no result line."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=120)
    assert time.monotonic() - t0 < 60
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_fails_outside_the_repo(tmp_path):
    """Alone in a directory (no ``paddle_tpu`` beside it) the script
    cannot pass: non-zero exit, no JSON."""
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                       cwd=str(tmp_path), capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert not r.stdout.strip().startswith("{")


def test_trainer_phase_tiny():
    out = chip_smoke.phase_trainer(None, batch=4, max_len=8, dtype="float32",
                                   dropout=0.1, steps=3, scan_steps=2,
                                   **TINY)
    assert out["last_loss"] < out["first_loss"]
    assert out["executor_compiles"] == 3 and out["steps"] == 3 + 2 * 2
    # run and run_steps each lowered the head once; off the chip, its fallback
    assert out["proj_xent"] == {"kernel": 0, "fallbacks": 2}


def test_kernels_phase_tiny():
    """Off-chip the kernels are forced in interpret mode and compared
    with the XLA lowering exactly as on the chip; only the Mosaic
    assertion is out of reach here."""
    out = chip_smoke.phase_kernels(
        None, on_chip=False,
        rnn=dict(batch=8, seq=4, hidden=128, layers=3),
        flash=(dict(batch=1, seq=128, n_head=2, head_dim=16),))
    assert set(out) == {"lstm", "gru", "flash_d16_t128"}
    for case in out.values():
        assert case["mosaic_custom_call"] is False
        assert case["max_rel_err"] <= case["tolerance"]


def test_decode_server_phase_tiny():
    # the phase reads the engine's counters, which are per model name and
    # process-wide: an "lm" engine of an earlier test file must not count
    from paddle_tpu import observability
    observability.reset()
    out = chip_smoke.phase_decode_server(
        on_chip=False, max_seq_len=64, max_slots=2,
        prompt_lens=(3, 9, 5), new_tokens=(6, 3, 4), **TINY)
    assert out["attn_impl"] == "pallas" and out["transport"] == "native"
    assert out["requests"] == 6 and out["joins"] == 6
    assert out["tokens_checked"] == 13
    assert out["tokens_exact"] == 13      # f32 on the CPU: no near-ties
    # the pool-in-place record: the step and a prefill were compiled
    # with the pool donated; a prefill holds no copy of the pool even
    # here (only the interpreted kernel's loop copies it, off the chip)
    rec = out["pool_in_place"]
    assert rec["pool_bytes"] > 0 and set(rec) == {"pool_bytes", "step",
                                                  "prefill"}
    assert rec["prefill"]["pool_copies"] == 0
    assert rec["prefill"]["temp_bytes"] < rec["pool_bytes"]


def test_latent_lm_phase_tiny():
    out = chip_smoke.phase_latent_lm(
        on_chip=False, vocab=64, hidden=32, heads=2, nope=16, rope=8,
        v_dim=16, rank=32, dense=48, expert=16, experts=4, top_k=2, layers=2,
        max_seq_len=64, max_slots=2, block_tokens=4, prefill_bucket=16,
        prompt_lens=(5, 16, 9), new_tokens=(6, 3, 4), dtype="float32")
    assert out["tokens_checked"] == 13 and out["tokens_exact"] == 13
    assert out["cache"]["kind"] == "latent" and out["cache"]["row_width"] == 128
    assert not any(out["fallbacks"].values())
    rec = out["pool_in_place"]
    assert set(rec) == {"pool_bytes", "step", "prefill"}
    assert rec["prefill"]["pool_copies"] == 0


def test_hybrid_lm_phase_tiny():
    out = chip_smoke.phase_hybrid_lm(
        vocab=64, hidden=256, heads=4, kv_heads=2, ffn=128,
        layers=8, window=8, max_seq_len=64, max_slots=2, block_tokens=16,
        prefill_bucket=16, prompt_len=11, new_tokens=14, dtype="float32")
    assert out["tokens_checked"] == 14 and out["tokens_exact"] == 14
    assert out["logit_err_max"] < 1e-4
    assert out["cache"]["kind"] == "hybrid"
    assert out["cache"]["window_state_bytes"] == 2 * 2 * 8 * 256 * 4
    assert not any(out["fallbacks"].values())


def test_parallel_hybrid_lm_phase_tiny():
    out = chip_smoke.phase_parallel_hybrid_lm(
        vocab=64, hidden=64, heads=2, kv_heads=1, head_dim=128, ffn=128,
        layers=2, ssm_heads=4, ssm_head_dim=16, groups=2, d_state=32,
        chunk=8, max_seq_len=64, max_slots=2, block_tokens=16,
        prefill_bucket=16, prompt_len=11, new_tokens=14, dtype="float32")
    assert out["tokens_checked"] == 14 and out["tokens_exact"] == 14
    assert out["logit_err_max"] < 1e-4
    assert out["cache"]["kind"] == "hybrid"
    assert "window_state_bytes" not in out["cache"]
    assert out["cache"]["recurrent_state_bytes"] == \
        2 * 2 * (4 * 32 * 16 * 4 + 3 * (64 + 2 * 64) * 4)
    assert not any(out["fallbacks"].values())


def test_window_expert_lm_phase_tiny():
    out = chip_smoke.phase_window_expert_lm(
        vocab=64, hidden=64, heads=2, kv_heads=1, head_dim=128,
        expert_ffn=32, experts=8, top_k=3, layers=4, window=32,
        max_seq_len=96, max_slots=2, block_tokens=16, prefill_bucket=64,
        prompt_len=45, new_tokens=14, dtype="float32")
    assert out["tokens_checked"] == 14 and out["tokens_exact"] == 14
    assert out["logit_err_max"] < 1e-4
    assert out["cache"]["kind"] == "hybrid"
    assert out["cache"]["window_state_bytes"] == 3 * 2 * 32 * 256 * 4
    assert "recurrent_state_bytes" not in out["cache"]
    assert not any(out["fallbacks"].values())


def test_conv_expert_lm_phase_tiny():
    out = chip_smoke.phase_conv_expert_lm(
        vocab=64, hidden=64, heads=4, kv_heads=2, head_dim=64, ffn=96,
        expert_ffn=32, experts=8, top_k=2, max_seq_len=96, max_slots=2,
        block_tokens=16, prefill_bucket=64, prompt_len=45, new_tokens=10,
        dtype="float32")
    assert out["tokens_checked"] == 10 and out["tokens_exact"] == 10
    assert out["logit_err_max"] < 1e-4
    assert out["cache"]["kind"] == "hybrid"
    assert out["cache"]["recurrent_state_bytes"] == 4 * 2 * 2 * 64 * 4
    assert "window_state_bytes" not in out["cache"]
    assert not any(out["fallbacks"].values())


def test_expert_walk_phase_tiny():
    out = chip_smoke.phase_expert_walk(
        on_chip=False, tokens=200, top_k=2, experts=8, hidden=128,
        expert_ffn=256)
    assert out["prompt"]["assignments"] == 400
    assert out["one_expert"]["experts_touched"] == 1
    assert out["one_expert"]["rows"] == 512
    # both plans have one shape: one lowering, by expert
    assert out["walks"] == {"expert_walks": 1, "tile_walks": 0,
                            "fallbacks": 0}
    assert max(out[p]["max_diff"] / out[p]["scale"]
               for p in ("prompt", "one_expert")) < 1e-2


def test_expert_plan_phase_tiny():
    out = chip_smoke.phase_expert_plan(
        shapes=(("a_step", 24, 2, 8, 0), ("a_prompt_share", 200, 3, 16, 8)),
        experts=8, hidden=128, expert_ffn=256)
    assert sorted(out) == ["a_prompt_share/one_expert",
                           "a_prompt_share/uniform", "a_step/one_expert",
                           "a_step/uniform"]
    assert out["a_step/one_expert"]["experts_touched"] == 1
    assert out["a_step/uniform"]["assignments"] == 2 * (24 - 24 // 7)
    assert out["a_prompt_share/one_expert"]["assignments"] == 3 * 172
    assert all(v["output_scale"] > 0 for v in out.values())


def test_group_flash_phase_tiny(monkeypatch):
    from paddle_tpu.kernels import gqa
    monkeypatch.setattr(gqa, "_Q_TILE", 8)
    monkeypatch.setattr(gqa, "_K_TILE", 32)
    out = chip_smoke.phase_group_flash(
        on_chip=False, tokens=96, window=20, block=32,
        layouts=(("group_of_7", 14, 2, 128), ("pairs_of_64", 16, 4, 64)))
    assert out["group_of_7_full"]["plan"] == [8, 32, 3]
    assert out["group_of_7_window"]["plan"] == [8, 32, 2]
    assert set(out) == {"group_of_7_window", "group_of_7_full",
                        "pairs_of_64_full", "fallbacks"}
    assert all(out[c]["pad_rows_zero"] and out[c]["max_row_diff"] < 2e-2
               for c in out if c != "fallbacks")
    assert out["fallbacks"] == 0


def test_proj_xent_phase_tiny():
    out = chip_smoke.phase_proj_xent(
        on_chip=False, batch=2, seq=128, d=128, vocab=300,
        tiles=((128, 128), (256, 256)), calls=(1, 2))
    assert sorted(out["ms_a_call"]) == [
        "kernel 128 x 128", "kernel 256 x 256", "xla: matmul + logsumexp",
        "xla: matmul alone"]
    # the CPU's float32 product does not round the rows to bf16: loose here,
    # the product's last bits on the chip
    assert out["logits_max_diff"] <= 2e-2 * out["scale"]
    assert out["lse_max_rel"] < 1e-3 and out["logits_differing"] > 0
    assert out["gflop_a_call"] == pytest.approx(2e-9 * 256 * 128 * 300)


def test_latent_walk_phase_tiny():
    out = chip_smoke.phase_latent_walk(
        on_chip=False, slots=3, heads=(4,), rank=32, rope=8, block=4,
        pools=(("long", 40, 90), ("short", 6, 10)), calls=(1, 2))
    assert sorted(out) == ["4_heads_long", "4_heads_short", "fallbacks"]
    assert out["fallbacks"] == 0
    for name in ("4_heads_long", "4_heads_short"):
        row = out[name]
        # the kernel rounds a chunk's weights to the pool's bf16
        assert row["max_diff"] <= 1e-2 * row["scale"]
        assert row["ms_at_hbm_peak"] == pytest.approx(
            1e3 * row["live_tokens"] * 40 * 2 / 819e9, abs=1e-4)
        assert {"ms_a_call", "ms_copies_taken_out"} <= set(row)


def test_four_chip_phase_tiny():
    """dp=2 x mp=2 and ZeRO dp=4 on four devices of the CPU mesh, loss
    parity against the one-device run of the same program."""
    sizes = dict(batch=4, max_len=8, dtype="float32", dropout=0.1, **TINY)
    ref = chip_smoke.phase_trainer(None, steps=2, scan_steps=1, **sizes)
    out = chip_smoke.phase_four_chip(None, jax.devices()[:4],
                                     ref["first_loss"], steps=2, **sizes)
    assert out["rel_diff"] <= 1e-2
    assert len(out["mesh_losses"]) == 2


def test_run_phase_records_failure_and_goes_on():
    report = {"phases": {}}

    def boom():
        chip_smoke.check(False, "a check that does not hold")

    assert chip_smoke.run_phase(report, "p", boom) is None
    assert report["phases"]["p"]["status"] == "failed"
    assert "does not hold" in report["phases"]["p"]["error"]
    assert chip_smoke.run_phase(report, "q", lambda: {"x": 1}) == {"x": 1}
    assert report["phases"]["q"]["status"] == "ok"
    json.dumps(report)


def test_result_line_is_exactly_ok_and_device():
    """The driver parses the last stdout line: ``ok`` and ``device``
    (``platform``, ``kind``, ``count``) and no other key — the detail
    goes on the report line before it."""
    report = {"ok": True, "phases": {"trainer": {"status": "ok"}},
              "env": {"jax": "x"}, "fallback_counters": {},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1}}
    line = chip_smoke.result_line(report)
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    report["ok"] = False
    assert json.loads(chip_smoke.result_line(report))["ok"] is False
