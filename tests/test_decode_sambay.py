"""The decoder-hybrid-decoder LM of ``decode/sambay.py`` against the
benchmark's plain reference (``benchmark/reference/sambay.py``, the one copy
there is) at a tiny size — 8 layers, so that every kind of layer and both
decoders exist; a window of 8; 16-token blocks — in float32 so that the
comparison is tight; through a real ``DecodeEngine``; and what the engine
serves and refuses for this model."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import sambay as ref  # noqa: E402

from paddle_tpu.decode import (DecodeEngine, PagedBeamDecoder,  # noqa: E402
                               SamplingParams, load_lm, save_lm)
from paddle_tpu.decode.cache import HybridStateCache  # noqa: E402
from paddle_tpu.decode.sambay import (SambaYConfig, SambaYLM,  # noqa: E402
                                      lambda_init, param_shapes)
from paddle_tpu.observability import stats  # noqa: E402

V, W, BS, NB, SLOTS = 96, 8, 16, 24, 2
CFG = SambaYConfig(vocab_size=V, hidden_size=256, num_hidden_layers=8,
                   num_attention_heads=4, num_key_value_heads=2,
                   intermediate_size=384, sliding_window=W, max_seq_len=96,
                   dtype="float32")


@pytest.fixture(scope="module")
def model():
    m = SambaYLM(CFG)
    params = m.init_params(1)
    return m, params, m.param_list(params)


def _ref(params, toks, at=None):
    at = np.arange(len(toks)) if at is None else at
    lg, h, _ = ref.forward({k: jnp.asarray(v) for k, v in params.items()},
                        CFG.to_dict(), np.asarray(toks, np.int32), len(toks),
                        at)
    return np.asarray(lg), np.asarray(h)


def _prefill(m, pl, state, prompt, bucket, slot, table):
    tk = np.zeros((1, bucket), np.int32)
    tk[0, :len(prompt)] = prompt
    jits = m.__dict__.setdefault("_test_jits", {})
    if "prefill" not in jits:
        jits["prefill"] = jax.jit(m.prefill)
    return jits["prefill"](
        pl, state, jnp.asarray(tk), jnp.int32(len(prompt)), jnp.int32(slot),
        jnp.asarray(table, jnp.int32), jnp.uint32(0), jnp.float32(0.0),
        jnp.int32(0))


def test_the_stack_s_layout_is_the_published_one():
    big = SambaYConfig(vocab_size=200064, hidden_size=2560,
                       num_hidden_layers=32, num_attention_heads=40,
                       num_key_value_heads=20, intermediate_size=10240,
                       sliding_window=512)
    shapes = param_shapes(big)
    # eight (state-space, window) pairs, layers 16 and 17 between, seven
    # (memory unit, cross attention) pairs: 9 + 8 + 1 + 7 + 7 = 32 layers
    assert shapes["sp.s.in_proj"][0] == (8, 2560, 10240)
    assert shapes["sp.w.wqkv"][0] == (8, 2560, 2560 + 2 * 1280)
    assert shapes["ms.x_proj"][0] == (5120, 160 + 32)
    assert shapes["mf.wqkv"][0] == (2560, 5120)
    assert shapes["cp.g.w1"][0] == (7, 2560, 5120)
    assert shapes["cp.c.wq"][0] == (7, 2560, 2560)
    assert "cp.c.wqkv" not in shapes        # cross attention has no K/V
    assert shapes["emb"][0] == (200064, 2560) and "head" not in shapes
    kinds = [ref.layer_kind(big.to_dict(), i) for i in range(32)]
    assert kinds[:18] == ["ssm", "swa"] * 8 + ["ssm", "full"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    # 3.85 B parameters (the model is "3.8B"), 5,120 B a cached token
    n = sum(int(np.prod(s)) for s, _ in param_shapes(big).values())
    assert n == 3_852_562_944
    assert 2 * big.kv_width * 2 == 5120 and big.d_inner == 5120 \
        and big.rank == 160 and big.n_heads == 20 and big.n_kv == 10
    assert abs(float(lambda_init(17)) - (0.8 - 0.6 * np.exp(-5.1))) < 1e-6
    for bad in ({"num_hidden_layers": 6}, {"mb_per_layer": 1},
                {"num_key_value_heads": 3}):
        with pytest.raises(ValueError):
            dataclasses.replace(CFG, **bad)


def test_full_forward_matches_the_reference(model):
    m, params, pl = model
    rng = np.random.default_rng(0)
    toks = rng.integers(0, V, size=(2, 24)).astype(np.int32)
    got = np.asarray(jax.jit(m.full_logits)(pl, jnp.asarray(toks)))
    for b in range(2):
        want, _ = _ref(params, toks[b])
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n,bucket", [(5, 16), (8, 16), (13, 16), (16, 16),
                                      (17, 32), (29, 32)])
def test_the_short_prefill_equals_the_full_forward_at_the_last_position(
        model, n, bucket):
    """Layers above the full-attention layer run for the last real position
    alone; the recurrent state is the reference's after that position."""
    m, params, pl = model
    prompt = np.random.default_rng(n).integers(0, V, size=n).astype(np.int32)
    cache = m.make_cache(NB, BS, "float32", slots=SLOTS)
    table = np.zeros((6,), np.int32)
    table[:2] = [3, 4]
    (tok, logits), state = _prefill(m, pl, cache.state(), prompt, bucket, 1,
                                    table)
    full = np.asarray(m.full_logits(pl, jnp.asarray(prompt[None])))[0]
    np.testing.assert_allclose(logits, full[n - 1], rtol=2e-4, atol=2e-4)
    assert int(tok) == int(full[n - 1].argmax())
    want, h = _ref(params, prompt, np.asarray([n - 1]))
    np.testing.assert_allclose(logits, want[0], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(state[2])[:, 1], h, rtol=2e-4,
                               atol=1e-5)
    assert not np.asarray(state[2])[:, 0].any()     # slot 0 was not touched


def test_pad_positions_leave_state_tail_and_ring_as_the_unpadded_prompt_does(
        model):
    m, _, pl = model
    prompt = np.random.default_rng(7).integers(0, V, size=13).astype(np.int32)
    table = np.asarray([5, 6, 0, 0, 0, 0], np.int32)
    states = []
    for bucket in (16, 32):
        cache = m.make_cache(NB, BS, "float32", slots=SLOTS)
        (_, logits), state = _prefill(m, pl, cache.state(), prompt, bucket,
                                      0, table)
        kv = np.asarray(state[0])[:, 1:]    # block 0 is the pads' trash
        states.append([np.asarray(logits), kv]
                      + [np.asarray(a) for a in state[1:]])
    nrb = W // min(W, HybridStateCache.RING_ROWS)
    for a, b in zip(*states):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    _, rings, h, conv = states[0][1:]
    assert rings[:, :nrb].any() and not rings[:, nrb:].any()
    assert h[:, 0].any() and conv[:, 0].any()
    # a prompt shorter than the convolution: the tail starts with zeros
    cache = m.make_cache(NB, BS, "float32", slots=SLOTS)
    _, state = _prefill(m, pl, cache.state(), prompt[:2], 16, 0, table)
    conv = np.asarray(state[3])
    assert not conv[:, 0, 0].any() and conv[:, 0, 1:].all()


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_prefill_then_decode_through_the_engine_matches_the_reference(
        model, impl):
    """Prompts shorter than, equal to and longer than the window, on and
    across block (16) and bucket (16, 32) edges; six streams on two slots,
    so every slot is reused; every generated position's logits against the
    reference's full forward."""
    m, params, _ = model
    eng = DecodeEngine(m, params, name=f"sy_{impl}", max_slots=SLOTS,
                       block_tokens=BS, num_blocks=NB,
                       prefill_buckets=[16, 32], capture_logits=True,
                       attn_impl=impl, prefix_cache=False, overcommit=False)
    try:
        assert isinstance(eng.cache, HybridStateCache)
        ring_bytes = eng.cache.window_state_bytes
        assert ring_bytes == 2 * SLOTS * W * 2 * CFG.kv_width * 4
        rng = np.random.default_rng(4)
        prompts = [rng.integers(0, V, size=n).astype(np.int32)
                   for n in (5, 8, 13, 16, 17, 32)]
        outs = (20, 12, 9, 18, 16, 5)
        hs = [eng.submit(p, SamplingParams(max_new_tokens=n))
              for p, n in zip(prompts, outs)]
        for p, h, n in zip(prompts, hs, outs):
            toks = h.result(timeout=600.0)["tokens"]
            assert len(toks) == n
            seq = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
            want, _ = _ref(params, seq, np.arange(len(p) - 1, len(seq)))
            np.testing.assert_allclose(np.stack(h.logits), want, rtol=3e-4,
                                       atol=3e-4)
        z = eng.decodez()
        # window state is bounded by the window: contexts reached 37 tokens
        assert eng.cache.window_state_bytes == ring_bytes
        assert z["cache"]["kind"] == "hybrid"
        assert z["cache"]["window_state_bytes"] == ring_bytes
        assert z["cache"]["bytes"] == eng.cache.nbytes == \
            z["cache"]["kv_pool_bytes"] + ring_bytes \
            + z["cache"]["recurrent_state_bytes"]
        assert z["cache"]["kv_live_tokens"] > 0
        assert z["joins"] == z["leaves"] == 6
        c = stats.to_dict()
        name = f"decode.sy_{impl}."
        assert c[name + "prefill_real_tokens"] == 5 + 8 + 13 + 16 + 17 + 32
        assert c[name + "prefill_pad_tokens"] == 11 + 8 + 3 + 0 + 15 + 0
        assert c[name + "prefill_scan_tokens"] == 91 * 3
        assert c[name + "step_streams"] == sum(outs) - 6
        assert c[name + "step_window_tokens"] <= W * c[name + "step_streams"]
        assert c[name + "step_context_tokens"] > c[name + "step_window_tokens"]
        assert c[name + "window_state_bytes"] == ring_bytes
        assert c[name + "kv_pool_bytes"] == eng.cache.kv_pool_bytes
        assert c[name + "recurrent_state_bytes"] == \
            eng.cache.recurrent_state_bytes
    finally:
        eng.close()


def test_the_observer_s_spans_carry_what_each_launch_added_to_the_counters(
        model, monkeypatch):
    from paddle_tpu.decode import sambay
    filed = []

    class Span:
        def __init__(self, name):
            self.name, self.args = name, {}

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            filed.append((self.name, self.args))

        def annotate(self, **args):
            self.args.update(args)

    monkeypatch.setattr(sambay._trace, "span", lambda name, **a: Span(name))
    m, _, _ = model
    cache = m.make_cache(NB, BS, "float32", slots=SLOTS)
    obs = m.observer("sy_o", cache, (SLOTS, 8))
    before = stats.to_dict()
    obs.prefill([], 13, 16)
    obs.step([], np.asarray([51, 6]))
    after = stats.to_dict()
    assert filed[0] == ("decode::prefill.observe", {
        "prefill_scan_tokens": 39, "prefill_window_pairs": 36 + 5 * 8,
        "prefill_tokens_sq": 169})
    assert filed[1] == ("decode::step.observe", {
        "step_context_tokens": 57, "step_window_tokens": 14,
        "step_streams": 2})
    for _, args in filed:
        for key, value in args.items():
            name = "decode.sy_o." + key
            assert after[name] - before.get(name, 0) == value
    assert cache.snapshot()["kv_live_tokens"] == 57


def test_save_and_load_round_trip_in_bfloat16(tmp_path):
    m = SambaYLM(dataclasses.replace(CFG, dtype="bfloat16"))
    params = m.init_params(3)
    assert params["sp.s.in_proj"].dtype == jnp.bfloat16
    save_lm(str(tmp_path), m.config, params)
    m2, p2 = load_lm(str(tmp_path))
    assert isinstance(m2, SambaYLM) and m2.config == m.config
    assert set(p2) == set(params)
    for k in params:
        assert np.array_equal(np.asarray(p2[k], np.float32),
                              np.asarray(params[k], np.float32))


def test_what_the_engine_and_the_beam_session_refuse_for_it(model):
    m, params, _ = model
    for kw in ({"prefix_cache": True}, {"overcommit": True}):
        with pytest.raises(ValueError, match="does not support"):
            DecodeEngine(m, params, name="sy_r", max_slots=2,
                         block_tokens=BS, num_blocks=NB,
                         prefill_buckets=[16], **{"prefix_cache": False,
                                                  "overcommit": False, **kw})
    with pytest.raises(ValueError, match="does not support beam"):
        PagedBeamDecoder(m, params, beam_size=2, end_id=1)
    with pytest.raises(ValueError, match="no int8 form"):
        m.make_cache(NB, BS, "int8", slots=2)
    with pytest.raises(ValueError, match="slot count"):
        m.make_cache(NB, BS, "float32")
    with pytest.raises(ValueError, match="not whole blocks"):
        HybridStateCache(128, NB, BS, 2, rings=(2, 24))
