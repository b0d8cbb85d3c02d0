"""The parallel-hybrid LM of ``decode/falcon_h1.py`` against the benchmark's
plain reference (``benchmark/reference/falcon_h1.py``, the one copy there is)
at a tiny size — 3 layers, two state-space groups, 4 query heads over 2 K/V
heads, a scan chunk of 8, 16-token blocks, every multiplier another number —
in float32 so that the comparison is tight; through a real ``DecodeEngine``;
and what the engine serves and refuses for this model."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import falcon_h1 as ref  # noqa: E402

from paddle_tpu.decode import (DecodeEngine, PagedBeamDecoder,  # noqa: E402
                               SamplingParams, load_lm, save_lm)
from paddle_tpu.decode.cache import HybridStateCache  # noqa: E402
from paddle_tpu.decode.falcon_h1 import (FalconH1Config,  # noqa: E402
                                         FalconH1LM, mup_vector,
                                         param_shapes, rotary)
from paddle_tpu.observability import stats  # noqa: E402

V, BS, NB, SLOTS, L = 96, 16, 24, 2, 3
MULTIPLIERS = dict(
    embedding_multiplier=1.7, lm_head_multiplier=0.6,
    attention_in_multiplier=0.9, attention_out_multiplier=0.8,
    key_multiplier=1.3, ssm_in_multiplier=0.75, ssm_out_multiplier=1.2,
    ssm_multipliers=(0.9, 1.1, 0.8, 1.25, 0.7), mlp_multipliers=(1.4, 0.65))
CFG = FalconH1Config(
    vocab_size=V, hidden_size=64, num_hidden_layers=L, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, intermediate_size=128,
    mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_n_groups=2,
    mamba_d_state=32, mamba_d_conv=4, mamba_chunk_size=8, max_seq_len=96,
    dtype="float32", **MULTIPLIERS)


@pytest.fixture(scope="module")
def model():
    m = FalconH1LM(CFG)
    params = m.init_params(1)
    return m, params, m.param_list(params)


def _ref(params, toks, at=None, cfg=CFG):
    at = np.arange(len(toks)) if at is None else at
    lg, S, _ = ref.forward({k: jnp.asarray(v) for k, v in params.items()},
                           cfg.to_dict(), np.asarray(toks, np.int32),
                           len(toks), at)
    return np.asarray(lg), np.asarray(S)


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


def test_the_published_shapes_and_their_parameter_count():
    big = FalconH1Config(
        vocab_size=261120, hidden_size=5120, num_hidden_layers=72,
        num_attention_heads=20, num_key_value_heads=4, head_dim=128,
        intermediate_size=21504, mamba_d_ssm=4096, mamba_n_heads=32,
        mamba_d_head=128, mamba_n_groups=2, mamba_d_state=256, mamba_d_conv=4)
    shapes = param_shapes(big)
    assert shapes["lay.in_proj"][0] == (72, 5120, 9248)
    assert shapes["lay.conv_w"][0] == (72, 4, 5120)
    assert shapes["lay.out_proj"][0] == (72, 4096, 5120)
    assert shapes["lay.wqkv"][0] == (72, 5120, 2560 + 512 + 512)
    assert shapes["lay.wo"][0] == (72, 2560, 5120)
    assert shapes["lay.mlp_gate"][0] == (72, 5120, 21504)
    assert shapes["emb"][0] == shapes["head"][0] == (261120, 5120)
    per_layer = sum(int(np.prod(s[1:])) for k, (s, _) in shapes.items()
                    if k.startswith("lay."))
    top = sum(int(np.prod(s)) for k, (s, _) in shapes.items()
              if not k.startswith("lay."))
    # 430.1 M a layer; embedding + untied head 2,673.9 M (+ the final norm)
    assert per_layer == 47_349_760 + 20_971_520 + 25_600 + 96 + 4096 \
        + 31_457_280 + 330_301_440 + 2 * 5120 == 430_120_032
    assert top == 2 * 261120 * 5120 + 5120 == 2_673_873_920
    assert 72 * per_layer + top == 33_642_516_224       # "34B"
    # a cached token a layer: 2,048 B in bf16; a slot's row a layer: 4.19 MB
    assert 2 * big.kv_width * 2 == 2048
    assert int(np.prod(big.state_shape)) * 4 == 4_194_304
    assert big.conv_width == 5120 and big.in_width == 9248
    mu = mup_vector(big)
    assert mu.shape == (9248,)
    assert [float(mu[i]) for i in (0, 4096, 8192, 8704, 9216)] == \
        [1.0] * 5
    for bad in ({"mamba_d_ssm": 48}, {"mamba_n_groups": 3},
                {"num_key_value_heads": 3}, {"ssm_multipliers": (1.0,) * 4}):
        with pytest.raises(ValueError):
            dataclasses.replace(CFG, **bad)


def test_mu_repeats_the_five_multipliers_over_their_segments():
    mu = mup_vector(CFG)
    widths = (64, 64, 64, 64, 4)       # z, x, B (2 x 32), C, dt
    assert mu.shape == (sum(widths),)
    at = 0
    for w, m in zip(widths, CFG.ssm_multipliers):
        assert np.all(mu[at:at + w] == np.float32(m))
        at += w


def test_rotary_is_the_reference_s_and_keeps_relative_position():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((12, 3, 16)), jnp.float32)
    got = rotary(x, jnp.arange(12), 1e11)
    np.testing.assert_allclose(got, ref.rotate(x, 1e11), rtol=1e-6,
                               atol=1e-6)
    # q_t . k_s depends on t - s alone
    q, k = x[:1], x[1:2]
    a = jnp.sum(rotary(q, jnp.asarray([7]), 1e4)
                * rotary(k, jnp.asarray([3]), 1e4))
    b = jnp.sum(rotary(q, jnp.asarray([9]), 1e4)
                * rotary(k, jnp.asarray([5]), 1e4))
    np.testing.assert_allclose(a, b, rtol=1e-5)


def test_full_forward_matches_the_reference(model):
    m, params, pl = model
    rng = np.random.default_rng(0)
    toks = rng.integers(0, V, size=(2, 24)).astype(np.int32)
    got = np.asarray(jax.jit(m.full_logits)(pl, jnp.asarray(toks)))
    for b in range(2):
        want, _ = _ref(params, toks[b])
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("which", [
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers.0", "ssm_multipliers.1",
    "ssm_multipliers.2", "ssm_multipliers.3", "ssm_multipliers.4",
    "mlp_multipliers.0", "mlp_multipliers.1"])
def test_every_multiplier_matters_and_is_where_the_reference_has_it(
        model, which):
    """Another value of one multiplier moves the logits, and moves them as it
    moves the reference's."""
    _, params, _ = model
    name, _, at = which.partition(".")
    value = getattr(CFG, name)
    if at:
        value = tuple(v * (0.5 if i == int(at) else 1.0)
                      for i, v in enumerate(value))
    else:
        value = value * 0.5
    cfg = dataclasses.replace(CFG, **{name: value})
    m = FalconH1LM(cfg)
    toks = np.random.default_rng(3).integers(0, V, size=20).astype(np.int32)
    got = np.asarray(m.full_logits(m.param_list(params),
                                   jnp.asarray(toks[None])))[0]
    base = np.asarray(FalconH1LM(CFG).full_logits(
        m.param_list(params), jnp.asarray(toks[None])))[0]
    assert np.abs(got - base).max() > 1e-3
    want, _ = _ref(params, toks, cfg=cfg)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n,bucket", [(5, 16), (8, 16), (9, 16), (16, 16),
                                      (17, 32), (29, 32)])
def test_a_prefill_equals_the_full_forward_at_the_last_position(
        model, n, bucket):
    """Prompts shorter than a chunk (8), on its boundary, one past it; the
    recurrent rows are the reference's after the last real position."""
    m, params, pl = model
    prompt = np.random.default_rng(n).integers(0, V, size=n).astype(np.int32)
    cache = m.make_cache(NB, BS, "float32", slots=SLOTS)
    table = np.zeros((6,), np.int32)
    table[:2] = [3, 4]
    before = stats.to_dict().get("ssm.ssd_fallbacks", 0)
    (tok, logits), state = _prefill(m, pl, cache.state(), prompt, bucket, 1,
                                    table)
    assert stats.to_dict().get("ssm.ssd_fallbacks", 0) == before
    want, S = _ref(params, prompt, np.asarray([n - 1]))
    np.testing.assert_allclose(logits, want[0], rtol=2e-4, atol=2e-4)
    assert int(tok) == int(want[0].argmax())
    np.testing.assert_allclose(np.asarray(state[1])[:, 1], S, rtol=2e-4,
                               atol=1e-5)
    assert not np.asarray(state[1])[:, 0].any()     # slot 0 was not touched
    kv = np.asarray(state[0])
    assert kv[:, 3].any() and kv.shape[0] == L      # every layer wrote rows


def test_pad_positions_leave_pool_rows_and_tail_as_the_unpadded_prompt_does(
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
    for a, b in zip(*states):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    _, S, conv = states[0][1:]
    assert S[:, 0].any() and conv[:, 0].any() and not S[:, 1].any()
    # a prompt shorter than the convolution: the tail starts with zeros
    cache = m.make_cache(NB, BS, "float32", slots=SLOTS)
    _, state = _prefill(m, pl, cache.state(), prompt[:2], 16, 0, table)
    conv = np.asarray(state[2])
    assert not conv[:, 0, 0].any() and conv[:, 0, 1:].all()


def test_prefill_then_decode_through_the_engine_matches_the_reference(model):
    """Prompts around a chunk (8), a block (16) and the buckets (16, 32); six
    streams on two slots, so every slot is reused by a join after a leave;
    every generated position's logits against the reference's full
    forward."""
    m, params, _ = model
    eng = DecodeEngine(m, params, name="fh", max_slots=SLOTS,
                       block_tokens=BS, num_blocks=NB,
                       prefill_buckets=[16, 32], capture_logits=True,
                       prefix_cache=False, overcommit=False)
    try:
        assert isinstance(eng.cache, HybridStateCache)
        assert eng.cache.rings is None and len(eng.cache.state()) == 3
        assert eng.cache.kv.shape == (L, NB, BS, 2 * CFG.kv_width)
        assert eng.cache.h.shape == (L, SLOTS, 4, 32, 16)
        assert eng.cache.conv.shape == (L, SLOTS, 3, CFG.conv_width)
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
        assert z["cache"]["kind"] == "hybrid"
        assert "window" not in z["cache"] \
            and "window_state_bytes" not in z["cache"]
        assert z["cache"]["bytes"] == eng.cache.nbytes == \
            z["cache"]["kv_pool_bytes"] + z["cache"]["recurrent_state_bytes"]
        assert z["cache"]["kv_pool_bytes"] == L * NB * BS * 2 * 32 * 4
        assert z["cache"]["recurrent_state_bytes"] == \
            L * SLOTS * (4 * 32 * 16 * 4 + 3 * CFG.conv_width * 4)
        assert z["cache"]["kv_live_tokens"] > 0
        assert z["joins"] == z["leaves"] == 6
        assert z["cache"]["free_blocks"] == NB - 1      # released at a leave
        c = stats.to_dict()
        name = "decode.fh."
        assert c[name + "prefill_real_tokens"] == 5 + 8 + 13 + 16 + 17 + 32
        assert c[name + "prefill_pad_tokens"] == 11 + 8 + 3 + 0 + 15 + 0
        assert c[name + "prefill_scan_chunks"] == 1 + 1 + 2 + 2 + 3 + 4
        assert c[name + "prefill_tokens_sq"] == sum(
            n * n for n in (5, 8, 13, 16, 17, 32))
        assert c[name + "step_streams"] == sum(outs) - 6
        assert c[name + "step_state_bytes"] == \
            c[name + "step_streams"] * L * 2 * 4 * 4 * 32 * 16
        assert c[name + "step_context_tokens"] > c[name + "step_streams"]
        assert c[name + "kv_pool_bytes"] == eng.cache.kv_pool_bytes
        assert c[name + "recurrent_state_bytes"] == \
            eng.cache.recurrent_state_bytes
        assert c.get("ssm.ssd_fallbacks", 0) == 0
    finally:
        eng.close()


def test_the_observer_s_spans_carry_what_each_launch_added_to_the_counters(
        model, monkeypatch):
    from paddle_tpu.decode import falcon_h1
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

    monkeypatch.setattr(falcon_h1._trace, "span",
                        lambda name, **a: Span(name))
    m, _, _ = model
    cache = m.make_cache(NB, BS, "float32", slots=SLOTS)
    obs = m.observer("fh_o", cache, (SLOTS, 8))
    before = stats.to_dict()
    obs.prefill([], 13, 16)
    obs.step([], np.asarray([51, 6]))
    after = stats.to_dict()
    assert filed[0] == ("decode::prefill.observe", {
        "prefill_real_tokens": 13, "prefill_pad_tokens": 3,
        "prefill_scan_chunks": 2, "prefill_tokens_sq": 169})
    assert filed[1] == ("decode::step.observe", {
        "step_context_tokens": 57, "step_streams": 2,
        "step_state_bytes": 2 * L * 2 * 4 * 4 * 32 * 16})
    for _, args in filed:
        for key, value in args.items():
            name = "decode.fh_o." + key
            assert after[name] - before.get(name, 0) == value
    assert cache.snapshot()["kv_live_tokens"] == 57


def test_save_and_load_round_trip_in_bfloat16(tmp_path):
    m = FalconH1LM(dataclasses.replace(CFG, dtype="bfloat16"))
    params = m.init_params(3)
    assert params["lay.in_proj"].dtype == jnp.bfloat16
    save_lm(str(tmp_path), m.config, params)
    m2, p2 = load_lm(str(tmp_path))
    assert isinstance(m2, FalconH1LM) and m2.config == m.config
    assert set(p2) == set(params)
    for k in params:
        assert np.array_equal(np.asarray(p2[k], np.float32),
                              np.asarray(params[k], np.float32))


def test_what_the_engine_and_the_beam_session_refuse_for_it(model):
    m, params, _ = model
    for kw in ({"prefix_cache": True}, {"overcommit": True}):
        with pytest.raises(ValueError, match="does not support"):
            DecodeEngine(m, params, name="fh_r", max_slots=2,
                         block_tokens=BS, num_blocks=NB,
                         prefill_buckets=[16], **{"prefix_cache": False,
                                                  "overcommit": False, **kw})
    with pytest.raises(ValueError, match="does not support beam"):
        PagedBeamDecoder(m, params, beam_size=2, end_id=1)
    with pytest.raises(ValueError, match="no int8 form"):
        m.make_cache(NB, BS, "int8", slots=2)
    with pytest.raises(ValueError, match="slot count"):
        m.make_cache(NB, BS, "float32")
