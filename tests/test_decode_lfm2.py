"""``decode/lfm2.py`` at a small size on the CPU: prefill and decode steps
through a :class:`HybridStateCache` of a pool and convolution tails against
the plain reference (``benchmark/reference/lfm2_moe.py``) on seeded weights —
heads of 64 (a pair of K/V heads a lane tile) and of 128, prompts of one
token, of two, inside a rung and one that ends its rung exactly —, the same
through a ``DecodeEngine``, the cache's kinds, the observer's counts and what
the configuration refuses."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import lfm2_moe as reference  # noqa: E402
from paddle_tpu.decode import (DecodeEngine, LFM2Config,  # noqa: E402
                               LFM2LM, SamplingParams)
from paddle_tpu.decode.cache import HybridStateCache  # noqa: E402
from paddle_tpu.decode.lfm2 import param_shapes  # noqa: E402
from paddle_tpu.observability import stats  # noqa: E402

V, RUNGS, BS = 96, (16, 32, 64), 8


def raw_config(head_dim: int) -> dict:
    """Hidden 64 or 128, 8 experts at top-2, one dense layer and two periods;
    published key names."""
    nh, nkv = (4, 2) if head_dim == 64 else (2, 1)
    return dict(
        vocab_size=V, hidden_size=head_dim, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=nh,
        num_key_value_heads=nkv, head_dim=head_dim, num_hidden_layers=9,
        num_dense_layers=1,
        layer_types=["conv"] + ["full_attention", "conv", "conv", "conv"] * 9,
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
        use_expert_bias=True, routed_scaling_factor=1.0, norm_eps=1e-5,
        conv_L_cache=3, conv_bias=False,
        rope_parameters={"rope_theta": 1e6, "rope_type": "default"})


@pytest.fixture(scope="module", params=[64, 128], ids=["heads64", "heads128"])
def built(request):
    raw = raw_config(request.param)
    model = LFM2LM(LFM2Config.from_dict(
        {**raw, "dtype": "float32", "max_seq_len": 96}))
    params = model.init_params(7)
    return raw, model, params, model.param_list(params), \
        jax.jit(model.prefill), jax.jit(model.decode_step)


@pytest.mark.parametrize("P", [1, 2, 11, 16], ids=[
    "one_token", "two_tokens", "inside_a_rung", "ends_its_rung"])
def test_prefill_then_steps_through_pool_and_tails_equal_the_reference(
        built, P):
    raw, model, params, plist, prefill, step = built
    n, S, slot = 6, 3, 1
    rng = np.random.default_rng(P)
    seq = rng.integers(0, V, size=P + n).astype(np.int32)
    want, _, _ = reference.forward(params, raw, seq, P + n,
                                   np.arange(P - 1, P + n))
    want = np.asarray(want)
    cache = model.make_cache(24, BS, "float32", slots=S)
    assert isinstance(cache, HybridStateCache)
    bucket = next(r for r in RUNGS if r >= P)
    feed = np.zeros((1, bucket), np.int32)
    feed[0, :P] = seq[:P]
    table = np.zeros((8,), np.int32)
    table[:4] = [3, 5, 2, 9]
    outs, state = prefill(plist, cache.state(), feed, np.int32(P),
                          np.int32(slot), table, np.uint32(0),
                          np.float32(0), np.int32(0))
    np.testing.assert_allclose(outs[1], want[0], rtol=2e-4, atol=2e-4)
    assert int(outs[0]) == int(want[0].argmax())
    # the tail is z at the prompt's REAL last positions, zeros before it
    tails = np.asarray(state[1])[:, slot]
    if P == 1:
        assert not tails[:, 0].any() and tails[:, 1].any()
    tables = np.zeros((S, 8), np.int32)
    tables[slot] = table
    tokens, positions = np.zeros((S,), np.int32), np.zeros((S,), np.int32)
    zeros = np.zeros((S,), np.int32)
    for j in range(1, n + 1):
        tokens[slot], positions[slot] = seq[P + j - 1], P + j - 1
        outs, state = step(plist, state, tokens, positions, tables,
                           zeros.astype(np.uint32), zeros,
                           zeros.astype(np.float32), zeros)
        np.testing.assert_allclose(outs[1][slot], want[j], rtol=2e-4,
                                   atol=2e-4)
        load = np.asarray(outs[2])
        # one live slot: two assignments and two experts a layer; an idle
        # slot is routed nowhere
        assert (load[:, :3] == [2, 2, 1]).all() and load.shape == (8, 4)
        assert (load[:, 3] == 2 * 8).all()      # each padded to a row tile
    # the other slots' tails were scribbled on by idle rows only
    assert np.asarray(outs[5]).shape == (8, S, model.config.hidden_size)


def test_full_logits_is_the_reference_at_every_position(built):
    raw, model, params, plist, _, _ = built
    toks = np.random.default_rng(0).integers(0, V, size=(2, 24)).astype(
        np.int32)
    got = np.asarray(jax.jit(model.full_logits)(plist, toks))
    for b in range(2):
        want, own, own_stats = reference.forward(params, raw, toks[b], 24,
                                                 np.arange(24))
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-4)
    assert own.shape == (8, 24, 2)
    assert set(own_stats) == set(reference.STATS)
    assert own_stats["conv_rms"].shape == (7,) \
        and own_stats["attn_rms"].shape == (2,) \
        and own_stats["ffn_rms"].shape == (9,)


def test_an_engine_serves_it_as_it_is_and_its_observer_counts(built):
    raw, model, params, plist, _, _ = built
    name = f"lfm2t{model.config.head_dim}"
    engine = DecodeEngine(model, params, name=name, max_slots=3,
                          block_tokens=BS, num_blocks=40,
                          prefill_buckets=list(RUNGS), max_queue=8,
                          cache_dtype="float32")
    try:
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, V, size=n).astype(np.int32)
                   for n in (1, 2, 16, 23)]
        handles = [engine.submit(p, SamplingParams(temperature=0.0,
                                                   max_new_tokens=5))
                   for p in prompts]
        full = jax.jit(model.full_logits)
        for p, h in zip(prompts, handles):
            got = h.result(timeout=600.0)["tokens"]
            seq = list(p)
            for t in got:       # greedy, teacher-forced by its own tokens
                logits = np.asarray(full(plist, np.asarray([seq], np.int32)))
                assert int(logits[0, -1].argmax()) == t
                seq.append(t)
        c = stats.to_dict()
        k, layers = 2, 8
        real = sum(p.size for p in prompts)
        assert c[f"decode.{name}.prefill_real_tokens"] == real
        assert c[f"decode.{name}.prefill_routed_assignments"] == \
            real * k * layers           # no assignment dropped
        assert c[f"decode.{name}.prefill_moe_dispatches"] == 4 * layers
        assert c[f"decode.{name}.prefill_plan_rows"] \
            - c[f"decode.{name}.prefill_plan_pad_rows"] == real * k * layers
        assert c[f"decode.{name}.step_routed_assignments"] == \
            c[f"decode.{name}.step_streams"] * k * layers
        assert c[f"decode.{name}.step_context_tokens"] > 0
        z = engine.decodez()
        assert 0 < z["step_live_blocks"] <= z["step_table_blocks"]
        snap = z["cache"]
        assert snap["kind"] == "hybrid" and "window" not in snap
        assert snap["recurrent_state_bytes"] == 7 * 3 * 2 \
            * model.config.hidden_size * 4
    finally:
        engine.close()


def test_a_prefix_cache_overcommit_and_beams_are_refused():
    model = LFM2LM(LFM2Config.from_dict(
        {**raw_config(64), "dtype": "float32", "max_seq_len": 96}))
    assert model.supports == frozenset() and model.slot_state
    with pytest.raises(ValueError, match="slot"):
        model.make_cache(8, BS, "float32")
    for flag in ("prefix_cache", "overcommit"):
        with pytest.raises(ValueError):
            DecodeEngine(model, model.init_params(0), name="lfm2no",
                         max_slots=2, block_tokens=BS, num_blocks=16,
                         prefill_buckets=[16], **{flag: True})


def test_tails_are_a_kind_of_the_hybrid_cache_without_recurrent_rows():
    cache = HybridStateCache(128, 8, BS, slots=3, dtype="float32",
                             kv_layers=2, tails=(7, 3, 64))
    kv, conv = cache.state()
    assert cache.h is None and cache.rings is None
    assert kv.shape == (2, 8, BS, 256) and conv.shape == (7, 3, 2, 64)
    assert cache.recurrent_state_bytes == 7 * 3 * 2 * 64 * 4
    cache.update([kv + 1, conv + 2])
    assert float(cache.conv[0, 0, 0, 0]) == 2.0
    with pytest.raises(ValueError, match="holds"):
        cache.update([kv])
    # recurrent rows and tails beside each other, each as it is given
    both = HybridStateCache(128, 8, BS, slots=3, dtype="float32",
                            recurrent=(2, (4, 16)), tails=(2, 4, 16))
    assert [a.shape for a in both.state()[1:]] == [(2, 3, 4, 16),
                                                   (2, 3, 3, 16)]
    none = HybridStateCache(128, 8, BS, slots=3, dtype="float32")
    assert len(none.state()) == 1 \
        and "recurrent_state_bytes" not in none.snapshot()


def test_the_stack_s_three_shapes_and_what_the_configuration_refuses():
    cfg = LFM2Config.from_dict({**raw_config(64), "max_seq_len": 96})
    assert (cfg.period, cfg.periods, cfg.conv_layers, cfg.expert_layers) == \
        (4, 2, 7, 8)
    assert cfg.rope_theta == 1e6 and len(cfg.layer_types) == 9
    shapes = param_shapes(cfg)
    assert shapes["d.conv_in"][0] == (1, 64, 192)       # [B | C | x]
    assert shapes["pa.wqkv"][0] == (2, 64, 4 * 64 + 2 * 2 * 64)
    assert shapes["pa.q_norm"][0] == shapes["pa.k_norm"][0] == (2, 64)
    assert shapes["pc.e_gate"][0] == (2, 3, 8, 64, 32)
    assert shapes["pc.router_bias"][0] == (2, 3, 8)
    assert "head" not in shapes                         # tied to emb
    base = raw_config(64)
    for bad in ({"layer_types": ["full_attention"] + ["conv"] * 8},
                {"layer_types": ["conv"] * 9},
                {"num_hidden_layers": 8}, {"conv_bias": True},
                {"num_dense_layers": 9},
                {"layer_types": ["conv", "full_attention", "conv", "window",
                                 "conv"] * 2}):
        with pytest.raises(ValueError):
            LFM2Config.from_dict({**base, **bad})
    assert LFM2Config.from_dict({**base, "head_dim": None}).head_dim == 16


def test_save_and_load_round_trip_the_model_by_its_type(tmp_path):
    from paddle_tpu.decode import load_lm, save_lm
    m = LFM2LM(LFM2Config.from_dict({**raw_config(64), "max_seq_len": 96}))
    params = m.init_params(3)
    assert params["pc.e_gate"].dtype == jnp.bfloat16
    save_lm(str(tmp_path), m.config, params)
    m2, p2 = load_lm(str(tmp_path))
    assert isinstance(m2, LFM2LM) and m2.config == m.config
    assert set(p2) == set(params)
    for k in params:
        assert np.array_equal(np.asarray(p2[k], np.float32),
                              np.asarray(params[k], np.float32))
