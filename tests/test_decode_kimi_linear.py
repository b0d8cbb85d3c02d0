"""``decode/kimi_linear.py`` at a small size on the CPU: prefill and decode
steps through a :class:`HybridStateCache` of a latent pool, recurrent rows and
convolution tails against the plain reference
(``benchmark/reference/kimi_linear.py``: the gated delta rule one position at
a time) on seeded weights — prompts of one token, inside a chunk of the
chunked prefill, on its edge and past it —, a share of the router's experts
and the whole of them, the same through a ``DecodeEngine``, a prefill that
overwrites a live slot beside untouched neighbours, the four shares adding up
to the uncut layer, position-free latent keys, the cache's kinds, the
observer's counts and what the configuration refuses."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import kimi_linear as reference  # noqa: E402
from paddle_tpu.decode import (DecodeEngine, KimiLinearConfig,  # noqa: E402
                               KimiLinearLM, SamplingParams)
from paddle_tpu.decode.cache import HybridStateCache  # noqa: E402
from paddle_tpu.decode.kimi_linear import param_shapes  # noqa: E402
from paddle_tpu.kernels import kda as KK  # noqa: E402
from paddle_tpu.observability import stats  # noqa: E402

V, RUNGS, BS = 96, (64, 128, 192), 8
LAYOUT = {"kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13],
          "full_attn_layers": [4, 8, 12], "head_dim": 16, "num_heads": 2,
          "short_conv_kernel_size": 4}


def raw_config(held: int = 4, first: int = 4) -> dict:
    """Hidden 64, one dense layer and two periods (KDA, KDA, latent, KDA), a
    router of 16 at top-4 of which ``held`` are here; published key names."""
    raw = dict(
        vocab_size=V, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=9, num_attention_heads=4,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=32, q_lora_rank=None, mla_use_nope=True,
        first_k_dense_replace=1, num_experts=held, num_experts_per_token=4,
        num_shared_experts=1, moe_renormalize=True,
        moe_router_activation_func="sigmoid", routed_scaling_factor=2.446,
        num_expert_group=1, topk_group=1, rms_norm_eps=1e-5,
        tie_word_embeddings=False, linear_attn_config=LAYOUT,
        rope_theta=10000)
    if held != 16:
        raw.update(router_experts=16, first_expert=first)
    return raw


def build(raw):
    model = KimiLinearLM(KimiLinearConfig.from_dict(
        {**raw, "dtype": "float32", "max_seq_len": 256}))
    params = model.init_params(7)
    return raw, model, params, model.param_list(params), \
        jax.jit(model.prefill), jax.jit(model.decode_step)


@pytest.fixture(scope="module", params=[4, 16], ids=["a_share", "whole"])
def built(request):
    return build(raw_config(request.param))


def prefill_feed(seq, P, slot, table):
    bucket = next(r for r in RUNGS if r >= P)
    feed = np.zeros((1, bucket), np.int32)
    feed[0, :P] = seq[:P]
    return (feed, np.int32(P), np.int32(slot), table, np.uint32(0),
            np.float32(0), np.int32(0))


@pytest.mark.parametrize("P", [1, 11, 64, 70, 128], ids=[
    "one_token", "inside_a_chunk", "on_a_chunk_s_edge", "past_an_edge",
    "ends_its_rung"])
def test_prefill_then_steps_through_the_three_kinds_equal_the_reference(
        built, P):
    raw, model, params, plist, prefill, step = built
    if raw["num_experts"] == 16 and P != 64:
        pytest.skip("the whole router's case is one")
    n, S, slot = 5, 3, 1
    seq = np.random.default_rng(P).integers(0, V, size=P + n).astype(np.int32)
    want, _, _ = reference.forward(params, raw, seq, P + n,
                                   np.arange(P - 1, P + n))
    want = np.asarray(want)
    cache = model.make_cache(40, BS, "float32", slots=S)
    assert isinstance(cache, HybridStateCache)
    table = np.zeros((24,), np.int32)
    table[:18] = np.arange(3, 21)
    before = {k: stats.to_dict().get(k, 0)
              for k in ("kda.chunk_fallbacks", "kda.step_fallbacks")}
    outs, state = prefill(plist, cache.state(),
                          *prefill_feed(seq, P, slot, table))
    np.testing.assert_allclose(outs[1], want[0], rtol=3e-4, atol=3e-4)
    assert int(outs[0]) == int(want[0].argmax())
    pool, rec, conv = (np.asarray(a) for a in state)
    assert pool.shape == (2, 40, BS, 128) and rec.shape == (7, S, 2, 16, 16) \
        and conv.shape == (7, S, 3, 96)
    # the slot's rows were written, its neighbours' were not
    assert rec[:, slot].any() and not rec[:, [0, 2]].any()
    assert conv[:, slot, -1].any() and not conv[:, [0, 2]].any()
    if P == 1:      # zeros before the prompt
        assert not conv[:, slot, :2].any()
    tables = np.zeros((S, 24), np.int32)
    tables[slot] = table
    tokens, positions = np.zeros((S,), np.int32), np.zeros((S,), np.int32)
    zeros = np.zeros((S,), np.int32)
    k = raw["num_experts_per_token"]
    for j in range(1, n + 1):
        tokens[slot], positions[slot] = seq[P + j - 1], P + j - 1
        outs, state = step(plist, state, tokens, positions, tables,
                           zeros.astype(np.uint32), zeros,
                           zeros.astype(np.float32), zeros)
        np.testing.assert_allclose(outs[1][slot], want[j], rtol=3e-4,
                                   atol=3e-4)
        load, ids = np.asarray(outs[2]), np.asarray(outs[3])
        # one live slot: its k choices a layer, of which the held have rows
        assert load.shape == (8, 5) and (load[:, 4] == k).all()
        first = raw.get("first_expert", 0)
        held = ((ids[:, slot] >= first)
                & (ids[:, slot] < first + raw["num_experts"])).sum(-1)
        np.testing.assert_array_equal(load[:, 0], held)
    assert {k: stats.to_dict().get(k, 0) for k in before} == before
    assert np.asarray(outs[5]).shape == (8, S, 64) \
        and np.asarray(outs[6]).shape == (8, S, 16)


def test_full_logits_is_the_reference_at_every_position(built):
    raw, model, params, plist, _, _ = built
    if raw["num_experts"] == 16:
        pytest.skip("held to the reference once: as a share")
    toks = np.random.default_rng(0).integers(0, V, size=(2, 24)).astype(
        np.int32)
    got = np.asarray(jax.jit(model.full_logits)(plist, toks))
    for b in range(2):
        want, own, own_stats = reference.forward(params, raw, toks[b], 24,
                                                 np.arange(24))
        np.testing.assert_allclose(got[b], want, rtol=3e-4, atol=3e-4)
    assert own.shape == (8, 24, 4)
    assert set(own_stats) == set(reference.STATS) | {"states"}
    assert own_stats["states"].shape == (7, 2, 16, 16)
    assert own_stats["kda_rms"].shape == (7,) \
        and own_stats["mla_rms"].shape == (2,) \
        and own_stats["ffn_rms"].shape == (9,)
    share = np.asarray(own_stats["held_choice_share"])
    assert ((0 < share) & (share < 1)).all()


def test_a_prefill_overwrites_a_live_slot_beside_untouched_neighbours():
    raw, model, params, plist, prefill, step = build(raw_config())
    S = 3
    cache = model.make_cache(40, BS, "float32", slots=S)
    # not the draw of seed 4: its first prompt has a router tie at position 40
    # (experts 2 and 7 of the sixth layer), which 1e-5 of the chunked scan's
    # state turns — the reference here takes its own choices
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, V, size=n).astype(np.int32) for n in (70, 9, 30)]
    tables = np.zeros((S, 24), np.int32)
    for i in range(S):
        tables[i, :9] = 1 + 9 * i + np.arange(9)
    state = cache.state()
    for i, seq in enumerate(seqs):
        _, state = prefill(plist, state, *prefill_feed(seq, seq.size, i,
                                                       tables[i]))
    kept = [np.asarray(a) for a in state]
    # slot 1 leaves; another prompt joins there while slots 0 and 2 live on
    new = rng.integers(0, V, size=65).astype(np.int32)
    tables[1, :9] = 28 + np.arange(9)
    outs, state = prefill(plist, state, *prefill_feed(new, 65, 1, tables[1]))
    want, _, _ = reference.forward(params, raw, new, 65, np.asarray([64]))
    np.testing.assert_allclose(outs[1], np.asarray(want)[0], rtol=3e-4,
                               atol=3e-4)
    _, rec, conv = (np.asarray(a) for a in state)
    for i in (0, 2):
        np.testing.assert_array_equal(rec[:, i], kept[1][:, i])
        np.testing.assert_array_equal(conv[:, i], kept[2][:, i])
    assert (rec[:, 1] != kept[1][:, 1]).any()
    # ... and the neighbours' next tokens are what they would have been
    tokens = np.asarray([5, 6, 7], np.int32)
    positions = np.asarray([70, 65, 30], np.int32)
    zeros = np.zeros((S,), np.int32)
    outs, _ = step(plist, state, tokens, positions, tables,
                   zeros.astype(np.uint32), zeros, zeros.astype(np.float32),
                   zeros)
    for i, seq in ((0, seqs[0]), (2, seqs[2]), (1, new)):
        full = np.concatenate([seq, tokens[i:i + 1]])
        want, _, _ = reference.forward(params, raw, full, full.size,
                                       np.asarray([full.size - 1]))
        np.testing.assert_allclose(outs[1][i], np.asarray(want)[0],
                                   rtol=3e-4, atol=3e-4)


def test_the_four_shares_add_up_to_the_uncut_layer_with_the_shared_once():
    raw, model, params, plist, _, _ = build(raw_config(16))
    sz = reference.sizes(raw)
    w, stacks, at = reference.layer_weights(params, sz, 3)
    h = jnp.asarray(np.random.default_rng(1).standard_normal((40, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        routed, shared, own, *_ = reference.expert_block(w, stacks, at, h, sz)
        whole = np.asarray(routed + shared)
        parts = 0.0
        for first in (0, 4, 8, 12):
            cut = {**raw, "num_experts": 4, "router_experts": 16,
                   "first_expert": first}
            mine = tuple(s[:, first:first + 4] for s in stacks)
            r, sh, own_s, *_ = reference.expert_block(
                w, mine, at, h, reference.sizes(cut))
            np.testing.assert_array_equal(own_s, own)   # the router is whole
            np.testing.assert_array_equal(sh, shared)
            parts = parts + r
            # the program's share is the reference's
            m = KimiLinearLM(KimiLinearConfig.from_dict(
                {**cut, "dtype": "float32", "max_seq_len": 64}))
            x = jnp.zeros_like(h)
            lw = {**{k: jnp.asarray(v) for k, v in w.items()},
                  "ln2": jnp.ones((64,), jnp.float32)}
            got, (load, ids, *_rest) = m._expert_ffn(
                lw, tuple(jnp.asarray(s) for s in mine), at[0], h,
                jnp.ones((40,), bool), 8, False)
            hn = reference.rms_norm(h, jnp.ones((64,)), sz["eps"])
            r2, sh2, *_ = reference.expert_block(w, mine, at, hn,
                                                 reference.sizes(cut))
            np.testing.assert_allclose(got - h, r2 + sh2, rtol=3e-4,
                                       atol=3e-4)
            assert int(load[4]) == 40 * 4
    np.testing.assert_allclose(np.asarray(parts + shared), whole, rtol=2e-5,
                               atol=2e-5)


def test_latent_keys_carry_no_position_and_the_planted_faults_show():
    raw, model, params, plist, _, _ = build(raw_config())
    seq = np.random.default_rng(2).integers(0, V, size=24).astype(np.int32)
    at = np.arange(8, 24)
    got = np.asarray(jax.jit(model.full_logits)(plist, seq[None]))[0, at]
    sound, _, _ = reference.forward(params, raw, seq, 24, at)
    err = np.linalg.norm(got - sound, axis=-1) / np.linalg.norm(sound,
                                                                axis=-1)
    assert err.max() < 1e-4
    for fault in reference.FAULTS:
        other, _, _ = reference.forward(params, raw, seq, 24, at,
                                        faults=(fault,))
        e = np.linalg.norm(got - other, axis=-1) / np.linalg.norm(other,
                                                                  axis=-1)
        assert e.max() > 30 * err.max(), (fault, e.max(), err.max())
    with pytest.raises(ValueError, match="unknown"):
        reference.forward(params, raw, seq, 24, at, faults=("rope",))


def test_an_engine_serves_it_as_it_is_and_its_observer_counts(built):
    raw, model, params, plist, _, _ = built
    if raw["num_experts"] == 16:
        pytest.skip("served once: as a share")
    name = f"kl{raw['num_experts']}"
    engine = DecodeEngine(model, params, name=name, max_slots=3,
                          block_tokens=BS, num_blocks=60,
                          prefill_buckets=list(RUNGS), max_queue=8,
                          cache_dtype="float32")
    try:
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, V, size=n).astype(np.int32)
                   for n in (1, 64, 70)]
        handles = [engine.submit(p, SamplingParams(temperature=0.0,
                                                   max_new_tokens=3))
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
        k, layers = 4, 8
        real = sum(p.size for p in prompts)
        pre = f"decode.{name}."
        assert c[pre + "prefill_real_tokens"] == real
        assert c[pre + "prefill_choices"] == real * k * layers
        assert c[pre + "step_choices"] == c[pre + "step_streams"] * k * layers
        assert c[pre + "prefill_plan_rows"] - c[pre + "prefill_plan_pad_rows"] \
            == c[pre + "prefill_routed_assignments"]
        assert 0 < c[pre + "step_routed_assignments"] < c[pre + "step_choices"]
        assert c[pre + "step_state_bytes"] \
            == c[pre + "step_streams"] * 2 * 7 * 2 * 16 * 16 * 4
        z = engine.decodez()
        assert 0 < z["step_live_blocks"] <= z["step_table_blocks"]
        snap = z["cache"]
        assert snap["kind"] == "hybrid" and "window" not in snap
        assert snap["recurrent_state_bytes"] == 7 * 3 * (2 * 16 * 16 * 4
                                                         + 3 * 96 * 4)
    finally:
        engine.close()


def test_a_prefix_cache_overcommit_and_beams_are_refused():
    model = KimiLinearLM(KimiLinearConfig.from_dict(
        {**raw_config(), "dtype": "float32", "max_seq_len": 96}))
    assert model.supports == frozenset() and model.slot_state
    with pytest.raises(ValueError, match="slot"):
        model.make_cache(8, BS, "float32")
    for flag in ("prefix_cache", "overcommit"):
        with pytest.raises(ValueError):
            DecodeEngine(model, model.init_params(0), name="klno",
                         max_slots=2, block_tokens=BS, num_blocks=16,
                         prefill_buckets=[64], **{flag: True})


def test_the_pool_s_row_is_what_the_model_gives_beside_rows_and_tails():
    cache = HybridStateCache(0, 8, BS, slots=3, dtype="float32", kv_layers=2,
                             row_width=640, recurrent=(7, (32, 128, 128)),
                             tails=(7, 4, 12288))
    kv, h, conv = jax.eval_shape(cache.state)
    assert kv.shape == (2, 8, BS, 640) and cache.row_width == 640
    assert h.shape == (7, 3, 32, 128, 128) and h.dtype == jnp.float32
    assert conv.shape == (7, 3, 3, 12288)
    assert cache.kv_pool_bytes == 2 * 8 * BS * 640 * 4
    # a K/V pool's row is still [k | v]
    assert HybridStateCache(128, 8, BS, slots=3).row_width == 256
    with pytest.raises(ValueError, match="ring"):
        HybridStateCache(0, 8, BS, slots=3, row_width=640, rings=(2, 16))


def test_the_stack_s_shapes_the_share_and_what_the_configuration_refuses():
    cfg = KimiLinearConfig.from_dict({**raw_config(), "max_seq_len": 96})
    assert cfg.kinds == ("kda", "kda", "kda", "mla", "kda", "kda", "kda",
                         "mla", "kda")
    assert (cfg.pattern, cfg.periods, cfg.kda_layers, cfg.mla_layers,
            cfg.expert_layers) == (("kda", "kda", "mla", "kda"), 2, 7, 2, 8)
    assert (cfg.router_experts, cfg.num_experts, cfg.first_expert) == (16, 4, 4)
    whole = KimiLinearConfig.from_dict(raw_config(16))
    assert whole.first_expert == 0 and whole.router_experts == 16
    shapes = param_shapes(cfg)
    assert shapes["d.wqkv"][0] == (1, 64, 96) \
        and shapes["d.conv_w"][0] == (1, 4, 96)
    assert shapes["p0.wf1"][0] == (2, 64, 16) \
        and shapes["p0.wf2"][0] == (2, 16, 32)
    assert shapes["p0.a_log"][0] == (2, 2) \
        and shapes["p0.dt_bias"][0] == (2, 32)
    assert shapes["p2.wq"][0] == (2, 64, 4 * 24) and "p2.wqkv" not in shapes
    assert shapes["p1.router"][0] == (2, 64, 16)        # the router is whole
    assert shapes["p1.e_gate"][0] == (2, 4, 64, 32)     # the experts a share
    assert shapes["head"][0] == (64, V)
    base = raw_config()
    for bad in ({"q_lora_rank": 64}, {"mla_use_nope": False},
                {"tie_word_embeddings": True}, {"num_expert_group": 2},
                {"moe_router_activation_func": "softmax"},
                {"first_expert": 13}, {"num_hidden_layers": 1},
                {"first_k_dense_replace": 0}, {"first_k_dense_replace": 4},
                {"linear_attn_config": {**LAYOUT, "kda_layers": [1, 2, 3, 4],
                                        "full_attn_layers": [4]}}):
        with pytest.raises(ValueError):
            KimiLinearConfig.from_dict({**base, **bad})
    # the decay's two tensors are drawn by the family's own rule
    m = KimiLinearLM(KimiLinearConfig.from_dict({**base, "dtype": "float32"}))
    p = m.init_params(1)
    a = np.exp(p["p0.a_log"])
    assert (a >= 1).all() and (a <= 16).all()
    dt = np.log1p(np.exp(p["p0.dt_bias"]))
    assert (dt > 0.9e-3).all() and (dt < 0.11).all()


def test_save_and_load_round_trip_the_model_by_its_type(tmp_path):
    from paddle_tpu.decode import load_lm, save_lm
    m = KimiLinearLM(KimiLinearConfig.from_dict(
        {**raw_config(), "max_seq_len": 96}))
    params = m.init_params(3)
    assert params["p1.e_gate"].dtype == jnp.bfloat16
    save_lm(str(tmp_path), m.config, params)
    m2, p2 = load_lm(str(tmp_path))
    assert isinstance(m2, KimiLinearLM) and m2.config == m.config
    assert set(p2) == set(params)
    for k in params:
        assert np.array_equal(np.asarray(p2[k], np.float32),
                              np.asarray(params[k], np.float32))
    assert KK.CHUNK == 64
