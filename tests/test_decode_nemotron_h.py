"""``decode/nemotron_h.py`` (Nemotron-H: one mixer a layer by a pattern
string) at a small size on the CPU: the program against the plain reference
of the published equations (``benchmark/reference/nemotron_h.py``) on seeded
random weights — the full forward, and a prefill, decode steps through pool,
recurrent rows (64-wide heads two to a tile) and tails, and joins into used
slots —, the two shares of the experts and the shared one counted once
summed to the uncut layer, a pattern that is no whole number of periods, and
what the observer files."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import nemotron_h as reference  # noqa: E402
from paddle_tpu.decode import (DecodeEngine, PagedBeamDecoder,  # noqa: E402
                               SamplingParams, load_lm, save_lm)
from paddle_tpu.decode import adapter  # noqa: E402
from paddle_tpu.decode.cache import HybridStateCache  # noqa: E402
from paddle_tpu.decode.nemotron_h import (NemotronHConfig,  # noqa: E402
                                          NemotronHLM, relu2, step_bias)
from paddle_tpu.kernels import ssd  # noqa: E402
from paddle_tpu.observability import stats  # noqa: E402

# the cut's own shape in small: thirteen layers ``MEMEM*E MEMEM*`` — one
# period and six layers of the next —, heads of 64 channels (two to a lane
# tile) in groups of four, half of the router's experts held
V, SLOTS, BS, NB = 96, 2, 16, 24
SMALL = dict(vocab_size=V, hidden_size=64, num_hidden_layers=13,
             hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*E",
             num_attention_heads=8, num_key_value_heads=2, head_dim=16,
             mamba_num_heads=8, mamba_head_dim=64, n_groups=2,
             ssm_state_size=16, conv_kernel=4, chunk_size=8,
             moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
             n_routed_experts=4, num_experts_per_tok=3, router_experts=8,
             first_expert=0, max_seq_len=128, dtype="float32")
CFG = NemotronHConfig(**SMALL)


@pytest.fixture(scope="module")
def model():
    m = NemotronHLM(CFG)
    params = m.init_params(7)
    return m, params, m.param_list(params)


def _ref(params, seq, at, cfg=CFG, **kw):
    lg, own, got = reference.forward(params, cfg.to_dict(), seq, len(seq),
                                     np.asarray(at), **kw)
    return np.asarray(lg), np.asarray(own), got


def test_the_model_is_registered_under_its_published_type():
    m = adapter.MODEL_TYPES["nemotron_h"](dict(SMALL))
    cfg = m.config
    assert isinstance(m, NemotronHLM) and m.supports == frozenset()
    assert m.slot_state
    # a cut in depth reads the pattern's first characters
    assert cfg.hybrid_override_pattern == "MEMEM*EMEMEM*"
    assert [cfg.count(k) for k in "ME*"] == [6, 5, 2]
    assert (cfg.d_inner, cfg.conv_width, cfg.in_width, cfg.q_width,
            cfg.kv_width) == (512, 576, 1096, 128, 32)
    # 64-wide heads lie two to a lane tile
    assert cfg.state_shape == (4, 16, 128)
    assert cfg.to_dict()["model_type"] == "nemotron_h"
    shapes = m.param_shapes(cfg)
    assert "head" in shapes and shapes["emb"][0] == (V, 64)    # untied
    # an expert's two matrices both lie [F, D]: no gate
    assert shapes["e.e_up"][0] == shapes["e.e_down"][0] == (5, 4, 24, 64)
    assert "e.e_gate" not in shapes
    assert shapes["e.router"][0] == (5, 64, 8)
    assert shapes["m.in_proj"][0] == (6, 64, 1096)
    assert shapes["a.wqkv"][0] == (2, 64, 128 + 64)


@pytest.mark.parametrize("bad", [
    {"hybrid_override_pattern": "MEMEM-EMEMEM*"}, {"num_hidden_layers": 30},
    {"hybrid_override_pattern": "MMMMMMMMMMMMM"}, {"use_conv_bias": False},
    {"mlp_hidden_act": "silu"}, {"n_group": 2}, {"n_shared_experts": 2},
    {"tie_word_embeddings": True}, {"attention_bias": True},
    {"first_expert": 5}, {"n_groups": 3}, {"norm_topk_prob": False}])
def test_what_is_not_written_down_is_refused(bad):
    with pytest.raises(ValueError):
        NemotronHConfig(**{**SMALL, **bad})


def test_step_sizes_are_drawn_as_the_family_draws_them():
    u = jnp.linspace(0.0, 0.999, 64)
    dt = jax.nn.softplus(step_bias(u, 1e-3, 1e-1, 1e-4))
    assert abs(float(dt[0]) - 1e-3) < 1e-6 and 0.09 < float(dt[-1]) < 0.1
    # the floor holds a range that starts under it
    low = jax.nn.softplus(step_bias(u, 1e-6, 1e-1, 1e-4))
    assert abs(float(low.min()) - 1e-4) < 1e-7
    np.testing.assert_array_equal(relu2(jnp.asarray([-2.0, 0.0, 3.0])),
                                  [0.0, 0.0, 9.0])
    # ... and a model draws its own from the range its configuration states
    wide = NemotronHLM(dataclasses.replace(CFG, time_step_min=0.01,
                                           time_step_max=0.5))
    got = jax.nn.softplus(jnp.asarray(wide.init_params(0)["m.dt_bias"]))
    assert 0.01 * 0.99 <= float(got.min()) and float(got.max()) <= 0.5 * 1.01
    assert float(got.max()) > 0.1


def test_full_logits_are_the_references(model):
    """Float32 both sides: the program IS the published equations — one
    mixer a layer by the pattern, the gate before the grouped norm, a sigmoid
    router whose bias chooses and does not weigh, the held experts' ungated
    relu² units and the shared one, attention that rotates nothing."""
    m, params, plist = model
    tokens = np.random.default_rng(0).integers(0, V, (2, 40)).astype(np.int32)
    got = np.asarray(jax.jit(m.full_logits)(plist, jnp.asarray(tokens)))
    for b in range(2):
        want, _, own = _ref(params, tokens[b], np.arange(40))
        assert np.abs(got[b] - want).max() < 3e-5 * np.abs(want).max()
    # every mixer shows in the residual stream, some choices are held and
    # the selection bias turns some
    for name in ("mamba_rms", "experts_rms", "attn_rms"):
        assert (np.asarray(own[name]) > 0.02).all(), name
    assert 0 < np.asarray(own["held_choice_share"]).mean() < 1


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_planted_mechanism_is_another_model(model, fault):
    """Each equation the issue names is where the reference has it: the
    reference with the mechanism swapped is far from the program — and the
    reference with the step size and the decay in bfloat16 is the same model
    a lower precision away."""
    m, params, plist = model
    tokens = np.random.default_rng(1).integers(0, V, (1, 24)).astype(np.int32)
    got = np.asarray(jax.jit(m.full_logits)(plist, jnp.asarray(tokens)))[0]
    wrong, _, _ = _ref(params, tokens[0], np.arange(24), faults=(fault,))
    off = np.abs(got - wrong).max() / np.abs(got).max()
    assert 1e-4 < off < 1e-2 if fault == "bf16_step" else off > 1e-2, off


def test_prefill_then_decode_through_the_engine_matches_the_reference(model):
    """Prompts around a chunk (8), a block (16) and the buckets (16, 32); six
    streams on two slots, so every slot is reused by a join after a leave;
    every generated position's LOGITS against the reference's full forward,
    and the rows and tails a finished stream leaves against the
    reference's."""
    m, params, _ = model
    fell = {k: stats.to_dict().get(k, 0) for k in
            ("ssm.ssd_fallbacks", "moe.grouped_relu2_fallbacks")}
    eng = DecodeEngine(m, params, name="nh", max_slots=SLOTS,
                       block_tokens=BS, num_blocks=NB,
                       prefill_buckets=[16, 32], capture_logits=True,
                       cache_dtype="float32", prefix_cache=False,
                       overcommit=False)
    try:
        assert isinstance(eng.cache, HybridStateCache)
        assert eng.cache.rings is None and len(eng.cache.state()) == 3
        # three layer counts, none of them the depth
        assert eng.cache.kv.shape == (2, NB, BS, 2 * CFG.kv_width)
        assert eng.cache.h.shape == (6, SLOTS, 4, 16, 128)
        assert eng.cache.conv.shape == (6, SLOTS, 3, CFG.conv_width)
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
            want, _, _ = _ref(params, seq, np.arange(len(p) - 1, len(seq)))
            got = np.stack(h.logits)
            assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
        z = eng.decodez()
        assert z["cache"]["kind"] == "hybrid"
        assert z["joins"] == z["leaves"] == 6
        assert z["cache"]["free_blocks"] == NB - 1
        c = stats.to_dict()
        name = "decode.nh."
        real = 5 + 8 + 13 + 16 + 17 + 32
        assert c[name + "prefill_real_tokens"] == real
        assert c[name + "prefill_pad_tokens"] == 11 + 8 + 3 + 0 + 15 + 0
        assert c[name + "prefill_scan_chunks"] == 1 + 1 + 2 + 2 + 3 + 4
        streams = sum(outs) - 6
        assert c[name + "step_streams"] == streams
        assert c[name + "step_state_bytes"] == \
            streams * 6 * 2 * 4 * 8 * 16 * 64
        # every real token chose three of the router's eight in each of the
        # five expert layers; some of them are held here
        assert c[name + "prefill_choices"] == real * 3 * 5
        assert c[name + "step_choices"] == streams * 3 * 5
        assert 0 < c[name + "step_routed_assignments"] \
            < c[name + "step_choices"]
        assert 0 < c[name + "prefill_routed_assignments"] \
            < c[name + "prefill_choices"]
        assert c[name + "step_moe_dispatches"] % 5 == 0
        # no served program took a kernel's XLA fallback
        assert {k: c.get(k, 0) for k in fell} == fell
    finally:
        eng.close()


def test_the_rows_and_tails_a_prompt_leaves_are_the_references(model):
    """A prefill of 13 padded to 16 leaves in the slot's rows the state after
    position 12 (pads pass it by) in the kept layout, and in its tails the
    last three real inputs of the convolution."""
    m, params, plist = model
    cache = m.make_cache(NB, BS, "float32", slots=SLOTS)
    tokens = np.zeros((1, 16), np.int32)
    prompt = np.random.default_rng(2).integers(0, V, 13).astype(np.int32)
    tokens[0, :13] = prompt
    table = np.zeros((8,), np.int32)
    table[0] = 3
    outs, state = jax.jit(m.prefill)(
        plist, cache.state(), tokens, np.int32(13), np.int32(1), table,
        np.uint32(0), np.float32(0.0), np.int32(0))
    want, own, got = _ref(params, prompt, [12])
    np.testing.assert_allclose(outs[1], want[0], atol=1e-4 * np.abs(want).max())
    S = ssd.unpack_state(np.asarray(state[1])[:, 1], CFG.mamba_head_dim)
    np.testing.assert_allclose(S, got["states"], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(state[2])[:, 1], got["tails"],
                               rtol=1e-4, atol=1e-5)
    assert float(np.abs(np.asarray(state[1])[:, 0]).max()) == 0.0
    # the router's choices at the real positions are the reference's own
    # (float32 both sides), and the expert layers' outputs at the judged row
    np.testing.assert_array_equal(np.sort(np.asarray(outs[3])[:, :13], -1),
                                  np.sort(own, -1))
    np.testing.assert_allclose(np.asarray(outs[7])[:, 0],
                               np.asarray(got["expert_out"])[:, 0],
                               rtol=1e-4, atol=1e-5)


def test_the_two_shares_and_the_shared_expert_once_add_up_to_the_layer(model):
    """Experts 0-3 and 4-7 of the router's eight, on two chips: each share's
    routed part, plus the shared expert counted ONCE, is the uncut layer as
    the reference computes it."""
    m, params, plist = model
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((20, 64)), jnp.float32)
    valid = jnp.ones((20,), bool)
    at = 2
    p = dict(zip(m.param_names(), plist))
    # the uncut layer: all eight experts' matrices, the same router
    up8 = jnp.asarray(rng.standard_normal((5, 8, 24, 64)) * 0.2, jnp.float32)
    down8 = jnp.asarray(rng.standard_normal((5, 8, 24, 64)) * 0.2,
                        jnp.float32)
    w = {k[2:]: v[at] for k, v in p.items()
         if k.startswith("e.") and k[2:] not in ("e_up", "e_down")}
    u = m._rms(x, w["ln"])
    _, ids, weights = m._route(w, u)
    parts = []
    for first in (0, 4):
        share = NemotronHLM(dataclasses.replace(CFG, first_expert=first))
        stack = {"e_up": up8[:, first:first + 4],
                 "e_down": down8[:, first:first + 4]}
        r, load = share._routed(stack, at, u, ids, weights, valid, 8, False)
        parts.append(np.asarray(r))
        assert int(load[4]) == 20 * 3       # every choice, held or not
    assert sum(int((np.asarray(ids) // 4 == j).sum()) for j in (0, 1)) == 60
    whole = np.asarray(parts[0] + parts[1] + np.asarray(m._shared(w, u)))
    uncut = dict(CFG.to_dict(), n_routed_experts=8, first_expert=0)
    sz = reference.sizes(uncut)
    used, wts, _, _ = reference.route(
        reference.router_scores(w["router"], u), w["router_bias"], None, sz)
    np.testing.assert_array_equal(np.sort(used, -1), np.sort(ids, -1))
    with jax.default_matmul_precision("highest"):
        want = reference.held_experts(up8, down8, at, u, used, wts, 0) \
            + reference.relu2(u @ w["s_up"]) @ w["s_down"]
    np.testing.assert_allclose(whole, want, rtol=1e-4,
                               atol=1e-5 * float(np.abs(want).max()))
    # neither share alone is the layer
    assert np.abs(parts[0]).max() > 0.01 and np.abs(parts[1]).max() > 0.01


def test_the_observer_s_spans_carry_what_each_launch_added_to_the_counters(
        model, monkeypatch):
    from paddle_tpu.decode import nemotron_h
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

    monkeypatch.setattr(nemotron_h._trace, "span",
                        lambda name, **a: Span(name))
    m, _, _ = model
    cache = m.make_cache(NB, BS, "float32", slots=SLOTS)
    obs = m.observer("nh_o", cache, (SLOTS, 8))
    before = stats.to_dict()
    load = np.asarray([[7, 3, 4, 24, 39]] * 5)
    obs.prefill([load], 13, 16)
    obs.step([np.asarray([[4, 2, 3, 16, 6]] * 5)], np.asarray([51, 6]))
    after = stats.to_dict()
    assert filed[0] == ("decode::prefill.observe", {
        "prefill_routed_assignments": 35, "prefill_choices": 195,
        "prefill_plan_rows": 120, "prefill_real_tokens": 13,
        "prefill_pad_tokens": 3, "prefill_scan_chunks": 2,
        "prefill_tokens_sq": 169})
    assert filed[1] == ("decode::step.observe", {
        "step_routed_assignments": 20, "step_experts_touched": 10,
        "step_choices": 30, "step_context_tokens": 57, "step_streams": 2,
        "step_state_bytes": 2 * 6 * 2 * 4 * 8 * 16 * 64})
    for _, args in filed:
        for key, value in args.items():
            name = "decode.nh_o." + key
            assert after[name] - before.get(name, 0) == value
    assert after["decode.nh_o.step_moe_dispatches"] \
        - before.get("decode.nh_o.step_moe_dispatches", 0) == 5
    assert cache.snapshot()["kv_live_tokens"] == 57
    # the two attention layers walk the pool: 4 + 1 blocks of each slot's 8
    z = obs.decodez()
    assert (z["step_live_blocks"], z["step_table_blocks"]) == (10, 32)


def test_save_and_load_round_trip_in_bfloat16(tmp_path):
    m = NemotronHLM(dataclasses.replace(CFG, dtype="bfloat16"))
    params = m.init_params(3)
    assert params["e.e_up"].dtype == jnp.bfloat16
    save_lm(str(tmp_path), m.config, params)
    m2, p2 = load_lm(str(tmp_path))
    assert isinstance(m2, NemotronHLM) and m2.config == m.config
    assert set(p2) == set(params)
    for k in params:
        assert np.array_equal(np.asarray(p2[k], np.float32),
                              np.asarray(params[k], np.float32))


def test_what_the_engine_and_the_beam_session_refuse_for_it(model):
    m, params, _ = model
    for kw in ({"prefix_cache": True}, {"overcommit": True}):
        with pytest.raises(ValueError, match="does not support"):
            DecodeEngine(m, params, name="nh_r", max_slots=2,
                         block_tokens=BS, num_blocks=NB,
                         prefill_buckets=[16], **{"prefix_cache": False,
                                                  "overcommit": False, **kw})
    with pytest.raises(ValueError, match="does not support beam"):
        PagedBeamDecoder(m, params, beam_size=2, end_id=1)
    with pytest.raises(ValueError, match="slot count"):
        m.make_cache(NB, BS, "float32")
