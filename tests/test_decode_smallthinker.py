"""The window-and-full attention expert LM of ``decode/smallthinker.py``
against the benchmark's plain reference (``benchmark/reference/
smallthinker.py``, the one copy there is) at a tiny size — two periods of one
full and three window layers, 4 query heads over 2 K/V heads of 16, 8
experts of 32 at top-3, a window of 32, 16-token blocks — in float32 so that
the comparison is tight; through a real ``DecodeEngine``; what the engine
serves and refuses for this model; and the state cache's kinds."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import smallthinker as ref  # noqa: E402

from paddle_tpu.decode import (DecodeEngine, PagedBeamDecoder,  # noqa: E402
                               SamplingParams, SmallThinkerConfig,
                               SmallThinkerLM, load_lm, save_lm)
from paddle_tpu.decode import smallthinker  # noqa: E402
from paddle_tpu.decode.cache import HybridStateCache  # noqa: E402
from paddle_tpu.decode.smallthinker import param_shapes  # noqa: E402
from paddle_tpu.kernels import moe as moe_kernels  # noqa: E402
from paddle_tpu.observability import stats  # noqa: E402

V, BS, NB, SLOTS, L, W = 96, 16, 40, 2, 8, 32
CFG = SmallThinkerConfig(
    vocab_size=V, hidden_size=64, num_hidden_layers=L, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_ffn_hidden_size=32,
    moe_num_primary_experts=8, moe_num_active_primary_experts=3,
    rope_layout=(0, 1, 1, 1) * 13, sliding_window_layout=(0, 1, 1, 1) * 13,
    sliding_window_size=W, rope_theta=1.5e6, max_seq_len=160,
    dtype="float32")
# float32 against float32 at the highest precision through eight layers: the
# two differ by the order of their sums alone (read: up to 6e-5 of logits of
# order 1).  Anything computed in bf16 where float32 is stated reads 1e-2 or
# more (the test of the router's scores below)
TOL = dict(rtol=3e-4, atol=3e-4)


@pytest.fixture(scope="module")
def model():
    m = SmallThinkerLM(CFG)
    params = m.init_params(1)
    return m, params, m.param_list(params)


def _ref(params, toks, at=None, cfg=CFG, faults=()):
    at = np.arange(len(toks)) if at is None else at
    lg, own, _ = ref.forward({k: jnp.asarray(v) for k, v in params.items()},
                             cfg.to_dict(), np.asarray(toks, np.int32),
                             len(toks), at, faults=faults)
    return np.asarray(lg), np.asarray(own)


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
    layout = (0, 1, 1, 1) * 13
    big = SmallThinkerConfig(
        vocab_size=151936, hidden_size=2560, num_hidden_layers=52,
        num_attention_heads=28, num_key_value_heads=4, head_dim=128,
        moe_ffn_hidden_size=768, moe_num_primary_experts=64,
        moe_num_active_primary_experts=6, rope_layout=layout,
        sliding_window_layout=layout, sliding_window_size=4096)
    assert (big.period, big.periods, big.window_layers) == (4, 13, 39)
    shapes = param_shapes(big)
    assert shapes["pf.wqkv"][0] == (13, 2560, 3584 + 512 + 512)
    assert shapes["pw.wqkv"][0] == (13, 3, 2560, 4608)
    assert shapes["pw.wo"][0] == (13, 3, 3584, 2560)
    assert shapes["pf.router"][0] == (13, 2560, 64)
    assert shapes["pw.e_gate"][0] == shapes["pw.e_up"][0] == \
        (13, 3, 64, 2560, 768)
    assert shapes["pf.e_down"][0] == (13, 64, 768, 2560)
    assert shapes["emb"][0] == shapes["head"][0] == (151936, 2560)
    per_layer = sum(int(np.prod(s[1:])) for k, (s, _) in shapes.items()
                    if k.startswith("pf."))
    assert per_layer == sum(int(np.prod(s[2:])) for k, (s, _) in
                            shapes.items() if k.startswith("pw."))
    top = sum(int(np.prod(s)) for k, (s, _) in shapes.items()
              if k[:3] not in ("pf.", "pw."))
    # attention 20,971,520 + router 163,840 + 64 experts of 5,898,240 +
    # two norms
    assert per_layer == 20_971_520 + 163_840 + 64 * 5_898_240 + 5_120 \
        == 398_627_840
    assert top == 2 * 151936 * 2560 + 2560 == 777_914_880
    assert 52 * per_layer + top == 21_506_562_560       # "21B"
    # the benchmark's cut: eight layers, the vocabulary and the final norm
    assert 8 * per_layer + top == 3_966_937_600
    # a cached token a layer: 2,048 B in bf16; a ring a slot a layer 8.39 MB
    assert 2 * big.kv_width * 2 == 2048
    assert 4096 * 2 * big.kv_width * 2 == 8_388_608
    for bad in ({"num_hidden_layers": 6},
                {"sliding_window_layout": (1, 1, 1, 0) * 2},
                {"rope_layout": (0, 0, 1, 1) * 2},
                {"num_key_value_heads": 3},
                {"moe_primary_router_apply_softmax": False}):
        with pytest.raises(ValueError):
            dataclasses.replace(CFG, **bad)


def test_full_forward_matches_the_reference(model):
    m, params, pl = model
    rng = np.random.default_rng(0)
    toks = rng.integers(0, V, size=(2, 80)).astype(np.int32)    # 2.5 windows
    got = np.asarray(jax.jit(m.full_logits)(pl, jnp.asarray(toks)))
    for b in range(2):
        want, _ = _ref(params, toks[b])
        np.testing.assert_allclose(got[b], want, **TOL)


@pytest.mark.parametrize("fault,how", [
    ("route_from_h", {"faults": ("route_from_h",)}),
    ("silu_gate", {"faults": ("silu_gate",)}),
    ("rotary_on_a_full_layer", {"cfg": {"rope_layout": (1, 1, 1, 1) * 2}}),
    ("no_rotary_on_a_window_layer", {"cfg": {"rope_layout": (0, 0, 1, 1) * 2}}),
    ("half_the_window", {"cfg": {"sliding_window_size": W // 2}}),
    ("no_window", {"cfg": {"sliding_window_layout": (0, 0, 0, 0) * 2}}),
])
def test_a_model_with_another_mechanism_is_another_model(model, fault, how):
    """The reference with the router moved after the attention, ``silu`` for
    ``relu``, rotary positions on a full layer (or none on a window layer),
    or another window is NOT what the program computes: each moves the logits
    by far more than the tolerance that holds the program to the sound
    reference."""
    m, params, pl = model
    toks = np.random.default_rng(5).integers(0, V, size=80).astype(np.int32)
    got = np.asarray(jax.jit(m.full_logits)(pl, jnp.asarray(toks[None])))[0]
    want, _ = _ref(params, toks)
    np.testing.assert_allclose(got, want, **TOL)
    raw = dict(CFG.to_dict(), **how.get("cfg", {}))
    wrong, _, _ = ref.forward(
        {k: jnp.asarray(v) for k, v in params.items()}, raw, toks, len(toks),
        np.arange(len(toks)), faults=how.get("faults", ()))
    assert np.abs(np.asarray(wrong) - got).max() > 30 * TOL["atol"], fault


def test_router_scores_in_bfloat16_fail_the_tolerance(model, monkeypatch):
    """The configuration states float32 router scores: rounded to bf16 they
    choose other experts at near ties and weigh the chosen otherwise, and
    the float32 comparison does not hold."""
    m, params, pl = model
    toks = np.random.default_rng(6).integers(0, V, size=80).astype(np.int32)
    want, _ = _ref(params, toks)
    real = moe_kernels.route_topk

    def rounded(logits, *a, **kw):
        return real(logits.astype(jnp.bfloat16), *a, **kw)

    monkeypatch.setattr(smallthinker._moe, "route_topk", rounded)
    got = np.asarray(m.full_logits(pl, jnp.asarray(toks[None])))[0]
    assert np.abs(got - want).max() > 10 * TOL["atol"]


def test_the_router_reads_the_layer_s_input_before_its_attention(model):
    """What the programs return of the routing: the router's logits at the
    judged row are ``u W_r`` of the returned ``u``, the chosen experts their
    largest, and ``u`` is the norm of the layer's INPUT — for layer 0 of the
    embedding alone, whatever the attention does."""
    m, params, pl = model
    prompt = np.random.default_rng(8).integers(0, V, size=21).astype(np.int32)
    cache = m.make_cache(NB, BS, "float32", slots=SLOTS)
    table = np.asarray([3, 4, 0, 0, 0, 0, 0, 0, 0, 0], np.int32)
    (_, _, load, ids, u, rl), _ = _prefill(m, pl, cache.state(), prompt, 32,
                                           0, table)
    assert load.shape == (L, 3) and ids.shape == (L, 32, 3)
    assert u.shape == (L, 1, 64) and rl.shape == (L, 1, 8)
    assert np.asarray(load)[:, 0].tolist() == [21 * 3] * L
    x0 = np.asarray(params["emb"])[prompt[-1]].astype(np.float64)
    u0 = x0 / np.sqrt((x0 * x0).mean() + CFG.rms_norm_eps) \
        * np.asarray(params["pf.ln1"])[0]
    np.testing.assert_allclose(np.asarray(u)[0, 0], u0, rtol=1e-5, atol=1e-6)
    routers = [params["pf.router"][l // 4] if l % 4 == 0
               else params["pw.router"][l // 4, l % 4 - 1] for l in range(L)]
    for l in range(L):
        want = np.asarray(ref.router_scores(routers[l], np.asarray(u)[l]))
        np.testing.assert_allclose(np.asarray(rl)[l], want, rtol=1e-5,
                                   atol=1e-6)
        assert sorted(np.asarray(ids)[l, 20].tolist()) == \
            sorted(np.argsort(-want[0])[:3].tolist())
    _, own = _ref(params, prompt, np.asarray([20]))
    assert (np.sort(own, -1) == np.sort(np.asarray(ids)[:, :21], -1)).all()


@pytest.mark.parametrize("n,bucket", [(5, 16), (16, 16), (31, 32), (32, 32),
                                      (33, 64), (100, 128)],
                         ids=["inside", "a_block", "one_short", "the_window",
                              "one_past", "three_windows"])
def test_a_prefill_equals_the_full_forward_and_fills_pool_and_rings(
        model, n, bucket):
    """Prompts shorter than, equal to and several times the window: the last
    position's logits, the full layers' rows in the request's blocks, and in
    the slot's ring of every window layer, at ``position mod window``, the
    rows of the last ``window`` positions."""
    m, params, pl = model
    prompt = np.random.default_rng(n).integers(0, V, size=n).astype(np.int32)
    cache = m.make_cache(NB, BS, "float32", slots=SLOTS)
    assert len(cache.state()) == 2
    table = np.zeros((10,), np.int32)
    table[:7] = [3, 4, 5, 6, 7, 8, 9]
    (tok, logits, *_), state = _prefill(m, pl, cache.state(), prompt, bucket,
                                        1, table)
    want, _ = _ref(params, prompt, np.asarray([n - 1]))
    np.testing.assert_allclose(logits, want[0], **TOL)
    assert int(tok) == int(want[0].argmax())
    kv, rings = (np.asarray(a) for a in state)
    assert kv.shape == (2, NB, BS, 64) and rings.shape == (6, SLOTS * 2, 16, 64)
    assert kv[:, 3].any() and not rings[:, :2].any()    # slot 0 untouched
    # a bucket that is larger writes the same rows where the prompt is real
    (_, _, *_), other = _prefill(m, pl, cache.state(), prompt, 2 * bucket, 1,
                                 table)
    ring_a = rings[:, 2:].reshape(6, W, 64)
    ring_b = np.asarray(other[1])[:, 2:].reshape(6, W, 64)
    live = np.zeros((W,), bool)
    live[[t % W for t in range(max(0, n - W), n)]] = True
    np.testing.assert_allclose(ring_a[:, live], ring_b[:, live], rtol=1e-4,
                               atol=1e-5)
    assert live.sum() == min(n, W)


def test_prefill_then_decode_through_the_engine_matches_the_reference(model):
    """Contexts shorter than, equal to and several times the window (the ring
    wraps); seven streams on two slots, so streams of different ages share a
    step and every join overwrites a slot's rings while the other slot's are
    live; every generated position's logits against the reference's full
    forward."""
    m, params, _ = model
    eng = DecodeEngine(m, params, name="st", max_slots=SLOTS,
                       block_tokens=BS, num_blocks=NB,
                       prefill_buckets=[16, 32, 64, 128],
                       capture_logits=True, prefix_cache=False,
                       overcommit=False)
    try:
        assert isinstance(eng.cache, HybridStateCache)
        assert eng.cache.h is None and eng.cache.conv is None
        assert [a.shape for a in eng.cache.state()] == \
            [(2, NB, BS, 64), (6, SLOTS * 2, 16, 64)]
        rng = np.random.default_rng(4)
        lengths = (5, 30, 32, 17, 100, 40, 64)
        outs = (20, 12, 9, 45, 40, 30, 5)
        prompts = [rng.integers(0, V, size=n).astype(np.int32)
                   for n in lengths]
        hs = [eng.submit(p, SamplingParams(max_new_tokens=n))
              for p, n in zip(prompts, outs)]
        for p, h, n in zip(prompts, hs, outs):
            toks = h.result(timeout=900.0)["tokens"]
            assert len(toks) == n
            seq = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
            want, _ = _ref(params, seq, np.arange(len(p) - 1, len(seq)))
            np.testing.assert_allclose(np.stack(h.logits), want, **TOL)
        z = eng.decodez()
        assert z["cache"]["kind"] == "hybrid" and z["cache"]["window"] == W
        assert "recurrent_state_bytes" not in z["cache"]
        assert z["cache"]["bytes"] == eng.cache.nbytes == \
            z["cache"]["kv_pool_bytes"] + z["cache"]["window_state_bytes"]
        assert z["cache"]["kv_pool_bytes"] == 2 * NB * BS * 64 * 4
        assert z["cache"]["window_state_bytes"] == 6 * SLOTS * W * 64 * 4
        assert z["joins"] == z["leaves"] == 7
        assert z["cache"]["free_blocks"] == NB - 1      # released at a leave
        c = stats.to_dict()
        name = "decode.st."
        assert c[name + "prefill_real_tokens"] == sum(lengths)
        assert c[name + "prefill_pad_tokens"] == \
            11 + 2 + 0 + 15 + 28 + 24 + 0
        assert c[name + "prefill_routed_assignments"] == sum(lengths) * 3 * L
        assert c[name + "prefill_tokens_sq"] == sum(n * n for n in lengths)
        assert c[name + "prefill_window_pairs"] == sum(
            min(n, W) * (min(n, W) + 1) // 2 + max(n - W, 0) * W
            for n in lengths)
        streams = sum(outs) - 7
        assert c[name + "step_streams"] == streams
        assert c[name + "step_routed_assignments"] == streams * 3 * L
        assert c[name + "step_moe_dispatches"] == c[name + "steps"] * L
        assert c[name + "step_ring_rows_held"] == streams * W
        assert 0 < c[name + "step_ring_rows_live"] < streams * W
        assert 0 < c[name + "step_streams_past_window"] < streams
        assert c[name + "step_context_tokens"] > c[name + "step_ring_rows_live"]
        assert z["step_ring_rows_live"] == c[name + "step_ring_rows_live"]
        assert c[name + "kv_pool_bytes"] == eng.cache.kv_pool_bytes
        assert c[name + "window_state_bytes"] == eng.cache.window_state_bytes
    finally:
        eng.close()


def test_the_observer_s_spans_carry_what_each_launch_added_to_the_counters(
        model, monkeypatch):
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

    monkeypatch.setattr(smallthinker._trace, "span",
                        lambda name, **a: Span(name))
    m, _, _ = model
    cache = m.make_cache(NB, BS, "float32", slots=SLOTS)
    obs = m.observer("st_o", cache, (SLOTS, 10))
    before = stats.to_dict()
    load = np.asarray([[39, 8, 9]] * L)
    obs.prefill([load], 13, 16)
    obs.prefill([np.asarray([[120, 8, 30]] * L)], 40, 64)
    obs.step([np.asarray([[6, 5, 2]] * L)], np.asarray([51, 6]))
    after = stats.to_dict()
    assert filed[0] == ("decode::prefill.observe", {
        "prefill_routed_assignments": 39 * L, "prefill_real_tokens": 13,
        "prefill_window_pairs": 91, "prefill_tokens_sq": 169})
    assert filed[1][1]["prefill_window_pairs"] == 32 * 33 // 2 + 8 * 32
    assert filed[2] == ("decode::step.observe", {
        "step_routed_assignments": 6 * L, "step_experts_touched": 5 * L,
        "step_context_tokens": 57, "step_ring_rows_live": 32 + 6,
        "step_streams": 2})
    for key in set(filed[0][1]) | set(filed[2][1]):
        name = "decode.st_o." + key
        assert after[name] - before.get(name, 0) == sum(
            args.get(key, 0) for _, args in filed)
    d = {k: after["decode.st_o." + k] - before.get("decode.st_o." + k, 0)
         for k in ("step_ring_rows_held", "step_streams_past_window",
                   "step_expert_load_max_sum", "prefill_pad_tokens",
                   "step_live_blocks", "step_table_blocks")}
    assert d["step_ring_rows_held"] == 2 * W
    assert d["step_streams_past_window"] == 1
    assert d["step_expert_load_max_sum"] == 2 * L
    assert d["prefill_pad_tokens"] == 3 + 24
    # two full layers walk 4 + 1 pool blocks of 2 x 10, six window layers
    # 2 + 1 ring blocks of 2 x 2
    assert d["step_live_blocks"] == 2 * 5 + 6 * 3
    assert d["step_table_blocks"] == 2 * (2 * 10 + 6 * 2)
    assert cache.snapshot()["kv_live_tokens"] == 57


def test_save_and_load_round_trip_in_bfloat16(tmp_path):
    m = SmallThinkerLM(dataclasses.replace(CFG, dtype="bfloat16"))
    params = m.init_params(3)
    assert params["pw.e_gate"].dtype == jnp.bfloat16
    save_lm(str(tmp_path), m.config, params)
    m2, p2 = load_lm(str(tmp_path))
    assert isinstance(m2, SmallThinkerLM) and m2.config == m.config
    assert set(p2) == set(params)
    for k in params:
        assert np.array_equal(np.asarray(p2[k], np.float32),
                              np.asarray(params[k], np.float32))


def test_what_the_engine_and_the_beam_session_refuse_for_it(model):
    m, params, _ = model
    for kw in ({"prefix_cache": True}, {"overcommit": True}):
        with pytest.raises(ValueError, match="does not support"):
            DecodeEngine(m, params, name="st_r", max_slots=2,
                         block_tokens=BS, num_blocks=NB,
                         prefill_buckets=[16], **{"prefix_cache": False,
                                                  "overcommit": False, **kw})
    with pytest.raises(ValueError, match="does not support beam"):
        PagedBeamDecoder(m, params, beam_size=2, end_id=1)
    with pytest.raises(ValueError, match="no int8 form"):
        m.make_cache(NB, BS, "int8", slots=2)
    with pytest.raises(ValueError, match="slot count"):
        m.make_cache(NB, BS, "float32")


# -- the state cache's kinds: one whose layer count is zero has NO array ----
@pytest.mark.parametrize("window_layers,ssm_layers,held", [
    (2, 3, ["kv", "rings", "h", "conv"]), (0, 3, ["kv", "h", "conv"]),
    (2, 0, ["kv", "rings"]), (0, 0, ["kv"])])
def test_a_state_kind_with_no_layer_has_no_array(window_layers, ssm_layers,
                                                 held):
    kinds = {}
    if window_layers:
        kinds["rings"] = (window_layers, 32)
    if ssm_layers:
        kinds.update(recurrent=(ssm_layers, (16, 64)),
                     tails=(ssm_layers, 4, 64))
    cache = HybridStateCache(32, 8, 16, 2, dtype="float32", kv_layers=2,
                             **kinds)
    state = cache.state()
    assert len(state) == len(held)
    assert [getattr(cache, k) is not None
            for k in ("kv", "rings", "h", "conv")] == \
        [k in held for k in ("kv", "rings", "h", "conv")]
    assert all(a.size > 0 for a in state)
    snap = cache.snapshot()
    assert ("window_state_bytes" in snap) == ("rings" in held)
    assert ("recurrent_state_bytes" in snap) == ("h" in held)
    assert snap["bytes"] == sum(a.size * a.dtype.itemsize for a in state)
    cache.update([a + 1 for a in state])
    assert all(float(a.min()) == 1.0 for a in cache.state())
    with pytest.raises(ValueError, match="holds"):
        cache.update(state + [state[0]])
