"""The latent-attention (MLA) expert LM of ``decode/mla.py`` against the
benchmark's plain reference (``benchmark/reference/deepseek_v2.py``, the
one copy there is), at a toy size whose
YaRN ramp is exercised (original length 16, factor 4), in float32 so that
the comparison is tight; the kernels in interpret mode against their XLA
fallbacks; and what ``DecodeEngine`` serves and refuses for this model."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import deepseek_v2 as ref  # noqa: E402

from paddle_tpu.decode import (DecodeEngine, PagedBeamDecoder,  # noqa: E402
                               SamplingParams, load_lm, save_lm)
from paddle_tpu.decode.cache import PagedLatentCache  # noqa: E402
from paddle_tpu.decode.mla import (MLAConfig, MLATransformerLM,  # noqa: E402
                                   softmax_scale, yarn_inv_freq)
from paddle_tpu.kernels import mla as MK  # noqa: E402
from paddle_tpu.kernels import moe as EK  # noqa: E402
from paddle_tpu.observability import stats  # noqa: E402

RS = {"factor": 4.0, "original_max_position_embeddings": 16, "beta_fast": 32,
      "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
      "type": "yarn"}
CFG = MLAConfig(vocab_size=128, rope_scaling=RS, max_seq_len=64,
                dtype="float32")
V, BS, MB, NB = 128, 4, 16, 40


def _model(**over):
    import dataclasses
    cfg = dataclasses.replace(CFG, **over)
    m = MLATransformerLM(cfg)
    params = m.init_params(1)
    return m, params, m.param_list(params)


@pytest.fixture(params=["xla", "pallas"])
def impl(request, monkeypatch):
    """The model as it is (its kernels, interpreted off the chip) or with
    the routed experts and the prefill attention forced onto their XLA
    fallbacks, which the model itself never asks for; the decode step's
    attention takes the same choice as the engine's ``attn_impl``."""
    if request.param == "xla":
        for mod, name in ((EK, "grouped_glu"), (MK, "prefill_attention")):
            def forced(*a, _orig=getattr(mod, name), **kw):
                return _orig(*a, **dict(kw, impl="xla"))
            monkeypatch.setattr(mod, name, forced)
    return request.param


def _ref_logits(params, cfg, toks, forced=None):
    lg, own = ref.forward(params, cfg.to_dict(), jnp.asarray(toks),
                          jnp.int32(len(toks)), jnp.arange(len(toks)), forced)
    return np.asarray(lg), np.asarray(own)


def _jitted(m, name, **kw):
    """One jit a (model, entry point): eager dispatch of an interpreted
    kernel's every operation is most of this file's time otherwise."""
    cache = m.__dict__.setdefault("_test_jits", {})
    key = (name, tuple(sorted(kw.items())))
    if key not in cache:
        fn = getattr(m, name)
        cache[key] = jax.jit(lambda *a: fn(*a, **kw))
    return cache[key]


def _prefill(m, pl, state, table, prompt, bucket):
    tk = np.zeros((1, bucket), np.int32)
    tk[0, :len(prompt)] = prompt
    return _jitted(m, "prefill")(
        pl, state, jnp.asarray(tk), jnp.int32(len(prompt)),
        jnp.asarray(table), jnp.uint32(0), jnp.float32(0), jnp.int32(0))


def _step(m, pl, state, tokens, positions, tables, impl):
    S = len(tokens)
    z = jnp.zeros((S,), jnp.int32)
    return _jitted(m, "decode_step", attn_impl=impl)(
        pl, state, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(positions, jnp.int32), jnp.asarray(tables),
        z.astype(jnp.uint32), z, z.astype(jnp.float32), z)


def _routed_part_matches(params, x, y, ids):
    """What a program returns of its first expert layer at the judged rows —
    the experts' input ``x`` and their routed output ``y`` — against the
    reference's experts alone (shared experts zeroed) on those rows, given
    the program's choices ``ids``."""
    D = x.shape[1]
    p = {"l." + k: params["l1." + k]
         for k in ("router", "e_gate", "e_up", "e_down")}
    p.update({"l.s_gate": jnp.zeros((D, 1)), "l.s_up": jnp.zeros((D, 1)),
              "l.s_down": jnp.zeros((1, D))})
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(p, "l.", CFG.to_dict(), jnp.asarray(x, jnp.float32),
                          jnp.asarray(ids))
    assert y.dtype == jnp.float32 and y.shape == x.shape == want.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-6)


def test_yarn_frequencies_and_temperature_as_published():
    """DeepSeek-V2-Lite's keys: the ramp runs from pair 10 to pair 23 of 32,
    the softmax scale is 192^-1/2 x (0.1 x 0.707 x ln 40 + 1)^2."""
    rs = dict(RS, factor=40, original_max_position_embeddings=4096)
    inv = yarn_inv_freq(64, 10000.0, rs)
    f = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], f[23:] / 40, rtol=1e-6)
    assert np.all(np.diff(inv) < 0)
    big = MLAConfig(vocab_size=8, qk_nope_head_dim=128, qk_rope_head_dim=64,
                    rope_scaling=rs)
    assert abs(softmax_scale(big) - 0.1147) < 1e-4
    np.testing.assert_allclose(inv, ref.yarn(big.to_dict())[0], rtol=1e-6)


def test_full_forward_matches_the_reference(impl):
    m, params, pl = _model()
    toks = np.random.RandomState(0).randint(0, V, size=(2, 32)).astype(np.int32)
    got = np.asarray(_jitted(m, "full_logits")(pl, jnp.asarray(toks)))
    for b in range(2):
        want, _ = _ref_logits(params, m.config, toks[b])
        np.testing.assert_allclose(got[b], want, atol=2e-5)


def test_prefill_then_decode_through_the_latent_cache_matches_the_reference(
        impl):
    """Two streams of different lengths in one decode batch (and a slot with
    no stream), prompts shorter than their buckets, contexts that cross the
    original rotary length 16: logits at EVERY position against the
    reference's one full forward — the absorbed path against the expanded
    formula, on the cache the prefill wrote."""
    m, params, pl = _model()
    rng = np.random.RandomState(2)
    seqs = [rng.randint(0, V, size=n).astype(np.int32) for n in (40, 27)]
    prompts = [10, 19]
    cache = m.make_cache(NB, BS, "float32")
    state = cache.state()
    tables = np.zeros((3, MB), np.int32)
    want = [_ref_logits(params, m.config, s)[0] for s in seqs]
    for i, (s, P) in enumerate(zip(seqs, prompts)):
        blocks = cache.allocator.alloc(-(-len(s) // BS))
        tables[i + 1, :len(blocks)] = blocks
        (_, lg, load, ids, x, y), state = _prefill(
            m, pl, state, tables[i + 1], s[:P], 32)
        np.testing.assert_allclose(np.asarray(lg), want[i][P - 1], atol=3e-5)
        assert np.asarray(load)[:, 0].tolist() == [3 * P, 3 * P]
        assert ids.shape == (2, 32, 3)
        _routed_part_matches(params, x, y, ids[0, P - 1:P])
    for j in range(max(len(s) - p for s, p in zip(seqs, prompts))):
        pos = [0] + [p + j for p in prompts]
        on = [False] + [p + j < len(s) for s, p in zip(seqs, prompts)]
        tok = [0] + [int(s[p + j]) if o else 0
                     for s, p, o in zip(seqs, prompts, on[1:])]
        bt = np.where(np.asarray(on)[:, None], tables, 0)
        pos = [p if o else 0 for p, o in zip(pos, on)]
        (_, lg, load, ids, x, y), state = _step(m, pl, state, tok, pos, bt,
                                                impl)
        live = np.flatnonzero(on)
        _routed_part_matches(params, x[live], y[live], ids[0][live])
        for i in (0, 1):
            if on[i + 1]:
                np.testing.assert_allclose(np.asarray(lg)[i + 1],
                                           want[i][prompts[i] + j], atol=3e-5)
        # only the slots with a stream are routed
        assert np.asarray(load)[:, 0].tolist() == [3 * sum(on)] * 2


def test_the_absorbed_path_equals_the_expanded_one_on_the_same_cache():
    """The program's own two formulas: a decode step's logits against its
    full forward (expanded keys and values), token by token."""
    m, params, pl = _model()
    s = np.random.RandomState(5).randint(0, V, size=24).astype(np.int32)
    full = np.asarray(_jitted(m, "full_logits")(pl, jnp.asarray(s[None])))[0]
    cache = m.make_cache(NB, BS, "float32")
    table = np.zeros((1, MB), np.int32)
    table[0, :6] = cache.allocator.alloc(6)
    (_, lg, *_), state = _prefill(m, pl, cache.state(), table[0], s[:8], 16)
    np.testing.assert_allclose(np.asarray(lg), full[7], atol=2e-5)
    for pos in range(8, 24):
        (_, lg, *_), state = _step(m, pl, state, [s[pos]], [pos], table,
                                     "pallas")
        np.testing.assert_allclose(np.asarray(lg)[0], full[pos], atol=2e-5)


def test_router_ties_go_to_the_lower_index_and_weights_are_not_renormalised():
    logits = jnp.asarray([[1.0, 3.0, 3.0, 3.0, 0.0, 3.0, -1.0, 2.0],
                          [0.0] * 8])
    ids, w = EK.route_topk(logits, 3)
    assert np.asarray(ids).tolist() == [[1, 2, 3], [0, 1, 2]]
    s = np.asarray(jax.nn.softmax(logits, axis=-1))
    np.testing.assert_allclose(np.asarray(w)[0], s[0, [1, 2, 3]], rtol=1e-6)
    assert np.asarray(w)[1].sum() == pytest.approx(3 / 8)      # not 1
    _, wn = EK.route_topk(logits, 3, scale=2.0, normalize=True)
    np.testing.assert_allclose(np.asarray(wn).sum(-1), [2.0, 2.0], rtol=1e-6)
    own = ref.moe({"l.router": jnp.eye(8), "l.e_gate": jnp.zeros((8, 8, 4)),
                   "l.e_up": jnp.zeros((8, 8, 4)),
                   "l.e_down": jnp.zeros((8, 4, 8)),
                   "l.s_gate": jnp.zeros((8, 4)), "l.s_up": jnp.zeros((8, 4)),
                   "l.s_down": jnp.zeros((4, 8))}, "l.",
                  {"n_routed_experts": 8, "num_experts_per_tok": 3},
                  logits)[1]
    assert np.asarray(own).tolist() == [[1, 2, 3], [0, 1, 2]]


def test_no_assignment_is_dropped_when_every_token_goes_to_one_expert(
        impl, monkeypatch):
    """A router forced to put expert 0 among every token's choices (the
    weights stay its own scores): expert 0's load is all T tokens, the other
    two choices still vary, all 3 T assignments are computed, and the logits
    match the reference given the same choices."""
    orig = EK.route_topk

    def expert_zero_always(logits, k, scale=1.0, normalize=False):
        ids, _ = orig(logits.at[:, 0].add(50.0), k, scale, normalize)
        s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        return ids, jnp.take_along_axis(s, ids, axis=-1) * scale
    monkeypatch.setattr(EK, "route_topk", expert_zero_always)
    m, params, pl = _model()
    toks = np.random.RandomState(7).randint(0, V, size=32).astype(np.int32)
    cache = m.make_cache(NB, BS, "float32")
    table = np.zeros((MB,), np.int32)
    table[:8] = cache.allocator.alloc(8)
    (_, lg, load, ids, *_), _ = _prefill(m, pl, cache.state(), table, toks,
                                         32)
    load, ids = np.asarray(load), np.asarray(ids)
    assert load[:, 0].tolist() == [96, 96] and load[:, 2].tolist() == [32, 32]
    assert (ids[:, :, 0] == 0).all() and (load[:, 1] > 1).all()
    want, own = ref.forward(params, m.config.to_dict(), jnp.asarray(toks),
                            jnp.int32(32), jnp.asarray([31]), jnp.asarray(ids))
    assert (np.asarray(own)[:, :, 0] != 0).any()    # not the router's choice
    np.testing.assert_allclose(np.asarray(lg), np.asarray(want)[0], atol=3e-5)


def test_grouped_swiglu_kernel_matches_its_fallback_and_counts_it():
    rng = np.random.RandomState(0)
    T, D, F, E, K = 40, 32, 48, 8, 3
    x = jnp.asarray(rng.randn(T, D), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(E, D, F) * 0.2, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(E, F, D) * 0.2, jnp.float32)
    ids, w = EK.route_topk(jnp.asarray(rng.randn(T, E), jnp.float32), K)
    valid = jnp.asarray(np.arange(T) < 33)
    before = stats.to_dict().get("moe.grouped_swiglu_fallbacks", 0)
    y0, load0 = EK.routed_experts(x, ids, w, valid, wg, wu, wd, impl="xla")
    assert stats.to_dict()["moe.grouped_swiglu_fallbacks"] == before + 1
    y1, load1 = EK.routed_experts(x, ids, w, valid, wg, wu, wd, impl="pallas")
    assert stats.to_dict()["moe.grouped_swiglu_fallbacks"] == before + 1
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-4)
    assert np.asarray(load0).tolist() == np.asarray(load1).tolist()
    assert int(load0[0]) == 33 * K and np.all(np.asarray(y0)[33:] == 0)
    dense = sum(
        (np.asarray(w) * (np.asarray(ids) == e)).sum(-1, keepdims=True)
        * np.asarray((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
        for e in range(E))
    np.testing.assert_allclose(np.asarray(y0)[:33], dense[:33], atol=1e-4)


@pytest.mark.parametrize("layer_as", ["a_constant", "a_traced_scalar",
                                      "a_scan_s_index"])
def test_latent_decode_attention_kernel_matches_its_fallback(layer_as):
    """One kernel, the pool's layer a prefetched scalar: a program that
    unrolls its layers hands it a constant, one that scans an index."""
    rng = np.random.RandomState(1)
    S, H, rank, rope, bs, mb, nb, L = 5, 4, 32, 8, 4, 40, 64, 3
    W = MK.row_width(rank, rope)
    assert W == 128 and MK.row_width(512, 64) == 640
    pool = jnp.asarray(rng.randn(L, nb, bs, W), jnp.float32)
    q = np.zeros((S, H, W), np.float32)
    q[..., :rank + rope] = rng.randn(S, H, rank + rope)
    bt = rng.randint(1, nb, size=(S, mb)).astype(np.int32)
    cl = np.asarray([1, 4, 37, 130, 160], np.int32)  # 2 chunks of 32 blocks
    args = (jnp.asarray(q), pool, jnp.asarray(bt), jnp.asarray(cl), 2, rank,
            0.3)
    want = MK.decode_attention(*args, impl="xla")

    def walk(layer):
        return MK.decode_attention(*args[:4], layer, rank, 0.3,
                                   impl="pallas")

    if layer_as == "a_constant":
        got = walk(2)
    elif layer_as == "a_traced_scalar":
        got = jax.jit(walk)(jnp.int32(2))
    else:
        _, every = jax.lax.scan(lambda c, layer: (c, walk(layer)), 0,
                                jnp.arange(L, dtype=jnp.int32))
        got = every[2]
        # ... and each step of the scan read its own layer's rows
        assert np.abs(np.asarray(every[0]) - np.asarray(got)).max() > 0.1
    assert got.shape == (S, H, rank)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_prefill_attention_at_unequal_head_sizes_matches_its_fallback():
    rng = np.random.RandomState(2)
    H, T = 3, 200
    q, k = (jnp.asarray(rng.randn(H, T, 24), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.randn(H, T, 16), jnp.float32)
    want = MK.prefill_attention(q, k, v, 0.2, impl="xla")
    got = MK.prefill_attention(q, k, v, 0.2, impl="pallas")
    assert got.shape == (H, T, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_save_and_load_round_trip_in_bfloat16(tmp_path):
    import dataclasses
    m = MLATransformerLM(dataclasses.replace(CFG, dtype="bfloat16"))
    params = m.init_params(3)
    assert params["l1.e_gate"].dtype == jnp.bfloat16
    save_lm(str(tmp_path), m.config, params)
    m2, p2 = load_lm(str(tmp_path))
    assert isinstance(m2, MLATransformerLM) and m2.config == m.config
    assert set(p2) == set(params)
    for k in params:
        assert p2[k].dtype == params[k].dtype
        assert np.array_equal(np.asarray(p2[k], np.float32),
                              np.asarray(params[k], np.float32))
    toks = jnp.asarray(np.arange(12, dtype=np.int32)[None])
    a = m.full_logits(m.param_list(params), toks)
    b = m2.full_logits(m2.param_list(p2), toks)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_engine_serves_it_and_counts_its_mechanisms():
    m, params, pl = _model()
    eng = DecodeEngine(m, params, name="mla_t", max_slots=3, block_tokens=BS,
                       num_blocks=NB, prefill_buckets=[16, 32],
                       prefix_cache=False, overcommit=False)
    try:
        assert isinstance(eng.cache, PagedLatentCache)
        z = eng.decodez()["cache"]
        assert z["kind"] == "latent" and z["row_width"] == 128
        assert z["bytes"] == eng.cache.nbytes == 3 * NB * BS * 128 * 4
        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, V, size=n).astype(np.int32)
                   for n in (7, 18, 12, 25)]
        hs = [eng.submit(p, SamplingParams(max_new_tokens=n))
              for p, n in zip(prompts, (9, 5, 14, 3))]
        outs = [h.result(timeout=300.0)["tokens"] for h in hs]
        for p, toks in zip(prompts, outs):
            seq = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
            full = np.asarray(m.full_logits(pl, jnp.asarray(seq[None])))[0]
            gap = full[len(p) - 1:].max(-1) - np.take_along_axis(
                full[len(p) - 1:], np.asarray(toks)[:, None], 1)[:, 0]
            assert gap.max() < 1e-4        # the greedy token, up to a tie
        c = stats.to_dict()
        assert c["decode.mla_t.prefill_real_tokens"] == 7 + 18 + 12 + 25
        assert c["decode.mla_t.prefill_pad_tokens"] == 9 + 14 + 4 + 7
        assert c["decode.mla_t.prefill_tokens_sq"] == 49 + 324 + 144 + 625
        assert c["decode.mla_t.prefill_routed_assignments"] == 62 * 3 * 2
        steps = eng.decodez()["steps"]
        assert c["decode.mla_t.step_moe_dispatches"] == 2 * steps
        assert c["decode.mla_t.step_routed_assignments"] == \
            (sum(len(o) for o in outs) - 4) * 3 * 2
        assert 0 < c["decode.mla_t.step_experts_touched"] <= 8 * 2 * steps
        assert c["decode.mla_t.latent_pool_bytes"] == eng.cache.nbytes
        assert c["decode.mla_t.step_context_tokens"] > 0
    finally:
        eng.close()


def test_the_observer_s_spans_carry_what_each_launch_added_to_the_counters(
        monkeypatch):
    """A reader of a trace sums these arguments over the launches it times
    (``benchmark/metrics/kernel_roofline.py``): they must be the counters'
    own increments, under the counters' names."""
    from paddle_tpu.decode import mla
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

    monkeypatch.setattr(mla._trace, "span", lambda name, **a: Span(name))
    m, _, _ = _model()
    obs = m.observer("mla_o", m.make_cache(NB, BS, "float32"), (4, 8))
    before = stats.to_dict()
    obs.prefill([np.asarray([[30, 7, 9], [30, 8, 11]])], 10, 16)
    obs.step([np.asarray([[6, 5, 2], [6, 4, 3]])], np.asarray([30, 27]))
    after = stats.to_dict()
    assert [n for n, _ in filed] == ["decode::prefill.observe",
                                     "decode::step.observe"]
    assert filed[0][1] == {"prefill_routed_assignments": 60,
                           "prefill_tokens_sq": 100}
    assert filed[1][1] == {"step_routed_assignments": 12,
                           "step_experts_touched": 9,
                           "step_context_tokens": 57}
    for _, args in filed:
        for key, value in args.items():
            name = "decode.mla_o." + key
            assert after[name] - before.get(name, 0) == value


def test_what_the_engine_and_the_beam_session_refuse_for_it():
    m, params, _ = _model()
    for kw in ({"prefix_cache": True}, {"overcommit": True}):
        with pytest.raises(ValueError, match="does not support"):
            DecodeEngine(m, params, name="mla_r", max_slots=2,
                         block_tokens=BS, num_blocks=NB,
                         prefill_buckets=[16], **{"prefix_cache": False,
                                                  "overcommit": False, **kw})
    with pytest.raises(ValueError, match="does not support beam"):
        PagedBeamDecoder(m, params, beam_size=2, end_id=1)
    with pytest.raises(ValueError, match="no int8 form"):
        m.make_cache(NB, BS, "int8")
