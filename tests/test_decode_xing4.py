"""The ``xing4_0`` model of ``decode/mla.py`` (four residual streams mixed by
manifold-constrained hyper-connections, a low-rank query, a sigmoid router
with a selection bias) against the benchmark's plain reference
(``benchmark/reference/xing4.py``, the one copy there is), at a toy size in
float32 so that the comparison is tight: the full forward, prefill and then
decoding through the paged latent cache, three other models that must be told
apart, the parameter count at the published widths, and what ``DecodeEngine``
serves through ``DecodeServer`` / ``DecodeClient``."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import xing4 as ref  # noqa: E402

from paddle_tpu.decode import (DecodeClient, DecodeEngine,  # noqa: E402
                               DecodeServer, load_lm, save_lm)
from paddle_tpu.decode.adapter import MODEL_TYPES  # noqa: E402
from paddle_tpu.decode.cache import PagedLatentCache  # noqa: E402
from paddle_tpu.decode.mla import (HyperMLAConfig,  # noqa: E402
                                   HyperMLATransformerLM, MLATransformerLM,
                                   param_shapes, softmax_scale)
from paddle_tpu.observability import stats  # noqa: E402

RS = {"factor": 4.0, "original_max_position_embeddings": 16, "beta_fast": 32,
      "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0, "type": "yarn"}
# hidden 128: a stream is one whole lane tile, so the Pallas kernels of
# kernels/mhc.py run (interpreted) inside the model
CFG = HyperMLAConfig(
    vocab_size=128, hidden_size=128, num_hidden_layers=3,
    first_k_dense_replace=1, rope_scaling=RS, max_seq_len=64,
    dtype="float32", q_lora_rank=24, scoring_func="sigmoid",
    topk_method="noaux_tc", norm_topk_prob=True, routed_scaling_factor=2.0,
    n_shared_experts=1, hc_mult=4)
V, BS, MB, NB = 128, 4, 16, 40
# float32 on both sides, sums of a few hundred terms in another order, seven
# mixings deep; the logits' scale is about 4
ATOL = 3e-5


@pytest.fixture(scope="module")
def model():
    m = HyperMLATransformerLM(CFG)
    params = m.init_params(1)
    # two entries of one row of A_res past the clip at 30, in a layer's
    # feed-forward mixing: a model that does not clip weighs them e^7 apart
    b = np.array(params["l1.ffn_hc_b"])
    b[8], b[9] = 41.0, 34.0
    params["l1.ffn_hc_b"] = b
    return m, params, m.param_list(params)


def _ref_logits(params, toks, forced=None, faults=()):
    lg, own, st = ref.forward(params, CFG.to_dict(), jnp.asarray(toks),
                              len(toks), jnp.arange(len(toks)), forced,
                              faults)
    return np.asarray(lg), np.asarray(own), st


def _jitted(m, name, **kw):
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


def _step(m, pl, state, tokens, positions, tables):
    z = jnp.zeros((len(tokens),), jnp.int32)
    return _jitted(m, "decode_step", attn_impl="pallas")(
        pl, state, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(positions, jnp.int32), jnp.asarray(tables),
        z.astype(jnp.uint32), z, z.astype(jnp.float32), z)


def _probe_matches(params, x, y, ids, sx, pre, post, res):
    """What a program returns of its first expert layer at the judged rows:
    the experts' input and routed output against the reference's experts
    alone, the feed-forward mixing's streams and maps against the reference's
    maps alone on those streams."""
    want = ref.experts_alone(params, CFG.to_dict(), 1, x, ids)
    assert y.dtype == jnp.float32 and y.shape == x.shape == want.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-6)
    maps = ref.sublayer_maps(params, CFG.to_dict(), 1, "ffn", sx)
    assert sx.shape == (x.shape[0], 4 * 128)
    for got, w in zip((pre, post, res), maps):
        assert got.dtype == jnp.float32 and got.shape == w.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(w), atol=2e-6)


def test_the_softmax_scale_is_yarn_s_at_equal_mscales():
    """``mscale`` = ``mscale_all_dim`` = 1 at factor 64: the rotation's
    factor is 1 and the scale 192^-1/2 x (0.1 ln 64 + 1)^2."""
    big = HyperMLAConfig(
        vocab_size=8, qk_nope_head_dim=128, qk_rope_head_dim=64,
        rope_scaling=dict(RS, factor=64,
                          original_max_position_embeddings=4096))
    assert softmax_scale(big) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2, rel=1e-6)
    assert ref.yarn(big.to_dict())[1:] == pytest.approx(
        (1.0, softmax_scale(big)), rel=1e-6)


def test_full_forward_matches_the_reference(model):
    m, params, pl = model
    toks = np.random.RandomState(0).randint(
        0, V, size=(2, 32)).astype(np.int32)
    got = np.asarray(_jitted(m, "full_logits")(pl, jnp.asarray(toks)))
    for b in range(2):
        want, _, st = _ref_logits(params, toks[b])
        np.testing.assert_allclose(got[b], want, atol=ATOL)
    # the reference's own readings are there, one a sub-layer
    assert st["hres_diag"].shape == (6,) and st["attn_rms"].shape == (3,)
    assert st["stream_rms"].shape == (2,)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_another_model_is_told_apart(model, fault):
    """A dropped ``q_norm``, a dropped clip on ``A_res`` and a transposed
    ``H_res`` are other models: each moves the logits by a thousand times the
    tolerance the sound model is held to."""
    m, params, pl = model
    toks = np.random.RandomState(0).randint(
        0, V, size=(1, 32)).astype(np.int32)
    got = np.asarray(_jitted(m, "full_logits")(pl, jnp.asarray(toks)))[0]
    want, _, _ = _ref_logits(params, toks[0], faults=(fault,))
    assert np.abs(got - want).max() > 1e3 * ATOL
    with pytest.raises(ValueError, match="unknown planted faults"):
        _ref_logits(params, toks[0], faults=("no_such",))


def test_prefill_then_decode_through_the_latent_cache_matches_the_reference(
        model):
    """Two streams of different lengths in one decode batch (and a slot with
    no stream), prompts shorter than their buckets, contexts that cross the
    original rotary length 16: logits at EVERY position against the
    reference's one full forward and the program's own — the absorbed path
    against the expanded formula, the mixing's kernels on a step's three rows
    against a prompt's thirty-two."""
    m, params, pl = model
    rng = np.random.RandomState(2)
    seqs = [rng.randint(0, V, size=n).astype(np.int32) for n in (34, 27)]
    prompts = [10, 19]
    cache = m.make_cache(NB, BS, "float32")
    assert isinstance(cache, PagedLatentCache)
    state = cache.state()
    tables = np.zeros((3, MB), np.int32)
    want = [_ref_logits(params, s)[0] for s in seqs]
    full = [np.asarray(_jitted(m, "full_logits")(pl, jnp.asarray(s[None])))[0]
            for s in seqs]
    for i, (s, P) in enumerate(zip(seqs, prompts)):
        blocks = cache.allocator.alloc(-(-len(s) // BS))
        tables[i + 1, :len(blocks)] = blocks
        (_, lg, load, ids, x, y, *mix), state = _prefill(
            m, pl, state, tables[i + 1], s[:P], 32)
        np.testing.assert_allclose(np.asarray(lg), want[i][P - 1], atol=ATOL)
        np.testing.assert_allclose(np.asarray(lg), full[i][P - 1], atol=ATOL)
        assert np.asarray(load)[:, 0].tolist() == [3 * P, 3 * P]
        assert ids.shape == (2, 32, 3)
        _probe_matches(params, x, y, ids[0, P - 1:P], *mix)
    for j in range(max(len(s) - p for s, p in zip(seqs, prompts))):
        on = [False] + [p + j < len(s) for s, p in zip(seqs, prompts)]
        tok = [0] + [int(s[p + j]) if o else 0
                     for s, p, o in zip(seqs, prompts, on[1:])]
        pos = [0] + [p + j if o else 0 for p, o in zip(prompts, on[1:])]
        bt = np.where(np.asarray(on)[:, None], tables, 0)
        (_, lg, load, ids, x, y, *mix), state = _step(m, pl, state, tok, pos,
                                                     bt)
        if j % 8 == 0:      # the reference's two jits compile a call
            live = np.flatnonzero(on)
            _probe_matches(params, x[live], y[live], ids[0][live],
                           *(a[live] for a in mix))
        for i in (0, 1):
            if on[i + 1]:
                np.testing.assert_allclose(np.asarray(lg)[i + 1],
                                           want[i][prompts[i] + j], atol=ATOL)
        assert np.asarray(load)[:, 0].tolist() == [3 * sum(on)] * 2


def test_the_selection_bias_chooses_and_the_scores_weigh(model):
    """Given the program's choices the reference agrees (above); its OWN
    choices are the program's too, and a bias that is added to the weights is
    another model: here the chosen scores are divided by their own sum."""
    m, params, pl = model
    toks = np.random.RandomState(3).randint(0, V, size=32).astype(np.int32)
    cache = m.make_cache(NB, BS, "float32")
    table = np.zeros((MB,), np.int32)
    table[:8] = cache.allocator.alloc(8)
    (_, _, _, ids, *_), _ = _prefill(m, pl, cache.state(), table, toks, 32)
    _, own, _ = _ref_logits(params, toks)
    assert np.array_equal(np.sort(np.asarray(ids), -1), np.sort(own, -1))
    logits = jnp.asarray(np.random.RandomState(4).randn(5, 8), jnp.float32)
    bias = jnp.asarray([0.0, 3.0, 0, 0, 0, 0, 0, -3.0])
    sz = dict(ref.sizes(CFG.to_dict()), E=8, K=3)
    own, wts = ref.route(logits, bias, None, sz)
    assert (np.asarray(own) == 1).any(-1).all()        # the bias chooses ...
    s = np.asarray(jax.nn.sigmoid(logits))
    for t in range(5):                                  # ... the scores weigh
        chosen = np.asarray(own)[t]
        np.testing.assert_allclose(
            np.asarray(wts)[t, chosen],
            2.0 * s[t, chosen] / s[t, chosen].sum(), rtol=1e-6)


def test_param_shapes_at_the_published_widths_count_what_the_issue_reckons():
    cfg = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "xing4-29b-a4b-s0.json")))
    m = MODEL_TYPES["xing4_0"](cfg)
    assert isinstance(m, HyperMLATransformerLM)
    assert m.config.model_type == "xing4_0" and m.supports == frozenset()
    shapes = param_shapes(m.config)
    count = lambda keep: sum(  # noqa: E731
        int(np.prod(s)) for n, (s, _) in shapes.items() if keep(n))
    attn = ("wq_a", "wq_b", "wkva", "wkvb", "wo")
    assert count(lambda n: n.startswith("l3.") and n.endswith(attn)) == \
        3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584
    assert count(lambda n: n.startswith("l0.w_")) == 3 * 3584 * 9216
    assert count(lambda n: n.startswith("l3.e_")) == 64 * 3 * 3584 * 1024
    assert count(lambda n: n.startswith("l3.") and "_hc_phi" in n) == \
        2 * 14336 * 24
    assert count(lambda n: n in ("emb", "head")) == 2 * 131072 * 3584
    dense, expert = (count(lambda n, i=i: n.startswith(f"l{i}."))
                     for i in (0, 3))
    assert round(dense / 1e6, 1) == 128.2 and round(expert / 1e6, 1) == 745.0
    total = count(lambda n: True)
    assert total == 2 * dense + 5 * expert + 2 * 131072 * 3584 + 3584
    assert round(total / 1e6) == 4921
    # every hyper-connection tensor is float32, every other as the file says
    from paddle_tpu.decode.mla import param_dtype
    assert {str(param_dtype(m.config, n)) for n in shapes if "_hc_" in n} == \
        {"float32"}
    assert str(param_dtype(m.config, "l3.e_gate")) == "bfloat16"


def test_save_and_load_round_trip_under_its_own_model_type(tmp_path):
    m = HyperMLATransformerLM(dataclasses.replace(CFG, dtype="bfloat16"))
    params = m.init_params(3)
    assert params["l1.e_gate"].dtype == jnp.bfloat16
    assert params["l1.ffn_hc_phi"].dtype == np.float32
    save_lm(str(tmp_path), m.config, params)
    m2, p2 = load_lm(str(tmp_path))
    assert type(m2) is HyperMLATransformerLM and m2.config == m.config
    assert {k: str(v.dtype) for k, v in p2.items()} == \
        {k: str(v.dtype) for k, v in params.items()}
    # ... and DeepSeek-V2's model does not come back as this one
    assert type(MODEL_TYPES["deepseek_v2"]({"vocab_size": 8})) \
        is MLATransformerLM


def test_the_engine_serves_it_over_the_wire_and_counts_the_mixing(model):
    m, params, pl = model
    eng = DecodeEngine(m, params, name="xg_t", max_slots=3, block_tokens=BS,
                       num_blocks=NB, prefill_buckets=[16, 32],
                       prefix_cache=False, overcommit=False)
    server = DecodeServer("127.0.0.1:0", engines={"xg_t": eng})
    server.start()
    try:
        client = DecodeClient(endpoints=[server.endpoint])
        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, V, size=n).astype(np.int32)
                   for n in (7, 18, 25)]
        wants = (9, 5, 3)
        outs = [[int(t) for t in client.generate(
            "xg_t", p, max_new_tokens=n)["tokens"]]
            for p, n in zip(prompts, wants)]
        for p, toks, n in zip(prompts, outs, wants):
            assert len(toks) == n
            seq = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
            full = np.asarray(m.full_logits(pl, jnp.asarray(seq[None])))[0]
            gap = full[len(p) - 1:].max(-1) - np.take_along_axis(
                full[len(p) - 1:], np.asarray(toks)[:, None], 1)[:, 0]
            assert gap.max() < 1e-4        # the greedy token, up to a tie
        c = stats.to_dict()
        # real tokens x sub-layers (two a layer, three layers)
        assert c["decode.xg_t.prefill_mhc_rows"] == (7 + 18 + 25) * 6
        assert c["decode.xg_t.step_mhc_rows"] == (sum(wants) - 3) * 6
        assert c["decode.xg_t.prefill_routed_assignments"] == 50 * 3 * 2
    finally:
        server.stop()
    for kw in ({"prefix_cache": True}, {"overcommit": True}):
        with pytest.raises(ValueError, match="does not support"):
            DecodeEngine(m, params, name="xg_r", max_slots=2,
                         block_tokens=BS, num_blocks=NB,
                         prefill_buckets=[16], **{"prefix_cache": False,
                                                  "overcommit": False, **kw})


def test_the_observer_s_spans_carry_the_mixing_s_rows(monkeypatch):
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
    m = HyperMLATransformerLM(CFG)
    obs = m.observer("xg_o", m.make_cache(NB, BS, "float32"), (4, 8))
    before = stats.to_dict()
    obs.prefill([np.asarray([[30, 7, 9], [30, 8, 11]])], 10, 16)
    obs.step([np.asarray([[6, 5, 2], [6, 4, 3]])], np.asarray([30, 27]))
    after = stats.to_dict()
    assert filed[0][1]["prefill_mhc_rows"] == 10 * 6
    assert filed[1][1]["step_mhc_rows"] == 2 * 6
    for _, args in filed:
        for key, value in args.items():
            name = "decode.xg_o." + key
            assert after[name] - before.get(name, 0) == value


def test_config_refuses_what_is_not_written_down():
    with pytest.raises(ValueError, match="scoring_func"):
        HyperMLAConfig(vocab_size=8, scoring_func="tanh")
    with pytest.raises(ValueError, match="topk_method"):
        HyperMLAConfig(vocab_size=8, topk_method="group_limited_greedy")
    with pytest.raises(ValueError, match="symmetric"):
        HyperMLAConfig(vocab_size=8, hc_mult=4, mhc_h_res_clamp_min=-10.0)
