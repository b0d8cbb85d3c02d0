"""``decode/command_a.py`` (Command A+'s parallel block) at a small size on
the CPU: the program against the plain reference of the published equations
(``benchmark/reference/command_a.py``) on seeded random weights — the full
forward, and a prefill and decode steps through rings that wrap and the pool
—, the GPT-J rotary against rotate-half, the group-16 flash plan and both
walks at the published head shape against their XLA fallbacks, the eight
shares summed to the uncut layer, and the share's plan walked in blocks with
every choice on a held expert."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import command_a as reference  # noqa: E402
from paddle_tpu.decode import DecodeEngine, SamplingParams  # noqa: E402
from paddle_tpu.decode import adapter  # noqa: E402
from paddle_tpu.decode.command_a import (CommandAConfig,  # noqa: E402
                                         CommandALM, rotary_gptj)
from paddle_tpu.kernels import gqa, moe  # noqa: E402

SMALL = dict(vocab_size=96, hidden_size=64, intermediate_size=32,
             num_hidden_layers=4, num_attention_heads=8,
             num_key_value_heads=2, head_dim=16, num_experts=4,
             num_experts_per_tok=2, num_shared_experts=4, sliding_window=32,
             router_experts=16, first_expert=4, max_seq_len=128,
             dtype="float32")


def _model(**over):
    model = CommandALM(CommandAConfig(**{**SMALL, **over}))
    params = model.init_params(5)
    return model, params, model.param_list(params)


def test_the_model_is_registered_under_its_published_type():
    model = adapter.MODEL_TYPES["cohere2_moe"](dict(SMALL))
    cfg = model.config
    assert isinstance(model, CommandALM) and model.supports == frozenset()
    assert (cfg.period, cfg.periods, cfg.window_layers, cfg.q_width,
            cfg.kv_width, cfg.shared_width) == (4, 1, 3, 128, 32, 128)
    assert cfg.layer_types == ("sliding_attention",) * 3 \
        + ("full_attention",)
    assert cfg.to_dict()["model_type"] == "cohere2_moe"
    # the published list whole: a cut in depth reads its first entries
    whole = CommandAConfig(**{**SMALL, "layer_types": list(
        cfg.layer_types) * 8})
    assert whole.layer_types == cfg.layer_types
    names = model.param_names()
    assert "head" not in names and "emb" in names       # one table, tied
    assert model.param_shapes(cfg)["pw.s_gate"][0] == (1, 3, 64, 128)
    assert model.param_shapes(cfg)["pf.router"][0] == (1, 64, 16)


@pytest.mark.parametrize("bad", [
    {"use_parallel_block": False}, {"tie_word_embeddings": False},
    {"expert_selection_fn": "softmax"}, {"use_qk_norm": True},
    {"shared_expert_combination_strategy": "sum"},
    {"position_embedding_type": "rope"}, {"first_k_dense_replace": 1},
    {"layer_types": ["full_attention"] + ["sliding_attention"] * 3},
    {"first_expert": 13}, {"num_hidden_layers": 3}])
def test_what_is_not_written_down_is_refused(bad):
    with pytest.raises(ValueError):
        CommandAConfig(**{**SMALL, **bad})


def test_full_logits_are_the_references():
    """Float32 both sides: the program IS the published equations — one
    LayerNorm, GPT-J pairs on the window layers alone, a sigmoid router
    renormalised over its eight, the held experts only, the four shared
    experts averaged, the tied head."""
    model, params, plist = _model()
    tokens = np.random.default_rng(0).integers(0, 96, (2, 80)).astype(np.int32)
    got = np.asarray(jax.jit(model.full_logits)(plist, jnp.asarray(tokens)))
    for b in range(2):
        want, _, own = reference.forward(params, model.config.to_dict(),
                                         tokens[b], 80, np.arange(80))
        assert np.abs(got[b] - np.asarray(want)).max() \
            < 2e-5 * np.abs(want).max()
    stats = np.asarray(own["stats"])
    # every branch shows, and some choices of the sixteen are held
    assert (stats[:, :3] > 0.05).all() and 0 < stats[:, 5].mean() < 1


def test_a_prompt_past_the_window_then_steps_through_rings_and_pool():
    """A prompt of 70 on a window of 32 (the rings wrap twice in the prefill
    and go on wrapping through the steps) and one inside it, through a
    DecodeEngine: every token's logits are the reference's, and the ring
    holds at row r the last position that is r mod 32."""
    model, params, plist = _model()
    engine = DecodeEngine(model, params, name="ca_t", max_slots=2,
                          block_tokens=16, num_blocks=24,
                          prefill_buckets=[32, 96], max_queue=4,
                          cache_dtype="float32", prefix_cache=False,
                          overcommit=False)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (70, 20)]
    try:
        handles = [engine.submit(p, SamplingParams(temperature=0.0,
                                                   max_new_tokens=24))
                   for p in prompts]
        outs = [h.result(timeout=600.0)["tokens"] for h in handles]
    finally:
        engine.close()
    for prompt, produced in zip(prompts, outs):
        seq = np.concatenate([prompt, produced[:-1]]).astype(np.int32)
        at = prompt.size - 1 + np.arange(len(produced))
        want, _, _ = reference.forward(params, model.config.to_dict(), seq,
                                       seq.size, at)
        want = np.asarray(want)
        assert len(produced) == 24
        # greedy: each token is the reference's argmax (or within its noise)
        top = want.max(-1)
        chosen = want[np.arange(len(produced)), produced]
        assert (top - chosen < 1e-3 * np.abs(want).max()).all()
    z = engine.decodez()
    assert z["step_ring_rows_live"] <= z["step_ring_rows_held"]


def test_prefill_and_steps_file_the_rows_where_the_reference_says():
    model, params, plist = _model()
    cfg = model.config
    cache = model.make_cache(16, 16, "float32", slots=2)
    assert [a.shape for a in cache.state()] == \
        [(1, 16, 16, 64), (3, 2 * 2, 16, 64)]
    toks = np.random.default_rng(2).integers(0, 96, 60).astype(np.int32)
    P, n = 45, 15
    feed = np.zeros((1, 96), np.int32)
    feed[0, :P] = toks[:P]
    table = np.zeros((8,), np.int32)
    table[:4] = [3, 4, 5, 6]
    out, state = jax.jit(model.prefill)(
        plist, cache.state(), jnp.asarray(feed), jnp.int32(P), jnp.int32(1),
        jnp.asarray(table), jnp.uint32(0), jnp.float32(0), jnp.int32(0))
    assert np.asarray(out[2]).shape == (4, 5) \
        and np.asarray(out[3]).shape == (4, 96, 2)
    # every real token made its choices of ALL sixteen experts
    assert (np.asarray(out[2])[:, 4] == P * 2).all()
    tables = np.zeros((2, 8), np.int32)
    tables[1] = table
    step = jax.jit(model.decode_step)
    zeros = jnp.zeros((2,), jnp.int32)
    for j in range(P, P + n):
        tk, pos = np.zeros((2,), np.int32), np.zeros((2,), np.int32)
        tk[1], pos[1] = toks[j], j
        out, state = step(plist, state, jnp.asarray(tk), jnp.asarray(pos),
                          jnp.asarray(tables), jnp.zeros((2,), jnp.uint32),
                          zeros, jnp.zeros((2,), jnp.float32), zeros)
    L = P + n
    want, _, own = reference.forward(params, cfg.to_dict(), toks[:L], L,
                                     np.array([L - 1]), rings=True)
    assert np.abs(np.asarray(out[1])[1] - np.asarray(want)[0]).max() \
        < 2e-5 * np.abs(want).max()
    ring = np.asarray(state[1])[:, 2:4].reshape(3, 32, 64)   # slot 1's blocks
    assert np.asarray(own["ring_rows"]).all()
    assert np.abs(ring - np.asarray(own["rings"])).max() < 1e-4
    # the idle slot was routed nowhere
    assert np.asarray(out[2])[:, 4].tolist() == [2] * 4


def test_the_rotary_pairs_neighbours_and_not_halves():
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 3, 16), jnp.float32)
    pos = jnp.arange(40) + 7
    got = np.asarray(rotary_gptj(x, pos, 50000.0))
    padded = jnp.concatenate([jnp.zeros((7, 3, 16)), x])
    pairs = np.asarray(reference.rotate_pairs(padded, 50000.0))[7:]
    halves = np.asarray(reference.rotate_half(padded, 50000.0))[7:]
    assert np.abs(got - pairs).max() < 1e-5
    assert np.abs(got - halves).max() > 0.1
    assert np.abs(got - np.asarray(adapter.rotary(x, pos, 50000.0))).max() \
        > 0.1
    # pair i turns by position x theta^(-2i/dh): lane 0 and 1 of position 1
    one = np.asarray(rotary_gptj(jnp.ones((1, 1, 16)), jnp.array([1]), 50000.))
    assert np.allclose(one[0, 0, :2], [np.cos(1) - np.sin(1),
                                      np.cos(1) + np.sin(1)], atol=1e-6)
    # a rotation: norms of pairs are kept, and in bf16 nothing is lost to
    # the lanes' exchange
    assert np.allclose((got ** 2).reshape(40, 3, 8, 2).sum(-1),
                       (np.asarray(x) ** 2).reshape(40, 3, 8, 2).sum(-1),
                       rtol=1e-5)


@pytest.mark.parametrize("group", [1, 5, 7, 8, 16])
def test_a_group_of_sixteen_keeps_the_accepted_plan_and_names_its_kernels(
        group):
    """The chip read 256 x 1,024 the fastest plan at a group of 16 too
    (``kernels/gqa.py``'s table, PR 59), so the plan does not ask the group;
    a group past eight is named in its kernels."""
    assert gqa.flash_plan(8192, None) == (256, 1024, 8)
    assert gqa.flash_plan(8192, 4096)[:2] == (256, 1024)
    assert gqa._name("gqa_window_flash_fwd", 128, group) == (
        "gqa16_window_flash_fwd" if group == 16 else "gqa_window_flash_fwd")
    assert gqa._name("gqa_ring_decode_attn", 64, group) == \
        "gqa64_ring_decode_attn"


@pytest.mark.parametrize("window", [None, 256], ids=["full", "window"])
def test_the_group_of_sixteen_flash_forward_is_dense_attention(window):
    """The published head shape — 128 query heads on 8 K/V heads of 128 — in
    interpret mode: the result is the XLA fallback's, a tile of padding
    zeros."""
    T, nh, nkv, dh, length = 768, 128, 8, 128, 300
    kq, kr = jax.random.split(jax.random.PRNGKey(3))
    q = jax.random.normal(kq, (T, nh, dh), jnp.float32)
    rows = jax.random.normal(kr, (T, 2 * nkv * dh), jnp.float32)
    assert gqa.flash_plan(T, window)[:2] == (256, 768)
    got = np.asarray(gqa.group_prefill_attention(q, rows, nkv, window,
                                                 length))
    want = np.asarray(gqa.prefill_attention_xla(q, rows, nkv, window))
    assert np.abs(got[:length] - want[:length]).max() < 2e-3
    assert not got[512:].any()


def test_both_walks_at_a_group_of_sixteen_are_their_xla_fallbacks():
    S, nh, nkv, dh, bs = 3, 128, 8, 128, 16
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(k1, (S, nh, dh), jnp.float32)
    pool = jax.random.normal(k2, (2, 12, bs, 2 * nkv * dh), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [0, 0, 0, 0]],
                         jnp.int32)
    cl = jnp.asarray([60, 17, 1], jnp.int32)
    got = gqa.decode_attention(q, pool, tables, cl, 1, nkv)
    want = gqa.decode_attention_xla(q, pool, tables, cl, 1, nkv)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-3
    rings = jax.random.normal(k3, (3, S * 4, bs, 2 * nkv * dh), jnp.float32)
    ring_tables = jnp.arange(S * 4, dtype=jnp.int32).reshape(S, 4)
    live = jnp.asarray([64, 30, 1], jnp.int32)
    got = gqa.ring_decode_attention(q, rings, ring_tables, live, 2, nkv)
    want = gqa.decode_attention_xla(q, rings, ring_tables, live, 2, nkv)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-3


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The share tied to the model: eight models that hold two of sixteen
    experts each — the router, its top-4 and the renormalisation over all
    sixteen in every one — give, attention and the shared experts counted
    once, the uncut reference's layer."""
    base = {**SMALL, "num_experts_per_tok": 4}
    whole, params, _ = _model(**{**base, "num_experts": 16,
                                 "first_expert": 0})
    rng = np.random.default_rng(6)
    T = 48
    x = jnp.asarray(rng.normal(size=(T, 64)), jnp.float32)
    sz = reference.sizes(whole.config.to_dict())
    attn, ffn, _, _, _ = reference._fns(tuple(sorted(sz.items())),
                                        frozenset())
    w, stacks, at = reference.layer_weights(params, sz, 0)
    with jax.default_matmul_precision("highest"):
        u, a, *_ = attn(w, x, T, True, sz["window"])
        r, s, *_ = ffn(w, stacks, x, u, at, None, T)
    want = np.asarray(x + a + r + s)

    valid = jnp.ones((T,), bool)
    tile = moe.row_tile(T, jnp.float32)
    lay = {k[3:]: v[0, 0] for k, v in params.items() if k.startswith("pw.")}
    total = None
    for share in range(8):
        model = CommandALM(CommandAConfig(**{**base, "num_experts": 2,
                                             "first_expert": 2 * share}))
        held = tuple(params["pw." + k][0, :1, 2 * share:2 * share + 2]
                     for k in adapter.EXPERT_LEAVES)
        un = model._ln(x, lay["ln"])
        logits, ids, weights = model._route(lay, un)
        part, load = model._routed(held, 0, un, ids, weights, valid, tile,
                                   True)
        assert int(load[4]) == T * 4 and 0 <= int(load[0]) <= T * 4
        total = part if total is None else total + part
    pos = jnp.arange(T, dtype=jnp.int32)
    q, rows = model._qkv(lay, un, pos, True, jnp.float32)
    o = gqa.prefill_attention_xla(q, rows, 2, 32)
    got = x + model._attn_out(lay, o, jnp.float32) + total \
        + model._shared(lay, un)
    assert np.abs(np.asarray(got) - want).max() < 2e-5 * np.abs(want).max()
    assert np.abs(np.asarray(total)).max() > 0.01


@pytest.mark.parametrize("wide", [False, True], ids=["whole", "by_f_block"])
def test_no_assignment_to_a_held_expert_is_dropped_at_any_rung(monkeypatch,
                                                               wide):
    """Every choice of every token on the held experts — eight times what the
    share's block expects: the plan is walked in as many blocks as its rows
    need and the sum is the unblocked one, to the rounding; with an expert
    too wide for VMEM (``by_f_block``: the budget shrunk) both walks take it
    a block of its intermediate axis at a time, to the same sum."""
    if wide:
        monkeypatch.setattr(moe, "_WEIGHT_BLOCKS_BYTES", 6 * 128 * 128 * 4)
    T, K, D, F, held, router = 512, 4, 128, 256, 4, 32
    keys = jax.random.split(jax.random.PRNGKey(9), 5)
    x = jax.random.normal(keys[0], (T, D), jnp.float32)
    wg = jax.random.normal(keys[1], (held, D, F), jnp.float32) * D ** -0.5
    wu = jax.random.normal(keys[2], (held, D, F), jnp.float32) * D ** -0.5
    wd = jax.random.normal(keys[3], (held, F, D), jnp.float32) * F ** -0.5
    assert moe.f_block(D, F, 4) == (128 if wide else F)
    # the token's four choices are the four held experts, 8 .. 11 of 32
    ids = jnp.tile(jnp.arange(8, 12, dtype=jnp.int32)[None], (T, 1))
    weights = jax.nn.softmax(jax.random.normal(keys[4], (T, K), jnp.float32),
                             -1)
    valid = jnp.arange(T) < 500
    tile = moe.row_tile(T, jnp.float32)
    plan = moe.plan_groups(ids, valid, held, tile, first=8)
    assert int(plan.load[0]) == 500 * K
    block = moe.share_block_rows(T, K, held, router, tile)
    assert block == 896 and plan.row_token.shape[0] == 2560     # 3 blocks
    want = moe.planned_experts(x, weights, plan, wg, wu, wd, tile,
                               impl="xla")
    got = moe.planned_experts(x, weights, plan, wg, wu, wd, tile,
                              row_block=block)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    assert not np.asarray(got)[500:].any()
    whole = moe.planned_experts(x, weights, plan, wg, wu, wd, tile)
    assert np.abs(np.asarray(got) - np.asarray(whole)).max() < 1e-4
    # a decode step's few rows: the tile walk, whole or a block at a time
    S = 32
    plan = moe.plan_groups(ids[:S], valid[:S], held, 8, first=8)
    got = moe.planned_experts(x[:S], weights[:S], plan, wg, wu, wd, 8)
    want = moe.planned_experts(x[:S], weights[:S], plan, wg, wu, wd, 8,
                               impl="xla")
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4


def test_the_published_experts_are_walked_a_quarter_at_a_time():
    # 4,096 x 4,096 bf16: 96 MB an expert, a quarter of it a grid step; the
    # accepted models' experts whole
    assert moe.f_block(4096, 4096, 2) == 1024
    for D, F in ((2048, 1408), (2560, 768), (2048, 1536), (2304, 1024),
                 (3584, 1024)):
        assert moe.f_block(D, F, 2) == F
    assert moe.share_block_rows(8192, 8, 16, 128, 128) == 11264
    assert moe.plan_rows(8192, 8, 16, 128) == 65536 + 16 * 128
