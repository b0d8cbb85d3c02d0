"""``decode/adapter.py``: the model protocol as the six served models keep it,
and the pieces they share — the layer math against NumPy, the paged pool's
addressing (the trash block's rule), the observers' common series.

The per-model tables below are the contract the benchmark reads by name: the
series each model registers under ``decode.<engine>.`` (none gained, none
lost) and the arguments its ``decode::prefill.observe`` /
``decode::step.observe`` spans carry (the rooflines pair them with launches).
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.decode import (FalconH1Config, FalconH1LM, LFM2Config, LFM2LM,
                               LMConfig, MLAConfig, MLATransformerLM,
                               SambaYConfig, SambaYLM, SmallThinkerConfig,
                               SmallThinkerLM, TransformerLM)
from paddle_tpu.decode import adapter
from paddle_tpu.decode import (falcon_h1, kimi_linear, lfm2, mla, model,
                               sambay, smallthinker)
from paddle_tpu.decode import KimiLinearConfig, KimiLinearLM
from paddle_tpu.observability import stats
from paddle_tpu.observability import trace

COMMON = {"prefill_real_tokens", "prefill_pad_tokens", "prefill_tokens_sq",
          "step_context_tokens"}
POOL = {"step_streams", "step_live_blocks", "step_table_blocks",
        "kv_live_tokens", "kv_pool_bytes"}
ROUTED = {"prefill_routed_assignments", "step_routed_assignments",
          "step_moe_dispatches", "step_experts_touched",
          "step_expert_load_max_sum", "expert_load_max"}
BUCKETS_TO_8K = tuple(float(2 ** i) for i in range(14))
BUCKETS_TO_16K = tuple(float(2 ** i) for i in range(15))

# model → (its model, the module whose ``param_shapes`` orders ``const``, the
# series it registers, the arguments of its prefill span, of its step span,
# the ``expert_load_max`` buckets, the columns of a dispatch's load row, and
# what one step of two streams of 30 and 27 tokens and one idle slot adds to
# (``step_live_blocks``, ``step_table_blocks``) at 8-token blocks and tables
# of 8: a walk over the pool fetches 4 + 4 + 1 blocks of 3 x 8 entries)
ADAPTERS = {
    "lm": (lambda: TransformerLM(LMConfig(vocab=48, d_model=32, n_head=2,
                                          d_ffn=48, max_seq_len=64)),
           None, {"step_live_blocks", "step_table_blocks"}, None, None, None,
           0, (9, 24)),
    "mla": (lambda: MLATransformerLM(MLAConfig(vocab_size=64)), mla,
            COMMON | ROUTED | {"latent_live_tokens", "latent_pool_bytes"},
            {"prefill_routed_assignments", "prefill_tokens_sq"},
            {"step_routed_assignments", "step_experts_touched",
             "step_context_tokens"}, BUCKETS_TO_8K, 3, None),
    "sambay": (lambda: SambaYLM(SambaYConfig(vocab_size=64, hidden_size=64,
                                             intermediate_size=96)), sambay,
               COMMON | POOL | {"prefill_scan_tokens", "prefill_window_pairs",
                                "step_window_tokens", "window_state_bytes",
                                "recurrent_state_bytes"},
               {"prefill_scan_tokens", "prefill_window_pairs",
                "prefill_tokens_sq"},
               {"step_context_tokens", "step_window_tokens", "step_streams"},
               # two pool readers; two rings of one 8-row block a slot
               None, 0, (2 * 9 + 2 * 3, 3 * (2 * 8 + 2 * 1))),
    "falcon_h1": (lambda: FalconH1LM(FalconH1Config(vocab_size=64)),
                  falcon_h1,
                  COMMON | POOL | {"prefill_scan_chunks", "step_state_bytes",
                                   "recurrent_state_bytes"},
                  {"prefill_real_tokens", "prefill_pad_tokens",
                   "prefill_scan_chunks", "prefill_tokens_sq"},
                  {"step_context_tokens", "step_streams", "step_state_bytes"},
                  None, 0, (2 * 9, 2 * 24)),             # two layers
    "smallthinker": (lambda: SmallThinkerLM(SmallThinkerConfig(vocab_size=64)),
                     smallthinker,
                     COMMON | POOL | ROUTED | {
                         "prefill_window_pairs", "step_ring_rows_live",
                         "step_ring_rows_held", "step_streams_past_window",
                         "window_state_bytes"},
                     {"prefill_routed_assignments", "prefill_real_tokens",
                      "prefill_window_pairs", "prefill_tokens_sq"},
                     {"step_routed_assignments", "step_experts_touched",
                      "step_context_tokens", "step_ring_rows_live",
                      "step_streams"}, BUCKETS_TO_16K, 3,
                     # one full layer; three rings of two 16-row blocks a slot
                     (9 + 3 * 5, 3 * (8 + 3 * 2))),
    "lfm2": (lambda: LFM2LM(LFM2Config(vocab_size=64)), lfm2,
             COMMON | POOL | ROUTED | {
                 "prefill_moe_dispatches", "prefill_expert_load_max_sum",
                 "prefill_plan_rows", "prefill_plan_pad_rows",
                 "conv_state_bytes"},
             {"prefill_routed_assignments", "prefill_plan_rows",
              "prefill_real_tokens", "prefill_tokens_sq"},
             {"step_routed_assignments", "step_experts_touched",
              "step_context_tokens", "step_streams"}, BUCKETS_TO_16K, 4,
             (9, 24)),                                   # one attention layer
    "kimi_linear": (lambda: KimiLinearLM(KimiLinearConfig(vocab_size=64)),
                    kimi_linear,
                    COMMON | POOL | ROUTED | {
                        "step_choices", "prefill_choices", "step_state_bytes",
                        "prefill_moe_dispatches", "prefill_experts_touched",
                        "prefill_expert_load_max_sum", "prefill_plan_rows",
                        "prefill_plan_pad_rows", "recurrent_state_bytes"},
                    {"prefill_routed_assignments", "prefill_choices",
                     "prefill_plan_rows", "prefill_real_tokens",
                     "prefill_tokens_sq"},
                    {"step_routed_assignments", "step_experts_touched",
                     "step_choices", "step_context_tokens", "step_streams",
                     "step_state_bytes"}, BUCKETS_TO_16K, 5,
                    (9, 24)),                            # one latent layer
}
SLOTS, TABLE, NB, BS = 3, 8, 9, 8


@pytest.fixture()
def spans(monkeypatch):
    """Every span the observers open, as (name, arguments)."""
    filed = []

    class Span:
        def __init__(self, name, **args):
            self.name, self.args = name, dict(args)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            filed.append((self.name, self.args))

        def annotate(self, **args):
            self.args.update(args)

    monkeypatch.setattr(trace, "span", Span)
    return filed


@pytest.mark.parametrize("key", list(ADAPTERS))
def test_a_served_model_keeps_the_protocol(key, spans):
    (make, module, series, prefill_args, step_args, buckets, columns,
     walks) = ADAPTERS[key]
    m = make()
    assert isinstance(m, adapter.LMAdapter)
    assert type(m).from_dict(m.config.to_dict()).config == m.config
    # const's order is param_shapes' order, and init_params draws exactly it
    names = m.param_names()
    if module is None:
        assert names == model._param_names(m.config)
    else:
        assert names == list(module.param_shapes(m.config))
        assert m.config.to_dict()["model_type"] == module.MODEL_TYPE
        assert adapter.MODEL_TYPES[module.MODEL_TYPE] == type(m).from_dict
    # ... a function of the seed, and of nothing else
    a, b, c = m.init_params(3), m.init_params(3), m.init_params(4)
    assert set(a) == set(names)
    assert all(np.array_equal(np.asarray(a[n]), np.asarray(b[n]))
               for n in names)
    assert any(not np.array_equal(np.asarray(a[n]), np.asarray(c[n]))
               for n in names)
    assert [np.asarray(x).shape for x in m.param_list(a)] == \
        [np.asarray(a[n]).shape for n in names]
    # a model with rows by slot is refused a cache without the slot count
    if m.slot_state:
        with pytest.raises(ValueError, match="slot count"):
            m.make_cache(NB, BS, "float32")
        cache = m.make_cache(NB, BS, "float32", slots=SLOTS)
    else:
        cache = m.make_cache(NB, BS, "float32")
    # the series it registers under decode.<engine>.: none gained, none lost
    name = f"adp_{key}"
    prefix = f"decode.{name}."
    obs = m.observer(name, cache, (SLOTS, TABLE))
    registered = {n[len(prefix):] for n in stats.default_registry().names()
                  if n.startswith(prefix)}
    assert registered == series
    if buckets is not None:
        edges = stats.snapshot()[prefix + "expert_load_max"]["buckets"]
        assert tuple(b for b in edges if math.isfinite(b)) == buckets
    # ... and what its spans carry: the launch's own additions to the
    # counters of the same names
    extra = [np.asarray([[30, 7, 9, 128, 40][:columns],
                         [30, 8, 11, 128, 40][:columns]])] if columns else []
    before = stats.to_dict()
    obs.prefill(extra, 10, 16)
    obs.step(extra, np.asarray([30, 27]))
    after = stats.to_dict()
    if prefill_args is None:
        assert spans == []
    else:
        assert [n for n, _ in spans] == ["decode::prefill.observe",
                                         "decode::step.observe"]
        assert set(spans[0][1]) == prefill_args
        assert set(spans[1][1]) == step_args
        for _, args in spans:
            for k, value in args.items():
                assert after[prefix + k] - before.get(prefix + k, 0) == value
        assert after[prefix + "prefill_real_tokens"] \
            - before.get(prefix + "prefill_real_tokens", 0) == 10
        assert after[prefix + "prefill_pad_tokens"] \
            - before.get(prefix + "prefill_pad_tokens", 0) == 6
        assert after[prefix + "step_context_tokens"] \
            - before.get(prefix + "step_context_tokens", 0) == 57
    z = obs.decodez()
    if walks is None:
        assert z == {}
    else:
        assert (z["step_live_blocks"], z["step_table_blocks"]) == walks


def test_no_adapter_imports_a_sibling_and_the_shared_names_are_the_adapter_s():
    for mod in (mla, sambay, falcon_h1, smallthinker, lfm2, kimi_linear):
        assert not [v for v in vars(mod).values()
                    if getattr(v, "__name__", "") in (
                        "paddle_tpu.decode.mla", "paddle_tpu.decode.sambay",
                        "paddle_tpu.decode.kimi_linear",
                        "paddle_tpu.decode.falcon_h1",
                        "paddle_tpu.decode.smallthinker",
                        "paddle_tpu.decode.lfm2", "paddle_tpu.decode.model")]
        assert mod.MODEL_TYPES is adapter.MODEL_TYPES
    assert model.MODEL_TYPES is adapter.MODEL_TYPES
    assert model.TOPK_MAX == adapter.TOPK_MAX
    assert smallthinker.rotary is lfm2.rotary is falcon_h1.rotary \
        is adapter.rotary
    assert smallthinker.EXPERT_LEAVES is lfm2.EXPERT_LEAVES \
        is kimi_linear.EXPERT_LEAVES is adapter.EXPERT_LEAVES
    # the latent attention's layer math is written once, and both models
    # that have it use the adapter's
    assert mla.LatentAttention is kimi_linear.LatentAttention \
        is adapter.LatentAttention


# -- the shared layer math against NumPy ------------------------------------
@pytest.mark.parametrize("dtype,eps", [("float32", 1e-5), ("bfloat16", 1e-6)])
def test_rms_norm_is_numpy_s(dtype, eps):
    rng = np.random.RandomState(0)
    x = rng.randn(5, 3, 16).astype("float32")
    g = (1.0 + 0.1 * rng.randn(16)).astype("float32")
    xd, gd = jnp.asarray(x, dtype), jnp.asarray(g, dtype)
    got = adapter.rms_norm(xd, gd, eps)
    assert got.dtype == xd.dtype and got.shape == x.shape
    x32, g32 = np.asarray(xd, "float32"), np.asarray(gd, "float32")
    want = x32 / np.sqrt(np.mean(x32 * x32, -1, keepdims=True) + eps) * g32
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got, "float32"), want, rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("theta", [1e4, 1.5e6, 1e11])
def test_rotary_is_numpy_s_rotate_half(theta):
    rng = np.random.RandomState(1)
    x = rng.randn(6, 2, 8).astype("float32")
    pos = np.asarray([0, 1, 2, 17, 300, 4095])
    got = np.asarray(adapter.rotary(jnp.asarray(x), jnp.asarray(pos), theta))
    half = 4
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = pos[:, None].astype(np.float64) * inv[None, :]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # position 0 rotates nothing, and a rotation keeps a pair's length
    np.testing.assert_array_equal(got[0], x[0])
    np.testing.assert_allclose(got[..., :half] ** 2 + got[..., half:] ** 2,
                               a * a + b * b, rtol=1e-4, atol=1e-5)


def test_mm_sub_unscanned_and_init_tensor():
    x = jnp.ones((2, 4), jnp.bfloat16)
    y = adapter.mm(x, jnp.full((4, 3), 0.5, jnp.bfloat16))
    assert y.dtype == jnp.bfloat16 and float(y[0, 0]) == 2.0
    w = {"pa.ln1": 1, "pa.e_gate": 2, "pc.ln1": 3, "emb": 4}
    assert adapter.sub(w, "pa.") == {"ln1": 1, "e_gate": 2}
    assert adapter.unscanned(adapter.sub(w, "pa.")) == {"ln1": 1}
    key = jax.random.PRNGKey(0)
    n = np.asarray(jax.random.normal(key, (64,), jnp.float32))
    for init, want in (("norm", 1.0 + 0.1 * n), ("bias", 0.02 * n),
                       (0.25, 0.25 * n)):
        got = adapter.init_tensor(key, (64,), init, jnp.float32)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                                   atol=1e-7)
    with pytest.raises(KeyError):
        adapter.init_tensor(key, (4,), "a_log", jnp.float32)
    # a model's own rules come first, the rest are the adapter's
    for own in (sambay.init_tensor, falcon_h1.init_tensor):
        np.testing.assert_array_equal(
            np.asarray(own(key, (64,), "norm", jnp.float32)),
            np.asarray(adapter.init_tensor(key, (64,), "norm", jnp.float32)))
        assert float(own(key, (2, 2), "skip", jnp.float32)[0, 0]) == 1.0


def test_a_prefill_s_sampling_tail_is_row_zero_of_the_epilogue():
    logits = jnp.asarray(np.random.RandomState(2).randn(50), jnp.float32)
    seed, top_k = jnp.uint32(9), jnp.int32(5)
    for temp in (0.0, 0.8):
        one = adapter.sample_first(logits, seed, jnp.float32(temp), top_k)
        many = adapter.sample(logits[None], seed[None],
                              jnp.zeros((1,), jnp.int32),
                              jnp.float32(temp)[None], top_k[None])
        assert one.shape == () and int(one) == int(many[0])
    assert int(adapter.sample_first(logits, seed, jnp.float32(0.0), top_k)) \
        == int(np.argmax(np.asarray(logits)))


# -- the paged pool's addressing --------------------------------------------
@pytest.mark.parametrize("length,table", [
    (0, [3, 5, 7]), (1, [3, 5, 7]), (5, [3, 5, 7]), (12, [3, 5, 7]),
    (12, [3, 5]), (9, [4])],
    ids=["empty", "one", "inside", "whole", "short_table", "one_entry"])
def test_a_prompt_s_pads_land_in_the_trash_block_and_a_short_table_is_clamped(
        length, table):
    bucket, bs = 12, 4
    pos, valid, blocks, last = adapter.prompt_addresses(
        jnp.int32(length), bucket, jnp.asarray(table, jnp.int32), bs)
    np.testing.assert_array_equal(np.asarray(pos), np.arange(bucket))
    assert pos.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(valid),
                                  np.arange(bucket) < length)
    want = [table[min(p // bs, len(table) - 1)] if p < length else 0
            for p in range(bucket)]
    np.testing.assert_array_equal(np.asarray(blocks), want)
    assert not np.any(np.asarray(blocks)[length:])      # pads: block 0
    assert int(last) == max(length - 1, 0)


def test_a_step_s_idle_slot_is_not_live_and_writes_the_trash_block():
    bs = 4
    tables = jnp.asarray([[3, 5, 7], [0, 0, 0], [2, 6, 0], [9, 0, 0]],
                         jnp.int32)
    positions = jnp.asarray([9, 0, 4, 3], jnp.int32)
    cl, live, slots, blocks = adapter.step_addresses(positions, tables, bs)
    np.testing.assert_array_equal(np.asarray(cl), [10, 1, 5, 4])
    np.testing.assert_array_equal(np.asarray(live),
                                  [True, False, True, True])
    np.testing.assert_array_equal(np.asarray(slots), [0, 1, 2, 3])
    assert slots.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(blocks), [7, 0, 6, 9])


def test_walked_blocks_counts_a_live_stream_s_blocks_and_an_idle_slot_s_one():
    assert adapter.walked_blocks([30, 27], 8, 3) == 4 + 4 + 1
    assert adapter.walked_blocks([], 8, 3) == 3
    assert adapter.walked_blocks(np.asarray([8, 9]), 8, 2) == 1 + 2


# -- the observers' bases ---------------------------------------------------
class _Cache:
    block_tokens, kv_pool_bytes, live_tokens = 8, 4096, 0


def _delta(before, after, name):
    return after[name] - before.get(name, 0)


def test_the_observer_bases_add_a_launch_s_own_figures():
    cache = _Cache()
    obs = adapter.PoolObserver("adp_base", cache, "cfg", (3, 8))
    assert obs.config == "cfg" and obs.cache is cache
    p = "decode.adp_base."
    assert stats.to_dict()[p + "kv_pool_bytes"] == 4096
    before = stats.to_dict()
    obs.count_prompt(10, 16)
    obs.count_prompt(16, 16)
    assert obs.count_streams(np.asarray([30, 27])) == (57, 2)
    assert obs.pool_walk(np.asarray([30, 27])) == 9
    obs.count_walks(2 * 9, 2 * 24)
    after = stats.to_dict()
    assert [_delta(before, after, p + n) for n in (
        "prefill_real_tokens", "prefill_pad_tokens", "prefill_tokens_sq",
        "step_context_tokens", "step_streams", "step_live_blocks",
        "step_table_blocks")] == [26, 6, 356, 57, 2, 18, 48]
    assert after[p + "kv_live_tokens"] == 57 and cache.live_tokens == 57
    z = obs.decodez()
    assert z == {"step_live_blocks": after[p + "step_live_blocks"],
                 "step_table_blocks": after[p + "step_table_blocks"]}
    assert adapter.LaunchObserver("adp_base", cache, None, (3, 8)
                                  ).decodez() == {}


def test_the_routed_load_series_count_every_dispatch():
    routed = adapter.RoutedLoadSeries(stats.scope("decode.adp_routed"),
                                      buckets=(1, 2, 4, 8, 16))
    p = "decode.adp_routed."
    before = stats.to_dict()
    load = np.asarray([[30, 7, 9], [30, 8, 11]])
    assert routed.count_prefill(load) == 60
    assert routed.count_step(np.asarray([[6, 5, 2], [6, 4, 3]])) == (12, 9)
    after = stats.to_dict()
    assert [_delta(before, after, p + n) for n in (
        "prefill_routed_assignments", "step_routed_assignments",
        "step_moe_dispatches", "step_experts_touched",
        "step_expert_load_max_sum")] == [60, 12, 2, 9, 5]
    hist = stats.snapshot()[p + "expert_load_max"]
    assert hist["count"] == 4 and hist["sum"] == 9 + 11 + 2 + 3


def test_config_dict_round_trips_and_names_the_model():
    cfg = LFM2Config.from_dict({"vocab_size": 64, "unknown_key": 1,
                                "rope_parameters": {"rope_theta": 5e5}})
    assert cfg.rope_theta == 5e5        # LFM2's own step, then the shared one
    d = cfg.to_dict()
    assert d["model_type"] == "lfm2_moe" and "unknown_key" not in d
    assert LFM2Config.from_dict(d) == cfg
    assert "model_type" not in LMConfig(vocab=8).to_dict()
    assert "model_type" not in {f.name
                                for f in dataclasses.fields(LFM2Config)}
