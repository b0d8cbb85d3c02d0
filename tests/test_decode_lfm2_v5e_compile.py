"""Pool and convolution tails of ``decode/lfm2.py`` stay in place in every
layer, Mosaic accepts the kernels at LFM2-24B-A2B's published shapes — heads
of 64, a pair of K/V heads a lane tile —, no program builds a ``[T, T]`` score
or takes an XLA fallback, no layer's experts are sliced out of their stack,
and the benchmark's cut fits the chip — checked with the TPU's own compiler
for a v5e that is described and not attached (no chip, no chip time), as
``test_decode_smallthinker_v5e_compile.py`` does.

The configuration is the benchmark's whole
(``benchmark/configs/lfm2-24b-a2b-pp5s0.json``, ``traffic/topic_sat.json``):
ten layers, 64 experts a layer, the whole vocabulary, 64 slots, the mix's
pool, 1,024-block tables.  Nothing is allocated: the programs are compiled
from shapes.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.decode.lfm2 import LFM2Config, LFM2LM, param_shapes
from paddle_tpu.kernels import diffattn as DK
from paddle_tpu.kernels import gqa as GK
from paddle_tpu.kernels import moe as EK
from paddle_tpu.observability import stats
from paged_walks import check_both_walks_on, eqns_under

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmark", "configs",
                       "lfm2-24b-a2b-pp5s0.json")) as f:
    RAW = json.load(f)
with open(os.path.join(REPO, "benchmark", "traffic", "topic_sat.json")) as f:
    ENGINE = json.load(f)["engine"]
CFG = LFM2Config.from_dict(RAW)
S, NB, BS = ENGINE["max_slots"], ENGINE["num_blocks"], ENGINE["block_tokens"]
MB = CFG.max_seq_len // BS
LADDER = ENGINE["prefill_buckets"]
# what the issue set before any chip time: arguments + temporaries of the
# step and of the largest rung at or under this, or the pool shrinks
FITS_BYTES = 14.6e9
FALLBACKS = ("moe.grouped_swiglu_fallbacks", "attn.gqa_decode_fallbacks",
             "attn.gqa_window_prefill_fallbacks",
             "attn.gqa_prefill_fallbacks")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def mosaic(monkeypatch):
    """As on the chip: off it the kernels interpret themselves (compile
    them), and tier-1 turns x64 on (the chip's processes never do)."""
    for mod in (DK, GK, EK):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)
    with jax.enable_x64(False):
        yield


def _shapes(one_chip, bucket):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    model = LFM2LM(CFG)
    plist = [sds(shape, jnp.bfloat16)
             for shape, _ in param_shapes(CFG).values()]
    state = [sds(a.shape, a.dtype) for a in jax.eval_shape(
        lambda: model.make_cache(NB, BS, "bfloat16", slots=S).state())]
    i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32
    if bucket is None:
        feed = [sds((S,), i32), sds((S,), i32), sds((S, MB), i32),
                sds((S,), u32), sds((S,), i32), sds((S,), f32),
                sds((S,), i32)]
        fn = model.decode_step
    else:
        feed = [sds((1, bucket), i32), sds((), i32), sds((), i32),
                sds((MB,), i32), sds((), u32), sds((), f32), sds((), i32)]
        fn = model.prefill
    return (lambda feed, state, const: fn(const, state, *feed)), \
        feed, state, plist


def _layer(shapes, prefix):
    """Numbers in ONE layer of the stack under ``prefix``."""
    lead = 2 if prefix == "pc." else 1      # [P, period - 1, ...] / [n, ...]
    return sum(int(np.prod(s[lead:])) for k, (s, _) in shapes.items()
               if k.startswith(prefix))


def test_the_cut_is_the_issue_s_and_its_bytes_are_as_reckoned():
    assert (CFG.num_hidden_layers, CFG.vocab_size, CFG.hidden_size,
            CFG.max_seq_len, CFG.head_dim, CFG.intermediate_size,
            CFG.moe_intermediate_size, CFG.num_experts,
            CFG.num_experts_per_tok, CFG.conv_L_cache, CFG.rope_theta) == \
        (10, 65536, 2048, 16384, 64, 11776, 1536, 64, 4, 3, 1e6)
    assert (CFG.num_attention_heads, CFG.num_key_value_heads) == (32, 8)
    assert (CFG.num_dense_layers, CFG.periods, CFG.period,
            CFG.conv_layers) == (2, 2, 4, 8)
    assert RAW["reduced"] == ["num_hidden_layers"]
    assert len(RAW["layer_types"]) == 40        # kept whole: the cut reads 10
    assert (S, BS, MB) == (64, 16, 1024) and LADDER[-1] == 12288
    shapes = param_shapes(CFG)
    numbers = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert numbers == 5_267_090_176
    # a layer's three shapes: dense + conv, experts + attention, experts +
    # conv
    assert (_layer(shapes, "d."), _layer(shapes, "pa."),
            _layer(shapes, "pc.")) == (89_139_200, 614_600_896, 620_898_368)
    kv, conv = jax.eval_shape(lambda: LFM2LM(CFG).make_cache(
        NB, BS, "bfloat16", slots=S).state())
    assert kv.shape == (2, NB, BS, 1024)        # 4,096 B a token
    assert int(np.prod(kv.shape)) * 2 == NB * 16 * 4096
    assert conv.shape == (8, S, 2, 2048)
    assert int(np.prod(conv.shape)) * 2 == 4_194_304
    assert round(numbers * 2 / 1e9, 2) == 10.53


@pytest.mark.parametrize("bucket", [None, LADDER[0], LADDER[-1]],
                         ids=["step", "prefill_first", "prefill_last"])
def test_state_stays_in_place_no_wide_score_no_fallback_and_the_cut_fits(
        one_chip, mosaic, bucket):
    fn, feed, state, plist = _shapes(one_chip, bucket)
    before = {n: stats.to_dict().get(n, 0) for n in FALLBACKS}
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        feed, state, plist).compile()
    assert {n: stats.to_dict().get(n, 0) for n in FALLBACKS} == before
    text = compiled.as_text()
    kv = state[0]
    dims = ",".join(map(str, kv.shape))
    # no program copies the pool, and it keeps the layout it was given
    copies = re.findall(r"\[%s\]\S* copy\(" % dims, text)
    assert not copies, f"{len(copies)} copies of {kv.shape}"
    assert re.search(r"bf16\[%s\]\{3,2,1,0:T\(" % dims, text)
    # ... no [T, T]-wide score (nor a table-wide one in the step) anywhere
    # (a rung of 1,024 is as wide as a cached row: told apart at the others)
    T = bucket or MB * BS
    wide = re.findall(r"(?:f32|bf16)\[[\d,]*%d,%d\]" % (T, T), text)
    assert T == 2 * CFG.kv_width or not wide, wide[:2]
    # ... the short convolution needs no kernel: between its two products
    # the gates and the three taps are ONE fusion, and nothing of the
    # mixer's goes to HBM in float32 (the input product's [T, 6144] leaves
    # its fusion as bf16, the gated taps' [T, 2048] too)
    if bucket is not None:
        tops = re.findall(r"^  %%\S+ = (\w+)\[%d,(\d+)\]\S* fusion\(.*"
                          r"conv_mixer" % bucket, text, re.M)
        assert {w for _, w in tops} == {"2048", "6144"}, tops
        assert all(dtype == "bf16" for dtype, _ in tops), tops
    # ... no layer's experts are sliced out of their stack: nothing of an
    # expert stack's size, or of one layer's experts', is copied or sliced
    for lead in ("2,64", "6,64", "2,3,64", "64"):
        for tail in ("2048,1536", "1536,2048"):
            hit = re.findall(r"bf16\[%s,%s\]\S* (?:copy|dynamic-slice|"
                             r"slice)\(" % (lead, tail), text)
            assert not hit, hit[:2]
    # ... Mosaic took every kernel of the program (the layers are scanned:
    # one attention layer's kernel, the experts' once a kind of layer)
    for name in (("gqa64_paged_decode_attn", "moe_grouped_swiglu")
                 if bucket is None else
                 ("gqa64_group_flash_fwd", "moe_grouped_swiglu")):
        assert name in text, name
    assert "gqa_paged_decode_attn" not in text \
        and "gqa_group_flash_fwd" not in text
    assert text.count("tpu_custom_call") == 3       # the experts' twice
    # ... and arguments + temporaries + what is not aliased of the results
    # fit the chip as the issue reckoned
    mem = compiled.memory_analysis()
    live = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    print(f"bucket {bucket}: arguments {mem.argument_size_in_bytes / 1e9:.3f} "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} outputs "
          f"{(mem.output_size_in_bytes - mem.alias_size_in_bytes) / 1e9:.3f} "
          f"GB")
    assert 12.0e9 < live <= FITS_BYTES, live


def test_the_step_s_walk_steps_by_slot_over_pairs_of_kv_heads(one_chip,
                                                              mosaic):
    fn, feed, state, plist = _shapes(one_chip, None)
    calls = {e.params["name"]: e for e in eqns_under(
        jax.make_jaxpr(fn)(feed, state, plist).jaxpr)
        if e.primitive.name == "pallas_call"}
    walk = calls["gqa64_paged_decode_attn"]
    assert tuple(walk.params["grid_mapping"].grid) == (S,)
    # four lane tiles of a pair of K/V heads, the pair's 2 x 4 query heads
    # the eight rows that share one
    assert walk.invars[3].aval.shape == (S, 4, 8, 128)
    # 64 tokens x 4 experts in 16-row tiles, every expert's last tile padded
    assert tuple(calls["moe_grouped_swiglu"].params["grid_mapping"].grid) \
        == (EK.plan_rows(S, 4, 64, 16) // 16,)


def test_mosaic_accepts_the_expert_walk_and_the_step_keeps_its_tiles(
        one_chip, mosaic):
    """[2048, 1536] x 64 experts at top-4, a layer of the stack of eight: the
    12,288 rung's rows an expert a grid step, the step's 64 tokens a 16-row
    tile a grid step."""
    check_both_walks_on(one_chip, S, LADDER[-1], CFG.num_experts_per_tok,
                        CFG.num_experts, CFG.hidden_size,
                        CFG.moe_intermediate_size, "silu", jnp.bfloat16,
                        layers=8)
