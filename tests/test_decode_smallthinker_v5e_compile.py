"""The two kinds of per-stream state of ``decode/smallthinker.py`` stay in
place in every layer, Mosaic accepts the new kernels at SmallThinker-21BA3B's
published shapes, no layer's experts are sliced out of their stack, and the
benchmark's cut fits the chip — checked with the TPU's own compiler for a v5e
that is described and not attached (no chip, no chip time), as
``test_decode_falcon_h1_v5e_compile.py`` does.

The configuration is the benchmark's whole
(``benchmark/configs/smallthinker-21b-pp7s0.json``,
``traffic/mixed_sat.json``): eight layers, 64 experts a layer, the whole
vocabulary, 64 slots, 4,096-row rings, the mix's pool, 1,024-block tables.
Nothing is allocated: the programs are compiled from shapes.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.decode.smallthinker import (SmallThinkerConfig,
                                            SmallThinkerLM, param_shapes)
from paddle_tpu.kernels import diffattn as DK
from paddle_tpu.kernels import gqa as GK
from paddle_tpu.kernels import moe as EK
from paged_walks import check_both_walks_on, eqns_under

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmark", "configs",
                       "smallthinker-21b-pp7s0.json")) as f:
    RAW = json.load(f)
with open(os.path.join(REPO, "benchmark", "traffic", "mixed_sat.json")) as f:
    ENGINE = json.load(f)["engine"]
CFG = SmallThinkerConfig.from_dict(RAW)
S, NB, BS = ENGINE["max_slots"], ENGINE["num_blocks"], ENGINE["block_tokens"]
MB = CFG.max_seq_len // BS
LADDER = ENGINE["prefill_buckets"]
# what the issue set before any chip time: arguments + temporaries of the
# step and of the largest rung at or under this, or the pool shrinks
FITS_BYTES = 14.6e9


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

    model = SmallThinkerLM(CFG)
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


def test_the_cut_is_the_issue_s_and_its_bytes_are_as_reckoned():
    assert (CFG.num_hidden_layers, CFG.vocab_size, CFG.hidden_size,
            CFG.max_seq_len, CFG.sliding_window_size) == \
        (8, 151936, 2560, 16384, 4096)
    assert (CFG.periods, CFG.window_layers) == (2, 6)
    assert (S, BS, MB) == (64, 16, 1024) and LADDER[-1] == 12288
    shapes = param_shapes(CFG)
    numbers = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert numbers == 3_966_937_600
    a_layer = sum(int(np.prod(s[1:])) for k, (s, _) in shapes.items()
                  if k.startswith("pf."))
    assert a_layer == 398_627_840
    kv, rings = jax.eval_shape(lambda: SmallThinkerLM(CFG).make_cache(
        NB, BS, "bfloat16", slots=S).state())
    assert rings.shape == (6, S * 256, 16, 1024)
    assert int(np.prod(rings.shape)) * 2 == 6 * 64 * 4096 * 2048 \
        == 3_221_225_472
    assert kv.shape == (2, NB, BS, 1024)
    assert int(np.prod(kv.shape)) * 2 == NB * 16 * 2 * 2048
    assert round(numbers * 2 / 1e9, 2) == 7.93


@pytest.mark.parametrize("bucket", [None, LADDER[0], LADDER[-1]],
                         ids=["step", "prefill_first", "prefill_last"])
def test_pool_and_rings_are_neither_copied_nor_relaid_and_the_cut_fits(
        one_chip, mosaic, bucket):
    fn, feed, state, plist = _shapes(one_chip, bucket)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        feed, state, plist).compile()
    text = compiled.as_text()
    for a in state:
        dims = ",".join(map(str, a.shape))
        # no program copies the pool or the rings ...
        copies = re.findall(r"\[%s\]\S* copy\(" % dims, text)
        assert not copies, f"{len(copies)} copies of {a.shape}"
        # ... and both keep the layout they were given, row-major
        assert re.search(r"bf16\[%s\]\{3,2,1,0:T\(" % dims, text)
    # ... no layer's experts are sliced out of their stack: nothing of an
    # expert stack's size, or of one layer's experts', is copied or sliced
    for lead in ("2,64", "6,64", "2,3,64", "64"):
        for tail in ("2560,768", "768,2560"):
            hit = re.findall(r"bf16\[%s,%s\]\S* (?:copy|dynamic-slice|"
                             r"slice)\(" % (lead, tail), text)
            assert not hit, hit[:2]
    # ... Mosaic took every kernel of the program (the layers are scanned:
    # one full and one window layer's code)
    names = (("gqa_paged_decode_attn", "gqa_ring_decode_attn",
              "moe_grouped_reglu") if bucket is None else
             ("gqa_group_flash_fwd", "gqa_window_flash_fwd",
              "moe_grouped_reglu"))
    for name in names:
        assert name in text, name
    assert "moe_grouped_swiglu" not in text and "gqa_flash_fwd" not in text
    assert text.count("tpu_custom_call") == 4       # the experts' twice
    # ... and arguments + temporaries + what is not aliased of the results
    # fit the chip as the issue reckoned
    mem = compiled.memory_analysis()
    live = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    print(f"bucket {bucket}: arguments {mem.argument_size_in_bytes / 1e9:.3f} "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} outputs "
          f"{(mem.output_size_in_bytes - mem.alias_size_in_bytes) / 1e9:.3f} "
          f"GB")
    assert 12.5e9 < live <= FITS_BYTES, live


def test_the_step_s_walks_step_by_slot_under_their_own_names(one_chip,
                                                              mosaic):
    fn, feed, state, plist = _shapes(one_chip, None)
    calls = {e.params["name"]: tuple(e.params["grid_mapping"].grid)
             for e in eqns_under(jax.make_jaxpr(fn)(feed, state, plist).jaxpr)
             if e.primitive.name == "pallas_call"}
    assert calls["gqa_paged_decode_attn"] == (S,)
    assert calls["gqa_ring_decode_attn"] == (S,)
    # 64 tokens x 6 experts in 16-row tiles, every expert's last tile padded
    assert calls["moe_grouped_reglu"] == (EK.plan_rows(S, 6, 64, 16) // 16,)


def test_mosaic_accepts_the_expert_walk_and_the_step_keeps_its_tiles(
        one_chip, mosaic):
    """[2560, 768] x 64 experts at top-6, a layer of the stack of eight: the
    12,288 rung's 81,920 rows an expert a grid step, the step's 64 tokens a
    16-row tile a grid step."""
    check_both_walks_on(one_chip, S, LADDER[-1],
                        CFG.moe_num_active_primary_experts,
                        CFG.moe_num_primary_experts, CFG.hidden_size,
                        CFG.moe_ffn_hidden_size, "relu", jnp.bfloat16,
                        layers=CFG.num_hidden_layers)
