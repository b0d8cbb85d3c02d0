"""Latent pool, recurrent rows and convolution tails of
``decode/kimi_linear.py`` stay in place in every layer, Mosaic accepts the
kernels at Kimi-Linear-48B-A3B's published shapes — 32 KDA heads of 128, the
latent walk with the layer a prefetched scalar, 64 held experts of 2,304 x
1,024 —, no program builds a ``[T, T]`` score or takes an XLA fallback, no
place's experts are sliced out of their stack, and the benchmark's cut fits
the chip — checked with the TPU's own compiler for a v5e that is described and
not attached (no chip, no chip time), as ``test_decode_lfm2_v5e_compile.py``
does.

The configuration is the benchmark's whole
(``benchmark/configs/kimi-linear-48b-a3b-ep4-pp3s0.json``,
``traffic/longdoc_sat.json``): nine layers, 64 of 256 experts a layer, a
quarter of the vocabulary, 64 slots, the mix's pool, 1,088-block tables.
Nothing is allocated: the programs are compiled from shapes.
"""
import base64
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.decode.kimi_linear import (KimiLinearConfig, KimiLinearLM,
                                           param_shapes)
from paddle_tpu.kernels import attention as AK
from paddle_tpu.kernels import kda as KK
from paddle_tpu.kernels import mla as MK
from paddle_tpu.kernels import moe as EK
from paddle_tpu.observability import stats
from paged_walks import eqns_under

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmark", "configs",
                       "kimi-linear-48b-a3b-ep4-pp3s0.json")) as f:
    RAW = json.load(f)
with open(os.path.join(REPO, "benchmark", "traffic",
                       "longdoc_sat.json")) as f:
    ENGINE = json.load(f)["engine"]
CFG = KimiLinearConfig.from_dict(RAW)
S, NB, BS = ENGINE["max_slots"], ENGINE["num_blocks"], ENGINE["block_tokens"]
MB = CFG.max_seq_len // BS
LADDER = ENGINE["prefill_buckets"]
# what the issue set before any chip time: arguments + temporaries of the
# step and of the largest rung at or under this, or the pool shrinks
FITS_BYTES = 14.6e9
FALLBACKS = ("moe.grouped_swiglu_fallbacks", "kda.chunk_fallbacks",
             "kda.step_fallbacks", "mla.decode_attn_fallbacks",
             "mla.prefill_attn_fallbacks")


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
    for mod in (AK, KK, MK, EK):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)
    with jax.enable_x64(False):
        yield


def _shapes(one_chip, bucket):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    model = KimiLinearLM(CFG)
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


def _layer(shapes, prefix, leaves=None):
    """Numbers in ONE layer of the stack under ``prefix`` (of ``leaves``)."""
    return sum(int(np.prod(s[1:])) for k, (s, _) in shapes.items()
               if k.startswith(prefix)
               and (leaves is None or k[len(prefix):] in leaves))


def test_the_cut_is_the_issue_s_and_its_parameters_are_as_counted():
    assert (CFG.num_hidden_layers, CFG.vocab_size, CFG.hidden_size,
            CFG.max_seq_len, CFG.intermediate_size, CFG.moe_intermediate_size,
            CFG.num_experts, CFG.router_experts, CFG.first_expert,
            CFG.num_experts_per_token, CFG.num_shared_experts) == \
        (9, 40960, 2304, 17408, 9216, 1024, 64, 256, 0, 8, 1)
    assert (CFG.kda_heads, CFG.kda_dim, CFG.taps, CFG.num_attention_heads,
            CFG.qk_nope_head_dim, CFG.qk_rope_head_dim, CFG.v_head_dim,
            CFG.kv_lora_rank) == (32, 128, 4, 32, 128, 64, 128, 512)
    assert (CFG.first_k_dense_replace, CFG.periods, CFG.pattern,
            CFG.kda_layers, CFG.mla_layers, CFG.first_expert) == \
        (1, 2, ("kda", "kda", "mla", "kda"), 7, 2, 0)
    assert RAW["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (S, BS, MB) == (64, 16, 1088) and LADDER[-1] == 16384
    shapes = param_shapes(CFG)
    numbers = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert numbers == 4_272_540_512 and round(numbers * 2 / 1e9, 2) == 8.55
    # ... one whole layer of each shape: dense + KDA, experts + KDA, experts
    # + latent attention; a mixer of each kind; what a layer's experts are
    kda = {"wqkv", "conv_w", "wf1", "wf2", "dt_bias", "a_log", "wb", "wg1",
           "wg2", "o_norm", "wo"}
    assert (_layer(shapes, "d."), _layer(shapes, "p0."),
            _layer(shapes, "p2.")) == (103_219_872, 500_171_680, 489_772_288)
    assert _layer(shapes, "p1.") == _layer(shapes, "p3.") == 500_171_680
    assert _layer(shapes, "d.", kda) == _layer(shapes, "p0.", kda) \
        == 39_514_272
    assert _layer(shapes, "p2.", {"wq", "wkva", "kv_norm", "wkvb",
                                  "wo"}) == 29_114_880
    assert _layer(shapes, "p0.", {"e_gate", "e_up", "e_down"}) \
        == 64 * 7_077_888 == 452_984_832
    assert _layer(shapes, "p0.", {"s_gate", "s_up", "s_down"}) == 7_077_888
    assert _layer(shapes, "p0.", {"router", "router_bias"}) == 590_080
    assert shapes["emb"][0] == (40960, 2304) \
        and shapes["head"][0] == (2304, 40960)
    # the whole model: 1 + 26 such layers at 256 experts and 163,840 rows
    whole = 103_219_872 + 20 * (500_171_680 + 3 * 452_984_832) \
        + 6 * (489_772_288 + 3 * 452_984_832) + 2 * 163840 * 2304 + 2304
    assert round(whole / 1e9, 1) == 49.1
    pool, rec, conv = jax.eval_shape(lambda: KimiLinearLM(CFG).make_cache(
        NB, BS, "bfloat16", slots=S).state())
    assert pool.shape == (2, NB, BS, 640)       # 2,560 B a token
    assert rec.shape == (7, S, 32, 128, 128) and rec.dtype == jnp.float32
    assert int(np.prod(rec.shape)) * 4 == 7 * 64 * 2_097_152
    assert conv.shape == (7, S, 3, 12288)


@pytest.mark.parametrize("bucket", [None, LADDER[-1]],
                         ids=["step", "prefill_last"])
def test_state_stays_in_place_no_wide_score_no_fallback_and_the_cut_fits(
        one_chip, mosaic, bucket):
    fn, feed, state, plist = _shapes(one_chip, bucket)
    before = {n: stats.to_dict().get(n, 0) for n in FALLBACKS}
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        feed, state, plist).compile()
    assert {n: stats.to_dict().get(n, 0) for n in FALLBACKS} == before
    text = compiled.as_text()
    # no program copies the pool or the recurrent rows, and both keep the
    # layouts they were given (the tails, 33 MB with three rows a slot, are
    # re-tiled on the way in and out of the scan: 0.3% of a step's bytes)
    for a in state[:2]:
        dims = ",".join(map(str, a.shape))
        copies = re.findall(r"\[%s\]\S* copy\(" % dims, text)
        assert not copies, f"{len(copies)} copies of {a.shape}"
    assert re.search(r"bf16\[%s\]\{3,2,1,0:T\(" % ",".join(
        map(str, state[0].shape)), text)
    assert re.search(r"f32\[%s\]\{4,3,2,1,0:T\(8,128\)" % ",".join(
        map(str, state[1].shape)), text)
    # ... no [T, T]-wide score (nor a table-wide one in the step) anywhere
    T = bucket or MB * BS
    wide = re.findall(r"(?:f32|bf16)\[[\d,]*%d,%d\]" % (T, T), text)
    assert not wide, wide[:2]
    # ... no place's experts are sliced out of their stack
    for lead in ("2,64", "64"):
        for tail in ("2304,1024", "1024,2304"):
            hit = re.findall(r"bf16\[%s,%s\]\S* (?:copy|dynamic-slice|"
                             r"slice)\(" % (lead, tail), text)
            assert not hit, hit[:2]
    # ... Mosaic took every kernel of the program
    for name in (("kda_state_step", "mla_paged_decode_attn",
                  "moe_grouped_swiglu") if bucket is None else
                 ("kda_chunk_prefill", "flash_fwd", "moe_grouped_swiglu")):
        assert name in text, name
    # a period's four layers run in turn inside the scan: three KDA and one
    # latent kernel, the experts' four times, and the dense layer's KDA
    assert text.count("tpu_custom_call") == 9
    # ... and arguments + temporaries + what is not aliased of the results
    # fit the chip as the issue reckoned
    mem = compiled.memory_analysis()
    live = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    print(f"bucket {bucket}: arguments {mem.argument_size_in_bytes / 1e9:.3f} "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} outputs "
          f"{(mem.output_size_in_bytes - mem.alias_size_in_bytes) / 1e9:.3f} "
          f"GB")
    assert 11.0e9 < live <= FITS_BYTES, live


def test_the_chunk_kernel_s_products_are_single_passes_of_bf16_pieces(
        one_chip, mosaic):
    """``kda_chunk_prefill`` as Mosaic is handed it, 32 heads of 128: every
    product a plain bf16 x bf16 pass into float32 — none asks the compiler
    for ``contract_precision<fp32>`` (six passes; ``kernels/kda.py`` names no
    product that stays there), and a (chunk, head) takes 63 of them where
    24 float32 products took 144."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    H, K = CFG.kda_heads, CFG.kda_dim
    qkv = sds((2 * KK.CHUNK, H, K), jnp.bfloat16)
    text = jax.jit(lambda *a: KK.kda_scan(*a, out_dtype=jnp.bfloat16)).lower(
        qkv, qkv, qkv, sds(qkv.shape, jnp.float32),
        sds(qkv.shape[:2], jnp.float32)).as_text()
    assert text.count("tpu_custom_call") == 1
    (body,) = re.findall(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22', text)
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=False)
    products = re.findall(r'"stable_mosaic\.tpu\.matmul"\(.*', asm)
    assert len(products) == 63 * 4              # _SCAN_HEADS a grid step
    assert "contract_precision" not in asm
    for line in products:
        lhs, rhs, acc = re.search(r": \((.*)\) ->", line).group(1).split(
            ", ")
        assert lhs.endswith("xbf16>") and rhs.endswith("xbf16>") \
            and acc.endswith("xf32>"), line


def test_the_step_s_kernels_step_by_slot_and_the_layer_is_a_scalar(one_chip,
                                                                  mosaic):
    fn, feed, state, plist = _shapes(one_chip, None)
    calls = [e for e in eqns_under(
        jax.make_jaxpr(fn)(feed, state, plist).jaxpr)
        if e.primitive.name == "pallas_call"]
    by_name = {}
    for e in calls:
        by_name.setdefault(e.params["name"], []).append(e)
    # a slot's 32 heads in two grid steps of 16, in place
    (upd,) = {tuple(e.params["grid_mapping"].grid)
              for e in by_name["kda_state_step"]}
    assert upd == (S, 2) and len(by_name["kda_state_step"]) == 4
    assert all(dict(e.params["input_output_aliases"]) == {6: 1}
               for e in by_name["kda_state_step"])
    # the latent walk: a grid step a slot (its live blocks in chunks of 64
    # inside it, into a double buffer), three prefetched scalars (tables,
    # lengths AND the layer)
    (walk,) = by_name["mla_paged_decode_attn"]
    assert tuple(walk.params["grid_mapping"].grid) == (S,)
    assert walk.params["grid_mapping"].num_index_operands == 3
    assert walk.params["grid_mapping"].num_scratch_operands == 3
    # 64 tokens x 8 choices, of which any may be held: 16-row tiles
    assert tuple(by_name["moe_grouped_swiglu"][0].params[
        "grid_mapping"].grid) == (EK.plan_rows(S, 8, 64, 16) // 16,)
