"""The latent pool stays in place, and Mosaic accepts the three new kernels
(and, since PR 62, the decode walk on its grid of slots) at
DeepSeek-V2-Lite's widths — checked with the TPU's own compiler for a v5e
that is described and not attached (no chip, no chip time), as
``test_decode_pool_v5e_compile.py`` does for the K/V pools.

Depth is cut to one dense and one expert layer and the vocabulary to 1,024
(the layout depends on neither; both are most of the compile time); widths,
experts, slots, block size, table width and pool length are the benchmark's
(``benchmark/configs/deepseek-v2-lite-pp4s0.json``, ``traffic/doc_sat.json``).
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.decode.mla import MLAConfig, MLATransformerLM, param_shapes
from paddle_tpu.kernels import attention as AK
from paddle_tpu.kernels import mla as MK
from paddle_tpu.kernels import moe as EK
from paged_walks import check_both_walks_on

CFG = MLAConfig(
    vocab_size=1024, hidden_size=2048, num_hidden_layers=2,
    num_attention_heads=16, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, kv_lora_rank=512, intermediate_size=10944,
    moe_intermediate_size=1408, n_routed_experts=64, num_experts_per_tok=6,
    n_shared_experts=2, first_k_dense_replace=1,
    rope_scaling={"factor": 40, "original_max_position_embeddings": 4096,
                  "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                  "mscale_all_dim": 0.707},
    max_seq_len=8192, dtype="bfloat16")
S, MB, NB, BS = 64, 512, 16385, 16


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
    for mod in (AK, MK, EK):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)
    with jax.enable_x64(False):
        yield


def _shapes(one_chip, bucket, MB=MB):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    model = MLATransformerLM(CFG)
    plist = [sds(shape, jnp.bfloat16)
             for shape, _ in param_shapes(CFG).values()]
    state = [sds(a.shape, a.dtype) for a in jax.eval_shape(
        lambda: model.make_cache(NB, BS, "bfloat16").state())]
    i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32
    if bucket is None:
        feed = [sds((S,), i32), sds((S,), i32), sds((S, MB), i32),
                sds((S,), u32), sds((S,), i32), sds((S,), f32),
                sds((S,), i32)]
        fn = model.decode_step
    else:
        feed = [sds((1, bucket), i32), sds((), i32), sds((MB,), i32),
                sds((), u32), sds((), f32), sds((), i32)]
        fn = model.prefill
    return (lambda feed, state, const: fn(const, state, *feed)), \
        feed, state, plist


@pytest.mark.parametrize("bucket", [None, 1024, 8192],
                         ids=["step", "prefill_1024", "prefill_8192"])
def test_latent_pool_is_neither_copied_nor_relaid(one_chip, mosaic, bucket):
    fn, feed, state, plist = _shapes(one_chip, bucket)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        feed, state, plist).compile()
    text = compiled.as_text()
    pool = state[0]
    pool_bytes = int(np.prod(pool.shape)) * pool.dtype.itemsize
    dims = ",".join(map(str, pool.shape))
    # the pool keeps the layout it was given, row-major and unpadded ...
    assert re.search(r"bf16\[%s\]\{3,2,1,0:T\(" % dims, text)
    # ... no program copies it ...
    copies = re.findall(r"\[%s\]\S* copy\(" % dims, text)
    assert not copies, f"{len(copies)} copies of the pool"
    # ... or materialises a layer of it ...
    layer = ",".join(map(str, pool.shape[1:]))
    assert not re.search(r"= \w+\[%s\]" % layer, text)
    # ... and its scratch is small beside this two-layer pool (671 MB); the
    # 8,192 rung's own rows (57,280 expert rows of 2,048 float32, the
    # expanded keys and values) are more than that, and far less than the
    # 4.3 GB of a [16, 8192, 8192] float32 score array
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (pool_bytes / 2 if bucket != 8192 else 2 << 30), \
        (temp, pool_bytes)
    calls = text.count("tpu_custom_call")
    # a layer's attention kernel, and the expert layer's grouped SwiGLU
    assert calls == CFG.num_hidden_layers + 1, calls


@pytest.mark.parametrize("blocks", [MB, MB + 8], ids=[
    "the_cell_s_table", "a_table_that_is_no_multiple_of_the_chunk"])
def test_the_step_holds_one_latent_walk_a_layer_and_pads_no_table(
        one_chip, mosaic, blocks):
    """Mosaic takes ``mla_paged_decode_attn`` on its grid of slots (a double
    buffer of 2 x 1.3 MB, the softmax state of 16 heads in the loop's carry)
    inside the whole step; every latent layer calls it once, on the table as
    the engine hands it over — nothing pads the table to whole chunks."""
    fn, feed, state, plist = _shapes(one_chip, None, blocks)
    text = jax.jit(fn, donate_argnums=(1,)).lower(
        feed, state, plist).compile().as_text()
    walks = re.findall(r"%mla_paged_decode_attn\S* = \S+ custom-call\(.*?"
                       r"operand_layout_constraints=\{(s32\[[\d,]+\])", text)
    assert walks == [f"s32[{S},{blocks}]"] * CFG.num_hidden_layers, walks
    assert not re.search(r"s32\[%d,\d+\]\S* pad\(" % S, text)


def test_mosaic_accepts_the_expert_walk_and_the_step_keeps_its_tiles(
        one_chip, mosaic):
    """[2048, 1408] x 64 experts at top-6: the 8,192 rung's 57,344 rows an
    expert a grid step, the step's 64 tokens a 16-row tile a grid step."""
    check_both_walks_on(one_chip, S, 8192, CFG.num_experts_per_tok,
                        CFG.n_routed_experts, CFG.hidden_size,
                        CFG.moe_intermediate_size, "silu", jnp.float32)
