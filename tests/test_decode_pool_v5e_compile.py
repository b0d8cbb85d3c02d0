"""The KV pool stays in place — checked with the TPU's own compiler, for
a v5e that is described and not attached (no chip, no chip time).

What interpret mode cannot show: whether Mosaic accepts the paged
kernel at GPT-1's geometry (12 heads of 64 merged into 768 lanes), and
whether XLA:TPU keeps the donated pool ``[L, NB, bs, H*Dh]`` where it
lies through the decode step and a prefill.  Before PR 28 each program
relaid the whole pool four times (PERF.md, section 6); here each must
compile with less scratch than one K pool and without a copy of the
pool's shape.  Depth is cut to two layers and the vocabulary to 1,024 (the
layout depends on neither); widths, block size and pool length are the
benchmark's.

Since PR 33 also: the sampling epilogue's sort of the vocabulary lies in
a branch of its ``conditional`` in each of those programs, and at both
served vocabularies (40,478 and 102,400 columns) XLA:TPU keeps that
conditional — a launch whose rows are all greedy sorts nothing.

The topology is described inside a fixture, never at import: one
process at a time may load the TPU's library, and every xdist worker
imports every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.decode import LMConfig, PagedKVCache, TransformerLM
from paddle_tpu.decode.adapter import sample as _sample
from paddle_tpu.decode.model import _param_names
from paddle_tpu.kernels import attention as AK

from hlo_text import sorts_outside_a_branch

# tlm-gpt1w (benchmark/configs/tlm-gpt1w.json) at two layers and a small
# vocabulary (neither touches the pool; both are most of the compile
# time), and the serve mixes' engine: 64 slots, 16-token blocks, 2,049
# blocks, 32 a slot
CFG = LMConfig(vocab=1024, d_model=768, n_head=12, d_ffn=3072, n_layer=2,
               max_seq_len=512)
S, MB, NB, BS = 64, 32, 2049, 16


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
    monkeypatch.setattr(AK, "pallas_interpret", lambda: False)
    with jax.enable_x64(False):
        yield


def _shapes(one_chip, kv_dtype):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    D, F, V = CFG.d_model, CFG.d_ffn, CFG.vocab
    by_suffix = {"emb": (V, D), "out_proj": (D, V), "fc1": (D, F),
                 "fc2": (F, D), "wq": (D, D), "wk": (D, D), "wv": (D, D),
                 "wo": (D, D)}
    plist = [sds(by_suffix.get(n.split(".")[-1], (D,)), jnp.float32)
             for n in _param_names(CFG)]
    state = [sds(a.shape, a.dtype) for a in jax.eval_shape(
        lambda: PagedKVCache(CFG.n_layer, CFG.n_head, CFG.head_dim, NB, BS,
                             dtype=kv_dtype).state())]
    i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32
    feeds = {
        "step": [sds((S,), i32), sds((S,), i32), sds((S, MB), i32),
                 sds((S,), u32), sds((S,), i32), sds((S,), f32),
                 sds((S,), i32)],
        "prefill": [sds((1, 128), i32), sds((), i32), sds((MB,), i32),
                    sds((), u32), sds((), f32), sds((), i32)],
        "prefill_suffix": [sds((1, 64), i32), sds((), i32), sds((), i32),
                           sds((MB,), i32), sds((), u32), sds((), f32),
                           sds((), i32)],
    }
    return plist, state, feeds


def _program(model, name):
    """fn(feed, state, const) as the engine hands it to run_callable."""
    call = {"step": lambda *a, **kw: model.decode_step(
                *a, attn_impl="pallas", **kw),
            "prefill": model.prefill,
            "prefill_suffix": model.prefill_suffix}[name]

    def fn(feed, state, const):
        return call(const, state, *feed)
    return fn


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
@pytest.mark.parametrize("name", ["step", "prefill", "prefill_suffix"])
def test_pool_is_neither_copied_nor_relaid(one_chip, mosaic, name, kv_dtype):
    plist, state, feeds = _shapes(one_chip, kv_dtype)
    fn = _program(TransformerLM(CFG), name)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        feeds[name], state, plist).compile()
    text = compiled.as_text()
    pool = state[0]
    pool_bytes = int(np.prod(pool.shape)) * pool.dtype.itemsize
    dims = ",".join(map(str, pool.shape))
    # the pool keeps the layout it was given, row-major and unpadded ...
    assert re.search(r"\[%s\]\{3,2,1,0:T\(" % dims, text)
    # ... no program copies it ...
    copies = re.findall(r"\[%s\]\S* copy\(" % dims, text)
    assert not copies, f"{len(copies)} copies of the pool in {name}"
    # ... or a layer of it ...
    layer = ",".join(map(str, pool.shape[1:]))
    assert not re.search(r"= \w+\[%s\]" % layer, text), \
        f"a layer slice of the pool is materialised in {name}"
    # ... and its scratch is small beside one K pool
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes / 4, (temp, pool_bytes)
    if name == "step":
        assert text.count("tpu_custom_call") == CFG.n_layer
    # ... and whatever sorts the vocabulary lies in a branch that only a
    # launch with a sampled row enters
    outside, inside = sorts_outside_a_branch(text)
    assert not outside and inside, (outside, inside)


def test_kernel_refuses_nothing_at_full_context_width(one_chip, mosaic):
    """The kernel alone, on a 12-layer pool, last layer: Mosaic accepts
    the copies of [bs, H*Dh] blocks out of the whole pool into the chunk
    buffers and the per-head lane sums."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    pool = sds((12, NB, BS, CFG.d_model), jnp.float32)
    compiled = jax.jit(
        lambda q, k, v, bt, cl: AK.decode_attention(q, k, v, bt, cl, 11)
    ).lower(sds((S, CFG.n_head, CFG.head_dim), jnp.float32), pool, pool,
            sds((S, MB), jnp.int32), sds((S,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def _pallas_grids(jaxpr):
    """The grid of every ``pallas_call`` equation under ``jaxpr``."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            grids += _pallas_grids(sub)
    return grids


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_the_step_s_attention_grid_steps_by_slot_not_by_table_entry(
        one_chip, mosaic, kv_dtype):
    """A layer's kernel takes at most one grid step a chunk of a slot's
    table — ``S x ceil(MB / chunk)``, 256 where the kernel that stepped by
    table entry took ``S x MB`` = 2,048 — and what it skips inside a step
    it skips from ``context_lens`` (the parity tests)."""
    plist, state, feeds = _shapes(one_chip, kv_dtype)
    fn = _program(TransformerLM(CFG), "step")
    grids = _pallas_grids(jax.make_jaxpr(fn)(feeds["step"], state,
                                             plist).jaxpr)
    assert len(grids) == CFG.n_layer
    most = S * -(-MB // AK._DECODE_CHUNK_BLOCKS)
    assert all(int(np.prod(g)) <= most for g in grids), (grids, most)


def test_the_step_s_layers_share_one_trace_of_the_kernel(one_chip, mosaic):
    """The layer reaches the kernel as a prefetched scalar, so the step's
    ``n_layer`` calls are ONE traced function (and one lowering to Mosaic:
    jit lowers a shared jaxpr once) — a layer baked into the kernel made
    each its own, and a warm start's build of the step program then took
    3.7-4.0 s on the chip's host where the kernel this one replaced took
    2.1-2.4 and this one 1.6-1.7 (PERF.md §6, PR 35)."""
    plist, state, feeds = _shapes(one_chip, "float32")
    fn = _program(TransformerLM(CFG), "step")
    calls = [eqn.params["jaxpr"] for eqn in jax.make_jaxpr(fn)(
        feeds["step"], state, plist).jaxpr.eqns
        if eqn.primitive.name in ("pjit", "jit")
        and eqn.params["name"] == "_paged_attn_pallas"]
    assert len(calls) == CFG.n_layer
    assert len({id(c) for c in calls}) == 1


@pytest.mark.parametrize("rows,vocab", [(64, 40478), (1, 40478),
                                        (64, 102400), (1, 102400)])
def test_a_greedy_launch_sorts_nothing_at_the_served_vocabularies(
        one_chip, rows, vocab):
    """``_sample`` at both served vocabularies, a step's 64 rows and a
    prefill's one: XLA:TPU keeps the conditional (it is not flattened
    into a select that runs both sides), and every sort and ``TopK`` lies
    in the branch that a launch with a ``temperature > 0`` row takes."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    with jax.enable_x64(False):
        text = jax.jit(_sample).lower(
            sds((rows, vocab), jnp.float32), sds((rows,), jnp.uint32),
            sds((rows,), jnp.int32), sds((rows,), jnp.float32),
            sds((rows,), jnp.int32)).compile().as_text()
    assert " conditional(" in text
    outside, inside = sorts_outside_a_branch(text)
    assert not outside and inside, (outside, inside)
