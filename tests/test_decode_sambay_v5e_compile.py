"""The three kinds of per-stream state stay in place, and Mosaic accepts the
new kernels at Phi-4-mini-flash-reasoning's widths — checked with the TPU's
own compiler for a v5e that is described and not attached (no chip, no chip
time), as ``test_decode_pool_v5e_compile.py`` does for the K/V pools.

Depth is cut to 8 layers (two scanned pairs below, one above: the programs
scan over pairs, so their code does not depend on the depth) and the
vocabulary to 1,024; widths, slots, block size, table width, window and pool
length are the benchmark's
(``benchmark/configs/phi4-mini-flash-reasoning.json``,
``traffic/reason_sat.json``).
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.decode.sambay import SambaYConfig, SambaYLM, param_shapes
from paddle_tpu.kernels import diffattn as DK
from paddle_tpu.kernels import ssm as SK
from paged_walks import eqns_under

CFG = SambaYConfig(
    vocab_size=1024, hidden_size=2560, num_hidden_layers=8,
    num_attention_heads=40, num_key_value_heads=20, intermediate_size=10240,
    sliding_window=512, max_seq_len=8192, dtype="bfloat16")
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
    for mod in (DK, SK):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)
    with jax.enable_x64(False):
        yield


def _shapes(one_chip, bucket):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    model = SambaYLM(CFG)
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


@pytest.mark.parametrize("bucket", [None, 1024, 3072],
                         ids=["step", "prefill_1024", "prefill_3072"])
def test_pool_rings_and_recurrent_rows_are_neither_copied_nor_relaid(
        one_chip, mosaic, bucket):
    fn, feed, state, plist = _shapes(one_chip, bucket)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        feed, state, plist).compile()
    text = compiled.as_text()
    for a in state:
        dims = ",".join(map(str, a.shape))
        # no program copies a pool, the rings or the slot rows ...
        copies = re.findall(r"\[%s\]\S* copy\(" % dims, text)
        assert not copies, f"{len(copies)} copies of {a.shape}"
    kv, rings = state[0], state[1]
    for a in (kv, rings):
        # ... the K/V arrays keep the layout they were given, row-major ...
        dims = ",".join(map(str, a.shape))
        assert re.search(r"bf16\[%s\]\{3,2,1,0:T\(" % dims, text)
    # ... and a program's scratch is small beside the state (2.9 GB): no
    # [T, T] score array, no gathered context
    state_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in state)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < state_bytes / 4, (temp, state_bytes)
    calls = text.count("tpu_custom_call")
    # step: the ring kernel (scanned), the pool kernel of the full layer and
    # of the cross layers (scanned); prefill: the selective scan (scanned,
    # and layer L/2's), the window flash (scanned) and the full flash
    assert calls == (3 if bucket is None else 4), calls


def test_the_step_s_three_walks_step_by_slot_and_the_pool_s_share_a_trace(
        one_chip, mosaic):
    """The rings' kernel, the full layer's and the cross layers' take one
    grid step a slot (the walk that stepped by chunk took ``S x MB / 32``:
    ``S x 16`` over the pool) — what a slot's step fetches it reads from
    ``context_lens`` (the parity tests) — and the layer reaches the kernel as
    a prefetched scalar, so the full layer and the scanned cross layers are
    ONE traced function, lowered to Mosaic once."""
    fn, feed, state, plist = _shapes(one_chip, None)
    eqns = list(eqns_under(jax.make_jaxpr(fn)(feed, state, plist).jaxpr))
    grids = [(e.params["name"], tuple(e.params["grid_mapping"].grid))
             for e in eqns if e.primitive.name == "pallas_call"]
    assert sorted(name for name, _ in grids) == [
        "diff_paged_decode_attn"] * 2 + ["diff_ring_decode_attn"]
    assert all(int(np.prod(g)) <= S for _, g in grids), grids
    walks = [e.params["jaxpr"] for e in eqns
             if e.primitive.name in ("pjit", "jit")
             and e.params["name"] == "_walk_call"]
    assert len(walks) == 3 and len({id(w) for w in walks}) == 2
