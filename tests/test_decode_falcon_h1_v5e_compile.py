"""The two kinds of per-stream state of ``decode/falcon_h1.py`` stay in place
in every layer, Mosaic accepts the new kernels at Falcon-H1-34B's published
shapes, and the benchmark's cut fits the chip — checked with the TPU's own
compiler for a v5e that is described and not attached (no chip, no chip
time), as ``test_decode_sambay_v5e_compile.py`` does.

The configuration is the benchmark's whole
(``benchmark/configs/falcon-h1-34b-pp12s0.json``, ``traffic/chat_sat.json``):
six layers, the whole vocabulary, 64 slots, an 8,193-block pool, 256-block
tables.  Nothing is allocated: the programs are compiled from shapes.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.decode.falcon_h1 import (FalconH1Config, FalconH1LM,
                                         param_shapes)
from paddle_tpu.kernels import diffattn as DK
from paddle_tpu.kernels import gqa as GK
from paddle_tpu.kernels import ssd as SK
from paged_walks import eqns_under

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmark", "configs",
                       "falcon-h1-34b-pp12s0.json")) as f:
    RAW = json.load(f)
with open(os.path.join(REPO, "benchmark", "traffic", "chat_sat.json")) as f:
    ENGINE = json.load(f)["engine"]
CFG = FalconH1Config.from_dict(RAW)
S, NB, BS = ENGINE["max_slots"], ENGINE["num_blocks"], ENGINE["block_tokens"]
MB = CFG.max_seq_len // BS
# what the issue set before any chip time: arguments + temporaries of the
# step and of the largest rung at or under this, or the cut is five layers
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
    for mod in (DK, GK, SK):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)
    with jax.enable_x64(False):
        yield


def _shapes(one_chip, bucket):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    model = FalconH1LM(CFG)
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
    assert (CFG.num_hidden_layers, CFG.vocab_size, CFG.hidden_size) == \
        (6, 261120, 5120) and (S, NB, BS, MB) == (64, 8193, 16, 256)
    weights = 2 * sum(int(np.prod(s)) for s, _ in param_shapes(CFG).values())
    cache = jax.eval_shape(lambda: FalconH1LM(CFG).make_cache(
        NB, BS, "bfloat16", slots=S).state())
    kv, rows, tails = (int(np.prod(a.shape)) * a.dtype.itemsize for a in cache)
    assert round(weights / 1e9, 2) == 10.51
    assert round(kv / 1e9, 2) == 1.61 and round(rows / 1e9, 2) == 1.61
    assert round(tails / 1e9, 2) == 0.01
    assert round((weights + kv + rows + tails) / 1e9, 2) == 13.74


@pytest.mark.parametrize("bucket", [None, 512, 3072],
                         ids=["step", "prefill_512", "prefill_3072"])
def test_pool_and_rows_of_every_layer_are_neither_copied_nor_relaid(
        one_chip, mosaic, bucket):
    fn, feed, state, plist = _shapes(one_chip, bucket)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        feed, state, plist).compile()
    text = compiled.as_text()
    for a in state:
        dims = ",".join(map(str, a.shape))
        # no program copies the pool, the recurrent rows or the tails ...
        copies = re.findall(r"\[%s\]\S* copy\(" % dims, text)
        assert not copies, f"{len(copies)} copies of {a.shape}"
    # ... the pool and the rows keep the layout they were given, row-major ...
    kv, rows = state[0], state[1]
    assert re.search(r"bf16\[%s\]\{3,2,1,0:T\(" % ",".join(
        map(str, kv.shape)), text)
    assert re.search(r"f32\[%s\]\{4,3,2,1,0:T\(8,128\)" % ",".join(
        map(str, rows.shape)), text)
    # ... Mosaic took both kernels of the program (the layers are scanned):
    # the one-token update and the paged walk, or the chunk scan and the
    # flash forward ...
    assert text.count("tpu_custom_call") == 2
    for name in (("ssd_state_step", "gqa_paged_decode_attn")
                 if bucket is None else ("ssd_chunk_scan", "gqa_flash_fwd")):
        assert name in text
    # ... and arguments + temporaries + what is not aliased of the results
    # fit the chip as the issue reckoned: six layers, not five
    mem = compiled.memory_analysis()
    live = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert 13.7e9 < live <= FITS_BYTES, live
    assert mem.temp_size_in_bytes < 0.6e9, mem.temp_size_in_bytes


def test_the_step_s_walk_steps_by_slot(one_chip, mosaic):
    """The scanned layers' paged walk takes one grid step a slot (the walk
    that stepped by chunk took ``S x MB / 32`` = ``S x 8``); what a slot's
    step fetches it reads from ``context_lens`` (the parity tests)."""
    fn, feed, state, plist = _shapes(one_chip, None)

    grids = [tuple(e.params["grid_mapping"].grid) for e in eqns_under(
        jax.make_jaxpr(fn)(feed, state, plist).jaxpr)
        if e.primitive.name == "pallas_call"
        and e.params["name"] == "gqa_paged_decode_attn"]
    assert len(grids) == 1 and int(np.prod(grids[0])) <= S, grids
