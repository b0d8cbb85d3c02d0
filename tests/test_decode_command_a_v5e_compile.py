"""Whether ``command-a-plus-218b-ep8-pp8s0`` fits one v5e is decided here, off
the chip: the WHOLE configuration as the benchmark runs it (four layers, 16 of
128 experts a layer, 32,768 rows of the tied table;
``benchmark/configs/command-a-plus-218b-ep8-pp8s0.json`` under
``traffic/rag_sat.json``'s engine numbers) compiled by the TPU's own compiler
for a v5e that is described and not attached — the 8,192 rung, whose
temporaries are the largest, and the decode step.  PERF.md section 4 records
what this reads (PR 59): weights 9.467 GB + rings 1.611 GB + pool 1.074 GB
live, the 8,192 rung's temporaries beside them under the 14.6 GB the compiler
allowed ``falcon-h1-34b-pp12s0``.  The routed experts' rows are what decides
it: a share's plan is bounded by every choice of the wider router (65,536 +
2,032 rows at this rung), and walked whole it would gather 0.55 GB of rows
in and as many out; in blocks sized by the share (``kernels/moe.py
planned_experts(row_block=)``) the same rows take 0.09 GB each way."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.decode.adapter import MODEL_TYPES
from paddle_tpu.decode.command_a import param_shapes
from paddle_tpu.kernels import diffattn as DK
from paddle_tpu.kernels import gqa as GK
from paddle_tpu.kernels import moe as EK

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmark", "configs",
                       "command-a-plus-218b-ep8-pp8s0.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(REPO, "benchmark", "traffic", "rag_sat.json")) as f:
    ENGINE = json.load(f)["engine"]
S, NB, BS = ENGINE["max_slots"], ENGINE["num_blocks"], ENGINE["block_tokens"]
MB = CONFIG["max_seq_len"] // BS
FITS = 14.6e9       # live + temporaries a v5e's compiler has allowed


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

    model = MODEL_TYPES["cohere2_moe"](CONFIG)
    cfg = model.config
    dtype = jnp.dtype(cfg.dtype)
    plist = [sds(shape, dtype) for shape, _ in param_shapes(cfg).values()]
    state = [sds(a.shape, a.dtype) for a in jax.eval_shape(
        lambda: model.make_cache(NB, BS, CONFIG["kv_dtype"], slots=S).state())]
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


def _bytes(arrays):
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in arrays)


@pytest.mark.parametrize("bucket", [8192, None],
                         ids=["prefill_8192", "step"])
def test_the_whole_share_fits_one_chip_and_no_program_copies_its_cache(
        one_chip, mosaic, bucket):
    fn, feed, state, plist = _shapes(one_chip, bucket)
    # what the chip holds while nothing runs: 4,733 M parameters, the pool of
    # the one full layer and three rings a slot
    assert round(_bytes(plist) / 1e9, 3) == 9.467
    assert round(_bytes(state[:1]) / 1e9, 3) == 1.074
    assert round(_bytes(state[1:]) / 1e9, 3) == 1.611
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        feed, state, plist).compile()
    text = compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    live = _bytes(plist) + _bytes(state)
    print(f"live {live / 1e9:.3f} GB, temporaries {temp / 1e9:.3f} GB")
    assert live + temp < FITS, (live, temp)
    assert temp < (2.4e9 if bucket else 0.1e9), temp
    # pool and rings keep the layout they were given and no program copies
    # them
    for a in state:
        dims = ",".join(map(str, a.shape))
        assert re.search(r"bf16\[%s\]\{3,2,1,0:T\(" % dims, text)
        assert not re.findall(r"\[%s\]\S* copy\(" % dims, text)
    # a window layer's and the full layer's attention kernel and each one's
    # grouped experts: the layers are scanned, so a program holds two of each
    assert text.count("tpu_custom_call") == 4
