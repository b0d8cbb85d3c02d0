"""Whether ``xing4-29b-a4b-s0`` fits one v5e is decided here, off the chip:
the WHOLE configuration as the benchmark runs it (seven layers, all 64
experts, the whole vocabulary; ``benchmark/configs/xing4-29b-a4b-s0.json``
under ``traffic/doc_sat.json``'s engine numbers) compiled by the TPU's own
compiler for a v5e that is described and not attached — the 8,192 rung, whose
temporaries are the largest, and the decode step.  PERF.md section 4 records
what this reads (PR 54): weights 9.851 GB + latent pool 2.349 GB live, 1.894 GB
of temporaries at the 8,192 rung, 14.09 GB in all against the 14.6 GB the
compiler allowed ``falcon-h1-34b-pp12s0``; had it not fitted, the cut would be
layers 0-5."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.decode.adapter import MODEL_TYPES
from paddle_tpu.decode.mla import param_dtype, param_shapes
from paddle_tpu.kernels import attention as AK
from paddle_tpu.kernels import mhc as HK
from paddle_tpu.kernels import mla as MK
from paddle_tpu.kernels import moe as EK

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmark", "configs",
                       "xing4-29b-a4b-s0.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(REPO, "benchmark", "traffic", "doc_sat.json")) as f:
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
    for mod in (AK, MK, EK, HK):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)
    with jax.enable_x64(False):
        yield


def _shapes(one_chip, bucket):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    model = MODEL_TYPES["xing4_0"](CONFIG)
    cfg = model.config
    plist = [sds(shape, param_dtype(cfg, name))
             for name, (shape, _) in param_shapes(cfg).items()]
    state = [sds(a.shape, a.dtype) for a in jax.eval_shape(
        lambda: model.make_cache(NB, BS, CONFIG["kv_dtype"]).state())]
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


def _bytes(arrays):
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in arrays)


@pytest.mark.parametrize("bucket", [8192, None],
                         ids=["prefill_8192", "step"])
def test_the_whole_stage_fits_one_chip_and_the_streams_are_never_copied(
        one_chip, mosaic, bucket):
    fn, feed, state, plist = _shapes(one_chip, bucket)
    # what the chip holds while nothing runs: 4,921 M parameters and the pool
    assert round(_bytes(plist) / 1e9, 3) == 9.851
    assert round(_bytes(state) / 1e9, 3) == 2.349
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        feed, state, plist).compile()
    text = compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    live = _bytes(plist) + _bytes(state)
    assert live + temp < FITS, (live, temp)
    # PERF.md's figures: 1.894 GB at the 8,192 rung, 0.022 GB a step
    assert temp < (2.0e9 if bucket else 0.05e9), temp
    # the pool keeps the layout it was given and no program copies it ...
    pool = state[0]
    dims = ",".join(map(str, pool.shape))
    assert re.search(r"bf16\[%s\]\{3,2,1,0:T\(" % dims, text)
    assert not re.findall(r"\[%s\]\S* copy\(" % dims, text)
    # ... nor the four streams of a rung's rows, which mhc_post rewrites in
    # place (a step copies its 64 rows once: the probe reads them first)
    rows = bucket or S
    copies = re.findall(r"bf16\[%d,14336\]\S* copy\(" % rows, text)
    assert len(copies) <= (0 if bucket else 1), copies
    # seven flash forwards (or paged walks), five expert layers' grouped
    # SwiGLU, and the mixing's two kernels round each of fourteen sub-layers
    assert text.count("tpu_custom_call") == 7 + 5 + 28
