"""Whether ``nemotron-3-nano-30b-a3b-ep2-pp4s0`` fits one v5e, and whether
Mosaic takes its kernels at the published shapes, is decided here, off the
chip: the WHOLE configuration as the benchmark runs it (thirteen layers
``MEMEM*EMEMEM*``, 64 of 128 experts a layer, 65,536 rows of table and head;
``benchmark/configs/nemotron-3-nano-30b-a3b-ep2-pp4s0.json`` under
``traffic/agent_sat.json``'s engine numbers: 128 slots, a 49,153-block pool,
896-block tables) compiled by the TPU's own compiler for a v5e that is
described and not attached — the 12,288 rung, whose temporaries are the
largest, and the 128-slot decode step.  No kernel of either program may take
its XLA fallback: the scan and the one-token update at heads of 64 channels
(two to a lane tile, ``ssd64_*``), the two-matrix relu2 experts at an
intermediate width of 1,856 = 14.5 lane tiles (``moe_grouped_relu2``), and
the group-16 flash forward and paged walk on two K/V heads (``gqa16_*``).
PERF.md section 4 records what this reads."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.decode.adapter import MODEL_TYPES
from paddle_tpu.decode.nemotron_h import param_shapes
from paddle_tpu.kernels import diffattn as DK
from paddle_tpu.kernels import gqa as GK
from paddle_tpu.kernels import moe as EK
from paddle_tpu.kernels import ssd as SK
from paddle_tpu.observability import stats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmark", "configs",
                       "nemotron-3-nano-30b-a3b-ep2-pp4s0.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(REPO, "benchmark", "traffic", "agent_sat.json")) as f:
    ENGINE = json.load(f)["engine"]
S, NB, BS = ENGINE["max_slots"], ENGINE["num_blocks"], ENGINE["block_tokens"]
MB = CONFIG["max_seq_len"] // BS
FITS = 14.6e9       # live + temporaries a v5e's compiler has allowed
FALLBACKS = ("ssm.ssd_fallbacks", "moe.grouped_relu2_fallbacks",
             "attn.gqa_decode_fallbacks", "attn.gqa_window_prefill_fallbacks")


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
    for mod in (DK, GK, EK, SK):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)
    with jax.enable_x64(False):
        yield


def _shapes(one_chip, bucket):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    model = MODEL_TYPES["nemotron_h"](CONFIG)
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


def test_the_cut_is_the_issue_s_and_its_bytes_are_as_reckoned():
    cfg = MODEL_TYPES["nemotron_h"](CONFIG).config
    assert cfg.hybrid_override_pattern == "MEMEM*EMEMEM*"
    assert (cfg.n_routed_experts, cfg.router_experts, cfg.vocab_size,
            cfg.hidden_size, cfg.moe_intermediate_size) == \
        (64, 128, 65536, 2688, 1856)
    assert (S, NB, BS, MB) == (128, 49153, 16, 896)
    shapes = param_shapes(cfg)
    count = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert count == 3_926_018_560
    kinds = {k: sum(int(np.prod(s)) // s[0] for n, (s, _) in shapes.items()
                    if n.startswith(k)) for k in ("m.", "e.", "a.")}
    assert kinds == {"m.": 38_744_896, "e.": 658_885_376, "a.": 23_399_040}
    # 64-wide heads two to a lane tile: a stream's row of a layer is 2 MB
    assert cfg.state_shape == (32, 128, 128)
    assert int(np.prod(cfg.state_shape)) * 4 == 2_097_152


@pytest.mark.parametrize("bucket", [12288, None],
                         ids=["prefill_12288", "step"])
def test_the_whole_share_fits_one_chip_and_every_kernel_is_mosaic_s(
        one_chip, mosaic, bucket):
    fn, feed, state, plist = _shapes(one_chip, bucket)
    # what the chip holds while nothing runs: 3,926 M parameters, the pool of
    # the two attention layers, six layers' rows and tails a slot
    assert round(_bytes(plist) / 1e9, 3) == 7.852
    assert round(_bytes(state[:1]) / 1e9, 3) == 1.611
    assert round(_bytes(state[1:2]) / 1e9, 3) == 1.611
    assert round(_bytes(state[2:]) / 1e9, 3) == 0.028
    before = stats.snapshot()
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        feed, state, plist).compile()
    after = stats.snapshot()
    assert {k: after.get(k, 0) - before.get(k, 0) for k in FALLBACKS} \
        == dict.fromkeys(FALLBACKS, 0)
    text = compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    live = _bytes(plist) + _bytes(state)
    print(f"live {live / 1e9:.3f} GB, temporaries {temp / 1e9:.3f} GB")
    assert live + temp < FITS, (live, temp)
    assert temp < (2.4e9 if bucket else 0.3e9), temp
    # pool, rows and tails keep the layout they were given and no program
    # copies them; the rows are whole (8, 128) float32 tiles: nothing of a
    # 64-wide head is padded
    for a in state:
        dims = ",".join(map(str, a.shape))
        assert not re.findall(r"\[%s\]\S* copy\(" % dims, text), a.shape
    assert re.search(r"f32\[%s\]\{4,3,2,1,0:T\(8,128\)" % ",".join(
        map(str, state[1].shape)), text)
    # the experts' matrices, [F, D] both, are handed to the kernel as they
    # lie: no copy of a stack
    assert not re.findall(r"bf16\[5,64,1856,2688\]\S* copy\(", text)
    # six Mamba layers, five expert layers, two attention layers: not scanned
    names = ("ssd64_state_step", "moe_grouped_relu2",
             "gqa16_paged_decode_attn") if bucket is None else (
        "ssd64_chunk_scan", "moe_grouped_relu2", "gqa16_group_flash_fwd")
    for name in names:
        assert name in text, name
    # ... thirteen kernels a program, each layer's own
    assert text.count("tpu_custom_call") == 13
