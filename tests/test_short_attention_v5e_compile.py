"""The trainer's short-sequence attention kernels — checked with the TPU's
own compiler, for a v5e that is described and not attached (no chip, no chip
time).

What interpret mode cannot show: whether Mosaic accepts the forward and the
one backward kernel at Transformer-base's geometry (8 heads of 64, the whole
sequence one tile, a transposed product for dk and dv) at 256, 512 and 1,024;
whether XLA:TPU folds the forward kernel that ``fused_attention_grad``
re-traces into the forward op's (ONE forward launch an attention, not two);
and whether under a ``dp`` mesh the kernel runs per shard with no gather of
q, k, v.

The topology is described inside a fixture, never at import: one process at
a time may load the TPU's library, and every xdist worker imports every test
file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from paddle_tpu.kernels import short_attention as S
from paddle_tpu.ops import attention_ops as O

H, D = 8, 64
CUSTOM_CALL = re.compile(
    r"^\s*(?:ROOT )?%?(short_attn_\w+?)[.\d]* = .*custom-call\(", re.M)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture()
def mosaic(monkeypatch):
    """As on the chip: off it the kernels interpret themselves (compile
    them), and tier-1 turns x64 on (the chip's processes never do)."""
    monkeypatch.setattr(S, "pallas_interpret", lambda: False)
    with jax.enable_x64(False):
        yield


def _sds(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


@pytest.mark.parametrize("B, T", [(96, 256), (48, 512), (24, 1024)])
@pytest.mark.parametrize("causal", [False, True])
def test_mosaic_accepts_forward_and_backward(topo, mosaic, B, T, causal):
    sds = _sds(SingleDeviceSharding(topo.devices[0]))
    x = sds((B, T, H * D), jnp.bfloat16)
    kw = dict(causal=causal, sm_scale=0.125, rate=0.1, interpret=False)
    seeds, mask = sds((2,), jnp.int32), sds((B, T), jnp.float32)
    fwd = S._forward.lower(seeds, x, x, x, mask, heads=H, **kw).compile()
    lse = sds((B, H, 1, T), jnp.float32)
    bwd = S._backward.lower(seeds, x, x, x, mask, x, lse, x, **kw).compile()
    for compiled, name in ((fwd, "short_attn_fwd"), (bwd, "short_attn_bwd")):
        text = compiled.as_text()
        assert CUSTOM_CALL.findall(text) == [name]
        # nothing the size of the scores outside the kernel
        assert f"f32[{B},{H},{T},{T}]" not in text
        assert f"bf16[{B},{H},{T},{T}]" not in text


def test_float32_operands_reach_the_kernel_as_bf16(topo, mosaic):
    """The trainer's q, k, v are float32 at the op (bf16 weights, f32
    activations): compiled for the TPU they take the MXU's default
    precision as XLA's own dots do — rounded to bf16 OUTSIDE the kernel,
    which then fetches half the bytes — and o, dq, dk, dv stay float32."""
    sds = _sds(SingleDeviceSharding(topo.devices[0]))
    x = sds((96, H, 256, D), jnp.float32)
    mask, seed = sds((96, 256), jnp.float32), sds((1,), jnp.int32)

    def step(q, k, v, do, mask, seed):
        return jax.vjp(lambda q, k, v: S.short_attention(
            q, k, v, mask, seed, None, False, None, 0.1), q, k, v)[1](do)

    compiled = jax.jit(step).lower(x, x, x, x, mask, seed).compile()
    assert all(o.dtype == jnp.float32 for o in compiled.out_info)
    calls = {m.group(1): line for line in compiled.as_text().splitlines()
             if (m := CUSTOM_CALL.match(line))}
    assert sorted(calls) == ["short_attn_bwd", "short_attn_fwd"]
    layouts = re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=",
                        calls["short_attn_fwd"]).group(1)
    # heads merged into the lanes: 512 wide, nothing padded to 128
    assert layouts.count("bf16[96,256,512]") == 3
    assert "f32[96,256,512]" not in layouts
    assert calls["short_attn_fwd"].lstrip().split(" = ")[1].startswith(
        "(f32[96,256,512]")


def _attention_and_its_grad(attend):
    """A forward op and the grad op that re-traces it, named as
    ``core/registry.py scoped_vjp`` names them."""
    def step(q, k, v, do):
        with jax.named_scope("fwd/dec_0/self_attn/fused_attention"):
            out = attend(q, k, v)
        with jax.named_scope("fwd/dec_0/self_attn/fused_attention"):
            _, pull = jax.vjp(attend, q, k, v)
        with jax.named_scope("bwd/dec_0/self_attn/fused_attention_grad"):
            return out, pull(do + out)
    return step


def _calls_and_names(text):
    return sorted(
        (m.group(1), re.search(r'op_name="([^"]*)"', line).group(1))
        for line in text.splitlines() if (m := CUSTOM_CALL.match(line)))


def test_the_retraced_forward_folds_into_the_forward_ops(topo, mosaic):
    sds = _sds(SingleDeviceSharding(topo.devices[0]))
    x = sds((96, H, 256, D), jnp.bfloat16)
    mask, seed = sds((96, 256), jnp.float32), sds((1,), jnp.int32)

    def step(q, k, v, do, mask, seed):
        return _attention_and_its_grad(lambda q, k, v: S.short_attention(
            q, k, v, mask, seed, None, True, None, 0.1))(q, k, v, do)

    text = jax.jit(step).lower(x, x, x, x, mask, seed).compile().as_text()
    (bwd, bwd_name), (fwd, fwd_name) = _calls_and_names(text)
    assert (fwd, bwd) == ("short_attn_fwd", "short_attn_bwd")
    assert "/fwd/dec_0/self_attn/fused_attention/" in fwd_name
    assert "/bwd/" not in fwd_name
    assert "/bwd/dec_0/self_attn/fused_attention_grad/" in bwd_name
    assert "f32[96,8,256,256]" not in text


def test_under_a_dp_mesh_the_kernel_runs_per_shard(topo, mosaic):
    mesh = Mesh(np.array(topo.devices).reshape(4), ("dp",))
    sds = _sds(NamedSharding(mesh, P("dp")))
    x = sds((384, H, 256, D), jnp.bfloat16)
    mask = sds((384, 256), jnp.float32)
    seed = jax.ShapeDtypeStruct((1,), jnp.int32,
                                sharding=NamedSharding(mesh, P()))

    def step(q, k, v, do, mask, seed):
        return _attention_and_its_grad(lambda q, k, v: O._short(
            mesh, q, k, v, mask, seed, False, None, 0.1))(q, k, v, do)

    text = jax.jit(step).lower(x, x, x, x, mask, seed).compile().as_text()
    assert [c for c, _ in _calls_and_names(text)] == [
        "short_attn_bwd", "short_attn_fwd"]
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective
    # a shard's rows, not the global batch
    assert "bf16[96,8,256,64]" in text and "bf16[384,8,256,64]" not in text


def test_unwrapped_the_kernel_cannot_be_compiled_across_devices(topo, mosaic):
    """Why ``auto`` keeps ``mha_xla`` where a program with no mesh is
    compiled across several devices (``ctx.spans_devices``: a plain Executor
    over a scope that a ParallelExecutor placed): GSPMD cannot partition a
    Mosaic kernel, replicated operands or not."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("dp",))
    sds = _sds(NamedSharding(mesh, P()))
    x = sds((8, H, 256, D), jnp.bfloat16)
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(lambda q, k, v: S.short_attention(
            q, k, v, None, None, None, False, None, 0.0)).lower(x, x, x)
