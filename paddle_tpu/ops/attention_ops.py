"""fused_attention op: one IR node for the whole attention block.

The program-level counterpart of the reference's fused ops
(``fused_elemwise_activation_op``, ``fusion_lstm_op`` — one op standing for
a subgraph, dispatched to a tuned kernel).  Impl selection via attr:

- ``auto``  : XLA fused attention below seq 2048 (faster on v5e), pallas
              flash kernel beyond (O(block) memory wins at long context)
- ``xla``   : jnp einsum/softmax chain
- ``pallas``: force the flash kernel (interpret mode off-TPU)
- ``ring``  : sequence-parallel ring attention over mesh axis ``sp_axis``
              (wraps shard_map; requires lowering under a ParallelExecutor
              mesh that has that axis)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.registry import register, register_grad, scoped_vjp
from ..kernels import attention as A


@register("fused_attention", no_grad_slots=("KvMask", "Seed"))
def _fused_attention(ctx, ins, attrs):
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    # None flows through every impl and lets the pallas kernels compile
    # out the mask load + per-tile where entirely (ring materializes ones
    # below because shard_map must shard a real array)
    kv_mask = ins["KvMask"][0] if ins.get("KvMask") else None
    causal = attrs.get("causal", False)
    scale = attrs.get("scale", None)
    impl = attrs.get("impl", "auto")
    # attention-prob dropout is seeded by an explicit program input (drawn
    # per step by the layer), so the grad op re-lowers the identical
    # computation on either path — in-kernel tile hashes on pallas,
    # deterministic bernoulli keys on xla/ring; no stored mask, no stale rng
    rate = float(attrs.get("dropout_rate", 0.0) or 0.0)
    if not ctx.training or attrs.get("is_test", False):
        rate = 0.0
    seed = ins["Seed"][0] if ins.get("Seed") else None
    if impl == "auto":
        # measured on v5e: XLA's fused attention beats the pallas kernel
        # through seq 1024 in-model (105k vs 76k tok/s at 256; 49k vs 37k
        # at 1024, Transformer-base); the flash kernel's win is O(block)
        # memory, so auto switches only where the O(T^2) scores would
        # dominate HBM (long-context training).  The crossover is
        # head_dim-aware (PERF.md §1 round 4): at D >= 128 the kernel
        # runs 44-64 TFLOPs and wins from 2048; at D < 128 every MXU dot
        # is half-filled by construction (~23-25 TFLOPs ceiling, packing
        # remedies measured equal) while the XLA ratio narrows only
        # slowly (1.8x at 256 -> 1.3x at 1024), so D=64 geometries stay
        # on XLA until 4096, where the score materialization cost
        # dominates either way.
        threshold = 2048 if q.shape[-1] >= 128 else 4096
        impl = "pallas" if (jax.default_backend() == "tpu"
                            and k.shape[2] >= threshold) else "xla"

    if impl == "xla":
        out = A.mha_xla(q, k, v, kv_mask, causal, scale,
                        dropout_rate=rate, dropout_seed=seed)
    elif impl == "pallas":
        out = A.flash_attention(q, k, v, kv_mask, causal, scale,
                                dropout_rate=rate, dropout_seed=seed)
    elif impl == "ring":
        mesh = ctx.mesh
        sp = attrs.get("sp_axis", "sp")
        if mesh is None or sp not in mesh.axis_names:
            out = A.mha_xla(q, k, v, kv_mask, causal, scale,
                            dropout_rate=rate, dropout_seed=seed)
        else:
            if kv_mask is None:
                kv_mask = jnp.ones((q.shape[0], k.shape[2]), jnp.float32)
            dp = "dp" if "dp" in mesh.axis_names else None
            qspec = P(dp, None, sp, None)
            mspec = P(dp, sp)
            sspec = P()

            def ring(q, k, v, m, s):
                return A.ring_attention(q, k, v, m, sp, causal, scale,
                                        dropout_rate=rate, dropout_seed=s)

            seed_in = (seed if seed is not None
                       else jnp.zeros((1,), jnp.int32))
            out = jax.shard_map(
                ring, mesh=mesh,
                in_specs=(qspec, qspec, qspec, mspec, sspec),
                out_specs=qspec)(q, k, v, kv_mask, seed_in)
    else:
        raise ValueError(f"unknown attention impl {impl!r}")
    return {"Out": [out]}


@register_grad("fused_attention", retraces=True)
def _fused_attention_grad(ctx, ins, attrs):
    """Backward: differentiate the forward lowering (flash recompute /
    ring ppermute-transpose handled by jax)."""
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    g = ins["Out@GRAD"][0]
    extra = {"KvMask": ins["KvMask"]} if ins.get("KvMask") else {}
    if ins.get("Seed"):
        extra["Seed"] = ins["Seed"]  # same seed → identical dropout bits

    def f(q, k, v):
        return _fused_attention(ctx, {"Q": [q], "K": [k], "V": [v],
                                      **extra}, attrs)["Out"][0]

    _, vjp_fn = scoped_vjp(ctx, f, q, k, v)
    dq, dk, dv = vjp_fn(g)
    return {"Q@GRAD": [dq], "K@GRAD": [dk], "V@GRAD": [dv]}
