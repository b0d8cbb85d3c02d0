"""fused_attention op: one IR node for the whole attention block.

The program-level counterpart of the reference's fused ops
(``fused_elemwise_activation_op``, ``fusion_lstm_op`` — one op standing for
a subgraph, dispatched to a tuned kernel).  Impl selection via attr:

- ``auto``  : chosen from the back end, the shapes and the mesh
              (``_auto_impl``, which carries the measurements): on a TPU the
              short-sequence kernel of ``kernels/short_attention.py`` while
              both lengths are at most 1,024 (one forward and one backward
              kernel, no score in HBM), the flash kernel from 2,048 keys at
              head size >= 128 and from 4,096 below it, XLA between and on
              every other back end
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
from ..kernels import short_attention as S
from ..observability import stats as _obs_stats

# auto takes the short kernel while both lengths are at most this and a head
# group's blocks and score tiles fit VMEM (S.plan): see _auto_impl
SHORT_MAX_LEN = 1024


def _auto_impl(backend, q_shape, k_shape, dtype, mesh=None,
               spans_devices=False):
    """What ``auto`` lowers to, from what the op can observe: the back end,
    the shapes, the mesh, and whether the program is compiled across several
    devices with no mesh given.  Returns ``short``, ``pallas`` or ``xla``.

    Measured on one v5e (PR 40), 8 heads of 64, dropout 0.1, 18 attentions
    (6 causal) chained under one ``jit``, ms an attention, forward /
    forward + backward, ``[B, T]`` at 24,576 positions a step:

    ====================  ===========  ===========  ============
    operands                 96 x 256     48 x 512    24 x 1,024
    ====================  ===========  ===========  ============
    float32   XLA         0.64 / 3.09  1.24 / 5.81  2.45 / 12.35
    float32   short       0.58 / 1.40  0.76 / 1.85  1.22 /  2.91
    bfloat16  XLA         0.63 / 2.36  1.23 / 4.46  2.44 /  8.67
    bfloat16  flash       2.02 / 5.33  1.00 / 2.87  1.33 /  4.42
    bfloat16  short       0.50 / 1.07  not measured  not measured
    ====================  ===========  ===========  ============

    (float32 operands are what Transformer-base hands the op; the short
    kernel and XLA's default-precision dots both round them to bf16.)  The
    short kernel is under XLA at every length it fits, 2.2x at 256 and 4.2x
    at 1,024, and under the flash kernel too: the flash kernel's loss at 256
    was its grid (3,072 steps of one head's 128 x 128 tile, three kernels a
    training step), not its rate.  In Transformer-base at 96 x 256 the step
    went from 125,436 to 171,210 target tokens/s.  So: the short kernel
    while both lengths are at most ``SHORT_MAX_LEN`` (beyond it ``S.plan``
    still fits but nothing was measured), the mesh is one ``_short`` has a
    spec for (``_dp_only``), the operands are bf16 or float32 and the heads
    fill whole lane groups; the flash kernel from the thresholds it had (its
    O(block) memory is what wins there: 44-64 TFLOP/s at D >= 128, 23-25 at
    D = 64 where every product half-fills the MXU); ``mha_xla`` between, and
    on every other back end."""
    B, H, Tq, D = q_shape
    Tk = k_shape[2]
    if backend != "tpu":
        return "xla"
    if Tk >= (2048 if D >= 128 else 4096):
        return "pallas"
    if (max(Tq, Tk) <= SHORT_MAX_LEN and _dp_only(mesh, B, spans_devices)
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)
            # whole lane groups: two heads of 64, one of 128
            and S.lane_group(H, D) * D % S.LANE == 0
            and S.plan(H, Tq, Tk, D, jnp.dtype(dtype).itemsize, True)):
        return "short"
    return "xla"


def _dp_only(mesh, batch, spans_devices=False):
    """The meshes ``_short`` has a spec for: none, or one whose only axis
    wider than 1 is ``dp`` and divides the batch.  With no mesh the program
    must be one device's: GSPMD partitions what is compiled across several
    (a plain ``Executor`` over a scope that a ``ParallelExecutor`` placed:
    the benchmark's reference check on four chips) and cannot partition a
    Mosaic kernel, and there is no mesh to wrap the call over."""
    if mesh is None:
        return not spans_devices
    return ("dp" in mesh.axis_names and batch % mesh.shape["dp"] == 0
            and all(n == 1 for a, n in mesh.shape.items() if a != "dp"))


def _short(mesh, q, k, v, kv_mask, seed, causal, scale, rate):
    """The short kernel, per shard under a mesh: GSPMD cannot partition a
    custom call and would gather q, k, v, so the call is wrapped in
    ``shard_map`` over the batch axis, each shard telling the dropout hash
    where its rows lie in the global batch."""
    if mesh is None:
        return S.short_attention(q, k, v, kv_mask, seed, None, causal, scale,
                                 rate)
    rows = q.shape[0] // mesh.shape["dp"]
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    masked = kv_mask is not None

    def shard(q, k, v, seed, *mask):
        return S.short_attention(
            q, k, v, mask[0] if masked else None, seed,
            jax.lax.axis_index("dp") * rows, causal, scale, rate)

    by_row = P("dp")
    # check_vma off: interpret mode evaluates the kernel's body per equation
    # against varying blocks, and its literals vary over nothing
    return jax.shard_map(
        shard, mesh=mesh, check_vma=False,
        in_specs=(by_row, by_row, by_row, P()) + (by_row,) * masked,
        out_specs=by_row)(q, k, v, seed, *([kv_mask] if masked else []))


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
        impl = _auto_impl(jax.default_backend(), q.shape, k.shape, q.dtype,
                          ctx.mesh, ctx.spans_devices)
        # which lowering auto chose, counted when the op is lowered
        _obs_stats.scope("attn").counter(f"fused_auto_{impl}").inc()
        if impl == "short":  # auto's alone: no attr value selects it
            return {"Out": [_short(ctx.mesh, q, k, v, kv_mask, seed, causal,
                                   scale, rate)]}

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
