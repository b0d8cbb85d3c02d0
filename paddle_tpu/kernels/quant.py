"""Low-precision serving kernels: fused-dequant int8 matmul + KV helpers.

ROADMAP item 3's serving legs.  QAT fake-quant (``ops/quant_ops.py``,
``contrib/quantize.py``) models int8 numerics during training but every
inference matmul still runs f32 — nothing is faster for having
quantized.  This module is where low precision starts paying rent:

- ``int8_fc``: ONE Pallas launch computes a calibrated FC layer as an
  int8 x int8 -> int32 MXU matmul with a fused dequant(+bias+activation)
  epilogue.  Weights arrive pre-quantized (per-out-channel abs-max
  scales, derived by the ``quantize_int8`` calibration pass in
  ``inference/passes.py``); activations quantize per dispatch with the
  QAT moving-average scale when one was calibrated, else dynamically
  from the batch abs-max (one traced reduction — no recompiles, the
  scale is data, not shape).
- ``Int8Plan``: the ``core/lowering.py`` peephole over calibrated
  mul/fused_fc ops (the ops the calibration pass stamped), mirroring
  the sparse-fusion plan contract: ``covers(pos)`` / ``lower(pos, env)``
  with per-op fallback to the untouched f32 lowering on any fault.
- KV-cache qdq helpers (``kv_quantize``/``kv_dequantize``/
  ``kv_head_amax``): ONE definition of the int8 round-trip shared by
  the paged cache writers (``decode/model.py``), the quantized paged
  decode-attention kernel (``kernels/attention.py``) and the tests, so
  the storage and compute planes can never disagree on scale semantics.

Scale semantics everywhere (the ``_qdq`` convention of
``ops/quant_ops.py``, r=127): ``q = clip(round(x / s * 127), -127, 127)``
and ``x ~= q * s / 127`` where ``s`` is a float abs-max.  A matmul of
two such codes dequantizes with ``s_x * s_w[j] / 127^2`` per out
channel j — exactly what the epilogue applies, so the kernel reproduces
the QAT fake-quant reference to f32 rounding.

Fallback contract: every entry point degrades on any build/trace
fault — ``int8_fc`` returns ``None``
(counted ``quant.matmul_fallbacks``) and the caller takes
``int8_fc_xla``, the same quantized math as plain XLA ops (counted
``quant.xla_dequant``); the peephole returns False (counted
``quant.lower_fallbacks``) to re-lower the op through the untouched f32
path.  A kernel fault can never fail a dispatch.  Off-TPU the kernel
runs in Pallas interpret mode (tier-1 CPU coverage).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..observability import stats as _obs_stats
from ..platform import pallas_interpret

__all__ = [
    "enabled_for",
    "count_runtime_disable",
    "quantize_weight",
    "clip_fraction",
    "int8_fc",
    "int8_fc_xla",
    "plan_int8",
    "Int8Plan",
    "kv_quantize",
    "kv_dequantize",
    "kv_head_amax",
    "note_calibration",
    "calibrations",
    "note_kv_cache",
    "quantz",
]

# the qdq code range of ops/quant_ops.py (r = (1 << 7) - 1)
QMAX = 127
# floor on every scale so an all-zero channel/block divides cleanly
# (same epsilon _qdq uses)
SCALE_EPS = 1e-8

# activations the fused epilogue implements; anything else (or any act
# carrying attrs, e.g. leaky_relu alpha) falls back per-op
_EPILOGUE_ACTS = {
    "": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "gelu": jax.nn.gelu,
}

# whole-operand VMEM budget for the single-launch kernel; bigger
# problems take the XLA dequantized path (still quantized math)
_VMEM_BUDGET_BYTES = 8 << 20

# pull-mirror of the quant.* counters so /quantz renders without
# scraping the metrics registry
_COUNTERS: Dict[str, int] = {}


def _count(name: str, n: int = 1) -> None:
    # unconditional (not gated on FLAGS_runtime_stats): trace-time only,
    # and an uncounted fallback is a kernel that silently never ran
    _COUNTERS[name] = _COUNTERS.get(name, 0) + n
    _obs_stats.scope("quant").counter(name).inc(n)


def enabled_for(ctx) -> bool:
    """Per-lowering gate for the int8 peephole.  Activation is driven by
    the op attrs the calibration pass stamped (so an uncalibrated
    program can never change), gated off under a mesh (GSPMD cannot
    partition the custom call) and on fault-recovery re-lowers (the
    executor sets ``ctx.disable_int8_fused`` when retrying a step whose
    compile died with the quant kernels in it)."""
    return (ctx.mesh is None
            and not getattr(ctx, "disable_int8_fused", False))


def count_runtime_disable() -> None:
    """A whole-step compile fault surfaced AFTER trace time (Mosaic/XLA,
    only reachable on a real TPU backend) is recovered by re-lowering
    without the int8 kernels; counted so the degrade is loud."""
    _count("runtime_disables")


# ---------------------------------------------------------------------------
# calibration (pass-time, numpy): per-out-channel weight quantization
# ---------------------------------------------------------------------------

def quantize_weight(w):
    """Quantize a 2-D [K, N] FC weight per OUT channel (per column).

    Returns ``(q, scales)``: ``q`` int8 [K, N], ``scales`` f32 [N]
    abs-max per column — the axis that factors out of ``x @ w`` so the
    dequant rides the epilogue, not the accumulation."""
    w = np.asarray(w, dtype=np.float32)
    if w.ndim != 2:
        raise ValueError(f"int8 FC weight must be 2-D, got {w.shape}")
    scales = np.maximum(np.max(np.abs(w), axis=0), SCALE_EPS)
    q = np.clip(np.round(w / scales[None, :] * QMAX),
                -QMAX, QMAX).astype(np.int8)
    return q, scales.astype(np.float32)


def clip_fraction(q) -> float:
    """Fraction of quantized codes at the clip boundary (|q| == 127) —
    the /quantz saturation signal: a high fraction means the abs-max
    scale is dominated by outliers and the layer deserves a look."""
    q = np.asarray(q)
    if q.size == 0:
        return 0.0
    return float(np.mean(np.abs(q.astype(np.int32)) >= QMAX))


# ---------------------------------------------------------------------------
# the fused-dequant int8 matmul
# ---------------------------------------------------------------------------

def _fc_kernel(x_ref, w_ref, dq_ref, b_ref, o_ref, *, act):
    # int8 x int8 -> int32 on the MXU, dequant+bias+act in the epilogue
    acc = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * dq_ref[:] + b_ref[:]
    o_ref[:] = _EPILOGUE_ACTS[act](out)


def _quantize_act(x, in_scale: float):
    """Per-dispatch activation quantization: the calibrated
    moving-average scale when the QAT stats provided one, else the
    batch abs-max (dynamic — a traced reduction, never a new shape)."""
    if in_scale and in_scale > 0.0:
        sx = jnp.float32(in_scale)
    else:
        sx = jnp.maximum(jnp.max(jnp.abs(x)).astype(jnp.float32),
                         SCALE_EPS)
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / sx * QMAX),
                  -QMAX, QMAX).astype(jnp.int8)
    return xq, sx


def int8_fc(x, w_q, w_scale, in_scale: float = 0.0, bias=None,
            act: str = "", interpret=None):
    """Fused-dequant int8 FC: ONE Pallas launch, or ``None`` (counted)
    when the launch cannot be built — the caller then takes
    ``int8_fc_xla`` (same math, plain XLA ops).

    ``x`` f32 [M, K]; ``w_q`` int8 [K, N]; ``w_scale`` f32 [N];
    ``bias`` f32 [N] or None; ``act`` one of the epilogue set."""
    try:
        if x.ndim != 2 or w_q.ndim != 2 or act not in _EPILOGUE_ACTS:
            raise ValueError("int8_fc needs 2-D operands / known act")
        m, k = int(x.shape[0]), int(x.shape[1])
        n = int(w_q.shape[1])
        if int(w_q.shape[0]) != k:
            raise ValueError("int8_fc shape mismatch")
        # whole-operand launch: int8 x + int8 w + f32 out (+ epilogue
        # vectors) must fit the VMEM budget; bigger shapes fall back
        if m * k + k * n + 4 * (m * n + 2 * n) > _VMEM_BUDGET_BYTES:
            raise ValueError("int8_fc operands exceed the VMEM budget")
        if interpret is None:
            interpret = pallas_interpret()
        xq, sx = _quantize_act(x, in_scale)
        dq = (sx * w_scale.astype(jnp.float32) / (QMAX * QMAX))
        b = (bias.astype(jnp.float32) if bias is not None
             else jnp.zeros((n,), jnp.float32))
        out = pl.pallas_call(
            functools.partial(_fc_kernel, act=act),
            name="int8_fc",
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
            interpret=interpret,
        )(xq, w_q, dq.reshape(1, n), b.reshape(1, n))
        _count("matmul_launches")
        return out
    except Exception:
        _count("matmul_fallbacks")
        return None


def int8_fc_xla(x, w_q, w_scale, in_scale: float = 0.0, bias=None,
                act: str = ""):
    """The counted fallback: identical quantized math through plain XLA
    ops (int8 codes widened to f32 for the dot — XLA's portable int8
    story).  Also the dequantized reference the parity tests pin the
    kernel against."""
    xq, sx = _quantize_act(x, in_scale)
    acc = jnp.dot(xq.astype(jnp.float32), w_q.astype(jnp.float32))
    out = acc * (sx * w_scale.astype(jnp.float32) / (QMAX * QMAX))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    _count("xla_dequant")
    return _EPILOGUE_ACTS[act](out)


# ---------------------------------------------------------------------------
# block-level peephole over calibrated mul / fused_fc ops
# ---------------------------------------------------------------------------

class Int8Plan:
    """Peephole plan for a block: ops the ``quantize_int8`` calibration
    pass stamped (``quant_int8`` attr + WInt8/WScale sidecar inputs)
    lower through the fused-dequant int8 matmul.

    ``core/lowering.py`` consults ``covers(pos)`` per op and calls
    ``lower(pos, env)`` — True fills the op's output into ``env``
    (Pallas launch, or the counted XLA dequantized path on a build
    fault); False (counted) lets the op lower through the untouched
    f32 path."""

    def __init__(self, positions):
        self._pos = dict(positions)  # block-op index -> op

    def covers(self, pos: int) -> bool:
        return pos in self._pos

    def lower(self, pos: int, env: dict) -> bool:
        op = self._pos[pos]
        try:
            if op.type == "fused_fc":
                x_name = op.inputs["X"][0]
                bias = env[op.inputs["Bias"][0]]
                act = op.attrs.get("act", "") or ""
                # op_role is bookkeeping every op carries, not a real
                # activation parameter
                if any(k != "op_role"
                       for k in (op.attrs.get("act_attrs") or {})):
                    raise ValueError("act attrs not in the epilogue set")
            else:  # mul
                x_name = op.inputs["X"][0]
                bias = None
                act = ""
            if act not in _EPILOGUE_ACTS:
                raise ValueError(f"unsupported epilogue act {act!r}")
            if int(op.attrs.get("y_num_col_dims", 1)) != 1:
                raise ValueError("int8 FC needs y_num_col_dims == 1")
            w_q = env[op.inputs["WInt8"][0]]
            w_scale = env[op.inputs["WScale"][0]]
            x = env[x_name]
            xnc = int(op.attrs.get("x_num_col_dims", 1))
            lead = tuple(int(d) for d in x.shape[:xnc])
            xm = x.reshape((int(np.prod(lead)) if lead else 1, -1))
            in_scale = float(op.attrs.get("in_scale", 0.0))
            if bias is not None:
                bias = bias.reshape(-1)
            out = int8_fc(xm, w_q, w_scale, in_scale, bias, act)
            if out is None:
                out = int8_fc_xla(xm, w_q, w_scale, in_scale, bias, act)
            n = int(w_q.shape[1])
            env[op.outputs["Out"][0]] = out.reshape(lead + (n,))
            return True
        except Exception:
            _count("lower_fallbacks")
            return False


def plan_int8(block):
    """Scan ``block`` for calibrated ops; an ``Int8Plan`` or None.  An
    op qualifies only with the full calibration stamp (attr + both
    sidecar inputs) — a half-stamped op lowers f32."""
    positions = []
    for pos, op in enumerate(block.ops):
        if op.type not in ("mul", "fused_fc"):
            continue
        if not op.attrs.get("quant_int8"):
            continue
        if not op.inputs.get("WInt8") or not op.inputs.get("WScale"):
            continue
        positions.append((pos, op))
    return Int8Plan(positions) if positions else None


# ---------------------------------------------------------------------------
# KV-cache int8 round-trip: ONE definition of the scale semantics
# ---------------------------------------------------------------------------

def kv_head_amax(rows):
    """Per-head abs-max of KV rows [..., H, D] -> [..., H] (the scale a
    block stores for each head)."""
    return jnp.maximum(jnp.max(jnp.abs(rows.astype(jnp.float32)),
                               axis=-1), SCALE_EPS)


def kv_quantize(rows, scales):
    """Quantize KV rows [..., H, D] with per-head scales [..., H] ->
    int8 codes (the storage form of the paged cache)."""
    s = jnp.maximum(scales.astype(jnp.float32), SCALE_EPS)[..., None]
    q = jnp.round(rows.astype(jnp.float32) / s * QMAX)
    return jnp.clip(q, -QMAX, QMAX).astype(jnp.int8)


def kv_dequantize(q, scales):
    """Dequantize int8 KV codes [..., H, D] with per-head scales
    [..., H] -> f32 rows (what the attention kernel computes against)."""
    s = jnp.maximum(scales.astype(jnp.float32), SCALE_EPS)[..., None]
    return q.astype(jnp.float32) * s / QMAX


# ---------------------------------------------------------------------------
# /quantz observability payload
# ---------------------------------------------------------------------------

# per-layer calibration records appended by the quantize_int8 pass
# (bounded: one per calibrated op per pass run; reset on each pass run
# of the same program would double-count, so records carry the op's
# weight var name and the page shows the latest per name)
_CALIB: List[dict] = []
_CALIB_CAP = 256

# KV caches note their geometry here at construction (keyed by engine
# name) so /quantz shows the storage-plane dtype + bytes/block next to
# the compute-plane scales
_KV_INFO: Dict[str, dict] = {}


def note_calibration(rec: dict) -> None:
    _CALIB.append(dict(rec))
    del _CALIB[:-_CALIB_CAP]


def calibrations() -> List[dict]:
    return list(_CALIB)


def note_kv_cache(name: str, info: dict) -> None:
    _KV_INFO[name] = dict(info)


def quantz() -> dict:
    """The /quantz debug-page payload: per-layer calibration records
    (scales, clip fractions), the quant.* counter mirror, and every
    noted KV cache's dtype + bytes/block."""
    latest: Dict[str, dict] = {}
    for rec in _CALIB:
        latest[str(rec.get("weight", len(latest)))] = rec
    return {
        "calibrated_layers": list(latest.values()),
        "counters": dict(_COUNTERS),
        "kv_caches": {k: dict(v) for k, v in _KV_INFO.items()},
    }


def quantz_text() -> str:
    """Human rendering of :func:`quantz` (the ``?text=1`` form, the
    allocz/capacityz pattern)."""
    z = quantz()
    lines = ["== int8 calibration =="]
    if not z["calibrated_layers"]:
        lines.append("  (no calibrated layers)")
    for rec in z["calibrated_layers"]:
        lines.append(
            "  {op:<10} w={weight}  shape={shape}  act={act!r}  "
            "in_scale={in_scale:.6g}  w_scale=[{lo:.4g}, {hi:.4g}]  "
            "clip={clip:.4%}".format(
                op=rec.get("op", "?"), weight=rec.get("weight", "?"),
                shape=rec.get("shape"), act=rec.get("act", ""),
                in_scale=float(rec.get("in_scale", 0.0)),
                lo=float(rec.get("w_scale_min", 0.0)),
                hi=float(rec.get("w_scale_max", 0.0)),
                clip=float(rec.get("clip_fraction", 0.0))))
    lines.append("== quant.* counters ==")
    if not z["counters"]:
        lines.append("  (none)")
    for k in sorted(z["counters"]):
        lines.append(f"  {k:<24} {z['counters'][k]}")
    lines.append("== quantized KV caches ==")
    if not z["kv_caches"]:
        lines.append("  (none)")
    for name in sorted(z["kv_caches"]):
        info = z["kv_caches"][name]
        lines.append("  {n}: dtype={d}  blocks={b}  "
                     "bytes/block={bb}  pool={p}".format(
                         n=name, d=info.get("dtype"),
                         b=info.get("num_blocks"),
                         bb=info.get("bytes_per_block"),
                         p=info.get("pool_bytes")))
    return "\n".join(lines) + "\n"
