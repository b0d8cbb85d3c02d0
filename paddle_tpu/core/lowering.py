"""Whole-block lowering: Program block → one pure JAX function.

This replaces the reference's op-by-op interpreters (``Executor``
``executor.cc:357-392`` hot loop and the ParallelExecutor SSA machinery in
``framework/details/``) with ahead-of-time lowering: a static analysis pass
finds the block's external reads (scope state) and persistable writes, then
every op is traced through its registered lowering rule into a single
``(feeds, state, rng) -> (fetches, new_state, rng')`` function that XLA
JIT-compiles and fuses end-to-end.  Data-dependence ordering, memory reuse,
kernel fusion, and stream scheduling — everything ``details/`` did by hand —
is delegated to the XLA compiler.

Every op is traced under ONE ``jax.named_scope``, so device time carries the
program's names: ``<role>/<op_namescope...>/<op.type>[/<param>]`` with role
``fwd`` | ``bwd`` | ``opt`` (:func:`op_scope`; a sub-block's ops inherit the
role of their control-flow op).  A grad op that re-traces its forward
(``registry.vjp_grad``, ``scoped_vjp``) traces the primal half of its
``jax.vjp`` under the FORWARD op's scope and only the cotangent half under its
own ``bwd/...``: XLA's CSE merges the re-traced forward with the original and
keeps either one's metadata, so both copies must be named alike for the
forward/backward split to be true.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax

from . import compile_cache, registry
from .program import (Program, Block, EMPTY_VAR, GRAD_REWRITE_ATTR,
                      OP_ROLE_ATTR, OP_ROLE_VAR_ATTR, OpRole)
from .registry import GRAD_OP_SUFFIX, LowerContext
from ..observability import stats as _obs_stats
from ..observability import trace as _obs_trace

# ops handled by the executor itself, not lowered
SKIP_OPS = ("feed", "fetch")

_telemetry_on = _obs_trace.flags_on


@dataclass
class BlockPlan:
    """Static dataflow summary of one block (+ its sub-blocks)."""

    block_idx: int
    feed_names: tuple
    fetch_names: tuple
    state_reads: List[str] = field(default_factory=list)     # scope vars read
    persist_writes: List[str] = field(default_factory=list)  # scope vars written
    has_stateful: bool = False

    @property
    def donated_reads(self) -> List[str]:
        w = set(self.persist_writes)
        return [n for n in self.state_reads if n in w]

    @property
    def const_reads(self) -> List[str]:
        w = set(self.persist_writes)
        return [n for n in self.state_reads if n not in w]

    @property
    def donated_write_indices(self) -> List[int]:
        """For step-loop drivers: indices into the returned ``new_state``
        (persist_writes order) that refeed the donated inputs
        (donated_reads order) on the next call."""
        pos = {n: i for i, n in enumerate(self.persist_writes)}
        return [pos[n] for n in self.donated_reads]


def analyze_block(program: Program, block_idx: int, feed_names: Sequence[str],
                  fetch_names: Sequence[str]) -> BlockPlan:
    with _obs_trace.span("lowering::analyze"):
        return _analyze_block(program, block_idx, feed_names, fetch_names)


def _analyze_block(program: Program, block_idx: int,
                   feed_names: Sequence[str],
                   fetch_names: Sequence[str]) -> BlockPlan:
    t0 = time.perf_counter_ns() if _telemetry_on() else None
    plan = BlockPlan(block_idx, tuple(feed_names), tuple(fetch_names))
    seen_reads = set()
    persist_written = set()

    def is_persistable(block: Block, name: str) -> bool:
        v = block.var_or_none(name)
        return bool(v and v.persistable)

    def walk(block: Block, defined: set):
        for op in block.ops:
            if op.type in SKIP_OPS:
                continue
            base = op.type[: -len(GRAD_OP_SUFFIX)] if op.type.endswith(GRAD_OP_SUFFIX) else op.type
            if registry.has(base) and registry.get(base).stateful:
                plan.has_stateful = True
            for n in op.input_arg_names():
                if n and n != EMPTY_VAR and n not in defined and n not in seen_reads:
                    seen_reads.add(n)
                    plan.state_reads.append(n)
            # names bound inside sub-blocks by the control-flow lowering
            # (scan step inputs, memories, loop carries) are not scope reads
            inner = set(op.attr("carry_vars", ()) or ())
            inner |= set(op.attr("step_input_vars", ()) or ())
            inner |= {m[0] for m in (op.attr("memories", ()) or ())}
            for sub in op.sub_block_ids:
                walk(program.blocks[sub], set(defined) | inner)
            for n in op.output_arg_names():
                if not n or n == EMPTY_VAR:
                    continue
                defined.add(n)
                if is_persistable(block, n) and n not in persist_written:
                    persist_written.add(n)
                    plan.persist_writes.append(n)

    walk(program.blocks[block_idx], set(feed_names))

    # fetches of vars never touched by ops must still come from scope
    defined_or_read = seen_reads | set(feed_names)
    for b in [program.blocks[block_idx]]:
        for op in b.ops:
            defined_or_read |= set(op.output_arg_names())
    for n in fetch_names:
        if n not in defined_or_read and n not in seen_reads:
            seen_reads.add(n)
            plan.state_reads.append(n)
    if t0 is not None:
        _obs_stats.scope("lowering").histogram("analyze_ms").observe(
            (time.perf_counter_ns() - t0) / 1e6)
    return plan


def op_scope(op, top: bool = True, type: Optional[str] = None) -> str:
    """The ``jax.named_scope`` an op is traced under — a function of the op
    alone, so two lowerings of a program name their instructions alike.
    ``top``: the op lies in the block the executor was handed (a sub-block's
    ops carry no role of their own).  ``type``: name the scope after this op
    type instead (the forward op's, for the primal half of a grad op)."""
    parts = []
    param = None
    if top:
        role = int(op.attr(OP_ROLE_ATTR, OpRole.Forward)) & ~OpRole.Loss
        if type is not None or role == OpRole.Forward:
            parts.append("fwd")
        elif role == OpRole.Backward and not op.attr(GRAD_REWRITE_ATTR):
            parts.append("bwd")
        else:       # Optimize, LRSched, clipping, regularisation, RPC, Dist
            parts.append("opt")
            param = (op.attr(OP_ROLE_VAR_ATTR) or (None,))[0]
    ns = (op.attr("op_namescope") or "").strip("/")
    if ns:
        parts.append(ns)
    parts.append(type or op.type)
    if param:
        parts.append(param)
    return "/".join(parts)


def _retraced_forward(op) -> Optional[registry.OpDef]:
    """The op whose forward lowering this grad op re-traces under ``jax.vjp``
    (the default rule, or a rule of its own that says so), else None."""
    if not op.type.endswith(GRAD_OP_SUFFIX) or registry.has(op.type):
        return None
    base_type = op.type[: -len(GRAD_OP_SUFFIX)]
    if not registry.has(base_type):
        return None
    base = registry.get(base_type)
    return base if base.grad is None or base.grad_retraces else None


@contextlib.contextmanager
def _halves_named(ctx: LowerContext, fwd_scope: str, bwd_scope: str):
    """No scope around the grad op as a whole: ``registry.scoped_vjp`` names
    the re-traced forward as the original was, and the cotangent half as the
    grad op, so the copy that XLA's CSE keeps is named right either way."""
    ctx.grad_scopes = (fwd_scope, bwd_scope)
    try:
        yield
    finally:
        ctx.grad_scopes = ("", "")


def lower_ops(ctx: LowerContext, program: Program, block: Block, env: Dict) -> Dict:
    """Trace every op in ``block`` through its lowering rule, each under its
    :func:`op_scope`, mutating env."""
    from ..ops.control_flow_ops import CONTROL_FLOW_OPS

    # int8 inference peephole: mul/fused_fc ops the quantize_int8
    # calibration pass stamped (quant_int8 attr + WInt8/WScale sidecar
    # inputs) lower through the fused-dequant int8 Pallas matmul
    # (kernels/quant.py).  Activation is attr-driven — an uncalibrated
    # program builds no plan and lowers byte-identically.
    from ..kernels import quant as _quant_kernels
    int8_plan = (_quant_kernels.plan_int8(block)
                 if _quant_kernels.enabled_for(ctx) else None)

    top = block.parent_idx < 0
    for pos, op in enumerate(block.ops):
        if op.type in SKIP_OPS:
            continue
        scope = op_scope(op, top)
        forward = (None if op.type in CONTROL_FLOW_OPS
                   else _retraced_forward(op))
        with (jax.named_scope(scope) if forward is None else _halves_named(
                ctx, op_scope(op, top, type=forward.type), scope)):
            if int8_plan is not None and int8_plan.covers(pos) \
                    and int8_plan.lower(pos, env):
                ctx.int8_fused_used = True
                continue
            if op.type in CONTROL_FLOW_OPS:
                try:
                    CONTROL_FLOW_OPS[op.type](ctx, program, op, env, lower_ops)
                except Exception as e:
                    raise type(e)(
                        f"while lowering control-flow op {op!r} in block "
                        f"{block.idx}: {e}") from e
                continue
            ins = {}
            for slot, names in op.inputs.items():
                if slot.endswith("@GRAD"):
                    # grad slots keep positional alignment; missing grads → None
                    vals = [env.get(n) if n and n != EMPTY_VAR else None for n in names]
                    if any(v is not None for v in vals):
                        ins[slot] = vals
                else:
                    vals = [env[n] for n in names if n and n != EMPTY_VAR]
                    if vals:
                        ins[slot] = vals
            try:
                if op.type.endswith(GRAD_OP_SUFFIX) and not registry.has(op.type):
                    base = registry.get(op.type[: -len(GRAD_OP_SUFFIX)])
                    if base.grad is not None:
                        outs = base.grad(ctx, ins, op.attrs)
                    else:
                        outs = registry.vjp_grad(base, ctx, ins, op.attrs)
                else:
                    outs = registry.get(op.type).lower(ctx, ins, op.attrs)
            except Exception as e:
                raise type(e)(f"while lowering op {op!r} in block {block.idx}: {e}") from e
            for slot, names in op.outputs.items():
                vals = outs.get(slot)
                if vals is None:
                    continue
                for name, val in zip(names, vals):
                    if name and name != EMPTY_VAR and val is not None:
                        env[name] = val
    return env


def build_block_fn(program: Program, plan: BlockPlan, training: bool = True,
                   mesh=None, disable_int8_fused: bool = False,
                   spans_devices: bool = False):
    """Return fn(feed_vals, donated_state, const_state, rng) ->
    (fetch_vals, new_persist_vals, rng_out).

    ``spans_devices``: the caller's arrays lie across several devices, so
    jit will compile this lowering as one partitioned program whether or
    not a ``mesh`` is given (``ctx.spans_devices``).

    ``disable_int8_fused``: lower WITHOUT the int8 inference peephole even
    where the program is calibrated for it — the executor's dispatch-fault
    recovery re-lowers a step this way when its compile died with the
    int8 kernels in it (kernels/quant.py counted-fallback contract)."""
    block = program.blocks[plan.block_idx]
    donated, const = plan.donated_reads, plan.const_reads
    # trace-time latch: did THIS lowering actually emit int8 kernels?  The
    # executor's dispatch-fault recovery gates on it (a calibrated program
    # may still have lowered every stamped op through XLA)
    used = {"int8_fused": False}

    def fn(feed_vals, donated_state, const_state, rng):
        # host-side timing of the op-by-op jax trace: runs once per XLA
        # compile (and per scan/eval_shape re-trace), never on cached
        # executions — the "build" half of the lowering cost
        t0 = time.perf_counter_ns() if _telemetry_on() else None

        def lower_sub(block_idx, env):
            return lower_ops(ctx, program, program.blocks[block_idx], env)

        ctx = LowerContext(block=block, mesh=mesh, lower_block_fn=lower_sub,
                           training=training)
        ctx.spans_devices = spans_devices
        ctx.disable_int8_fused = disable_int8_fused
        ctx.set_rng(rng)
        env: Dict = {}
        env.update(zip(plan.feed_names, feed_vals))
        env.update(zip(donated, donated_state))
        env.update(zip(const, const_state))
        with _obs_trace.span("lowering::trace"):
            lower_ops(ctx, program, block, env)
        if getattr(ctx, "int8_fused_used", False):
            used["int8_fused"] = True
        fetches = [env[n] for n in plan.fetch_names]
        new_state = [env[n] for n in plan.persist_writes]
        if t0 is not None:
            _obs_stats.scope("lowering").histogram("trace_ms").observe(
                (time.perf_counter_ns() - t0) / 1e6)
        return fetches, new_state, ctx.rng_key

    fn._int8_fused_used = used
    fn.__name__ = fn.__qualname__ = compile_cache.program_name("fn")
    return fn
