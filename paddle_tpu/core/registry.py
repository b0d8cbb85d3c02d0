"""Op registry: op type → XLA lowering rule (+ optional custom grad rule).

TPU-native replacement for the reference's OperatorWithKernel registry
(``paddle/fluid/framework/op_registry.h:43,124`` and the OpKernelType
dispatch in ``operator.cc:686-723``).  There is no runtime kernel dispatch:
each op type registers a *lowering rule* — a pure function from JAX values
to JAX values — and whole blocks are traced through these rules into one
XLA computation (see ``core/lowering.py``).  Hot ops may register a Pallas
implementation; the rule decides internally (the reference's
library_type={Plain,cuDNN,MKLDNN} analogue).

Gradients: the default grad rule applies ``jax.vjp`` to the forward rule —
XLA CSE merges the re-traced forward with the original, so this costs no
extra FLOPs inside a jitted block.  Ops whose lowering consumes randomness
or host state must register an explicit ``grad`` rule (reference analogue:
GradOpDescMaker, ``grad_op_desc_maker.h``).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

GRAD_OP_SUFFIX = "_grad"

# in/out values passed to lowering rules: dict slot -> list[jax.Array]
SlotVals = Dict[str, List[Any]]


class LowerContext:
    """Per-block lowering context handed to every rule.

    Provides split PRNG keys (rng is threaded through the block as hidden
    state — the functional translation of the reference's per-op ``seed``
    attrs), access to the block being lowered (for sub-block control flow),
    and mesh info for parallel lowering.
    """

    def __init__(self, block=None, mesh=None, lower_block_fn=None, training=True):
        self.block = block
        self.mesh = mesh
        # the program is compiled across several devices although no mesh
        # is given (core/lowering.py build_block_fn)
        self.spans_devices = False
        self.training = training
        self._rng_key = None
        self._rng_key0 = None
        self._rng_used = False
        self._lower_block_fn = lower_block_fn  # (block_idx, env) -> env
        # (forward op's scope, grad op's own) while lower_ops dispatches a
        # grad op that re-traces its forward: see scoped_vjp
        self.grad_scopes = ("", "")

    def set_rng(self, key):
        self._rng_key = key
        self._rng_key0 = key
        self._rng_used = False

    def named_prng(self, name: str, seed: int = 0):
        """Order-independent PRNG key derived from (base key, name).

        Used by initializer ops (attr ``seed_name``) so that initialization
        is a pure function of (program.random_seed, var name) regardless of
        op order or program partitioning — program rewrites (transpilers,
        pserver splits) then initialize identical values to the local run.
        The reference gets the equivalent property from per-op ``seed``
        attrs (uniform_random_op.cc) set at build time.
        """
        import zlib

        base = jax.random.PRNGKey(seed) if seed else self._rng_key0
        if base is None:
            raise RuntimeError("op requires randomness but no rng state was provided")
        self._rng_used = True
        return jax.random.fold_in(base, zlib.crc32(name.encode("utf-8")))

    def prng(self):
        """Split off a fresh PRNG key (marks rng as consumed)."""
        if self._rng_key is None:
            raise RuntimeError("op requires randomness but no rng state was provided")
        self._rng_key, sub = jax.random.split(self._rng_key)
        self._rng_used = True
        return sub

    @property
    def rng_key(self):
        return self._rng_key

    def lower_sub_block(self, block_idx: int, env: dict) -> dict:
        if self._lower_block_fn is None:
            raise RuntimeError("sub-block lowering not available in this context")
        return self._lower_block_fn(block_idx, env)


class OpDef:
    def __init__(
        self,
        type: str,
        lower: Callable[[LowerContext, SlotVals, dict], SlotVals],
        grad: Optional[Callable] = None,
        stateful: bool = False,
        input_slots: Optional[Sequence[str]] = None,
        output_slots: Optional[Sequence[str]] = None,
        no_grad_slots: Sequence[str] = (),
        infer_shape: Optional[Callable] = None,
    ):
        self.type = type
        self.lower = lower
        self.grad = grad            # custom grad lowering, else vjp default
        self.grad_retraces = False  # the custom rule re-traces the forward
        self.stateful = stateful    # consumes rng / host state → needs custom grad
        self.input_slots = list(input_slots) if input_slots else None
        self.output_slots = list(output_slots) if output_slots else None
        self.no_grad_slots = set(no_grad_slots)  # input slots never differentiated
        self.infer_shape = infer_shape


_REGISTRY: Dict[str, OpDef] = {}


def register(
    type: str,
    *,
    grad=None,
    stateful: bool = False,
    input_slots=None,
    output_slots=None,
    no_grad_slots=(),
    infer_shape=None,
):
    """Decorator: register a lowering rule for ``type``."""

    def deco(fn):
        _REGISTRY[type] = OpDef(
            type,
            fn,
            grad=grad,
            stateful=stateful,
            input_slots=input_slots,
            output_slots=output_slots,
            no_grad_slots=no_grad_slots,
            infer_shape=infer_shape,
        )
        return fn

    return deco


def register_grad(type: str, retraces: bool = False):
    """Decorator: attach a custom grad rule to an already-registered op.

    Signature: ``grad(ctx, ins, attrs) -> {in_slot + '@GRAD': [vals]}`` where
    ``ins`` contains the forward ins, forward outs, and ``slot@GRAD`` entries.
    ``retraces``: the rule differentiates a re-trace of the forward lowering
    (through :func:`scoped_vjp` or :func:`vjp_grad`), which names its own two
    halves; any other rule is traced whole under the grad op's scope.
    """

    def deco(fn):
        _REGISTRY[type].grad = fn
        _REGISTRY[type].grad_retraces = retraces
        return fn

    return deco


def get(type: str) -> OpDef:
    if type not in _REGISTRY:
        raise KeyError(f"no lowering registered for op type {type!r}")
    return _REGISTRY[type]


def has(type: str) -> bool:
    return type in _REGISTRY


def all_ops() -> List[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Default (vjp-based) grad lowering
# ---------------------------------------------------------------------------

def _scope(name: str):
    return jax.named_scope(name) if name else contextlib.nullcontext()


def scoped_vjp(ctx: LowerContext, fwd: Callable, *primals):
    """``jax.vjp`` of a re-traced forward lowering, its two halves named
    apart: the primal half under the FORWARD op's scope, the cotangent half
    under the grad op's own (``ctx.grad_scopes``, set by ``lower_ops``).
    XLA's CSE merges the re-traced forward with the original and keeps either
    one's metadata, so both copies have to be named alike or a forward
    matmul's time lands under ``bwd`` by the compiler's choice."""
    fwd_scope, bwd_scope = ctx.grad_scopes
    with _scope(fwd_scope):
        out, vjp_fn = jax.vjp(fwd, *primals)

    def pull(*cotangents):
        with _scope(bwd_scope):
            return vjp_fn(*cotangents)

    return out, pull


def vjp_grad(opdef: OpDef, ctx: LowerContext, ins: SlotVals, attrs: dict) -> SlotVals:
    """Differentiate the forward lowering rule with jax.vjp.

    ``ins`` holds the forward input slots, forward output slots, and
    ``slot@GRAD`` cotangents for outputs that received gradients.  Returns
    ``slot@GRAD`` for each differentiable forward input slot.  Integer and
    ``no_grad_slots`` inputs are held constant.  The forward is re-traced
    inside vjp; within one jitted block XLA CSE merges it with the original
    forward, so there is no duplicated compute at run time (and
    :func:`scoped_vjp` names the copy as the original).
    """
    bwd_scope = ctx.grad_scopes[1]
    fwd_out_slots = set(attrs.get("__fwd_out_slots__", ()))
    if opdef.output_slots:
        fwd_out_slots |= set(opdef.output_slots)
    in_slots = [
        s for s in ins
        if not s.endswith("@GRAD")
        and (opdef.input_slots is None or s in opdef.input_slots)
        and s not in fwd_out_slots
    ]
    diff_slots = [
        s for s in in_slots
        if s not in opdef.no_grad_slots
        and all(jnp.issubdtype(jnp.asarray(v).dtype, jnp.inexact) for v in ins[s])
    ]
    const_vals = {s: ins[s] for s in in_slots if s not in diff_slots}
    if not diff_slots:
        return {}

    def fwd(d: dict):
        full = {k: list(v) for k, v in d.items()}
        full.update(const_vals)
        fwd_attrs = {k: v for k, v in attrs.items() if not k.startswith("__")}
        return opdef.lower(ctx, full, fwd_attrs)

    primals_out, vjp_fn = scoped_vjp(ctx, fwd, {s: ins[s] for s in diff_slots})

    def make_cot(path_slot, j, primal):
        g_list = ins.get(path_slot + "@GRAD")
        if g_list is not None and j < len(g_list) and g_list[j] is not None:
            g = g_list[j]
            pdt = jnp.asarray(primal).dtype
            # declared grad-var dtype can differ from the promoted primal
            # dtype under mixed precision (bf16 activations, f32 stats)
            return g.astype(pdt) if g.dtype != pdt else g
        if jnp.issubdtype(jnp.asarray(primal).dtype, jnp.inexact):
            return jnp.zeros_like(primal)
        import numpy as _np
        return _np.zeros(jnp.shape(primal), dtype=jax.dtypes.float0)

    with _scope(bwd_scope):
        cot = {
            s: [make_cot(s, j, p) for j, p in enumerate(vals)]
            for s, vals in primals_out.items()
        }
    (grads,) = vjp_fn(cot)
    return {s + "@GRAD": list(v) for s, v in grads.items()}
