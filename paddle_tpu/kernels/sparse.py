"""Fused Pallas sparse-embedding kernels: multi-table gather + lazy update.

The DeepFM sparse path's binding term, when last measured, was the COUNT
of scatter-class ops (~1 ms flat each) plus the full-table HBM sweeps of
the masked-dense lazy update (PERF.md §5).

NOT COMPILED FOR A TPU: Mosaic refuses both kernels' ``(1, D)`` row
blocks (D = 10 and D = 1 — "the last two dimensions of your block shape
[must be] divisible by 8 and 128 respectively, or be equal to the
respective dimensions of the overall array", PERF.md Bring-up).  On a
TPU ``FLAGS_sparse_fused_kernel`` therefore ends in the executor's
counted re-lower (``sparse_fused.runtime_disables``); the kernels run
only in interpret mode, for their tests.  This module is the
TPU-native analogue of the reference's ``SelectedRows`` CPU functors
(``operators/math/selected_rows_functor.cc``) — the same move the flash
attention path made for the hot attention op:

- ``fused_gather``: ONE Pallas launch gathers the same id batch from k
  tables (both DeepFM tables per step), amortizing the flat dispatch cost
  of per-table XLA gathers.  Grid = one sorted-position per id; each grid
  step's input block is selected by a scalar-prefetch dynamic index map
  (``PrefetchScalarGridSpec``), so the pipeline streams exactly the
  touched rows.
- ``fused_adam`` / ``fused_momentum`` / ``fused_adagrad``: ONE Pallas
  launch per table replaces the whole per-table update chain (sorted
  path: 3 gathers + 3 scatter-sets + argsort + 2 segment ops; masked
  dense: scatter-add + ~7 full-table HBM sweeps).  Ids are sorted on
  device (argsort + reorder gathers — no scatter-class ops anywhere),
  segment boundaries are marked with first/last flags, and the kernel
  walks the sorted positions accumulating duplicate rows in VMEM
  (the ``merge_rows`` segment-sum formulation, done in-kernel in the
  same left-to-right order) and, at each segment's last position,
  applies the duplicate-exact lazy moment math and writes params +
  moments back through ``input_output_aliases`` — untouched table rows
  are never read or written.

Index-map discipline (why the in-place aliasing is hazard-free): rows are
processed in sorted order, so output block indices are non-decreasing and
every row's block is visited by exactly one run of consecutive grid steps.
Within a run the block index does not change, so Mosaic's revisiting
semantics keep the block in VMEM (one write-back per touched row at the
index change); across runs, all future input rows are strictly greater
than all already-written rows, so prefetches can never race a write-back.

Semantics notes:
- duplicate handling is exact: per-row gradients sum once (in sorted ==
  original order for equal ids — ``jnp.argsort`` is stable), then the
  optimizer math applies once per unique row, matching
  ``merge_rows``-then-update bit-for-bit on f32 tables.
- out-of-range ids (they come from user FEED data — a data bug must
  fail loudly on either path): ``fused_gather`` matches ``jnp.take``
  mode="fill" — ids in [-H, H) wrap-then-gather, anything else yields
  a NaN row (float tables; integer tables clamp), so the PR-7 NaN
  sentinel fires exactly as it does flag-off.  The update kernel clamps
  a malformed id to an edge row instead of dropping it — but the NaN
  forward already poisoned that step's loss AND gradient rows, so the
  loud failure precedes any silently-misdirected update.
- every entry point degrades to ``None`` (caller falls back to the
  existing masked-dense / sorted paths) on any build/trace fault, with a
  ``sparse_fused.*_fallbacks`` counter — a kernel fault can never fail a
  step.  Off-TPU the kernels run in Pallas interpret mode (tier-1 CPU
  coverage), like ``kernels/attention.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import flags
from ..observability import stats as _obs_stats
from ..platform import pallas_interpret

__all__ = [
    "fused_enabled",
    "enabled_for",
    "count_runtime_disable",
    "fused_gather",
    "fused_adam",
    "fused_momentum",
    "fused_adagrad",
    "plan_lookup_fusion",
    "LookupFusion",
    "jaxpr_census",
]


def jaxpr_census(jaxpr):
    """(scatter-class eqn count, pallas launch count) over ``jaxpr`` and
    every sub-jaxpr.  ONE definition on purpose: this census is both the
    ISSUE-10 acceptance pin (tests/test_sparse.py) and the structural
    evidence in the ``deepfm_fused`` bench analysis artifact — the two
    must never drift apart."""
    n_scatter = n_pallas = 0
    for eq in jaxpr.eqns:
        nm = str(eq.primitive)
        n_scatter += nm.startswith("scatter")
        n_pallas += nm == "pallas_call"
        for v in eq.params.values():
            for leaf in jax.tree_util.tree_leaves(
                    v, is_leaf=lambda x: hasattr(x, "eqns")
                    or hasattr(x, "jaxpr")):
                inner = getattr(leaf, "jaxpr", leaf)
                if hasattr(inner, "eqns"):
                    s, p = jaxpr_census(inner)
                    n_scatter += s
                    n_pallas += p
    return n_scatter, n_pallas


def _count(name: str, n: int = 1) -> None:
    # unconditional (not gated on FLAGS_runtime_stats): these fire at
    # trace time only, and a fallback nobody counted is a kernel that
    # silently never ran
    _obs_stats.scope("sparse_fused").counter(name).inc(n)


def fused_enabled() -> bool:
    """Trace-time gate: the flag is read when a program lowers, so cached
    executables keep the path they compiled with (same contract as
    FLAGS_sparse_dense_update_max_elems)."""
    return bool(flags.get_flags("sparse_fused_kernel"))


def enabled_for(ctx) -> bool:
    """Per-lowering gate: flag on, no mesh (GSPMD cannot partition the
    custom calls), and not a fault-recovery re-lower (the executor sets
    ``ctx.disable_sparse_fused`` when retrying a step whose compile died
    with the fused kernels in it — see Executor._recover_disk_entry)."""
    return (fused_enabled() and ctx.mesh is None
            and not getattr(ctx, "disable_sparse_fused", False))


def count_runtime_disable() -> None:
    """A whole-step compile fault surfaced AFTER trace time (Mosaic/XLA,
    only reachable on a real TPU backend) is recovered by the executor
    re-lowering without the fused kernels; counted here so the degrade
    is as loud as the trace-time fallbacks."""
    _count("runtime_disables")


# ---------------------------------------------------------------------------
# sorted segments: the merge_rows formulation without its scatter ops
# ---------------------------------------------------------------------------

def _sorted_segments(rows, vals):
    """Sort the id batch and mark duplicate-run boundaries.

    Returns ``(r, v, first, last)``: ``r`` the int32 sorted rows, ``v``
    the matching reordered value rows, ``first[i]``/``last[i]`` 1 iff
    position i starts/ends a run of equal rows.  Unlike ``merge_rows``
    this emits NO scatter-class ops (one sort + two reorder gathers +
    shifts); the segment SUM itself happens inside the update kernel, in
    the same left-to-right order ``jax.ops.segment_sum`` uses."""
    order = jnp.argsort(rows)
    r = rows[order].astype(jnp.int32)
    v = vals[order]
    neq = (r[1:] != r[:-1]).astype(jnp.int32)
    one = jnp.ones((1,), jnp.int32)
    first = jnp.concatenate([one, neq])
    last = jnp.concatenate([neq, one])
    return r, v, first, last


# ---------------------------------------------------------------------------
# fused multi-table gather
# ---------------------------------------------------------------------------

def _gather_kernel(*refs, k: int):
    # refs: k scalar-prefetch id vectors (consumed by the index maps),
    # then k table blocks, then k out blocks
    for t in range(k):
        refs[2 * k + t][:] = refs[k + t][:]


def fused_gather(tables, ids, interpret=None):
    """Gather ``table[ids]`` for every table in ONE Pallas launch.

    ``tables``: list of [H_t, D_t] arrays sharing the id batch; ``ids``:
    integer array of any shape.  Returns the per-table gathers shaped
    ``ids.shape + (D_t,)``, or ``None`` (counted fallback) if the launch
    cannot be built."""
    if not tables:
        return None
    try:
        flat = ids.reshape(-1)
        n = int(flat.shape[0])
        if n == 0:
            return [jnp.zeros(ids.shape + (int(t.shape[1]),), t.dtype)
                    for t in tables]
        if any(t.ndim != 2 for t in tables):
            raise ValueError("fused_gather needs 2-D tables")
        if interpret is None:
            interpret = pallas_interpret()
        k = len(tables)
        # jnp.take parity, including its LOUD out-of-range mode: ids in
        # [-H, H) wrap-then-gather; anything else DMAs a clamped edge
        # row but the output row is NaN-filled below (float tables) —
        # ids come from user feed data, and a data bug must fail the
        # same way on both paths (the PR-7 NaN sentinel fires instead
        # of silently training a clamped row)
        idx_args, valids = [], []
        for t in tables:
            h = int(t.shape[0])
            w = jnp.where(flat < 0, flat + h, flat)
            idx_args.append(jnp.clip(w, 0, h - 1).astype(jnp.int32))
            valids.append((flat >= -h) & (flat < h))

        def table_spec(t_pos, width):
            def imap(i, *idx):
                return (idx[t_pos][i], 0)
            return pl.BlockSpec((1, width), imap)

        def out_spec(width):
            return pl.BlockSpec((1, width), lambda i, *idx: (i, 0))

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=k,
            grid=(n,),
            in_specs=[table_spec(t, int(tb.shape[1]))
                      for t, tb in enumerate(tables)],
            out_specs=[out_spec(int(tb.shape[1])) for tb in tables],
        )
        outs = pl.pallas_call(
            functools.partial(_gather_kernel, k=k),
            name="fused_gather",
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((n, int(t.shape[1])), t.dtype)
                       for t in tables],
            interpret=interpret,
        )(*idx_args, *tables)
        outs = outs if isinstance(outs, (list, tuple)) else [outs]
        filled = []
        for o, t, valid in zip(outs, tables, valids):
            if jnp.issubdtype(t.dtype, jnp.inexact):
                o = jnp.where(valid[:, None], o,
                              jnp.asarray(jnp.nan, t.dtype))
            filled.append(o.reshape(ids.shape + (int(t.shape[1]),)))
        _count("gather_launches")
        return filled
    except Exception:
        _count("gather_fallbacks")
        return None


# ---------------------------------------------------------------------------
# fused row-wise lazy optimizer update
# ---------------------------------------------------------------------------

def _update_kernel(r_ref, first_ref, last_ref, scal_ref, v_ref, *refs,
                   k: int, math_fn):
    """Grid = one sorted id position per step.  Duplicate rows accumulate
    into VMEM scratch; the segment's last position applies ``math_fn`` and
    writes the row's new param/moment blocks (aliased in place)."""
    del r_ref  # consumed by the index maps only
    i = pl.program_id(0)
    acc = refs[2 * k]

    @pl.when(first_ref[i] == 1)
    def _start():
        acc[:] = v_ref[:].astype(jnp.float32)

    @pl.when(first_ref[i] == 0)
    def _accumulate():
        acc[:] = acc[:] + v_ref[:].astype(jnp.float32)

    @pl.when(last_ref[i] == 1)
    def _apply():
        math_fn(acc[:], scal_ref, refs[:k], refs[k:2 * k])


def _rowwise_update(sr, tables, scalars, math_fn, interpret=None):
    """Run ``math_fn`` once per unique row of ``sr`` over ``tables`` in a
    single Pallas launch; returns the updated tables (same order).

    ``scalars``: 1-D f32 array of traced step scalars (lr, ...), SMEM-
    resident.  ``math_fn(g_sum, scal_ref, in_refs, out_refs)`` reads the
    merged f32 gradient row plus the tables' current rows and writes every
    output row (all tables share the [H, D] row shape of the values)."""
    rows, vals = sr.rows, sr.values
    n = int(rows.shape[0])
    if n == 0:
        return list(tables)
    if interpret is None:
        interpret = pallas_interpret()
    d = int(vals.shape[1])
    h = int(sr.height)
    k = len(tables)
    # negative ids wrap (numpy/.at[] convention, same as fused_gather);
    # above-range ids clamp.  Program-produced ids are always in range —
    # this is belt-and-braces so a malformed id can at worst touch an
    # edge row, never fault the kernel.  Canonicalize BEFORE sorting:
    # ids that wrap onto the same row must land in ONE duplicate run
    # (exact accumulation), and sorted canonical rows keep the block
    # indices monotonic — the property the in-place aliasing relies on.
    rows = jnp.clip(jnp.where(rows < 0, rows + h, rows), 0, h - 1)
    r, v, first, last = _sorted_segments(rows, vals)

    row_spec = pl.BlockSpec((1, d), lambda i, r, f, l: (r[i], 0))
    slot_spec = pl.BlockSpec((1, d), lambda i, r, f, l: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),  # scalars
                  slot_spec] + [row_spec] * k,
        out_specs=[row_spec] * k,
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
    )
    # alias each table onto its output; operand numbering includes the 3
    # scalar-prefetch args + scalars + v ahead of the tables
    aliases = {5 + t: t for t in range(k)}
    outs = pl.pallas_call(
        functools.partial(_update_kernel, k=k, math_fn=math_fn),
        name="fused_sparse_update",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((h, d), t.dtype) for t in tables],
        input_output_aliases=aliases,
        interpret=interpret,
    )(r, first, last, scalars.astype(jnp.float32).reshape(-1), v, *tables)
    return list(outs) if isinstance(outs, (list, tuple)) else [outs]


def _eligible(sr, tables):
    """The fused update reproduces the sorted reference bit-for-bit only
    when the merge and the moment math both run in f32 (the production
    embedding configuration); anything else falls back, counted."""
    if getattr(sr, "merged", False):
        return False  # sentinel-padded input: the sorted path owns it
    if sr.values.ndim != 2 or sr.values.dtype != jnp.float32:
        return False
    return all(t.ndim == 2 and t.shape[1] == sr.values.shape[1]
               for t in tables)


def _f32(x):
    return jnp.float32(x)


def _adam_math(g, scal_ref, ins, outs, *, beta1, beta2, eps):
    p_ref, m1_ref, m2_ref = ins
    po_ref, m1o_ref, m2o_ref = outs
    b1, b2, e = _f32(beta1), _f32(beta2), _f32(eps)
    one = _f32(1.0)
    m1n = b1 * m1_ref[:] + (one - b1) * g
    m2n = b2 * m2_ref[:] + (one - b2) * g * g
    step = scal_ref[0] * m1n / (jnp.sqrt(m2n) + e)
    po_ref[:] = (p_ref[:].astype(jnp.float32) - step).astype(po_ref.dtype)
    m1o_ref[:] = m1n
    m2o_ref[:] = m2n


def fused_adam(p, m1, m2, sr, lr_eff, beta1, beta2, eps):
    """Lazy sparse Adam in one launch: returns (p', m1', m2') or None.
    ``lr_eff`` is the bias-corrected step scalar the sorted path uses."""
    if not _eligible(sr, (m1, m2)) or m1.dtype != jnp.float32 \
            or m2.dtype != jnp.float32:
        _count("update_fallbacks")
        return None
    try:
        math = functools.partial(_adam_math, beta1=float(beta1),
                                 beta2=float(beta2), eps=float(eps))
        scal = jnp.reshape(lr_eff, (1,))
        p2, m1n, m2n = _rowwise_update(sr, [p, m1, m2], scal, math)
        _count("update_launches")
        return p2, m1n, m2n
    except Exception:
        _count("update_fallbacks")
        return None


def _momentum_math(g, scal_ref, ins, outs, *, mu, nesterov):
    p_ref, v_ref = ins
    po_ref, vo_ref = outs
    muf = _f32(mu)
    v_new = muf * v_ref[:] + g
    if nesterov:
        p_new = p_ref[:].astype(jnp.float32) - (g + muf * v_new) * scal_ref[0]
    else:
        p_new = p_ref[:].astype(jnp.float32) - scal_ref[0] * v_new
    po_ref[:] = p_new.astype(po_ref.dtype)
    vo_ref[:] = v_new


def fused_momentum(p, velocity, sr, lr, mu, nesterov):
    """Lazy sparse momentum in one launch: (p', velocity') or None."""
    if not _eligible(sr, (velocity,)) or velocity.dtype != jnp.float32:
        _count("update_fallbacks")
        return None
    try:
        math = functools.partial(_momentum_math, mu=float(mu),
                                 nesterov=bool(nesterov))
        scal = jnp.reshape(lr, (1,))
        p2, v2 = _rowwise_update(sr, [p, velocity], scal, math)
        _count("update_launches")
        return p2, v2
    except Exception:
        _count("update_fallbacks")
        return None


def _adagrad_math(g, scal_ref, ins, outs, *, eps):
    p_ref, mom_ref = ins
    po_ref, momo_ref = outs
    mom_new = mom_ref[:] + g * g
    step = scal_ref[0] * g / (jnp.sqrt(mom_new) + _f32(eps))
    po_ref[:] = (p_ref[:].astype(jnp.float32) - step).astype(po_ref.dtype)
    momo_ref[:] = mom_new


def fused_adagrad(p, moment, sr, lr, eps):
    """Lazy sparse adagrad in one launch: (p', moment') or None."""
    if not _eligible(sr, (moment,)) or moment.dtype != jnp.float32:
        _count("update_fallbacks")
        return None
    try:
        math = functools.partial(_adagrad_math, eps=float(eps))
        scal = jnp.reshape(lr, (1,))
        p2, mom2 = _rowwise_update(sr, [p, moment], scal, math)
        _count("update_launches")
        return p2, mom2
    except Exception:
        _count("update_fallbacks")
        return None


# ---------------------------------------------------------------------------
# block-level lookup_table gather fusion (used by core/lowering.py)
# ---------------------------------------------------------------------------

class LookupFusion:
    """Peephole plan for a block: groups of ``lookup_table`` ops that share
    one Ids input (the DeepFM shape — k tables gathered over the same id
    batch per step) are lowered through ONE ``fused_gather`` launch.

    Built by ``plan_lookup_fusion``; ``core/lowering.py`` consults
    ``covers(pos)`` per op and calls ``lower(pos, env)`` — which fills the
    whole group's outputs into ``env`` at its first member and returns
    True, or returns False (counted) to let every member lower normally."""

    def __init__(self, groups):
        # groups: list of [(pos, op), ...]; positions are block-op indices
        self._by_pos = {}
        self._groups = groups
        for g in groups:
            for pos, _ in g:
                self._by_pos[pos] = g
        self._done = {}   # id(group) -> {out_name: value} or None (dead)

    def covers(self, pos: int) -> bool:
        return pos in self._by_pos

    def lower(self, pos: int, env: dict) -> bool:
        group = self._by_pos[pos]
        key = id(group)
        if key not in self._done:
            self._done[key] = self._lower_group(group, env)
        outs = self._done[key]
        if outs is None:
            return False
        _, op = next(p for p in group if p[0] == pos)
        out_name = op.outputs["Out"][0]
        env[out_name] = outs[out_name]
        return True

    def _lower_group(self, group, env):
        try:
            ids_name = group[0][1].inputs["Ids"][0]
            w_names = [op.inputs["W"][0] for _, op in group]
            if ids_name not in env or any(w not in env for w in w_names):
                raise KeyError("fusion inputs not lowered yet")
            ids = env[ids_name]
            squeeze_last = ids.ndim >= 2 and ids.shape[-1] == 1
            if squeeze_last:
                ids = ids.squeeze(-1)
            gathered = fused_gather([env[w] for w in w_names], ids)
            if gathered is None:
                return None
            outs = {}
            for (pos, op), out in zip(group, gathered):
                pad = op.attrs.get("padding_idx", -1)
                if pad is not None and pad != -1:
                    mask = (ids != pad)[..., None].astype(out.dtype)
                    out = out * mask
                outs[op.outputs["Out"][0]] = out
            return outs
        except Exception:
            _count("gather_fallbacks")
            return None


def plan_lookup_fusion(block):
    """Scan ``block`` for fusable ``lookup_table`` groups; returns a
    ``LookupFusion`` or None.  Only sparse-gradient lookups are grouped
    (the dense-table path is not the bottleneck this kernel exists for),
    and only groups of >= 2 sharing the same Ids var — a lone gather gains
    nothing from a fused launch."""
    if not fused_enabled():
        return None
    by_ids = {}
    for pos, op in enumerate(block.ops):
        if op.type != "lookup_table" or not op.attrs.get("is_sparse"):
            continue
        if not op.inputs.get("W") or not op.inputs.get("Ids"):
            continue
        w = op.inputs["W"]
        ids = op.inputs["Ids"]
        if len(w) != 1 or len(ids) != 1:
            continue
        by_ids.setdefault(ids[0], []).append((pos, op))
    groups = []
    for ids_name, g in by_ids.items():
        if len(g) < 2:
            continue
        # hoisting later members' table reads to the first member's
        # position is only sound if nothing BETWEEN the members writes a
        # grouped table or the Ids var — else the fused gather would read
        # stale values the per-op lowering would not.  Clobbered groups
        # fall back to per-op gathers (flag-off-identical semantics)
        member_pos = {pos for pos, _ in g}
        hazard = {ids_name} | {op.inputs["W"][0] for _, op in g}
        lo, hi = g[0][0], g[-1][0]
        clobbered = any(
            pos not in member_pos
            and any(n in hazard for n in block.ops[pos].output_arg_names())
            for pos in range(lo + 1, hi))
        if not clobbered:
            groups.append(g)
    return LookupFusion(groups) if groups else None
