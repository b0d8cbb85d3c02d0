"""Pipeline drivers: scan-mode (run_steps) and slot-clocked concurrent.

Two execution modes over a transpiled :class:`PipelineProgram`:

- **scan mode** (default): GPipe semantics on the executor's existing
  ``run_steps`` scan machinery — each stage's forward runs its M
  microbatches as ONE ``lax.scan`` dispatch (microbatch = scan step),
  boundary activations travel between stages as stacked ``[M, ...]``
  arrays, backwards run in reverse stage order, and each stage's
  optimizer block runs once on the accumulated mean gradient.  This is
  the numerics-reference path (bit-comparable to the single-process
  run) and the lowest-dispatch-overhead sequential execution.

- **concurrent slot mode**: one worker thread per stage (each optionally
  pinned to its own device), stepping a GPipe or 1F1B slot grid
  (pipeline/schedule.py) with a barrier per slot.  Stages genuinely
  overlap — the measured per-stage busy time vs wall time yields the
  real bubble fraction and per-stage utilization, exported through the
  observability plane (``pipeline.*`` gauges + the ``pipeline`` debug
  page).  Boundary tensors move through an in-process store, or via
  collective permute on a dedicated ``pp`` mesh axis
  (``transport="permute"``, pipeline/permute.py).

Multi-host stages ride the striped RPC transport instead — see
pipeline/rpc.py.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from ..core.executor import Executor, Scope
from ..observability import debug_server as _debug_server
from ..observability import stats as _obs_stats
from . import schedule as _sched
from .transpiler import PipelineProgram

__all__ = ["PipelineTrainer", "StepResult"]

_pipe_metrics = None
_last_run_summary: Dict[str, object] = {}


def _pm():
    """Cached pipeline metric handles (see executor._em)."""
    global _pipe_metrics
    m = _pipe_metrics
    if m is None:
        import types as _t
        sc = _obs_stats.scope("pipeline")
        m = _t.SimpleNamespace(
            scope=sc,
            steps=sc.counter("steps"),
            microbatches=sc.counter("microbatches"),
            bubble=sc.gauge(
                "bubble_fraction",
                "measured idle fraction of the last concurrent pipeline "
                "step: 1 - sum(stage busy)/(K * wall)"),
            bubble_slots=sc.gauge(
                "bubble_fraction_slots",
                "schedule-level bubble of the last step's slot grid "
                "(equals (K-1)/(M+K-1) for GPipe)"),
        )
        _pipe_metrics = m
    return m


def _pipeline_statusz() -> dict:
    return dict(_last_run_summary)


_debug_server.register_provider("pipeline", _pipeline_statusz)


@dataclass
class StepResult:
    """One minibatch through the pipeline."""

    loss: Optional[float]
    microbatch_losses: Optional[np.ndarray]
    wall_ms: float
    schedule: str
    mode: str                      # "scan" | "slots"
    bubble_fraction: Optional[float] = None        # measured (slots mode)
    bubble_fraction_slots: Optional[float] = None  # schedule-level
    stage_utilization: List[float] = field(default_factory=list)
    stage_busy_ms: List[float] = field(default_factory=list)
    stage_activation_bytes: List[int] = field(default_factory=list)


class _StageExecutor(Executor):
    """Executor pinned to one device (pipeline stage placement): feeds,
    state and rng are committed to the stage's device so the jitted
    stage programs execute there, letting stages overlap."""

    def __init__(self, device=None):
        super().__init__()
        self._device = device

    def _place(self, v):
        if self._device is None:
            return v
        import jax
        return jax.device_put(v, self._device)

    def _put_feed(self, arr):
        return self._place(arr)

    def _put_rng(self, rng):
        return self._place(rng)

    def _put_state(self, name, val):
        return self._place(val)


def _make_stage_parallel_executor(build_strategy, stage_program):
    """Stage executor for the pp×dp(×ZeRO) composition (SCAN mode
    only — PipelineTrainer.run gates the rest): each stage's programs
    run as ONE sharded jit over a dp mesh — state sharded per the
    BuildStrategy (kReduce = ZeRO), microbatch feeds sharded along
    their WITHIN-microbatch batch axis.  Scan-mode feeds are stacked
    ``[M, batch, ...]``, so the batch axis is axis 1, not axis 0 (the
    plain ParallelExecutor convention); axis-0 sharding would partition
    the scan, which is wrong by construction."""
    from ..parallel.parallel_executor import ParallelExecutor

    class _StagePE(ParallelExecutor):
        def run(self, program=None, feed=None, fetch_list=None,
                scope=None, return_numpy=True, **kwargs):
            # Executor-shaped signature: the pipeline drivers call every
            # stage executor positionally as run(program, ...)
            return ParallelExecutor.run(
                self, fetch_list=fetch_list, feed=feed, program=program,
                scope=scope, return_numpy=return_numpy, **kwargs)

        def _put_feed(self, arr):
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            dp = self.mesh.shape[self._dp_axis]
            nd = getattr(arr, "ndim", 0)
            if nd >= 2 and arr.shape[1] % dp == 0 and arr.shape[1] > 0:
                spec = P(None, self._dp_axis, *([None] * (nd - 2)))
                return jax.device_put(arr, NamedSharding(self.mesh, spec))
            return jax.device_put(arr, self._replicated())

    return _StagePE(main_program=stage_program,
                    build_strategy=build_strategy)


class PipelineTrainer:
    """Drive a transpiled pipeline for training steps.

    ``devices``: one jax device per stage enables the concurrent slot
    mode (stages genuinely overlap); without devices the scan mode runs
    everything sequentially on the default device.  ``transport``:
    ``"local"`` (in-process store / device-to-device put) or
    ``"permute"`` (collective permute over a ``pp`` mesh axis — requires
    ``devices`` and adjacent-only boundaries).  ``schedule`` may be
    reassigned between steps (``tr.schedule = "1f1b"``): it only orders
    the slot grid, the numerics and compiled executables are identical.
    """

    def __init__(self, pipeline_program: PipelineProgram,
                 schedule: str = "gpipe",
                 devices: Optional[List] = None,
                 concurrent: Optional[bool] = None,
                 transport: str = "local",
                 parallel=None):
        self.pp = pipeline_program
        self.K = pipeline_program.num_stages
        self.M = pipeline_program.num_microbatches
        # pp×dp(×ZeRO) composition: a BuildStrategy turns every stage
        # executor into a dp-mesh ParallelExecutor (state sharded per
        # reduce_strategy — kReduce is the ZeRO cell of the reshard
        # matrix); scan/sequential modes only, the slot runner pins
        # stages to single devices instead
        self.parallel = parallel
        if parallel is not None and devices is not None:
            raise ValueError(
                "parallel= (dp mesh per stage) and devices= (one device "
                "per stage) are mutually exclusive stage placements")
        if schedule not in ("gpipe", "1f1b", "one_f_one_b"):
            raise ValueError(f"unknown schedule {schedule!r}")
        self.schedule = "1f1b" if schedule == "one_f_one_b" else schedule
        if devices is not None and len(devices) < self.K:
            raise ValueError(
                f"{self.K} stages need {self.K} devices, got "
                f"{len(devices)}")
        self.devices = list(devices)[:self.K] if devices else None
        self.concurrent = (bool(concurrent) if concurrent is not None
                           else self.devices is not None)
        if transport not in ("local", "permute"):
            raise ValueError(f"unknown transport {transport!r}")
        if transport == "permute":
            if not self.devices:
                raise ValueError("transport='permute' needs per-stage "
                                 "devices (the pp mesh axis)")
            if not pipeline_program.adjacent_only():
                raise ValueError(
                    "transport='permute' requires adjacent-only stage "
                    "boundaries (every send crosses one hop); this "
                    "pipeline has skip boundaries — use the local or "
                    "RPC transport")
        self.transport = transport
        if self.parallel is not None:
            self.executors = [
                _make_stage_parallel_executor(self.parallel,
                                              st.fwd_program)
                for st in self.pp.stages]
        else:
            self.executors = [
                _StageExecutor(self.devices[s] if self.devices else None)
                for s in range(self.K)]
        self.scopes = [Scope() for _ in range(self.K)]
        self._initialized = False

    # -- lifecycle ---------------------------------------------------------
    def init(self) -> "PipelineTrainer":
        """Run every stage's startup program (named initializer draws
        make the union of stage scopes bit-identical to the
        single-process init)."""
        for st, exe, scope in zip(self.pp.stages, self.executors,
                                  self.scopes):
            exe.run(st.startup_program, scope=scope)
        self._initialized = True
        return self

    def state_dict(self) -> Dict[str, np.ndarray]:
        """All persistable stage state (params, moments, accumulators,
        LR counters) as host arrays, stage scopes merged."""
        out: Dict[str, np.ndarray] = {}
        for scope in self.scopes:
            for name in scope.local_names():
                out[name] = np.asarray(scope.find_var(name))
        return out

    # -- sharded checkpoints (paddle_tpu/checkpoint/) ----------------------
    def _stage_persist_names(self, k: int) -> List[str]:
        """Persistable vars a stage owns: declared persistable in any of
        its programs AND present in its scope (grad @ACC accumulators
        only exist after the first backward)."""
        from ..core.executor import RNG_STATE_VAR
        st = self.pp.stages[k]
        progs = [st.startup_program, st.fwd_program, st.bwd_program,
                 st.opt_program]
        names = set()
        for p in progs:
            if p is None:
                continue
            for v in p.global_block.vars.values():
                if v.persistable and v.name != RNG_STATE_VAR:
                    names.add(v.name)
        scope = self.scopes[k]
        return sorted(n for n in names if scope.find_var(n) is not None)

    def save_checkpoint(self, root: str, step: int,
                        commit: bool = True) -> bool:
        """Write one checkpoint piece per stage (writer ``stage<k>``)
        and two-phase commit the step.  Pipeline sharding partitions the
        VAR SET, not rows — each stage's vars are whole shards, and vars
        replicated across stages (the LR closure every optimizing stage
        carries) are marked replicated so any stage's copy restores
        them.  The manifest is topology-independent: restore onto a
        different stage count or a plain single host re-shards from the
        same files (``checkpoint.restore_scope`` / ``load_vars``)."""
        from .. import checkpoint as _ckpt
        per_stage = [self._stage_persist_names(k) for k in range(self.K)]
        count: Dict[str, int] = {}
        for names in per_stage:
            for n in names:
                count[n] = count.get(n, 0) + 1
        writers = [f"stage{k}" for k in range(self.K)]
        topo = {"kind": "pipeline", "pp": self.K,
                "schedule": self.schedule}
        if self.parallel is not None:
            topo["dp_mesh"] = dict(self.parallel.mesh_shape or {})
            from ..parallel.strategy import ReduceStrategy
            topo["zero"] = (self.parallel.reduce_strategy
                            == ReduceStrategy.kReduce)
        for k, names in enumerate(per_stage):
            scope = self.scopes[k]
            arrays, extents = {}, {}
            for n in names:
                arr = np.asarray(scope.find_var(n))
                arrays[n] = arr
                if count[n] > 1:
                    # stage-replicated (LR closure): identical
                    # deterministic evolution on every stage
                    extents[n] = {"var": n, "offset": None, "rows": None,
                                  "global_shape": list(arr.shape)}
            _ckpt.write_piece(root, step, f"stage{k}", arrays,
                              extents=extents, topology=topo,
                              expected_writers=writers)
        if commit:
            return _ckpt.try_commit(root, step, writers)
        return False

    def restore_checkpoint(self, root: str, step: Optional[int] = None,
                           verify: bool = True) -> int:
        """Hydrate every stage scope from the newest (or given) COMPLETE
        step — written by ANY topology (a different stage count, a
        plain single-host save, a pserver fleet).  Restored values are
        re-placed by each stage executor on its next dispatch."""
        from .. import checkpoint as _ckpt
        if step is None:
            step = _ckpt.latest_complete_step(root)
            if step is None:
                raise _ckpt.CheckpointError(
                    f"no COMPLETE checkpoint step under {root!r}")
        from .transpiler import ACC_SUFFIX
        man = _ckpt.load_manifest(root, step)
        have = man.vars()
        for k in range(self.K):
            names = self._stage_persist_names(k)
            # <grad>@ACC microbatch accumulators are pipeline-transpiler
            # transients, zeroed between minibatches: a checkpoint from a
            # NON-pipeline topology legitimately lacks them — keep the
            # startup zeros.  Anything else missing is a real hole.
            missing = [n for n in names if n not in have
                       and not n.endswith(ACC_SUFFIX)]
            if missing:
                raise _ckpt.CheckpointError(
                    f"checkpoint step {step} is missing stage {k} "
                    f"persistable vars {missing[:8]}")
            names = [n for n in names if n in have]
            vals = _ckpt.load_vars(root, step,
                                   {n: (None, None) for n in names},
                                   verify=verify)
            scope = self.scopes[k]
            for n, v in vals.items():
                scope.set_var(n, v)
            placed = getattr(self.executors[k], "_placed", None)
            if placed is not None:
                placed.clear()
        return step

    # -- feed plumbing -----------------------------------------------------
    def _split_feed(self, feed: Dict[str, object]):
        from .transpiler import split_microbatches
        return split_microbatches(feed, self.M)

    # -- public API --------------------------------------------------------
    def run(self, feed: Dict[str, object],
            mode: Optional[str] = None) -> StepResult:
        """One minibatch.  ``mode``: None = auto (slots when concurrent,
        else scan), or force ``"scan"`` / ``"slots"`` /
        ``"sequential"`` (the naive per-microbatch stage-by-stage
        baseline the bench compares against)."""
        if not self._initialized:
            raise RuntimeError("call PipelineTrainer.init() first")
        if mode is None:
            mode = "slots" if self.concurrent else "scan"
        if self.parallel is not None and mode != "scan":
            # sequential mode feeds per-microbatch [batch, ...] arrays
            # whose axis 1 is a FEATURE axis — the stage PE's scan-
            # stacked feed sharding would partition the wrong axis; and
            # the slot runner wants one device per stage, not a mesh
            raise ValueError(
                "parallel= stage composition supports scan mode only "
                f"(got mode={mode!r})")
        t0 = time.perf_counter()
        if mode == "slots":
            res = self._run_slots(feed)
        elif mode == "scan":
            res = self._run_scan(feed)
        elif mode == "sequential":
            res = self._run_sequential(feed)
        else:
            raise ValueError(f"unknown run mode {mode!r}")
        res.wall_ms = (time.perf_counter() - t0) * 1e3
        self._record(res, feed)
        return res

    def _record(self, res: StepResult, feed) -> None:
        m = _pm()
        m.steps.inc()
        m.microbatches.inc(self.M)
        if res.bubble_fraction is not None:
            m.bubble.set(res.bubble_fraction)
        if res.bubble_fraction_slots is not None:
            m.bubble_slots.set(res.bubble_fraction_slots)
        mb = next((np.asarray(v).shape[0] // self.M
                   for v in feed.values()
                   if np.asarray(v).ndim >= 1), 1)
        res.stage_activation_bytes = [
            st.activation_bytes(mb) for st in self.pp.stages]
        for s in range(self.K):
            m.scope.gauge(f"stage_activation_bytes.s{s}").set(
                res.stage_activation_bytes[s])
            if res.stage_utilization:
                m.scope.gauge(f"stage_utilization.s{s}").set(
                    res.stage_utilization[s])
        _last_run_summary.update({
            "schedule": res.schedule, "mode": res.mode,
            "num_stages": self.K, "num_microbatches": self.M,
            "transport": self.transport,
            "wall_ms": round(res.wall_ms, 3),
            "bubble_fraction": res.bubble_fraction,
            "bubble_fraction_slots": res.bubble_fraction_slots,
            "gpipe_bubble_bound": _sched.gpipe_bubble_bound(self.K,
                                                            self.M),
            "stage_utilization": [round(u, 4)
                                  for u in res.stage_utilization],
            "stage_activation_bytes": res.stage_activation_bytes,
        })

    # -- scan mode (sequential GPipe on run_steps) -------------------------
    def _run_scan(self, feed) -> StepResult:
        pp = self.pp
        stacked, _ = self._split_feed(feed)
        acts: Dict[str, np.ndarray] = {}
        for st, exe, scope in zip(pp.stages, self.executors, self.scopes):
            sfeed = {n: stacked[n] for n in st.fwd_feeds}
            sfeed.update({n: acts[n] for n in st.recv_acts_fwd})
            outs = exe.run_steps(st.fwd_program, feed=sfeed,
                                 fetch_list=st.fwd_fetches, scope=scope)
            acts.update(zip(st.fwd_fetches, outs))
        grads: Dict[str, np.ndarray] = {}
        for st, exe, scope in zip(reversed(pp.stages),
                                  reversed(self.executors),
                                  reversed(self.scopes)):
            bfeed = {n: acts[n] for n in st.stash}
            bfeed.update({n: acts[n] for n in st.recv_acts_bwd})
            bfeed.update({n: stacked[n] for n in st.bwd_feeds})
            bfeed.update({n: grads[n] for n in st.recv_grads})
            outs = exe.run_steps(st.bwd_program, feed=bfeed,
                                 fetch_list=st.bwd_fetches, scope=scope)
            grads.update(zip(st.bwd_fetches, outs))
        for st, exe, scope in zip(pp.stages, self.executors, self.scopes):
            if st.opt_program is not None:
                exe.run(st.opt_program, scope=scope)
        mb_losses = None
        loss = None
        if pp.loss_name and pp.loss_name in acts:
            mb_losses = np.asarray(acts[pp.loss_name]).reshape(self.M)
            loss = float(mb_losses.mean())
        return StepResult(loss=loss, microbatch_losses=mb_losses,
                          wall_ms=0.0, schedule=self.schedule,
                          mode="scan")

    # -- naive sequential baseline -----------------------------------------
    def _run_sequential(self, feed) -> StepResult:
        """Naive sequential stage execution: every microbatch's forward
        and backward dispatched stage by stage on ONE thread, no
        overlap, no scan amortization — the baseline the pipeline
        schedules are measured against."""
        pp, M = self.pp, self.M
        _, per_mb = self._split_feed(feed)
        acts: Dict[tuple, np.ndarray] = {}
        mb_losses = np.zeros(M, dtype=np.float64)
        for m in range(M):
            for st, exe, scope in zip(pp.stages, self.executors,
                                      self.scopes):
                sfeed = {n: per_mb[m][n] for n in st.fwd_feeds}
                sfeed.update({n: acts[(n, m)] for n in st.recv_acts_fwd})
                outs = exe.run(st.fwd_program, feed=sfeed,
                               fetch_list=st.fwd_fetches, scope=scope,
                               sync=True)
                for n, v in zip(st.fwd_fetches, outs):
                    acts[(n, m)] = v
                if st.idx == self.K - 1 and pp.loss_name:
                    mb_losses[m] = float(np.asarray(
                        outs[st.fwd_fetches.index(pp.loss_name)]))
        grads: Dict[tuple, np.ndarray] = {}
        for m in range(M):
            for st, exe, scope in zip(reversed(pp.stages),
                                      reversed(self.executors),
                                      reversed(self.scopes)):
                bfeed = {n: per_mb[m][n] for n in st.bwd_feeds}
                for n in st.stash + st.recv_acts_bwd:
                    bfeed[n] = acts[(n, m)]
                for n in st.recv_grads:
                    bfeed[n] = grads[(n, m)]
                outs = exe.run(st.bwd_program, feed=bfeed,
                               fetch_list=st.bwd_fetches, scope=scope,
                               sync=True)
                for n, v in zip(st.bwd_fetches, outs):
                    grads[(n, m)] = v
        for st, exe, scope in zip(pp.stages, self.executors, self.scopes):
            if st.opt_program is not None:
                exe.run(st.opt_program, scope=scope, sync=True)
        loss = float(mb_losses.mean()) if pp.loss_name else None
        return StepResult(loss=loss, microbatch_losses=mb_losses.copy(),
                          wall_ms=0.0, schedule=self.schedule,
                          mode="sequential")

    # -- concurrent slot mode ----------------------------------------------
    def _run_slots(self, feed) -> StepResult:
        pp, K, M = self.pp, self.K, self.M
        orders = _sched.stage_orders(self.schedule, K, M)
        _sched.validate_orders(orders, M)
        grid = _sched.simulate_slots(orders)
        _, per_mb = self._split_feed(feed)

        if self.transport == "permute":
            from .permute import PermuteTransport
            store = PermuteTransport(K, self.devices)
        else:
            store = _LocalTransport()
        barrier = threading.Barrier(K, action=store.end_slot)
        busy = [0.0] * K
        mb_losses = np.zeros(M, dtype=np.float64)
        errors: List[BaseException] = []

        def worker(s: int) -> None:
            st = pp.stages[s]
            exe, scope = self.executors[s], self.scopes[s]
            retained: Dict[tuple, np.ndarray] = {}
            try:
                for row in grid:
                    action = row[s]
                    if action is not None and not errors:
                        kind, m = action
                        t0 = time.perf_counter()
                        if kind == "F":
                            sfeed = {n: per_mb[m][n] for n in st.fwd_feeds}
                            for n in st.recv_acts:
                                v = store.get("act", n, m, s)
                                if n in st.recv_acts_fwd:
                                    sfeed[n] = v
                                if n in st.recv_acts_bwd:
                                    retained[(n, m)] = v
                            outs = exe.run(st.fwd_program, feed=sfeed,
                                           fetch_list=st.fwd_fetches,
                                           scope=scope, sync=True)
                            vals = dict(zip(st.fwd_fetches, outs))
                            for n in st.stash:
                                retained[(n, m)] = vals[n]
                            for n, dsts in st.send_acts.items():
                                store.put("act", n, m, vals[n], s, dsts)
                            if s == K - 1 and pp.loss_name:
                                mb_losses[m] = float(
                                    np.asarray(vals[pp.loss_name]))
                        else:
                            bfeed = {n: per_mb[m][n] for n in st.bwd_feeds}
                            for n in st.stash + st.recv_acts_bwd:
                                bfeed[n] = retained.pop((n, m))
                            for n in st.recv_grads:
                                bfeed[n] = store.get("grad", n, m, s)
                            outs = exe.run(st.bwd_program, feed=bfeed,
                                           fetch_list=st.bwd_fetches,
                                           scope=scope, sync=True)
                            vals = dict(zip(st.bwd_fetches, outs))
                            for n, dsts in st.send_grads.items():
                                store.put("grad", n, m, vals[n], s, dsts)
                        busy[s] += time.perf_counter() - t0
                    barrier.wait()
                if st.opt_program is not None and not errors:
                    exe.run(st.opt_program, scope=scope, sync=True)
            except BaseException as e:  # noqa: BLE001 - surfaced below
                errors.append(e)
                barrier.abort()

        t0 = time.perf_counter()
        threads = [threading.Thread(target=worker, args=(s,), daemon=True)
                   for s in range(K)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            # a worker failure aborts the barrier; peers then raise
            # BrokenBarrierError — surface the root cause, not the echo
            real = [e for e in errors
                    if not isinstance(e, threading.BrokenBarrierError)]
            raise (real or errors)[0]
        wall = time.perf_counter() - t0
        util = [b / wall if wall > 0 else 0.0 for b in busy]
        loss = (float(mb_losses.mean())
                if pp.loss_name and K >= 1 else None)
        return StepResult(
            loss=loss, microbatch_losses=mb_losses.copy(), wall_ms=0.0,
            schedule=self.schedule, mode="slots",
            bubble_fraction=max(0.0, 1.0 - sum(busy) / (K * wall))
            if wall > 0 else None,
            bubble_fraction_slots=_sched.slot_bubble_fraction(grid),
            stage_utilization=util,
            stage_busy_ms=[b * 1e3 for b in busy])


class _LocalTransport:
    """In-process boundary store for the slot runner: producers write
    during their slot, consumers read in a later slot (the per-slot
    barrier is the happens-before edge)."""

    def __init__(self):
        self._store: Dict[tuple, object] = {}

    def put(self, kind, name, m, value, src, dsts) -> None:
        self._store[(kind, name, int(m))] = value

    def get(self, kind, name, m, dst):
        try:
            return self._store[(kind, name, int(m))]
        except KeyError:
            raise RuntimeError(
                f"stage {dst} expected {kind} {name!r} (microbatch {m}) "
                "before its producer ran — schedule dependency bug"
            ) from None

    def end_slot(self) -> None:
        pass
