"""ParallelExecutor: data-/model-parallel program execution over a device
mesh.

Reference: ``paddle/fluid/framework/parallel_executor.cc:58`` +
``details/multi_devices_graph_pass.cc`` + ``python/paddle/fluid/
parallel_executor.py:32``.  The reference replicates the op graph per GPU,
inserts NCCL AllReduce op-handles per (param, grad) pair, and interprets the
SSA graph with a thread pool.

TPU-native redesign: the *same single program* is lowered once (see
core/lowering.py) and jitted over a ``jax.sharding.Mesh``:

- feeds arrive batch-sharded along the ``dp`` mesh axis (the reference's
  per-device feed split, ``parallel_executor.py:169``); a batch that does
  not divide the dp axis (a dataset's last partial batch — the reference's
  DataBalanceOpHandle case) falls back to replicated placement;
- parameters/optimizer state are device_put with replicated (kAllReduce) or
  dp-sharded (kReduce ≙ ZeRO) shardings — placement once, kept resident
  across steps via buffer donation (the BCastParamsToDevices analogue,
  ``parallel_executor.cc:180``);
- GSPMD partitions the computation and inserts all-reduce / reduce-scatter /
  all-gather collectives over ICI — everything
  ``details/all_reduce_op_handle.cc`` and friends did by hand;
- ``BuildStrategy.sharding_rules`` optionally shard parameters over an
  ``mp`` axis (tensor parallelism — a capability beyond the 2018 reference,
  SURVEY.md §7);
- ``GradientScaleStrategy`` is honored by rewriting the loss-grad seed op
  (the ScaleLossGradOpHandle analogue): kCoeffNumDevice keeps the global
  mean; kOne multiplies the seed by the dp degree (grads sum, not average);
  kCustomized drops the seed op so the user feeds ``<loss>@GRAD``.

Multi-host: the same mesh spans hosts (``jax.distributed``); collectives ride
ICI/DCN — replacing the reference's gen_nccl_id + ncclCommInitRank world
(``operators/gen_nccl_id_op.cc:31``).
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.executor import Executor, Scope, global_scope
from ..core.program import OP_ROLE_ATTR, OpRole, Program, default_main_program
from ..core.backward import grad_var_name
from ..observability import audit as _audit
from ..observability import stats as _obs_stats
from ..observability import trace as _obs_trace
from ..observability.step_stats import approx_nbytes as _approx_nbytes
from .strategy import (
    BuildStrategy,
    ExecutionStrategy,
    GradientScaleStrategy,
    ReduceStrategy,
)


def make_mesh(mesh_shape: Optional[Dict[str, int]] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Device mesh = the NCCLContextMap analogue (platform/nccl_helper.h:81)."""
    devices = list(devices if devices is not None else jax.devices())
    if not mesh_shape:
        mesh_shape = {"dp": len(devices)}
    axes = list(mesh_shape)
    sizes = [mesh_shape[a] for a in axes]
    n = int(np.prod(sizes))
    assert n == len(devices), f"mesh {mesh_shape} needs {n} devices, have {len(devices)}"
    arr = np.asarray(devices).reshape(sizes)
    return Mesh(arr, axes)


class ParallelExecutor(Executor):
    """Data-parallel (+ optional tensor-parallel) program runner.

    Reuses Executor's plan/jit/cache/state machinery; only device placement
    (the hooks) differs.
    """

    def __init__(
        self,
        use_cuda: bool = True,            # parity arg; devices come from JAX
        loss_name: Optional[str] = None,
        main_program: Optional[Program] = None,
        share_vars_from: Optional["ParallelExecutor"] = None,
        exec_strategy: Optional[ExecutionStrategy] = None,
        build_strategy: Optional[BuildStrategy] = None,
        num_trainers: int = 1,
        trainer_id: int = 0,
        scope: Optional[Scope] = None,
        places: Optional[Sequence] = None,
    ):
        super().__init__()
        self._program = main_program or default_main_program()
        self._loss_name = loss_name
        self._build_strategy = build_strategy or BuildStrategy()
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._scope = scope or global_scope()
        if share_vars_from is not None:
            self._scope = share_vars_from._scope
        self.mesh = make_mesh(self._build_strategy.mesh_shape, places)
        self._dp_axis = "dp" if "dp" in self.mesh.axis_names else self.mesh.axis_names[0]
        self._placed: set = set()
        self._scaled_programs: Dict[int, Program] = {}
        self._padded_batch: Optional[int] = None
        self._trains_cache: Optional[bool] = None
        # multi-host: the mesh spans every process's devices (nccl2-mode
        # flat world, nccl_helper.h:105-120); each process contributes its
        # local slice of feeds/state via make_array_from_* below
        self._multiproc = jax.process_count() > 1
        # divergence sentinel (FLAGS_divergence_check): training steps
        # since the last parameter checksum
        self._div_step = 0

    # -- public API (reference parallel_executor.py:169 signature) ---------
    def run(self, fetch_list=None, feed=None, feed_dict=None,
            return_numpy: bool = True, program=None, scope=None, **kwargs):
        with _obs_trace.span("parallel_executor::run"):
            return self._run(fetch_list, feed, feed_dict, return_numpy,
                             program, scope)

    def _run(self, fetch_list, feed, feed_dict, return_numpy, program,
             scope):
        # ``program``/``scope`` kwargs: Executor._run_segmented (host-op
        # programs — send/recv/pserver IO) re-enters run() per device
        # segment, so the trainer-mesh + remote-pserver topology runs
        # each compute segment over THIS executor's mesh
        feed = feed if feed is not None else (feed_dict or {})
        if program is None:
            # padding policy belongs to the CONFIGURED program; segmented
            # re-entries (program=sub) receive already-padded feeds and a
            # foreign program must not inherit this one's batch policy
            feed, true_batch = self._maybe_pad_partial_batch(feed)
        else:
            true_batch = None
        outs = super().run(
            program=program if program is not None else self._program,
            feed=feed, fetch_list=fetch_list,
            scope=scope if scope is not None else self._scope,
            return_numpy=return_numpy)
        if true_batch is not None:
            # Slice off padding rows only from batch-aligned fetches: a
            # var whose program-declared leading dim is symbolic (-1 =
            # batch).  A weight/table coincidentally sized [pad_to, ...]
            # has a concrete declared leading dim and must not be cut.
            names = [v.name if hasattr(v, "name") else str(v)
                     for v in (fetch_list or [])]
            blk = self._program.global_block
            def _batch_aligned(name):
                var = blk.var_or_none(name)
                return var is not None and len(var.shape) >= 1 \
                    and var.shape[0] == -1
            outs = [o[:true_batch]
                    if getattr(o, "ndim", 0) >= 1
                    and o.shape[0] == self._padded_batch
                    and (i >= len(names) or _batch_aligned(names[i]))
                    else o
                    for i, o in enumerate(outs)]
        if program is None and _audit.enabled() and self._program_trains():
            self._maybe_param_checksum()
        return outs

    def _maybe_param_checksum(self) -> None:
        """Every ``FLAGS_divergence_param_steps`` training steps, fold
        one u64 checksum of the persistable parameters into the audit
        plane under the reserved ``__params__`` model, keyed by the
        step index — the STATS_PULL merge (or the supervisor's lease
        sweep) groups the checksums ACROSS DP replicas, so a replica
        whose state silently diverged (bad optimizer apply, SDC in a
        parameter shard) is NAMED within K steps.  Identical-state
        replicas checksum identically by construction: the walk is
        name-sorted over the same program on every host."""
        self._div_step += 1
        if self._div_step % _audit.param_steps():
            return
        import zlib
        h = 0
        scope = self._scope
        from ..distributed import faults as _faults
        for name in sorted(self._persist_names(self._program, scope)):
            val = scope.find_var(name)
            if val is None:
                continue
            arr = np.ascontiguousarray(self._fetch_to_numpy(val))
            # chaos site: perturb one element of the checksummed view
            # (device state untouched) so only THIS replica's checksum
            # moves — the injected-SDC drill for the training sentinel
            if _faults.active():
                nbits = _faults.corrupt_fault(f"param_shard@{name}",
                                              "param_shard")
                if nbits:
                    arr = _faults.corrupt_array(arr, nbits)
            h = zlib.crc32(name.encode(), h)
            h = zlib.crc32(arr.tobytes(), h)
        _audit.note_param_checksum(self._div_step, h)

    def _maybe_pad_partial_batch(self, feed):
        """Pad a last partial batch up to the dp multiple so the feeds
        stay dp-sharded (the reference rebalanced uneven batches across
        devices — details/data_balance_op_handle.cc; SPMD pads instead).

        Only for fetch-only programs (no optimize-role ops): padding rows
        through a training step would bias gradients, so those keep the
        replicated fallback.  Fetch rows belonging to padding are sliced
        off in run()."""
        dp = self.mesh.shape[self._dp_axis]
        batch_feeds = {k: v for k, v in feed.items()
                       if getattr(np.asarray(v), "ndim", 0) >= 1}
        sizes = {np.asarray(v).shape[0] for v in batch_feeds.values()}
        if len(sizes) != 1:
            return feed, None
        (b,) = sizes
        if b % dp == 0 or b == 0:
            return feed, None
        if self._program_trains():
            return feed, None
        pad_to = ((b + dp - 1) // dp) * dp
        padded = dict(feed)
        for k, v in batch_feeds.items():
            arr = np.asarray(v)
            reps = [(0, pad_to - b)] + [(0, 0)] * (arr.ndim - 1)
            # repeat the last row (keeps values in-distribution for ops
            # like softmax/CRF; padded rows are discarded on fetch)
            padded[k] = np.concatenate(
                [arr, np.repeat(arr[-1:], pad_to - b, axis=0)], axis=0)
        self._padded_batch = pad_to
        return padded, b

    def _program_trains(self) -> bool:
        if self._trains_cache is None:
            self._trains_cache = any(
                op.attr(OP_ROLE_ATTR, 0) & (OpRole.Optimize | OpRole.Backward)
                for op in self._program.global_block.ops)
        return self._trains_cache

    # -- telemetry ---------------------------------------------------------
    _pe_metrics = None

    def _post_step_telemetry(self, ss, plan, donated_state) -> None:
        """Mesh-level stats per dispatched step (called from Executor.run
        when FLAGS_runtime_stats is on).  SPMD runs every device in
        lockstep, so the host wall time IS the per-device step time."""
        m = ParallelExecutor._pe_metrics
        if m is None:
            import types as _t
            sc = _obs_stats.scope("parallel")
            m = _t.SimpleNamespace(
                steps=sc.counter("steps"),
                mesh_devices=sc.gauge("mesh_devices"),
                step=sc.histogram("device_step_ms"),
                allreduce_bytes=sc.counter(
                    "allreduce_bytes_est",
                    "upper-bound estimate of per-step dp collective "
                    "payload: total bytes of donated persistable state, "
                    "each updated from an all-reduced gradient/statistic"))
            ParallelExecutor._pe_metrics = m
        m.steps.inc()
        m.mesh_devices.set(self.mesh.size)
        m.step.observe(ss.wall_ms)
        if self._program_trains() and donated_state:
            m.allreduce_bytes.inc(sum(_approx_nbytes(v)
                                      for v in donated_state))

    # -- placement hooks ---------------------------------------------------
    def _mesh(self):
        return self.mesh

    def _prepare_program(self, program: Program, feed: Dict) -> Program:
        gs = self._build_strategy.gradient_scale_strategy
        if gs == GradientScaleStrategy.kCoeffNumDevice or self._loss_name is None:
            return program
        # _uid, not id(): a GC'd program's reused address must never hit
        # another program's cached rewrite (see Program._uid_counter)
        key = (program._uid, program._version)
        cached = self._scaled_programs.get(key)
        if cached is not None:
            return cached
        p = program.clone()
        blk = p.global_block
        loss_grad = grad_var_name(self._loss_name)
        for i, op in enumerate(blk.ops):
            if op.type == "fill_constant" and loss_grad in op.output_arg_names() \
                    and (op.attr(OP_ROLE_ATTR, 0) & OpRole.Loss):
                if op.attr("@loss_seed_scaled@", False):
                    # already rewritten: segmented host-op execution clones
                    # sub-programs from the PREPARED program and re-enters
                    # run(); without this idempotence guard kOne would
                    # scale the seed dp^2 times
                    break
                if gs == GradientScaleStrategy.kOne:
                    # reference kOne: per-device seeds of 1 summed over the
                    # world → seed scaled by dp degree here
                    op.set_attr("value",
                                float(op.attr("value", 1.0)) * self.mesh.shape[self._dp_axis])
                    op.set_attr("@loss_seed_scaled@", True)
                elif gs == GradientScaleStrategy.kCustomized:
                    if loss_grad not in feed:
                        raise RuntimeError(
                            f"GradientScaleStrategy.kCustomized requires "
                            f"feeding {loss_grad!r}")
                    blk.remove_op(i)
                break
        self._scaled_programs[key] = p
        return p

    def _replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def _put_feed(self, arr):
        dp = self.mesh.shape[self._dp_axis]
        if self._multiproc:
            # each process feeds its LOCAL batch (nccl2-mode trainers each
            # read their own shard); the global batch is their dp-concat
            local_dp = dp // jax.process_count()
            # ONLY true scalars replicate implicitly (the kCustomized
            # loss-grad seed as shape ()); a (1,)-leading feed is
            # ambiguous — it could be a genuine per-trainer batch of one —
            # so it goes through the shard/error paths below and a
            # replicated-by-contract (1,) seed must be fed as shape ()
            if arr.ndim == 0:
                return self._make_global(arr, self._replicated())
            if local_dp > 0 and arr.shape[0] > 0 \
                    and arr.shape[0] % local_dp == 0:
                sharding = NamedSharding(
                    self.mesh, P(self._dp_axis, *([None] * (arr.ndim - 1))))
                return jax.make_array_from_process_local_data(
                    sharding, np.asarray(arr))
            raise ValueError(
                f"multi-host feed of shape {getattr(arr, 'shape', ())} does "
                f"not divide the local dp degree {local_dp}; pad the batch "
                f"(replicated fallback would need identical data on every "
                f"trainer)")
        if arr.ndim >= 1 and arr.shape[0] % dp == 0 and arr.shape[0] > 0:
            sharding = NamedSharding(
                self.mesh, P(self._dp_axis, *([None] * (arr.ndim - 1))))
        else:
            # partial last batch / scalar feed: replicate (the reference's
            # uneven-batch DataBalance case, details/data_balance_op_handle.cc)
            sharding = self._replicated()
        return jax.device_put(arr, sharding)

    def _put_rng(self, rng):
        if self._multiproc:
            return self._make_global(rng, self._replicated())
        return jax.device_put(rng, self._replicated())

    def _make_global(self, val, sharding):
        """Build a global array from this process's full local copy (every
        process holds identical full values — named-PRNG init guarantees
        it), reading each device's shard out of the local copy."""
        val = np.asarray(val)
        return jax.make_array_from_callback(val.shape, sharding,
                                            lambda idx: val[idx])

    def _put_state(self, name: str, val):
        if name in self._placed:
            return val
        self._placed.add(name)
        # initial placement = the reference's param broadcast
        if self._multiproc:
            return self._make_global(val, self._state_sharding(name, np.asarray(val)))
        return jax.device_put(val, self._state_sharding(name, val))

    def _fetch_to_numpy(self, v):
        if isinstance(v, jax.Array) and not v.is_fully_addressable:
            if v.is_fully_replicated:
                return np.asarray(v)
            from jax.experimental import multihost_utils
            return np.asarray(multihost_utils.process_allgather(v, tiled=True))
        return np.asarray(v)

    def _note_state_write(self, name: str) -> None:
        self._placed.add(name)

    def _state_sharding(self, name: str, val) -> NamedSharding:
        """Parameter/optimizer-state sharding per BuildStrategy."""
        for pattern, spec in self._build_strategy.sharding_rules:
            if re.fullmatch(pattern, name):
                dims = []
                for i, ax in enumerate(spec[: val.ndim]):
                    if ax is not None and ax in self.mesh.axis_names \
                            and val.shape[i] % self.mesh.shape[ax] == 0:
                        dims.append(ax)
                    else:
                        dims.append(None)
                return NamedSharding(self.mesh, P(*dims))
        if self._build_strategy.reduce_strategy == ReduceStrategy.kReduce:
            # ZeRO-style: shard dim 0 over dp when divisible
            if val.ndim >= 1 and val.shape[0] % self.mesh.shape[self._dp_axis] == 0 \
                    and val.shape[0] >= self.mesh.shape[self._dp_axis]:
                return NamedSharding(
                    self.mesh, P(self._dp_axis, *([None] * (val.ndim - 1))))
        return self._replicated()

    # -- sharded checkpoints (paddle_tpu/checkpoint/) ----------------------
    def _persist_names(self, program: Program, scope: Scope):
        from ..core.executor import RNG_STATE_VAR
        return [v.name for v in program.global_block.vars.values()
                if v.persistable and v.name != RNG_STATE_VAR
                and scope.find_var(v.name) is not None]

    def _local_extent(self, val):
        """(start, stop) of THIS process's contiguous dim-0 row range of
        a sharded global array, or None when the value is replicated /
        fully addressable here (write it whole).  Non-contiguous local
        shard sets (exotic meshes) also return None — correctness first:
        whoever holds the whole array writes the whole array."""
        if not isinstance(val, jax.Array) or val.ndim == 0:
            return None
        if val.is_fully_addressable or val.is_fully_replicated:
            return None
        idx = sorted((s.index[0].start or 0,
                      s.index[0].stop if s.index[0].stop is not None
                      else val.shape[0])
                     for s in val.addressable_shards)
        lo, hi = idx[0][0], idx[0][1]
        for s_lo, s_hi in idx[1:]:
            if s_lo > hi:
                return None            # non-contiguous: punt to gather
            hi = max(hi, s_hi)
        if (lo, hi) == (0, val.shape[0]):
            return None                # locally complete after all
        return lo, hi

    def save_sharded_state(self, root: str, step: int,
                           program: Optional[Program] = None,
                           scope: Optional[Scope] = None,
                           commit: bool = True) -> bool:
        """Write this process's shards of the persistable state (params,
        optimizer moments — incl. ZeRO/kReduce dim-0-sharded state) into
        the two-phase checkpoint store.  Single-process meshes hold the
        whole state and write one full piece; multi-host meshes write
        one piece per process covering its addressable row ranges, and
        the step commits when every process's piece lands.  The written
        manifest is topology-independent: restore onto ANY layout —
        including a plain single-host Executor (ZeRO off) — re-shards
        from the same files."""
        from .. import checkpoint as _ckpt
        program = program or self._program
        scope = scope or self._scope
        names = self._persist_names(program, scope)
        pidx, pcount = jax.process_index(), jax.process_count()
        arrays, extents = {}, {}
        for n in names:
            val = scope.find_var(n)
            ext = self._local_extent(val)
            if ext is None:
                # whole-array write.  A distributed-but-noncontiguous
                # value gathers COLLECTIVELY (every process must
                # participate) before the host0 gate; everything else
                # (numpy, fully-addressable, replicated) is identical
                # on every host by the named-PRNG/state invariant, so
                # host0 alone writes it — two hosts writing the same
                # dense extent would be an overlap disagreement restore
                # refuses
                gathered = None
                if isinstance(val, jax.Array) \
                        and not val.is_fully_addressable:
                    gathered = self._fetch_to_numpy(val)
                if pidx != 0 and pcount > 1:
                    continue
                arrays[n] = (gathered if gathered is not None
                             else self._fetch_to_numpy(val))
            else:
                lo, hi = ext
                # dedup by dim-0 range: a var replicated over a second
                # mesh axis holds the SAME rows on several local
                # devices — concatenating the copies would write a
                # shard whose recorded span contains duplicated data
                by_range = {}
                for s in val.addressable_shards:
                    start = s.index[0].start or 0
                    by_range.setdefault(start, s)
                parts = [np.asarray(by_range[k].data)
                         for k in sorted(by_range)]
                arrays[n] = (parts[0] if len(parts) == 1
                             else np.concatenate(parts, axis=0))
                extents[n] = {"var": n, "offset": int(lo),
                              "rows": int(hi - lo),
                              "global_shape": [int(s) for s in val.shape]}
        topology = {
            "kind": "mesh",
            "mesh": {ax: int(self.mesh.shape[ax])
                     for ax in self.mesh.axis_names},
            "zero": self._build_strategy.reduce_strategy
            == ReduceStrategy.kReduce,
            "processes": pcount,
        }
        writers = [f"host{i}" for i in range(pcount)]
        _ckpt.write_piece(root, step, f"host{pidx}", arrays,
                          extents=extents, topology=topology,
                          expected_writers=writers)
        if commit:
            return _ckpt.try_commit(root, step, writers)
        return False

    def load_sharded_state(self, root: str,
                           step: Optional[int] = None,
                           program: Optional[Program] = None,
                           scope: Optional[Scope] = None,
                           verify: bool = True) -> int:
        """Restore persistable state from the newest (or given) COMPLETE
        step, written under ANY topology.  Restored values land in the
        scope as host arrays and are re-placed under THIS executor's
        sharding rules on the next run — which is exactly how ZeRO
        on↔off conversion happens: the checkpoint stores global rows,
        placement is a property of the reader."""
        from .. import checkpoint as _ckpt
        from ..checkpoint.elastic import restore_scope
        program = program or self._program
        scope = scope or self._scope
        step = restore_scope(root, program, scope, step=step,
                             verify=verify)
        # restored vars must be RE-PLACED (their old placement died with
        # the host copy); _put_state runs again on next dispatch
        for v in program.global_block.vars.values():
            self._placed.discard(v.name)
        return step

    @property
    def device_count(self) -> int:
        return self.mesh.size
