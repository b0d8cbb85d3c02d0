"""Dump the public API surface of paddle_tpu as stable one-line records.

Reference role: ``tools/print_signatures.py`` (clean-room — same gate
capability, fresh implementation): every public function/class signature
prints as ``<qualified name> (args..., defaults...)`` so a checked-in
spec (``tools/api_spec.txt``) can freeze the surface and
``tools/diff_api.py`` / ``tests/test_api_freeze.py`` can fail CI on
accidental drift.

Usage: python tools/print_signatures.py [> tools/api_spec.txt]
"""
from __future__ import annotations

import importlib
import inspect
import sys


MODULES = [
    "paddle_tpu",
    "paddle_tpu.layers",
    "paddle_tpu.layers.learning_rate_scheduler",
    "paddle_tpu.layers.detection",
    "paddle_tpu.layers.metric_op",
    "paddle_tpu.nets",
    "paddle_tpu.optimizer",
    "paddle_tpu.initializer",
    "paddle_tpu.regularizer",
    "paddle_tpu.clip",
    "paddle_tpu.metrics",
    "paddle_tpu.io",
    "paddle_tpu.profiler",
    "paddle_tpu.observability",
    "paddle_tpu.observability.stats",
    "paddle_tpu.observability.step_stats",
    "paddle_tpu.observability.debug_server",
    "paddle_tpu.observability.health",
    "paddle_tpu.observability.aggregate",
    # the distributed-tracing + flight-recorder surface (trace ids,
    # sampling, span ring, stitching, crash dumps): frozen so wire/API
    # drift in the trace layer is loud
    "paddle_tpu.observability.trace",
    "paddle_tpu.observability.flight",
    # the perf/numerics attribution plane (cost/memory records,
    # rooflines, device-memory sampling, run-scalar log) + its operator
    # CLIs: frozen so record/log-format drift is loud
    "paddle_tpu.observability.perf",
    "paddle_tpu.observability.runlog",
    # the latency-anatomy / SLO plane (phase timelines, metric history
    # rings, SLO watchdog): frozen so the rule grammar, ring wire form
    # and phase-record shape drift loudly
    "paddle_tpu.observability.phase",
    "paddle_tpu.observability.history",
    "paddle_tpu.observability.slo",
    # the saturation-anatomy plane (phase utilization + capacity
    # modeling, per-tenant metering): frozen so the snapshot shapes
    # and the STATS_PULL rider forms drift loudly
    "paddle_tpu.observability.capacity",
    "paddle_tpu.observability.tenant",
    # the correctness plane (golden canary prober, divergence audit
    # ring) + the golden-set operator CLI: frozen so the golden file
    # format, digest scheme and rider shapes drift loudly
    "paddle_tpu.observability.canary",
    "paddle_tpu.observability.audit",
    # the memory-attribution plane (per-pool HBM ledger, event ring,
    # leak sentinel, OOM forensics): frozen so the ledger/rider shapes
    # and the /allocz payload drift loudly
    "paddle_tpu.observability.memory",
    "golden",          # tools/golden.py (tools/ on sys.path here)
    "runlog_report",   # tools/runlog_report.py
    # pipeline parallelism plane (stage transpiler, schedules, drivers,
    # permute transport, RPC stage workers): frozen so the stage-program
    # contract and schedule API drift loudly
    "paddle_tpu.pipeline",
    "paddle_tpu.pipeline.transpiler",
    "paddle_tpu.pipeline.schedule",
    "paddle_tpu.pipeline.runner",
    "paddle_tpu.pipeline.permute",
    "paddle_tpu.pipeline.rpc",
    # autoregressive decode plane (paged KV cache, continuous decode
    # batching, streaming server/client): frozen so the generative
    # serving API + wire tags drift loudly
    "paddle_tpu.decode",
    "paddle_tpu.decode.adapter",
    "paddle_tpu.decode.cache",
    "paddle_tpu.decode.model",
    "paddle_tpu.decode.mla",
    "paddle_tpu.decode.engine",
    "paddle_tpu.decode.server",
    "paddle_tpu.decode.client",
    "paddle_tpu.lod_tensor",
    "paddle_tpu.transpiler",
    "paddle_tpu.data_feeder",
    "paddle_tpu.param_attr",
    "paddle_tpu.average",
    "paddle_tpu.evaluator",
    "paddle_tpu.net_drawer",
    "paddle_tpu.debugger",
    "paddle_tpu.recordio_writer",
    # distributed/parallel/inference surfaces (VERDICT r4 #6): these
    # public classes churn the most — freeze them too
    "paddle_tpu.distributed",
    # the var-transport wire surface (batched SEND_VARS/GET_VARS,
    # scatter-gather serde): frozen so wire-format/API drift is loud
    "paddle_tpu.distributed.serde",
    "paddle_tpu.distributed.transport",
    # the HA control plane (standby registration/promotion/REG_SNAPSHOT,
    # replicated pserver loop, leader-elected master, fault-injection
    # rule grammar) + its operator CLI: frozen so failover/wire drift
    # is loud
    "paddle_tpu.distributed.registry",
    "paddle_tpu.distributed.master",
    "paddle_tpu.distributed.faults",
    # the self-healing fleet supervisor (FleetSpec grammar, worker
    # lifecycle state machine, rollback/resize actions) + its operator
    # CLI: frozen so the spec-file format and admin surface drift
    # loudly
    "paddle_tpu.distributed.supervisor",
    "fleet",        # tools/fleet.py (tools/ is on sys.path here)
    "chaos",        # tools/chaos.py (tools/ is on sys.path here)
    "paddle_tpu.parallel",
    "paddle_tpu.inference",
    # the model-serving plane (bucket-ladder batching, hot-swap model
    # registry, INFER wire, replica client) + its operator CLI: frozen
    # so the serving wire/API surface drifts loudly
    "paddle_tpu.serving",
    "paddle_tpu.serving.batcher",
    "paddle_tpu.serving.model_registry",
    "paddle_tpu.serving.server",
    "paddle_tpu.serving.client",
    "serve",        # tools/serve.py (tools/ is on sys.path here)
    "paddle_tpu.contrib.trainer",
    "paddle_tpu.contrib.inferencer",
    "paddle_tpu.contrib.decoder",
    # the persistent compile-cache surface (entry format, fingerprint,
    # store/load/prune) + its operator CLI: frozen so on-disk format /
    # admin-tooling drift is loud
    "paddle_tpu.core.compile_cache",
    "cache_admin",  # tools/cache_admin.py (tools/ is on sys.path here)
    # the low-precision serving surface (fused-dequant int8 matmul,
    # calibration plan, KV qdq helpers, /quantz payload): frozen so the
    # scale semantics and fallback contract drift loudly
    "paddle_tpu.kernels.quant",
    # the sharded-checkpoint plane (manifest/store/reshard/snapshot/
    # elastic) + its operator CLI: frozen so the on-disk format and the
    # restore-planner contract drift loudly
    "paddle_tpu.checkpoint",
    "paddle_tpu.checkpoint.manifest",
    "paddle_tpu.checkpoint.store",
    "paddle_tpu.checkpoint.reshard",
    "paddle_tpu.checkpoint.snapshot",
    "paddle_tpu.checkpoint.elastic",
    "ckpt_admin",   # tools/ckpt_admin.py (tools/ on sys.path here)
]


def _sig(obj) -> str:
    try:
        sig = inspect.signature(obj)
    except (TypeError, ValueError):
        return "(signature unavailable)"
    parts = []
    for p in sig.parameters.values():
        if p.default is inspect.Parameter.empty:
            parts.append(p.name)
        else:
            parts.append(f"{p.name}={p.default!r}")
    return "(" + ", ".join(parts) + ")"


def iter_api():
    for modname in MODULES:
        try:
            mod = importlib.import_module(modname)
        except ImportError:
            continue
        declared = getattr(mod, "__all__", None)
        names = declared if declared is not None else \
            [n for n in dir(mod) if not n.startswith("_")]
        for name in sorted(names):
            obj = getattr(mod, name, None)
            if obj is None:
                continue
            if inspect.ismodule(obj):
                continue
            if declared is None:
                # dir() fallback only: skip re-exports (typing etc.) —
                # an explicit __all__ may deliberately re-export
                own = getattr(obj, "__module__", modname) or modname
                if not own.startswith("paddle_tpu"):
                    continue
            if inspect.isclass(obj):
                yield f"{modname}.{name}.__init__ {_sig(obj.__init__)}"
                for m_name, m in sorted(vars(obj).items()):
                    if m_name.startswith("_"):
                        continue
                    if callable(m):
                        yield f"{modname}.{name}.{m_name} {_sig(m)}"
            elif callable(obj):
                yield f"{modname}.{name} {_sig(obj)}"


def main():
    for line in sorted(set(iter_api())):
        print(line)


if __name__ == "__main__":
    sys.path.insert(0, ".")
    main()
