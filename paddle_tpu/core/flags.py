"""Framework flags: the gflags + env-var bootstrap analogue.

Reference: gflags ``DEFINE_*`` at point-of-use (``executor.cc:27``,
``operator.cc:31`` FLAGS_check_nan_inf, ``scope.cc:23-34``,
``memory/malloc.cc:25``) re-exported to Python through
``fluid.__init__.__bootstrap__`` collecting ``--tryfromenv`` names
(``python/paddle/fluid/__init__.py:112-132``, ``pybind.cc:560``).

Here flags live in one registry; values bootstrap from the environment
(``FLAGS_<name>=...`` variables, the reference's spelling) at import and
can be read/written at runtime with ``get_flags``/``set_flags`` (the
paddle 1.x public API).  Consumers poll at use-sites, e.g. the executor's
NaN/Inf guard.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Iterable, Union

_DEFS: Dict[str, dict] = {}
_VALUES: Dict[str, Any] = {}


def _parse(value: str, default):
    if isinstance(default, bool):
        return value.strip().lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(value)
    if isinstance(default, float):
        return float(value)
    return value


def define_flag(name: str, default, help_str: str = "") -> None:
    _DEFS[name] = {"default": default, "help": help_str}
    env = os.environ.get("FLAGS_" + name)
    _VALUES[name] = _parse(env, default) if env is not None else default


def get_flags(names: Union[str, Iterable[str]]):
    """fluid.get_flags parity: str → value; list → {name: value}."""
    if isinstance(names, str):
        if names.startswith("FLAGS_"):
            names = names[len("FLAGS_"):]
        if names not in _DEFS:
            raise KeyError(f"unknown flag {names!r}")
        return _VALUES[names]
    return {n: get_flags(n) for n in names}


def set_flags(flags: Dict[str, Any]) -> None:
    """fluid.set_flags parity: {\"FLAGS_x\": v} or {\"x\": v}."""
    for name, value in flags.items():
        if name.startswith("FLAGS_"):
            name = name[len("FLAGS_"):]
        if name not in _DEFS:
            raise KeyError(f"unknown flag {name!r}")
        default = _DEFS[name]["default"]
        _VALUES[name] = (_parse(value, default) if isinstance(value, str)
                         else type(default)(value) if default is not None
                         else value)


def all_flags() -> Dict[str, Any]:
    return dict(_VALUES)


# ---------------------------------------------------------------------------
# flag definitions (the reference's DEFINE_* sites, TPU-relevant subset)
# ---------------------------------------------------------------------------

define_flag("check_nan_inf", False,
            "after each executor run, scan fetches and updated state for "
            "NaN/Inf and raise (operator.cc:31 post-kernel check, moved to "
            "post-block granularity under whole-block XLA compilation)")
define_flag("benchmark", False,
            "log per-run wall time from the executor (executor.cc:399)")
define_flag("rpc_deadline", 120.0,
            "pserver transport connect deadline in seconds "
            "(distributed/transport.py)")
define_flag("rpc_transport", "native",
            "pserver byte-transport backend: 'native' (C framed-TCP in "
            "native/paddle_tpu_native.cc, the reference's C++ gRPC layer "
            "role) or 'python' (stdlib sockets fallback)")
define_flag("sparse_dense_update_max_elems", 32_000_000,
            "lazy sparse optimizers (adam/momentum/adagrad) use the "
            "masked-dense update (2 scatters + full-table elementwise; "
            "4x faster on TPU for medium tables) when the table has at "
            "most this many elements; larger tables fall back to the "
            "sorted merge_rows path whose cost is independent of height. "
            "Read at trace time: set it before the first Executor.run of "
            "a program (cached executables keep the path they compiled)")
define_flag("runtime_stats", True,
            "collect runtime telemetry (paddle_tpu/observability): "
            "executor compile-cache and StepStats records, lowering/RPC/"
            "collective counters and latency histograms, and runtime:: "
            "profiler spans.  Collection is cheap (dict increments); set "
            "FLAGS_runtime_stats=0 to disable every hook for true-zero "
            "overhead")
define_flag("executor_cache_capacity", 256,
            "max cached compiled executables per Executor; exceeding it "
            "evicts the oldest entry (counted in executor.cache_evictions "
            "— an eviction storm means shape churn is defeating the "
            "compile cache).  0 = unbounded (the pre-telemetry behavior)")
define_flag("rpc_conns_per_endpoint", 2,
            "striped persistent connections per pserver endpoint "
            "(distributed/transport.py RPCClient): concurrent requests "
            "to one pserver pipeline across stripes instead of "
            "serializing on a single connection lock (the reference's "
            "multi-channel grpc_client).  Latched per endpoint at first "
            "use; 1 restores the single-connection behavior")
define_flag("rpc_vectored_io", True,
            "send multi-buffer RPC frames scatter-gather "
            "(socket.sendmsg / native sendmsg-iovec) straight from the "
            "ndarray views — no Python-level concat copy of tensor "
            "bytes.  0 falls back to joining buffers before send")
define_flag("rpc_stripe_chunk_bytes", 8 << 20,
            "SEND_VARS batches whose tensor payload exceeds this many "
            "bytes are split (at var granularity) into per-stripe "
            "sub-batches sent concurrently across the striped "
            "connections; 0 disables splitting (always one frame per "
            "endpoint per round)")
define_flag("rpc_batch_vars", True,
            "group send/recv host-op variables by endpoint into batched "
            "SEND_VARS/GET_VARS frames (one RPC per pserver per round "
            "instead of one per variable).  0 restores per-var "
            "SEND_VAR/GET_VAR wire behavior (e.g. against a peer that "
            "predates the batched frames)")
define_flag("rpc_server_profile_period", 0,
            "pserver self-profiling: log request-rate stats every N "
            "handled RPCs (reference FLAGS_rpc_server_profile_period, "
            "python/paddle/fluid/__init__.py:121); 0 disables")
define_flag("debug_server_port", 0,
            "port for the in-process observability debug HTTP server "
            "(observability/debug_server.py: /metrics /healthz /statusz "
            "/stepz).  0 (default) disables it entirely — no socket is "
            "opened and no thread is started")
define_flag("debug_server_host", "127.0.0.1",
            "bind address for the debug HTTP server; loopback by default "
            "(expose beyond the host deliberately, e.g. 0.0.0.0 behind a "
            "pod-network firewall)")
define_flag("health_suspect_misses", 1.0,
            "missed heartbeat-lease terms (units of each worker's own "
            "TTL) after which the health registry marks a worker SUSPECT "
            "(observability/health.py)")
define_flag("health_dead_misses", 3.0,
            "missed lease terms after which a worker is DEAD: its health "
            "gauge flips, and a TaskMaster consulting the registry "
            "requeues the worker's task leases immediately instead of "
            "waiting out the lease timeout")
define_flag("trace_sample_rate", 0.0,
            "distributed-tracing head-sampling rate in [0,1] "
            "(observability/trace.py): each top-level Executor.run rolls "
            "once and, when sampled, opens a step-root span whose context "
            "propagates over the RPC wire so trainer and pserver spans "
            "stitch under one trace id.  0 (default) disables tracing "
            "entirely — no span-ring writes and zero extra wire bytes")
define_flag("trace_ring_spans", 4096,
            "capacity of the in-memory completed-span ring each process "
            "keeps for TRACE_PULL / the /tracez debug page; oldest spans "
            "fall off — bound memory, never block the hot path")
define_flag("flight_record_dir", "",
            "directory for crash flight-recorder dumps "
            "(observability/flight.py): when set, unhandled exceptions, "
            "SIGTERM and Heartbeat.stop(bye=False)-style dirty exits "
            "write a JSON post-mortem (recent + in-flight spans, log "
            "events, step-stats tail) there.  Empty (default) disarms "
            "the recorder — no hooks installed")
define_flag("compile_cache_dir", "",
            "directory for the persistent cross-process compilation "
            "cache (core/compile_cache.py): AOT-compiled executables "
            "are serialized into content-addressed entry files keyed "
            "by a canonical program fingerprint (tier A), and "
            "jax_compilation_cache_dir is pointed at <dir>/xla for "
            "XLA-level reuse of anything tier A cannot serialize "
            "(tier B).  A warm process hydrates its executable cache "
            "from disk instead of recompiling (elastic restarts, "
            "bench worker respawns).  Empty (default) disables the "
            "cache entirely — no disk I/O, no new threads")
define_flag("compile_cache_max_bytes", 2 << 30,
            "LRU size cap for the persistent compile-cache directory: "
            "after each store, oldest-used entry files (mtime, touched "
            "on every hit) are pruned until the tier-A entries fit; "
            "counted in compile_cache.evictions.  0 = unbounded")
define_flag("fault_inject", "",
            "chaos-suite fault injection rules (distributed/faults.py): "
            "semicolon-separated 'kind[:target][:k=v,...]' rules — "
            "drop_conn (sever a matching request's connection), delay "
            "(sleep ms before handling), kill_after (os._exit(137) when "
            "the matching counter reaches n), refuse_accept (slam new "
            "connections).  Targets are RPC message names or loop "
            "events (apply_round, lease_grant).  Empty (default) "
            "disables every injection point — the transport is "
            "byte-identical to the fault-free build.  Runtime injection "
            "against a live fleet goes through the debug server's "
            "/chaosz endpoint (tools/chaos.py)")
define_flag("perf_attribution", False,
            "harvest XLA cost_analysis() (flops, bytes accessed) and "
            "memory_analysis() (argument/output/temp bytes) on every "
            "executable build (fresh compile, AOT warm start, or "
            "compile-cache hydrate) into per-executable perf records "
            "(observability/perf.py), combine them with measured step "
            "wall time into roofline positions vs the platform peak "
            "table (platform.PLATFORM_PEAKS), and sample live "
            "device-memory gauges per step.  Served on /profilez and "
            "/memz.  Forces ahead-of-time lower().compile() (same "
            "executable, eager compile) so the compiled handle is "
            "analyzable; off (default) keeps the lazy-jit path "
            "byte-identical")
define_flag("run_log_dir", "",
            "directory for the append-only run-scalar JSONL log "
            "(observability/runlog.py): each Executor.run/run_steps "
            "appends one record per step — step index, wall clock, "
            "every scalar fetch by name (loss, ...), grad global norm "
            "over fetched @GRAD vars, step_ms, samples/sec — with "
            "atomic size-capped rotation.  tools/runlog_report.py "
            "renders/compares logs.  Empty (default): zero new I/O")
define_flag("run_log_max_mb", 64,
            "rotation cap for one run-scalar log file in MB: when an "
            "append would exceed it, the file atomically rotates into "
            "a generation chain (<name>.1 newest .. .8 oldest, older "
            "ages out) and a fresh file starts.  0 = never rotate")
define_flag("numerics_check", "",
            "post-step NaN/Inf sentinel (observability plane): after "
            "each executor dispatch, device-side jnp.isfinite "
            "reductions over every float fetch and updated persistable "
            "var are read back as tiny flags (never a full-tensor host "
            "scan like FLAGS_check_nan_inf).  Offending variables are "
            "NAMED, numerics.{nan,inf} counters increment, and the "
            "flight recorder gets a note.  'warn' (or any truthy "
            "value) logs and continues; 'fatal' dumps a full flight "
            "record and raises BEFORE the poisoned state is applied "
            "to the scope (fatal keeps a pre-step device copy of the "
            "donated state so the scope is restored intact — one "
            "state copy per step is its price).  Either mode's flag "
            "readback waits on the dispatch, so with async fetches "
            "(sync=False) the sentinel serializes each step — the "
            "cost of a verdict before the next apply.  Empty "
            "(default) disables the pass")
define_flag("serving_buckets", "1,2,4,8,16,32",
            "batch-size bucket ladder for the model-serving plane "
            "(paddle_tpu/serving): concurrent requests coalesce into "
            "padded batches snapped to the smallest bucket that fits, "
            "so a handful of warmed executables cover all traffic and "
            "no dispatch ever recompiles.  Per-model override via "
            "DynamicBatcher(buckets=...) / ModelManager.load(buckets=...)")
define_flag("serving_max_queue_delay_ms", 5.0,
            "continuous-batching dispatch SLO: a queued request waits at "
            "most this long for more requests to coalesce before its "
            "(possibly partial, padded) batch dispatches.  Lower = "
            "latency-biased, higher = occupancy-biased")
define_flag("serving_max_queue_rows", 1024,
            "admission-control bound on a model's request queue in ROWS "
            "(sum of queued request batch sizes): past it, new requests "
            "are shed immediately with a typed Overloaded reply instead "
            "of queueing into timeout (counted in serving.<model>.shed)")
define_flag("serving_queue_delay_slo_ms", 0.0,
            "optional queue-delay SLO for admission control: when "
            "backlog x observed per-batch service time says a new "
            "request cannot be answered within this many ms, it is shed "
            "with a typed Overloaded reply.  0 (default) disables the "
            "estimate — only the serving_max_queue_rows bound sheds")
define_flag("int8_inference", False,
            "serving-plane kill-switch default for int8 inference: when "
            "on, create_predictor appends the 'quantize_int8' "
            "calibration pass (inference/passes.py) to every "
            "AnalysisConfig as if enable_int8() had been called — "
            "per-out-channel weight scales derived from QAT fake-quant "
            "stats when present (else post-training abs-max over the "
            "weight scope), activations quantized dynamically (or with "
            "the QAT moving-average scale), and calibrated mul/fused_fc "
            "ops lowered through the fused-dequant int8 Pallas matmul "
            "(kernels/quant.py; int8xint8->int32 accumulation, dequant+"
            "bias+activation epilogue).  Non-TPU backends run the "
            "kernel in interpret mode; odd shapes or build faults take "
            "the counted XLA dequantized path (quant.* counters — a "
            "fault can never fail a dispatch).  Off (default): only "
            "configs that explicitly call enable_int8() quantize; "
            "programs without the pass lower byte-identically")
define_flag("phase_attribution", False,
            "per-request latency-phase attribution for the serving and "
            "decode planes (observability/phase.py): each request "
            "stamps monotonic phase timestamps through its lifecycle "
            "(queue -> assemble -> dispatch -> device -> reply; decode "
            "adds queue -> prefill/TTFT -> per-token), recorded into "
            "per-phase histograms plus a bounded per-request sample "
            "ring with slowest-request exemplars linked to their trace "
            "ids — so a p99 regression NAMES its phase on /servingz / "
            "/decodez.  Also arms the decode TTFT/TBT histograms and "
            "goodput counters.  Host-side time.monotonic() stamps only "
            "— zero extra device syncs.  Off (default): no stamps, no "
            "new metric series")
define_flag("capacity_attribution", False,
            "phase-level utilization and capacity modeling for the "
            "serving and decode planes (observability/capacity.py): "
            "each pipeline component (batcher assemble/dispatch, "
            "device materialization, reply slicing; decode prefill and "
            "step) accounts its busy time into a bounded sliding "
            "window, turned into *.util.* gauges, operational-law "
            "service-time fits per shape bucket (U = X*S) and a "
            "predicted_max_qps + headroom_frac estimate naming the "
            "binding phase — rendered on /capacityz, merged over "
            "STATS_PULL, and riding the serving/decode lease-data "
            "payloads into the elastic controller as an informational "
            "capacity input.  Host-side clock reads only — no extra "
            "device syncs.  Off (default): no accounting, no new "
            "metric series, heartbeats byte-identical")
define_flag("tenant_accounting", False,
            "per-tenant usage metering for the serving and decode "
            "planes (observability/tenant.py): requests carrying an "
            "optional wire-level tenant id are accounted per tenant "
            "(requests/rows/prefill-tokens/decode-tokens/cancellations "
            "plus device-ms attributed proportionally from the shared "
            "batch's device wall) into a space-saving top-K heavy-"
            "hitter sketch with an 'other' rollup, rendered on "
            "/tenantz and merged over STATS_PULL.  Tenant ids are "
            "CLIENT-SUPPLIED and unauthenticated — attribution, not "
            "isolation.  Off (default): ids are ignored, no sketch, "
            "no new metric series")
define_flag("tenant_top_k", 20,
            "cardinality bound of the per-tenant accounting sketch "
            "(observability/tenant.py): at most this many tenants are "
            "tracked exactly; past it the space-saving sketch evicts "
            "the smallest tenant into the 'other' rollup, so an "
            "adversarial id stream cannot grow memory or the /tenantz "
            "payload")
define_flag("metrics_history_interval_s", 0.0,
            "sampling period for the in-process metric history rings "
            "(observability/history.py): every counter/gauge in the "
            "default registry retains a bounded, resolution-doubling "
            "downsampled time series, queryable as /varz?window=<s> "
            "and carried through the STATS_PULL fleet merge (aligned "
            "by sample AGE, so skewed worker wall clocks cannot "
            "misalign the fleet view).  0 (default) disables the "
            "sampler thread and the rings entirely")
define_flag("metrics_history_points", 512,
            "capacity of one metric's history ring in POINTS: past it "
            "the ring halves its resolution (adjacent samples merge "
            "into their mean) instead of growing — memory stays "
            "bounded while the window keeps extending")
define_flag("slo_rules", "",
            "declarative SLO watchdog rules (observability/slo.py), "
            "semicolon-separated "
            "'name=metric:stat(op)threshold:for=sustain_s' — e.g. "
            "'ttft=decode.lm.ttft_ms:p99>250:for=5'.  stat is p50/p90/"
            "p99/p999 (histograms), rate (counter per-second), or "
            "value (gauges).  Rules are evaluated in-process; a "
            "condition sustained for its window BREACHES (slo.* "
            "counters, flight-recorder note, /sloz, and an 'slo' "
            "health dimension in the registry heartbeat payload that "
            "ElasticController/supervisor consume as a damped, "
            "HOLD-safe decision input).  Empty (default): no watchdog "
            "thread, no heartbeat bytes added")
define_flag("slo_eval_interval_s", 1.0,
            "SLO watchdog evaluation period in seconds (only read when "
            "FLAGS_slo_rules is non-empty)")
define_flag("canary_probe", False,
            "golden canary prober for the serving and decode planes "
            "(observability/canary.py): a background thread "
            "periodically replays a small golden set (recorded "
            "input -> expected-output pairs, captured with "
            "'tools/golden.py record' against a trusted build) through "
            "the REAL submit path of every registered replica target, "
            "compares replies against the goldens with per-model rtol, "
            "and maintains per-replica pass/fail streaks (canary.* "
            "counters, /canaryz, a 'canary' health dimension on every "
            "registry heartbeat, and a STATS_PULL rider).  Probes are "
            "tenant-tagged '__canary__' so per-tenant metering "
            "(FLAGS_tenant_accounting) excludes them from user "
            "accounting.  A canary pass is a REGRESSION check against "
            "a recorded build, not a proof of correctness.  Off "
            "(default): no thread, no series, heartbeats and "
            "STATS_PULL byte-identical")
define_flag("canary_interval_s", 5.0,
            "golden canary probe period in seconds (only read when "
            "FLAGS_canary_probe is on): each cycle replays the full "
            "golden set through every registered target once")
define_flag("canary_golden_path", "",
            "path of the golden-set JSON consumed by the canary prober "
            "(written by 'tools/golden.py record'); empty with "
            "FLAGS_canary_probe on means the prober idles armed with "
            "zero goldens (streaks stay empty) until a set is loaded")
define_flag("canary_rtol", 1e-5,
            "default relative tolerance for golden-vs-reply numeric "
            "comparison in the canary prober; a golden set may carry a "
            "tighter/looser per-model rtol which wins over this flag")
define_flag("canary_fail_streak", 3,
            "consecutive canary-probe failures on one replica target "
            "before its heartbeat 'canary' health dimension flips to "
            "'fail' (the supervisor additionally applies its own "
            "hysteresis before quarantining, so a single flake can "
            "never drain a replica)")
define_flag("divergence_check", False,
            "cross-replica divergence sentinel "
            "(observability/audit.py): serving replicas fold a content "
            "digest of each reply batch (decode servers a per-stream "
            "token-id rolling hash) into a bounded audit ring that "
            "rides their registry lease data; the supervisor groups "
            "digests by (model, version, request-hash) across replicas "
            "and NAMES a minority replica whose digest disagrees with "
            "the majority (divergence.* counters, flight-recorder "
            "note, /canaryz audit section).  Training: "
            "ParallelExecutor folds a periodic u64 parameter checksum "
            "per DP replica (every FLAGS_divergence_param_steps steps) "
            "so state divergence is caught within K steps.  Off "
            "(default): no digests, no series, lease payloads "
            "byte-identical")
define_flag("divergence_param_steps", 50,
            "period in optimizer steps of the cross-DP-replica "
            "parameter checksum (only read when FLAGS_divergence_check "
            "is on): every K-th step each replica folds a u64 checksum "
            "of its persistable parameters into the audit plane")
define_flag("memory_attribution", False,
            "memory anatomy (observability/memory.py): every "
            "byte-holding subsystem (decode KV block pool, executor "
            "executable cache + persistent scope, compile-cache disk "
            "store, serving batch staging, checkpoint snapshot "
            "buffers) registers a pool on the process MemoryLedger; "
            "the ledger reconciles pool sums against live PJRT "
            "bytes_in_use per device into an explicit "
            "unattributed_bytes residual, keeps a bounded allocation "
            "event ring (alloc/free/park/reclaim/preempt/evict), runs "
            "a leak sentinel promoting failed refcount audits to a "
            "'memory' health dimension on registry heartbeats, and "
            "dumps OOM forensics (full ledger + top holders + event "
            "tail) on any RESOURCE_EXHAUSTED escaping a dispatch.  "
            "Surfaces: /allocz (+?text=1), /memz ledger section, "
            "STATS_PULL rider with fleet merge, compact lease-data "
            "rider for ElasticController.memory_headroom().  Off "
            "(default): no pools, no series, no thread, heartbeat / "
            "lease / STATS_PULL payloads byte-identical")
define_flag("memory_audit_interval_s", 5.0,
            "period of the memory leak sentinel's refcount-invariant "
            "audit sweep (only read when FLAGS_memory_attribution is "
            "on); <= 0 disables the sentinel thread while keeping "
            "ledger attribution available for pull-based audits")
define_flag("memory_event_ring", 1024,
            "bounded capacity of the allocation event ring "
            "(alloc/free/park/reclaim/preempt/evict records with "
            "sizes and pool ids; oldest events are overwritten) — "
            "only allocated when FLAGS_memory_attribution is on")
define_flag("pserver_registry", "",
            "host:port of the pserver discovery registry "
            "(distributed/registry.py — the etcd analogue): pservers "
            "register their logical endpoint with a TTL lease, trainers "
            "re-resolve on connection failure; empty = static endpoints")
