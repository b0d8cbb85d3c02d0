"""Runtime telemetry: metrics registry, per-step stats, runtime spans.

Three cooperating pieces (see each module's docstring):

- :mod:`stats` — process-wide counters / gauges / fixed-bucket
  histograms with ``snapshot()`` / ``to_prometheus_text()`` / JSON
  export; the instrumented layers (``core/executor.py``,
  ``core/lowering.py``, ``parallel/parallel_executor.py``,
  ``distributed/transport.py``) report here under the ``executor.*``,
  ``lowering.*``, ``parallel.*`` and ``rpc.*`` scopes.
- :mod:`step_stats` — a bounded ring of per-``Executor.run`` records
  (cache hit/miss, lowering + XLA compile time, feed/fetch bytes, wall
  time) with ``last_n()`` and percentile ``summary()``.
- :mod:`trace` — runtime spans feeding the existing profiler event
  stream under a ``runtime::`` category, so Chrome traces show executor
  internals alongside user spans — PLUS the distributed-tracing layer:
  trace/span ids, head sampling (``FLAGS_trace_sample_rate``),
  cross-process context propagation over the RPC wire, a bounded span
  ring per process, and ``stitch_chrome_trace`` fleet stitching.
- :mod:`flight` — the crash flight recorder: bounded log-event ring +
  post-mortem dumps (recent/in-flight spans, events, step tail) to
  ``FLAGS_flight_record_dir`` on unhandled exceptions, SIGTERM and
  dirty exits.
- :mod:`perf` — the perf/numerics attribution plane
  (``FLAGS_perf_attribution``): XLA ``cost_analysis``/
  ``memory_analysis`` per executable, roofline positions vs the
  platform peak table, live device-memory gauges; served on
  ``/profilez`` + ``/memz``.
- :mod:`runlog` — append-only JSONL per-step scalar log
  (``FLAGS_run_log_dir``): loss/any scalar fetch, grad global norm,
  step_ms, samples/s, with atomic rotation and a ``watch()`` tail;
  ``tools/runlog_report.py`` renders/compares.

The latency-anatomy / SLO plane (all strictly flag-gated):

- :mod:`phase` — per-request phase attribution
  (``FLAGS_phase_attribution``): monotonic phase timelines through the
  serving batcher / decode engine lifecycles, per-phase histograms, a
  bounded per-request sample ring with slowest-request exemplars
  linked to trace ids; phases sum to the end-to-end wall by
  construction, so a p99 regression names its phase.
- :mod:`history` — bounded, resolution-doubling metric history rings
  (``FLAGS_metrics_history_interval_s``): every counter/gauge retains
  a downsampled time series, served on ``/varz?window=...`` and
  carried (age-aligned, clock-skew-proof) through the STATS_PULL
  fleet merge.
- :mod:`capacity` — phase-level utilization + capacity modeling
  (``FLAGS_capacity_attribution``): per-component busy-time windows
  (``*.util.*`` gauges), operational-law service-time fits (U = X·S),
  ``predicted_max_qps`` / ``headroom_frac`` with a saturation verdict
  naming the binding phase; served on ``/capacityz``, merged over
  STATS_PULL, riding serving/decode lease data into the
  ElasticController's HOLD-safe ``capacity`` input.
- :mod:`tenant` — per-tenant usage metering
  (``FLAGS_tenant_accounting``): wire-optional tenant ids accounted
  into a space-saving top-K sketch (requests/rows/tokens/cancellations
  + proportionally attributed device-ms, per-tenant p99); served on
  ``/tenantz``, fleet-merged so a fleet-wide heavy hitter is visible
  from one endpoint.  Ids are client-supplied — attribution, not
  isolation.
- :mod:`slo` — the declarative SLO watchdog (``FLAGS_slo_rules``):
  metric × percentile/rate × threshold × sustain-window rules
  evaluated in-process; breaches count, leave flight notes, render on
  ``/sloz`` and ride the registry heartbeat as an ``slo`` health
  dimension the ElasticController/supervisor consume.
- :mod:`canary` — the golden canary prober (``FLAGS_canary_probe``):
  a background thread replays recorded input→expected-output goldens
  (``tools/golden.py record``) through every registered replica's real
  submit path, compares with per-model rtol, keeps per-replica
  pass/fail streaks; served on ``/canaryz``, fleet-merged, riding the
  heartbeat as a ``canary`` health dimension the supervisor's
  ``quarantine_on_canary_fail`` policy consumes (DRAIN, never kill).
- :mod:`audit` — the cross-replica divergence sentinel
  (``FLAGS_divergence_check``): reply-batch content digests / decode
  token rolling hashes / periodic DP parameter checksums folded into a
  bounded ring riding the lease data; digests grouped by (model,
  version, request-hash) across replicas NAME a divergent minority
  replica — silent data corruption surfaces without trusting any
  single machine.

The export/aggregation half (this package's fleet plane):

- :mod:`debug_server` — opt-in (``FLAGS_debug_server_port``) HTTP
  daemon serving ``/metrics`` ``/healthz`` ``/statusz`` ``/stepz``;
- :mod:`health` — heartbeat-driven worker liveness
  (HEALTHY/SUSPECT/DEAD), fed by the discovery registry's TTL leases;
- :mod:`aggregate` — STATS_PULL RPC + cross-worker merge of counters /
  gauges / histograms into per-worker-labeled ``fleet:*`` series.

Everything is gated by ``FLAGS_runtime_stats`` (env
``FLAGS_runtime_stats=0`` disables all collection); spans additionally
require the profiler to be armed, so the default-path overhead is a
flag lookup.
"""
from __future__ import annotations

from . import (  # noqa: F401
    aggregate,
    audit,
    canary,
    capacity,
    debug_server,
    flight,
    health,
    history,
    perf,
    phase,
    runlog,
    slo,
    stats,
    step_stats,
    tenant,
    trace,
)
from .aggregate import FleetAggregator  # noqa: F401
from .health import HealthTable  # noqa: F401
from .stats import (  # noqa: F401
    StatsRegistry,
    default_registry,
    snapshot,
    to_prometheus_text,
)
from .step_stats import StepStats, StepStatsRecorder  # noqa: F401
from .trace import SpanContext, start_span, stitch_chrome_trace  # noqa: F401


def enabled() -> bool:
    """Is runtime telemetry collection on (``FLAGS_runtime_stats``)?"""
    return trace.flags_on()


def export(step_tail: int = 32) -> dict:
    """One JSON-ready bundle: metrics snapshot + step-stats summary/tail.

    The shape the debug server serves on ``/stepz``.
    """
    return {"stats": stats.to_dict(),
            "step_stats": step_stats.recorder().export(tail=step_tail)}


def reset() -> None:
    """Zero all metrics and drop the step ring."""
    stats.reset()
    step_stats.clear()
