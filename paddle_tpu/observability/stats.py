"""Process-wide metrics registry: counters, gauges, fixed-bucket histograms.

The runtime-facing half of the observability layer (the reference's
profiler counted op spans only; production serving needs rates and
distributions that survive past a trace window).  Design points:

- one flat registry of named metrics; dots namespace them
  (``executor.cache_hits``) and ``scope()`` returns a prefixing view so
  call sites never concatenate strings by hand;
- every metric is thread-safe (executor runs, RPC server handlers and
  the data-layer threads all report concurrently);
- histograms are fixed-bucket (Prometheus semantics: cumulative
  ``le``-bucket counts + sum + count) so ``observe`` is O(log buckets)
  with no allocation — safe on hot paths;
- exports: ``snapshot()`` (plain dict), ``to_prometheus_text()``
  (text exposition format, scrape-ready), ``dump_json()`` (artifact
  files).

Collection is gated by ``FLAGS_runtime_stats`` at the *instrumentation
sites* (executor/transport/lowering), not here: the registry itself has
no opinion about whether the process wants telemetry.
"""
from __future__ import annotations

import bisect
import json
import re
import threading
import time
from typing import Dict, Iterable, Optional, Sequence, Tuple

# default latency buckets in MILLISECONDS: sub-ms dispatches up through
# multi-second XLA compiles / slow RPC round trips
DEFAULT_MS_BUCKETS: Tuple[float, ...] = (
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
    250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0)

_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_name(name: str) -> str:
    """Sanitize a dotted metric name into Prometheus [a-zA-Z0-9_:]."""
    out = _NAME_RE.sub("_", name)
    if out and out[0].isdigit():
        out = "_" + out
    return out


def _prom_label_value(v) -> str:
    """Escape a label value per the exposition format."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def prom_labels(labels: Dict[str, object], extra: str = "") -> str:
    """``{k="v",...}`` rendering (sorted keys; '' when empty)."""
    parts = [f'{_prom_name(k)}="{_prom_label_value(v)}"'
             for k, v in sorted(labels.items())]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def percentile_sorted(sorted_vals: Sequence[float], q: float) -> float:
    """THE percentile over raw samples, shared by every surface.

    Linear interpolation between closest ranks (numpy's default /
    Hyndman-Fan type 7): ``pos = q * (n - 1)``, value interpolated
    between ``sorted_vals[floor(pos)]`` and ``sorted_vals[ceil(pos)]``.
    ``/servingz``'s recent-window gauges, the StepStats summaries and
    the decode plane all route through here so a 5-sample window
    reports the SAME p99 everywhere (they used to disagree: the serving
    gauge truncated to a nearest rank while StepStats interpolated)."""
    n = len(sorted_vals)
    if n == 0:
        return 0.0
    if n == 1:
        return float(sorted_vals[0])
    pos = q * (n - 1)
    lo = int(pos)
    hi = min(lo + 1, n - 1)
    frac = pos - lo
    return float(sorted_vals[lo]) * (1 - frac) + float(sorted_vals[hi]) * frac


def histogram_percentile(snap: dict, q: float,
                         finite_max: Optional[float] = None) -> float:
    """Quantile estimate from a fixed-bucket snapshot (``{"buckets":
    {le: cumulative}, "count": n}`` — :meth:`Histogram.snapshot`).

    Prometheus ``histogram_quantile`` semantics: find the first bucket
    whose cumulative count reaches ``q * count``, then linearly
    interpolate INSIDE that bucket assuming observations are uniform
    over ``(lower_edge, upper_edge]`` (the first bucket interpolates
    from 0).  Returning the raw upper edge (the old behavior) made
    every small-window quantile snap to a bucket boundary and disagree
    with the raw-sample surfaces; interpolation keeps the estimate
    inside the same bucket but boundary-continuous.  The +Inf bucket
    has no finite width, so a quantile landing there reports the
    largest finite edge (``finite_max`` override) — the honest lower
    bound."""
    total = snap.get("count", 0)
    if not total:
        return 0.0
    target = q * total
    prev_le, prev_cum = 0.0, 0
    last_finite = 0.0
    edges = []
    for le, cum in snap["buckets"].items():
        le_f = float(le) if not isinstance(le, str) else (
            float("inf") if le == "+Inf" else float(le))
        edges.append((le_f, cum))
    for le_f, cum in sorted(edges):
        if le_f != float("inf"):
            last_finite = le_f
        if cum >= target:
            if le_f == float("inf"):
                return finite_max if finite_max is not None else prev_le
            in_bucket = cum - prev_cum
            if in_bucket <= 0:
                return le_f
            frac = (target - prev_cum) / in_bucket
            return prev_le + (le_f - prev_le) * min(max(frac, 0.0), 1.0)
        prev_le, prev_cum = (le_f if le_f != float("inf") else prev_le), cum
    return finite_max if finite_max is not None else last_finite


def _jsonable(v):
    if isinstance(v, dict):
        # histogram bucket keys are floats incl. +Inf: stringify every
        # key so sort_keys never compares str to float
        return {(k if isinstance(k, str) else _prom_num(k)):
                _jsonable(x) for k, x in v.items()}
    return v


def _prom_num(v) -> str:
    """Prometheus floats: +Inf spelled out, integers without .0 noise."""
    if v == float("inf"):
        return "+Inf"
    if isinstance(v, float) and v.is_integer():
        return str(int(v))
    return repr(v) if isinstance(v, float) else str(v)


class Counter:
    """Monotonic counter (``inc`` only; ``reset`` zeroes for tests/bench)."""

    kind = "counter"

    def __init__(self, name: str, help_str: str = ""):
        self.name = name
        self.help = help_str
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, n: int = 1) -> None:
        if n < 0:
            raise ValueError(f"counter {self.name!r} cannot decrease")
        with self._lock:
            self._value += n

    @property
    def value(self):
        with self._lock:
            return self._value

    def reset(self) -> None:
        with self._lock:
            self._value = 0

    def snapshot(self):
        return self.value


class Gauge:
    """Point-in-time value (queue depths, resident bytes, flags)."""

    kind = "gauge"

    def __init__(self, name: str, help_str: str = ""):
        self.name = name
        self.help = help_str
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, v) -> None:
        with self._lock:
            self._value = v

    def inc(self, n=1) -> None:
        with self._lock:
            self._value += n

    def dec(self, n=1) -> None:
        with self._lock:
            self._value -= n

    @property
    def value(self):
        with self._lock:
            return self._value

    def reset(self) -> None:
        with self._lock:
            self._value = 0.0

    def snapshot(self):
        return self.value


class Histogram:
    """Fixed-bucket histogram with Prometheus cumulative-``le`` semantics.

    ``buckets`` are the finite upper bounds (inclusive, sorted); an
    implicit ``+Inf`` bucket catches the tail.  ``observe`` is a bisect +
    two adds under the lock — hot-path safe.
    """

    kind = "histogram"

    def __init__(self, name: str, buckets: Sequence[float] = DEFAULT_MS_BUCKETS,
                 help_str: str = ""):
        b = sorted(float(x) for x in buckets)
        if not b:
            raise ValueError(f"histogram {name!r} needs at least one bucket")
        self.name = name
        self.help = help_str
        self.buckets = tuple(b)
        self._lock = threading.Lock()
        self._counts = [0] * (len(b) + 1)  # last slot = +Inf
        self._sum = 0.0
        self._count = 0

    def observe(self, v: float) -> None:
        v = float(v)
        i = bisect.bisect_left(self.buckets, v)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * (len(self.buckets) + 1)
            self._sum = 0.0
            self._count = 0

    def snapshot(self) -> dict:
        with self._lock:
            counts = list(self._counts)
            total, s = self._count, self._sum
        cum, cum_counts = 0, []
        for c in counts:
            cum += c
            cum_counts.append(cum)
        edges = list(self.buckets) + [float("inf")]
        return {"buckets": {le: c for le, c in zip(edges, cum_counts)},
                "sum": s, "count": total}

    def percentile(self, q: float) -> float:
        """Bucket quantile via the shared :func:`histogram_percentile`
        (linear interpolation inside the covering bucket; the +Inf
        bucket reports the largest finite edge — the honest lower
        bound)."""
        return histogram_percentile(self.snapshot(), q,
                                    finite_max=self.buckets[-1])


class _Scope:
    """Prefixing view over a registry: ``scope('rpc.client').counter('retries')``
    creates/fetches ``rpc.client.retries``."""

    def __init__(self, registry: "StatsRegistry", prefix: str):
        self._registry = registry
        self._prefix = prefix.rstrip(".") + "."

    def counter(self, name: str, help_str: str = "") -> Counter:
        return self._registry.counter(self._prefix + name, help_str)

    def gauge(self, name: str, help_str: str = "") -> Gauge:
        return self._registry.gauge(self._prefix + name, help_str)

    def histogram(self, name: str, buckets: Sequence[float] = DEFAULT_MS_BUCKETS,
                  help_str: str = "") -> Histogram:
        return self._registry.histogram(self._prefix + name, buckets, help_str)

    def scope(self, name: str) -> "_Scope":
        return _Scope(self._registry, self._prefix + name)


class StatsRegistry:
    """Name → metric map; get-or-create, kind-checked, thread-safe."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, object] = {}
        # constant labels stamped on every exported series (e.g.
        # process_index/process_count from parallel/multihost.py) so a
        # fleet scrape can tell per-process exports apart
        self._constant_labels: Dict[str, str] = {}

    def set_constant_labels(self, labels: Dict[str, object]) -> None:
        """Replace the constant label set ({} clears).  Applied at export
        time only — metric objects and snapshots are label-free."""
        with self._lock:
            self._constant_labels = {str(k): str(v)
                                     for k, v in (labels or {}).items()}

    def constant_labels(self) -> Dict[str, str]:
        with self._lock:
            return dict(self._constant_labels)

    def _get_or_create(self, name: str, factory, kind: str):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = factory()
                self._metrics[name] = m
            elif m.kind != kind:
                raise TypeError(
                    f"metric {name!r} already registered as {m.kind}, "
                    f"requested {kind}")
            return m

    def counter(self, name: str, help_str: str = "") -> Counter:
        return self._get_or_create(
            name, lambda: Counter(name, help_str), "counter")

    def gauge(self, name: str, help_str: str = "") -> Gauge:
        return self._get_or_create(
            name, lambda: Gauge(name, help_str), "gauge")

    def histogram(self, name: str, buckets: Sequence[float] = DEFAULT_MS_BUCKETS,
                  help_str: str = "") -> Histogram:
        h = self._get_or_create(
            name, lambda: Histogram(name, buckets, help_str), "histogram")
        if tuple(sorted(float(x) for x in buckets)) != h.buckets:
            raise ValueError(
                f"histogram {name!r} already registered with buckets "
                f"{h.buckets}")
        return h

    def scope(self, prefix: str) -> _Scope:
        return _Scope(self, prefix)

    def get(self, name: str):
        with self._lock:
            return self._metrics.get(name)

    def names(self) -> Iterable[str]:
        with self._lock:
            return sorted(self._metrics)

    def snapshot(self) -> Dict[str, object]:
        """{name: value} for counters/gauges, {name: {buckets,sum,count}}
        for histograms — JSON-ready except the +Inf key (see to_json)."""
        with self._lock:
            items = sorted(self._metrics.items())
        return {name: m.snapshot() for name, m in items}

    def to_prometheus_text(self) -> str:
        """Prometheus text exposition format, one family per metric."""
        with self._lock:
            items = sorted(self._metrics.items())
            clabels = dict(self._constant_labels)
        base = prom_labels(clabels)
        lines = []
        for name, m in items:
            pn = _prom_name(name)
            if m.help:
                lines.append(f"# HELP {pn} {m.help}")
            lines.append(f"# TYPE {pn} {m.kind}")
            if isinstance(m, Histogram):
                snap = m.snapshot()
                for le, cum in snap["buckets"].items():
                    lines.append(
                        pn + "_bucket"
                        + prom_labels(clabels, f'le="{_prom_num(le)}"')
                        + f" {cum}")
                lines.append(f"{pn}_sum{base} {_prom_num(snap['sum'])}")
                lines.append(f"{pn}_count{base} {snap['count']}")
            else:
                lines.append(f"{pn}{base} {_prom_num(m.snapshot())}")
        return "\n".join(lines) + ("\n" if lines else "")

    def to_dict(self) -> Dict[str, object]:
        """JSON-ready ``snapshot()``: histogram bucket keys (floats incl.
        +Inf) stringified so the dict survives ``json.dumps`` untouched —
        the shape ``observability.export()`` embeds directly."""
        return {k: _jsonable(v) for k, v in self.snapshot().items()}

    def export_state(self) -> dict:
        """Merge-ready wire form for cross-worker aggregation
        (observability/aggregate.py): every metric tagged with its kind,
        histogram buckets as stringified cumulative-``le`` counts, plus
        this process's constant labels."""
        with self._lock:
            items = sorted(self._metrics.items())
            clabels = dict(self._constant_labels)
        metrics = {}
        for name, m in items:
            if isinstance(m, Histogram):
                snap = _jsonable(m.snapshot())
                metrics[name] = {"kind": m.kind, **snap}
            else:
                metrics[name] = {"kind": m.kind, "value": m.snapshot()}
        return {"labels": clabels, "metrics": metrics}

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps({"ts": time.time(), "metrics": self.to_dict()},
                          indent=indent, sort_keys=True)

    def dump_json(self, path: str, indent: int = 2) -> None:
        with open(path, "w") as f:
            f.write(self.to_json(indent=indent))

    def reset(self) -> None:
        """Zero every metric IN PLACE (handles held by call sites stay
        valid)."""
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            m.reset()

    def clear(self) -> None:
        """Drop every registration (tests only: held handles detach)."""
        with self._lock:
            self._metrics.clear()


_default = StatsRegistry()


def default_registry() -> StatsRegistry:
    return _default


# module-level conveniences over the default registry
def counter(name: str, help_str: str = "") -> Counter:
    return _default.counter(name, help_str)


def gauge(name: str, help_str: str = "") -> Gauge:
    return _default.gauge(name, help_str)


def histogram(name: str, buckets: Sequence[float] = DEFAULT_MS_BUCKETS,
              help_str: str = "") -> Histogram:
    return _default.histogram(name, buckets, help_str)


def scope(prefix: str) -> _Scope:
    return _default.scope(prefix)


def snapshot() -> Dict[str, object]:
    return _default.snapshot()


def to_prometheus_text() -> str:
    return _default.to_prometheus_text()


def to_dict() -> Dict[str, object]:
    return _default.to_dict()


def export_state() -> dict:
    return _default.export_state()


def to_json(indent: Optional[int] = None) -> str:
    return _default.to_json(indent)


def dump_json(path: str, indent: int = 2) -> None:
    _default.dump_json(path, indent)


def reset() -> None:
    _default.reset()
