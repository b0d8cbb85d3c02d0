"""Performance attribution plane: XLA cost/memory analysis + rooflines.

The perf twin of the tracing plane (PR 4): instead of guessing where
time goes from wall clocks, attribute it from the XLA side.  On every
executable build — fresh compile, AOT warm start, or compile-cache
hydrate — the executor harvests the compiled executable's
``cost_analysis()`` (flops, bytes accessed, transcendentals) and
``memory_analysis()`` (argument/output/temp/generated-code bytes) into
a bounded table of :class:`PerfRecord`\\ s keyed by the executable's
cache identity.  Each ``Executor.run`` then feeds its measured wall
time back into the record, so every executable carries a live roofline
position: achieved FLOP/s, achieved HBM bandwidth, arithmetic
intensity, and the fraction of the platform peak table
(``platform.PLATFORM_PEAKS``) it reaches — compute-bound vs
memory-bound is data, not folklore.

Alongside the per-executable records, :func:`sample_device_memory`
reads the live PJRT device-memory stats (``bytes_in_use``,
``peak_bytes_in_use``, ``bytes_limit`` per ``jax.local_devices()``
entry, plus host RSS) into ``device_mem.*`` gauges on the stats
registry — which means the fleet view comes for free over the existing
``STATS_PULL`` aggregation path, per-worker labeled like every other
gauge.

Served by the debug server as ``/profilez`` (records + rooflines) and
``/memz`` (live memory), JSON by default, ``?text=1`` for the human
rendering; ``tools/dump_metrics.py --profilez/--memz`` is the operator
CLI.

Strictly opt-in: with ``FLAGS_perf_attribution`` unset (default) the
executor never calls in here beyond one flag read, the lazy-jit build
path is untouched, and no gauges are created.  When set, executables
are compiled ahead-of-time (``lower().compile()`` — the same
executable, eagerly) so the compiled handle is analyzable.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional

from . import debug_server as _debug_server
from . import stats as _stats
from ..core import flags as _flags

# bounded: a shape-churning process must not leak perf records
_RECORD_CAP = 256
# wall-time samples retained per record for the roofline summary
_WALL_WINDOW = 64

_lock = threading.Lock()
_records: "OrderedDict[str, PerfRecord]" = OrderedDict()
_seq = 0

_perf_metrics = None


def enabled() -> bool:
    """Is cost/memory attribution on (``FLAGS_perf_attribution``)?"""
    try:
        return bool(_flags.get_flags("perf_attribution"))
    except KeyError:  # pragma: no cover - flag always defined
        return False


def _pm():
    """Cached perf metric handles (same rationale as the executor's)."""
    global _perf_metrics
    m = _perf_metrics
    if m is None:
        sc = _stats.scope("perf")
        import types as _t
        m = _t.SimpleNamespace(
            executables=sc.counter(
                "executables", "executables harvested for cost/memory "
                "attribution"),
            harvest_errors=sc.counter("harvest_errors"),
            achieved_gflops=sc.gauge(
                "last_achieved_gflops",
                "achieved GFLOP/s of the most recently observed step"),
            achieved_gbps=sc.gauge(
                "last_achieved_gbps",
                "achieved HBM GB/s of the most recently observed step"),
            peak_frac=sc.gauge(
                "last_frac_of_peak_flops",
                "achieved/peak FLOP/s of the most recent step (0 when "
                "the platform peak is unknown)"),
        )
        _perf_metrics = m
    return m


def cost_dict(compiled) -> dict:
    """``cost_analysis()`` as a plain dict; {} when the executable
    cannot report.  Public: ``pipeline/transpiler.py xla_stage_flops``
    reads each stage's cost through this."""
    return dict(compiled.cost_analysis() or {})


def _memory_dict(compiled) -> dict:
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    out = {}
    for field, key in (
            ("argument_size_in_bytes", "argument_bytes"),
            ("output_size_in_bytes", "output_bytes"),
            ("temp_size_in_bytes", "temp_bytes"),
            ("alias_size_in_bytes", "alias_bytes"),
            ("generated_code_size_in_bytes", "generated_code_bytes")):
        v = getattr(ma, field, None)
        if v is not None:
            out[key] = int(v)
    if out:
        # resident estimate while the executable runs: args + outputs +
        # scratch + code, minus donated/aliased buffers counted twice
        out["peak_bytes"] = (out.get("argument_bytes", 0)
                             + out.get("output_bytes", 0)
                             + out.get("temp_bytes", 0)
                             + out.get("generated_code_bytes", 0)
                             - out.get("alias_bytes", 0))
    return out


class PerfRecord:
    """Cost/memory attribution + live wall-time window for ONE compiled
    executable (one executor-cache slot)."""

    __slots__ = ("key", "source", "mode", "flops", "bytes_accessed",
                 "transcendentals", "memory", "compile_ms", "steps",
                 "walls", "created_ts")

    def __init__(self, key: str, source: str, mode: str,
                 cost: dict, memory: dict,
                 compile_ms: Optional[float] = None):
        self.key = key
        self.source = source          # "compile" | "disk"
        self.mode = mode              # "run" | "run_steps"
        self.flops = float(cost.get("flops", 0.0) or 0.0)
        self.bytes_accessed = float(cost.get("bytes accessed", 0.0) or 0.0)
        self.transcendentals = float(cost.get("transcendentals", 0.0) or 0.0)
        self.memory = dict(memory)
        self.compile_ms = compile_ms
        self.steps = 0
        self.walls: deque = deque(maxlen=_WALL_WINDOW)
        self.created_ts = time.time()

    def observe(self, wall_ms: float) -> None:
        # under the module lock: /profilez sorts the walls window from
        # the HTTP thread while the executor appends from the training
        # thread (deque iteration raises on concurrent mutation)
        with _lock:
            self.steps += 1
            self.walls.append(float(wall_ms))

    def wall_ms_p50(self) -> float:
        with _lock:
            w = sorted(self.walls)
        return w[len(w) // 2] if w else 0.0

    def summary(self, peaks: Optional[dict] = None) -> dict:
        wall = self.wall_ms_p50()  # once: one lock+sort, and the
        # reported p50 always matches the rates computed from it
        out = {
            "key": self.key,
            "source": self.source,
            "mode": self.mode,
            "steps": self.steps,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "transcendentals": self.transcendentals,
            "memory": dict(self.memory),
            "compile_ms": self.compile_ms,
            "wall_ms_p50": round(wall, 3),
        }
        out.update(roofline_numbers(
            self.flops, self.bytes_accessed,
            wall / 1e3 if wall > 0 else None, peaks=peaks))
        return out


def roofline_numbers(flops: float, bytes_accessed: float,
                     seconds: Optional[float],
                     peaks: Optional[dict] = None) -> dict:
    """The roofline arithmetic of the executor's records: achieved rates
    from ``seconds``, arithmetic intensity, position vs the platform
    peak table.

    ``peaks`` defaults to ``platform.platform_peaks()``; pass
    ``{"flops": None}``-shaped dicts to skip the peak comparison.
    Per-step vs per-dispatch normalization cancels in the rates: a
    run_steps executable's flops cover K steps, and so does its wall.
    """
    out: Dict[str, object] = {}
    if flops and bytes_accessed:
        out["intensity_flops_per_byte"] = round(flops / bytes_accessed, 3)
    if seconds and seconds > 0:
        if flops:
            out["achieved_gflops"] = round(flops / seconds / 1e9, 3)
        if bytes_accessed:
            out["achieved_gbps"] = round(bytes_accessed / seconds / 1e9, 3)
    if peaks is None:
        peaks = platform_peaks_cached()
    peak_fl = peaks.get("flops")
    peak_bw = peaks.get("hbm_bytes_per_s")
    if peak_fl and peak_bw:
        out["peak_gflops"] = round(peak_fl / 1e9, 1)
        out["peak_gbps"] = round(peak_bw / 1e9, 1)
        if peaks.get("nominal"):
            out["peaks_nominal"] = True
        if flops and bytes_accessed:
            balance = peak_fl / peak_bw  # machine balance, flops/byte
            out["machine_balance_flops_per_byte"] = round(balance, 3)
            out["bound"] = ("compute"
                            if flops / bytes_accessed >= balance
                            else "memory")
        if seconds and seconds > 0:
            frac_fl = flops / seconds / peak_fl if flops else 0.0
            frac_bw = (bytes_accessed / seconds / peak_bw
                       if bytes_accessed else 0.0)
            if flops:
                out["frac_of_peak_flops"] = round(frac_fl, 4)
            if bytes_accessed:
                out["frac_of_peak_hbm"] = round(frac_bw, 4)
            # position against the roofline ceiling: how close the
            # dominant axis is to its limit
            out["roofline_frac"] = round(max(frac_fl, frac_bw), 4)
    return out


_peaks_cache = None


def platform_peaks_cached() -> dict:
    """``platform.platform_peaks()`` memoized (device kind never changes
    within a process; the lookup walks jax.local_devices())."""
    global _peaks_cache
    if _peaks_cache is None:
        try:
            from .. import platform as _platform
            _peaks_cache = _platform.platform_peaks()
        except Exception:  # pragma: no cover - backend init failure
            _peaks_cache = {"device_kind": "unknown", "platform": "unknown",
                            "flops": None, "hbm_bytes_per_s": None}
    return _peaks_cache


def harvest(compiled, source: str, mode: str,
            compile_ms: Optional[float] = None) -> Optional[PerfRecord]:
    """Build + register a :class:`PerfRecord` for a freshly resolved
    executable.  Never raises — attribution must never fail a run; a
    handle that cannot report (e.g. a deserialized executable on an old
    jaxlib) is counted in ``perf.harvest_errors`` and skipped."""
    global _seq
    if not enabled():
        return None
    try:
        cost = cost_dict(compiled)
        memory = _memory_dict(compiled)
    except Exception:
        _pm().harvest_errors.inc()
        return None
    with _lock:
        _seq += 1
        key = f"exe-{_seq}"
    rec = PerfRecord(key, source, mode, cost, memory, compile_ms=compile_ms)
    with _lock:
        _records[key] = rec
        while len(_records) > _RECORD_CAP:
            _records.popitem(last=False)
    _pm().executables.inc()
    return rec


def observe_step(rec: PerfRecord, program_key: str, wall_ms: float) -> None:
    """Feed one measured step wall time into a record (the executor's
    ``_record_step`` calls this with the StepStats wall).  The first
    observation renames the record to the executable's telemetry
    program_key so /profilez and the StepStats ring share an identity."""
    with _lock:
        if rec.key != program_key:
            _records.pop(rec.key, None)
            rec.key = program_key
        if _records.get(program_key) is not rec:
            # first observation renames in; an evicted-then-reobserved
            # record (its _CacheEntry still holds it) re-enters here
            # regardless of key — a still-dispatching executable must
            # stay visible on /profilez.  Re-enforce the table bound
            _records[program_key] = rec
            while len(_records) > _RECORD_CAP:
                _records.popitem(last=False)
    rec.observe(wall_ms)
    if wall_ms > 0:
        m = _pm()
        secs = wall_ms / 1e3
        m.achieved_gflops.set(round(rec.flops / secs / 1e9, 3))
        m.achieved_gbps.set(round(rec.bytes_accessed / secs / 1e9, 3))
        peaks = platform_peaks_cached()
        if peaks.get("flops"):
            m.peak_frac.set(round(rec.flops / secs / peaks["flops"], 4))


def records() -> List[PerfRecord]:
    with _lock:
        return list(_records.values())


def get_record(key: str) -> Optional[PerfRecord]:
    with _lock:
        return _records.get(key)


def reset() -> None:
    """Drop every record (tests / bench config isolation)."""
    with _lock:
        _records.clear()


def _host_rss_bytes() -> Optional[int]:
    try:
        import resource
        import sys
        try:
            with open("/proc/self/statm") as f:
                return int(f.read().split()[1]) * resource.getpagesize()
        except OSError:
            # non-Linux fallback: PEAK rss from getrusage — ru_maxrss
            # is bytes on macOS, kilobytes on Linux/BSD
            peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            return peak if sys.platform == "darwin" else peak * 1024
    except Exception:  # pragma: no cover - exotic hosts
        return None


def sample_device_memory(set_gauges: bool = True) -> dict:
    """Live device-memory snapshot: per-device PJRT ``memory_stats()``
    (bytes_in_use / peak_bytes_in_use / bytes_limit — None each on
    backends that don't report, e.g. CPU) + host RSS.  ``set_gauges``
    mirrors every reported number into ``device_mem.*`` gauges so the
    fleet STATS_PULL merge picks them up."""
    out: Dict[str, object] = {"ts": time.time(), "devices": []}
    sc = _stats.scope("device_mem") if set_gauges else None
    try:
        import jax
        devs = jax.local_devices()
    except Exception as e:  # pragma: no cover - backend init failure
        out["error"] = repr(e)[:200]
        devs = []
    for d in devs:
        try:
            ms = (d.memory_stats() or {}) if hasattr(d, "memory_stats") \
                else {}
        except Exception:
            ms = {}
        rec = {"id": d.id, "kind": str(getattr(d, "device_kind", "")),
               "platform": str(getattr(d, "platform", ""))}
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                    "largest_free_block_bytes"):
            rec[key] = ms.get(key)
            if sc is not None and ms.get(key) is not None:
                sc.gauge(f"d{d.id}.{key}").set(ms[key])
        out["devices"].append(rec)
    rss = _host_rss_bytes()
    out["host_rss_bytes"] = rss
    if sc is not None and rss is not None:
        sc.gauge("host_rss_bytes",
                 "resident set size of this process").set(rss)
    return out


# -- debug-server payloads (/memz, /profilez) -------------------------------

def memz() -> dict:
    # a read-only GET must not change the exported metric surface:
    # gauges only when the perf plane is opted in
    out = sample_device_memory(set_gauges=enabled())
    # memory-anatomy fold-in (FLAGS_memory_attribution): who owns the
    # bytes the PJRT numbers report.  Lazy import + flag guard keep the
    # flag-off page byte-identical.
    from . import memory as _memory
    if _memory.enabled():
        out["attribution"] = _memory.ledger(set_gauges=False)
    return out


def profilez() -> dict:
    peaks = platform_peaks_cached()
    return {"ts": time.time(),
            "enabled": enabled(),
            "platform_peaks": peaks,
            "records": [r.summary(peaks=peaks) for r in records()]}


def _fmt_bytes(n) -> str:
    if n is None:
        return "-"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return str(n)


def memz_text(d: Optional[dict] = None) -> str:
    d = d or memz()
    lines = [f"device memory @ {time.strftime('%H:%M:%S')}"]
    for dev in d.get("devices", []):
        lines.append(
            f"  dev {dev['id']} ({dev.get('kind') or dev.get('platform')}): "
            f"in_use={_fmt_bytes(dev.get('bytes_in_use'))} "
            f"peak={_fmt_bytes(dev.get('peak_bytes_in_use'))} "
            f"limit={_fmt_bytes(dev.get('bytes_limit'))}")
    lines.append(f"  host rss: {_fmt_bytes(d.get('host_rss_bytes'))}")
    if "error" in d:
        lines.append(f"  error: {d['error']}")
    led = d.get("attribution")
    if isinstance(led, dict):
        lines.append("  attribution (FLAGS_memory_attribution):")
        for name, p in sorted((led.get("pools") or {}).items()):
            lines.append(
                f"    {name} [{p.get('kind')}]: "
                f"used={_fmt_bytes(p.get('used'))} "
                f"parked={_fmt_bytes(p.get('parked'))} "
                f"reserved={_fmt_bytes(p.get('reserved'))}")
        for dev, rec in sorted((led.get("devices") or {}).items()):
            lines.append(
                f"    {dev}: in_use={_fmt_bytes(rec.get('bytes_in_use'))} "
                f"attributed={_fmt_bytes(rec.get('attributed'))} "
                f"unattributed={_fmt_bytes(rec.get('unattributed_bytes'))}")
    return "\n".join(lines) + "\n"


def profilez_text(d: Optional[dict] = None) -> str:
    d = d or profilez()
    peaks = d.get("platform_peaks", {})
    lines = [f"perf attribution ({'on' if d.get('enabled') else 'OFF'}) — "
             f"{peaks.get('device_kind') or peaks.get('platform')}"
             + (" [nominal peaks]" if peaks.get("nominal") else "")]
    for r in d.get("records", []):
        lines.append(
            f"  {r['key']} [{r['source']}/{r['mode']}] steps={r['steps']} "
            f"flops={r['flops']:.3g} bytes={r['bytes_accessed']:.3g} "
            f"peak_mem={_fmt_bytes(r.get('memory', {}).get('peak_bytes'))}")
        parts = []
        if "intensity_flops_per_byte" in r:
            parts.append(f"intensity={r['intensity_flops_per_byte']} f/B")
        if "achieved_gflops" in r:
            parts.append(f"achieved={r['achieved_gflops']} GF/s")
        if "achieved_gbps" in r:
            parts.append(f"{r['achieved_gbps']} GB/s")
        if "frac_of_peak_flops" in r:
            parts.append(f"{100 * r['frac_of_peak_flops']:.2f}% peak flops")
        if "frac_of_peak_hbm" in r:
            parts.append(f"{100 * r['frac_of_peak_hbm']:.2f}% peak hbm")
        if "bound" in r:
            parts.append(f"{r['bound']}-bound")
        if parts:
            lines.append("      " + "  ".join(parts))
    if not d.get("records"):
        lines.append("  (no records — FLAGS_perf_attribution=1 and run a "
                     "step)")
    return "\n".join(lines) + "\n"


def export() -> dict:
    """JSON-ready bundle for bench artifacts: records + live memory."""
    return {"profilez": profilez(), "memz": memz()}


def _platform_statusz() -> dict:
    from .. import platform as _platform
    return _platform.device_inventory()


# /statusz hardware card: fleet dashboards label perf series by device
_debug_server.register_provider("platform", _platform_statusz)
