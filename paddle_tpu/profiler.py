"""Profiler: host-side spans + device tracing + Chrome-trace export.

Reference: ``paddle/fluid/platform/profiler.h:73`` (RAII RecordEvent/
RecordBlock), ``profiler.py:221`` context managers, ``device_tracer.h``
(CUPTI device records), ``tools/timeline.py`` Chrome-trace conversion.

TPU mapping: device-side tracing is the XLA profiler's
(``enable_device_trace(dir)`` then ``start_profiler()`` starts a
``jax.profiler`` session → ``.xplane.pb`` for Perfetto/TensorBoard, the
CUPTI analogue).  Every ``RecordEvent`` and every runtime span
(``observability.trace.span``) opens a ``jax.profiler.TraceAnnotation``,
so while ANY profiler session is live — this module's, an operator's
``jax.profiler.start_trace``, the benchmark's ``--trace 1`` — the host
spans land in the same file and time base as the device's
``XLA Ops``/``XLA Modules`` lines.  With no session the annotation is a
no-op.  While ``start_profiler`` is armed the same spans are also filed
in this module's own event list, which feeds the printed summary and
``chrome_trace`` (the reference's report shape; ``tools/timeline.py``
merges such files).
"""
from __future__ import annotations

import contextlib
import functools
import json
import os
import threading
import time
from collections import defaultdict
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

_state = {"enabled": False, "tracer_dir": None}
_events: List[dict] = []
_lock = threading.Lock()

# thread ident → small stable lane id.  ``threading.get_ident() % N``
# could alias two threads into one Chrome-trace lane (idents are reused
# addresses); lanes are assigned densely in first-seen order instead and
# remembered with the thread's name for ``thread_name`` metadata.  The
# OS recycles idents after a thread exits, so a recycled ident whose
# CURRENT thread name differs gets a fresh lane (otherwise short-lived
# workers would inherit a dead thread's lane and its stale label — the
# exact aliasing class the dense mapping exists to fix); both tables are
# bounded (telemetry only: a clear just re-derives lanes on next use).
_lanes: Dict[int, tuple] = {}      # ident -> (lane, thread name)
_lane_names: Dict[int, str] = {}   # lane -> name
_next_lane = 0
_LANE_BOUND = 1024


def is_profiler_enabled() -> bool:
    return _state["enabled"]


def _thread_lane_locked() -> int:
    global _next_lane
    ident = threading.get_ident()
    name = threading.current_thread().name or ""
    ent = _lanes.get(ident)
    if ent is not None and ent[1] == name:
        return ent[0]
    if len(_lane_names) > _LANE_BOUND:
        _lanes.clear()
        _lane_names.clear()
    lane = _next_lane
    _next_lane += 1
    _lanes[ident] = (lane, name)
    _lane_names[lane] = name or f"thread-{lane}"
    return lane


def thread_lane() -> int:
    """This thread's stable lane id (shared with the distributed-trace
    spans so both streams agree on ``tid``)."""
    with _lock:
        return _thread_lane_locked()


def lane_names() -> Dict[int, str]:
    """{lane id: thread name} for ``ph:"M"`` thread_name metadata."""
    with _lock:
        return dict(_lane_names)


def _emit(name: str, t0_ns: int, t1_ns: int, cat: str = "op") -> None:
    """Append one completed span to the event stream.  Internal: the
    runtime telemetry layer (observability/trace.py) reuses it to file
    ``runtime::`` spans alongside user spans."""
    with _lock:
        _events.append({
            "name": name,
            "cat": cat,
            "ts": t0_ns / 1000.0,
            "dur": (t1_ns - t0_ns) / 1000.0,
            "tid": _thread_lane_locked(),
        })


class RecordEvent:
    """RAII span (profiler.h:73).  Usable as context manager or decorator:

        with RecordEvent("step"): ...

        @RecordEvent("step")
        def step(...): ...

    The decorator opens a FRESH span per call (never the shared instance
    state), so decorated functions are re-entrant and thread-safe.
    Keyword arguments become the annotation's arguments (event stats in
    the ``.xplane.pb``).  A subclass that sets ``cpu_clock`` also carries
    ``cpu_ns`` while a ``jax.profiler`` session is live: the CPU time of
    ITS thread between its two ends (``time.thread_time_ns``), so a reader
    has ``duration − cpu_ns``, the time the thread was off the CPU —
    blocked, or queueing for the interpreter.  With no session no clock is
    read and nothing is added; a span opened before the session started
    carries none.  Not every span: the thread clock is a system call, 0.3
    µs a read on a plain Linux; on the v5e's hosts 5.8 µs in a quiet
    process and some 25 µs inside a serving cell (PERF.md §6, PR 56).
    """

    cat = "op"     # the event list's category and name prefix: the
    prefix = ""    # runtime's own spans (observability.trace) set both
    cpu_clock = False

    def __init__(self, name: str, **args):
        self.name = name
        self._args = args
        self._ann = None
        self._t0 = None
        self._cpu0 = None

    def __enter__(self):
        # the annotation is a no-op unless a jax.profiler session is live;
        # then the span lands beside the device ops, in their file
        self._ann = TraceAnnotation(self.name, **self._args)
        self._ann.__enter__()
        if self.cpu_clock and TraceAnnotation.is_enabled():
            self._cpu0 = time.thread_time_ns()
        if _state["enabled"]:
            self._t0 = time.perf_counter_ns()
        return self

    def annotate(self, **args) -> None:
        """Add arguments known only once the region has run."""
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, *a):
        if self._t0 is not None:
            _emit(self.prefix + self.name, self._t0,
                  time.perf_counter_ns(), cat=self.cat)
            self._t0 = None
        ann, self._ann = self._ann, None
        if ann is not None:
            if self._cpu0 is not None:
                ann.set_metadata(cpu_ns=time.thread_time_ns() - self._cpu0)
                self._cpu0 = None
            ann.__exit__(*a)
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with RecordEvent(self.name):
                return fn(*args, **kwargs)
        return wrapper


record_event = RecordEvent  # snake_case alias


def start_profiler(state: str = "All", tracer_option=None) -> None:
    """state ∈ {CPU, GPU, All} kept for API parity; device tracing starts an
    XLA profiler session when a trace dir was configured."""
    _state["enabled"] = True
    if _state["tracer_dir"]:
        import jax
        jax.profiler.start_trace(_state["tracer_dir"])


def stop_profiler(sorted_key: Optional[str] = "total",
                  profile_path: Optional[str] = None) -> None:
    _state["enabled"] = False
    if _state["tracer_dir"]:
        import jax
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        _state["tracer_dir"] = None
    if profile_path:
        chrome_trace(profile_path)
    print_summary(sorted_key)


def enable_device_trace(logdir: str) -> None:
    """Arm XLA (xplane) device tracing for the next start_profiler."""
    _state["tracer_dir"] = logdir


def reset_profiler() -> None:
    with _lock:
        _events.clear()


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: str = "total",
             profile_path: Optional[str] = None):
    """with profiler.profiler(...): ... (reference profiler.py:221)."""
    reset_profiler()
    start_profiler(state)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


def events() -> List[dict]:
    with _lock:
        return list(_events)


def print_summary(sorted_key: str = "total") -> None:
    agg = defaultdict(lambda: {"calls": 0, "total": 0.0, "max": 0.0})
    with _lock:
        for e in _events:
            a = agg[e["name"]]
            a["calls"] += 1
            a["total"] += e["dur"]
            a["max"] = max(a["max"], e["dur"])
    if not agg:
        return
    rows = sorted(agg.items(), key=lambda kv: -kv[1]["total"])
    if sorted_key == "calls":
        rows = sorted(agg.items(), key=lambda kv: -kv[1]["calls"])
    width = max(len(n) for n, _ in rows)
    print(f"{'Event':<{width}}  {'Calls':>8} {'Total(us)':>12} "
          f"{'Avg(us)':>12} {'Max(us)':>12}")
    for name, a in rows:
        print(f"{name:<{width}}  {a['calls']:>8} {a['total']:>12.1f} "
              f"{a['total'] / a['calls']:>12.1f} {a['max']:>12.1f}")


def chrome_trace(path: str) -> None:
    """Write catapult trace-event JSON (tools/timeline.py output format).

    Includes ``ph:"M"`` ``process_name``/``thread_name`` metadata so
    Perfetto labels the process row and every thread lane instead of
    showing bare numeric ids."""
    pid = os.getpid()
    with _lock:
        events = [
            {"name": e["name"], "cat": e.get("cat", "op"), "ph": "X",
             "pid": pid, "tid": e["tid"], "ts": e["ts"], "dur": e["dur"]}
            for e in _events
        ]
        names = dict(_lane_names)
    meta = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": f"paddle_tpu (pid {pid})"}}]
    for lane in sorted(names):
        meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": lane, "args": {"name": names[lane]}})
    with open(path, "w") as f:
        json.dump({"traceEvents": meta + events}, f)


def cuda_profiler(*a, **kw):  # parity stub: no CUDA on this backend
    return contextlib.nullcontext()
