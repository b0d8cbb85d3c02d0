"""The program's own spans in the run's own trace, for the readers of the
``program_span`` metrics.

The program marks its regions with ``jax.profiler.TraceAnnotation`` under
``layer::phase`` names (``decode::step``, ``decode::step.emit``,
``executor::dispatch``, ...; ``paddle_tpu/observability/trace.py``), so in a
``--trace 1`` run they lie in the same ``.xplane.pb``, on the same clock, as
the device's operations.  A reader gets only ``ctx``, so :func:`load` finds
the trace itself: the newest ``*.xplane.pb`` under ``<checkout>/.bench_trace/``
(one cell per process, and ``Tracer.start`` has just rewritten that directory).

:func:`extract` turns it into a plain dict, small enough to write by hand in
a test: ``{"window": [start_ns, dur_ns] or None, "spans": [[name, thread,
start_ns, dur_ns, args], ...], "device_ops": [[start_ns, dur_ns], ...]}`` —
every host event whose name contains ``::`` with the number of its thread's
line and its arguments, the ``bench.window`` span, and the operations of the
first device.  A program without such spans (the parent of the PR that added
them) gives an empty ``spans``, and every reader then reports nothing.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from typing import List, Optional, Sequence, Tuple

from benchmark import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRACE_DIR = ".bench_trace"

Interval = Tuple[float, float]


def find_trace(root: Optional[str] = None) -> Optional[str]:
    found = glob.glob(os.path.join(root or ROOT, TRACE_DIR, "**",
                                   "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=2)
def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    raw = {"window": None, "spans": [], "device_ops": []}
    thread = 0
    devices = sorted((p for p in data.planes if tr.DEVICE_PLANE.match(p.name)),
                     key=lambda p: p.name)
    for line in (devices[0].lines if devices else ()):
        if line.name == tr.OPS_LINE:
            raw["device_ops"] = [[float(e.start_ns), float(e.duration_ns)]
                                 for e in line.events]
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread += 1
            for e in line.events:
                if e.name == tr.WINDOW_SPAN and raw["window"] is None:
                    raw["window"] = [float(e.start_ns), float(e.duration_ns)]
                elif "::" in e.name:
                    raw["spans"].append(
                        [e.name, thread, float(e.start_ns),
                         float(e.duration_ns), dict(e.stats)])
    return raw


def load() -> Optional[dict]:
    """The run's own trace, or nothing where there is none to read."""
    path = find_trace()
    return extract(path) if path else None


def window(raw: dict) -> Optional[Interval]:
    w = raw.get("window")
    return (w[0], w[0] + w[1]) if w else None


def inside(raw: dict, pattern: str, how: str = "whole") -> List[list]:
    """The spans whose name matches ``pattern`` (a whole-name regular
    expression) and that lie wholly inside the window — one cut by its edge
    is left out — or, with ``how="start"``, that start inside it."""
    win = window(raw)
    if win is None:
        return []
    lo, hi = win
    rx = re.compile(pattern)
    return [s for s in raw["spans"] if rx.fullmatch(s[0]) and lo <= s[2]
            and (s[2] < hi if how == "start" else s[2] + s[3] <= hi)]


def covered(raw: dict, span: Sequence, pattern: str) -> float:
    """The part of ``span``'s interval, in ns, that spans of its own thread
    whose name matches ``pattern`` cover."""
    rx = re.compile(pattern)
    lo, hi = span[2], span[2] + span[3]
    kids = ((s[2], s[2] + s[3]) for s in raw["spans"]
            if s[1] == span[1] and s is not span and rx.fullmatch(s[0]))
    return tr.total(tr.union(tr.clip(kids, lo, hi)))


def device_idle(raw: dict) -> List[Interval]:
    """The gaps between the first device's operations inside the window."""
    lo, hi = window(raw)
    busy = tr.union(tr.clip(((s, s + d) for s, d in raw["device_ops"]),
                            lo, hi))
    return tr.subtract([(lo, hi)], busy)


def thread_of(raw: dict, name: str) -> Optional[int]:
    """The thread line that holds spans called ``name``: the trace names a
    line by its OS thread (``python``), so the engine's thread is found by
    what it does."""
    for s in raw["spans"]:
        if s[0] == name:
            return s[1]
    return None
