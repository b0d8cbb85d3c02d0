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

A faster program launches more and shorter programs in the same window, so
no reader may cost more than n log n in the trace's events: what several
readers or several spans ask of one trace (:func:`derived`) is worked out
once and kept with it, and children are found by bisection (:func:`covered`).
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from typing import Callable, List, Optional, Sequence, Tuple

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


def derived(raw: dict, key, make: Callable[[dict], object]):
    """``make(raw)``, worked out at the first call for ``key`` and kept with
    the trace it was read from: every metric's reader is loaded as a module of
    its own and gets the same ``raw`` from :func:`load`."""
    memo = raw.setdefault("derived", {})
    if key not in memo:
        memo[key] = make(raw)
    return memo[key]


def _children(raw: dict, pattern: str) -> dict:
    """``{thread: (spans, starts, reach)}`` of the spans whose name matches
    ``pattern``: sorted by start, with ``reach[i]`` the latest end among the
    first ``i + 1`` of them."""
    rx = re.compile(pattern)
    out: dict = {}
    for s in raw["spans"]:
        if rx.fullmatch(s[0]):
            out.setdefault(s[1], []).append(s)
    for thread, spans in out.items():
        spans.sort(key=lambda s: s[2])
        reach, far = [], float("-inf")
        for s in spans:
            far = max(far, s[2] + s[3])
            reach.append(far)
        out[thread] = (spans, [s[2] for s in spans], reach)
    return out


def covered(raw: dict, span: Sequence, pattern: str) -> float:
    """The part of ``span``'s interval, in ns, that spans of its own thread
    whose name matches ``pattern`` cover.  The thread's matching spans are
    indexed once; the ones that can touch the interval start before its end
    (bisection) and lie after the last index whose ``reach`` stops short of its
    start, so a span costs its own children and not the trace."""
    spans, starts, reach = derived(
        raw, ("children", pattern), lambda r: _children(r, pattern)).get(
        span[1], ((), (), ()))
    lo, hi = span[2], span[2] + span[3]
    kids = []
    i = bisect.bisect_left(starts, hi) - 1
    while i >= 0 and reach[i] > lo:
        if spans[i] is not span:
            kids.append((spans[i][2], spans[i][2] + spans[i][3]))
        i -= 1
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
