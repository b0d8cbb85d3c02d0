"""From a profiler trace to numbers: device busy and idle share, time per
named device operation, time per launched program, and the idle gaps
attributed to the benchmark's own host spans.

Two steps, so that the arithmetic can be checked by hand on a small recorded
trace (``benchmark/testdata/``): :func:`extract` turns an ``.xplane.pb`` into
a plain dict of intervals — ``{"devices": {plane: {"ops": [[name, start_ns,
dur_ns], ...], "modules": [...]}}, "host": [[name, start_ns, dur_ns], ...]}``,
keeping only host spans whose name starts with ``bench.`` — and
:func:`reduce` turns that dict into the summary the per-layer readers use.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import time
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.IGNORECASE)

Interval = Tuple[float, float]


def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    raw = {"devices": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                dev[key] = [[e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events]
            raw["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        raw["host"].append(
                            [e.name, float(e.start_ns), float(e.duration_ns)])
    return raw


def describe(xplane_path: str, per_line: int = 6) -> List[str]:
    """What a trace holds, for a first look by hand: planes, lines, event
    counts and a few events of each with their stats."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    out = []
    for plane in data.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r}: {len(lines)} line(s)")
        for line in lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} event(s)")
            for e in events[:per_line]:
                stats = {k: (v if not isinstance(v, str) else v[:80])
                         for k, v in list(e.stats)[:8]}
                out.append(f"    {e.name[:100]!r} start={e.start_ns:.0f} "
                           f"dur={e.duration_ns:.0f} {stats}")
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two sorted unions of intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """``a`` minus ``b``, both sorted unions, in one pass over the two: an
    interval of ``b`` that ends before one of ``a`` starts lies before every
    later one too, so the cursor into ``b`` never goes back."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        cur, k = s, j
        while k < len(b) and b[k][0] < e:
            bs, be = b[k]
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def op_label(name: str) -> str:
    """A device event's HLO text cut to its instruction name and result type,
    ``%copy.50 = f32[12,2049,16,12,64]{...} copy(...)`` -> ``copy.50
    f32[12,2049,16,12,64]``.  Instruction names are unique only inside one
    program, so the type keeps apart what merely shares a number."""
    head, _, rest = name.partition(" = ")
    head = head.lstrip("%").strip()
    m = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return f"{head} {m.group(1)}" if m else head


def leaves(ops: Sequence[Sequence]) -> List[Sequence]:
    """The events of one line that contain no other event of it.  A ``while``
    or a ``conditional`` is an event too and spans every operation of its
    body; summed beside them it would count their time twice."""
    order = sorted(ops, key=lambda e: (e[1], -e[2]))
    parent = [False] * len(order)
    stack: List[int] = []
    for i, (_, start, dur) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= start:
            stack.pop()
        if stack and start + dur <= order[stack[-1]][1] + order[stack[-1]][2]:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(order, parent) if not p]


def reduce(raw: dict, span_order: Sequence[str]) -> dict:
    """The summary of one traced window.

    The window is the ``bench.window`` host span.  Per device: busy is the
    union of its operations' intervals inside the window, idle what is left.
    ``busy_s`` and the idle share are averaged over the devices.  Operation
    seconds are summed over the leaf events (:func:`leaves`) clipped to the
    window, program seconds over the launches that lie wholly inside it; both
    are averaged over the devices.  Idle gaps are those of the first device; an
    instant of a gap goes to the first name of ``span_order`` that has a span
    covering it, and to ``unattributed`` if none has."""
    wins = [h for h in raw["host"] if h[0] == WINDOW_SPAN]
    if not wins or not raw["devices"]:
        return {}
    lo = wins[0][1]
    hi = lo + wins[0][2]
    ns = 1e-9
    n_dev = len(raw["devices"])
    busy = 0.0
    ops: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    collective = 0.0
    gaps: List[Interval] = []
    for k, (_, dev) in enumerate(sorted(raw["devices"].items())):
        spans = clip(((s, s + d) for _, s, d in dev["ops"]), lo, hi)
        u = union(spans)
        busy += total(u) * ns / n_dev
        if k == 0:
            gaps = subtract([(lo, hi)], u)
        for name, s, d in leaves(dev["ops"]):
            got = (min(s + d, hi) - max(s, lo)) * ns / n_dev
            if got <= 0:
                continue
            label = op_label(name)
            ops[label] = ops.get(label, 0.0) + got
            if COLLECTIVE.search(label):
                collective += got
        for name, s, d in dev["modules"]:
            if s < lo or s + d > hi:
                continue        # a launch cut by the window's edge
            m = modules.setdefault(op_label(name), [0, 0.0])
            m[0] += 1.0 / n_dev
            m[1] += d * ns / n_dev
    by_span: Dict[str, float] = {}
    left = gaps
    for name in span_order:
        mine = union((s, s + d) for n, s, d in raw["host"] if n == name)
        took = intersect(left, mine)
        by_span[name] = total(took) * ns
        left = subtract(left, took)
    by_span["unattributed"] = total(left) * ns
    window_s = (hi - lo) * ns
    return {"window_s": window_s, "busy_s": busy,
            "idle_share": 1.0 - busy / window_s,
            "op_seconds": ops, "collective_s": collective,
            "modules": {k: {"launches": v[0], "seconds": v[1]}
                        for k, v in modules.items()},
            "idle_gaps": by_span}


def breakdown(summary: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took most
    time and the idle gaps by what the host was doing, at most ``top`` each."""
    ops = sorted(summary["op_seconds"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(summary["idle_gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


class Tracer:
    """One profiler session written under ``out_dir`` (inside the checkout),
    with the Python tracer off: the benchmark's own ``bench.*`` annotations
    and the device planes are all the reduction reads."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.raw: dict = {}
        self.xplane = ""
        self.t_open = 0.0
        self.stop_s = 0.0       # what stop_trace() took, on window()'s thread

    @staticmethod
    def span(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        import jax
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)

    def window(self, seconds: float) -> None:
        """Trace ``seconds`` from now, then stop the session.  Stopping bounds
        the trace, so it happens here, on this thread, while the run's window
        is still open; reading the file is Python that would hold the
        interpreter against the system under test, so :meth:`read` does that
        and the caller calls it once its window has closed and drained."""
        import jax
        with self.span(WINDOW_SPAN):
            self.t_open = time.perf_counter()
            time.sleep(seconds)
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t0

    def read(self) -> None:
        """Find the file the stopped session wrote and extract it."""
        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if found:
            self.xplane = found[-1]
            self.raw = extract(found[-1])

    def add_host_spans(self, spans) -> None:
        """Put the benchmark's own ``(name, start, end)`` spans, stamped with
        ``time.perf_counter()``, on the trace's clock.  The two clocks are
        tied at the opening of the ``bench.window`` span.  (Spans written by
        ``TraceAnnotation`` would be lost where they open before the trace
        starts or close after it stops — most of a serving run's.)"""
        wins = [h for h in self.raw.get("host", []) if h[0] == WINDOW_SPAN]
        if not wins:
            return
        for name, t0, t1 in spans:
            self.raw["host"].append(
                [name, wins[0][1] + (t0 - self.t_open) * 1e9, (t1 - t0) * 1e9])
