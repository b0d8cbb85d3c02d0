"""A named kernel's share of the chip's roofline inside the launches of a
named program, with the work counted over the very launches that are timed.

``program`` and ``kernel`` are regular expressions: the first picks launches
on the ``XLA Modules`` line (as ``program_ms.py``), the second the device
operations by their instruction name — a ``pallas_call``'s name is its
instruction's (``moe_grouped_swiglu.3``).  ``span`` names the span in which
the program's observer files a launch's own work (``decode::step.observe``:
its arguments are what that launch added to the ``decode.<model>.*`` counters,
under the counters' names).  The engine waits for a launch's result before it
observes it and before it dispatches the next, so a launch's span is the
first one that starts after the launch ends and before the next launch of the
program starts.  A launch counts only if it lies wholly in the traced window
and has its span: the kernel's seconds are summed over those launches, the
work (``COUNTS[count]`` over the summed arguments) over the same ones, and a
prompt-length mix that differs between the traced seconds and the whole
window moves nothing.  ``counts`` names the file whose ``COUNTS`` holds the
model's counting functions, by its path from the checkout's root
(``benchmark/kernel_counts.py`` where a metric names none): a model brings a
counts file and metric files, and no reader.  A path that leaves the
benchmark's own directories is refused (by ``check_manifest`` and here).

The share, in %, is the larger of operations per second over the chip's bf16
peak and bytes per second over its HBM peak.  Nothing where the run has no
raw trace, no such launch, no such kernel or no such span (the parent of the
PR that added them).

One pass: launches, spans and leaf operations are sorted once and walked
together.
"""
import bisect
import functools
import re

from benchmark import harness, peaks, trace_reduce
from benchmark.metrics import program_spans

DEFAULT_COUNTS = "benchmark/kernel_counts.py"

# the device plane's clock and the host's differ by under a millisecond on a
# v5e; a launch lasts tens of them
SLACK_NS = 2e6


def timed(raw, spans, program, kernel, span):
    """(kernel seconds, summed span arguments, launches) over the launches of
    ``program`` on the first device that lie in the window and have their
    ``span``; None where there is none."""
    win = [h for h in raw.get("host", []) if h[0] == trace_reduce.WINDOW_SPAN]
    if not win or not raw.get("devices"):
        return None
    lo, hi = win[0][1], win[0][1] + win[0][2]
    dev = raw["devices"][sorted(raw["devices"])[0]]
    prog, kern, named = (re.compile(program), re.compile(kernel),
                         re.compile(span))
    launches = sorted((s, s + d) for name, s, d in dev["modules"]
                      if s >= lo and s + d <= hi
                      and prog.search(trace_reduce.op_label(name)))
    filed = sorted((s[2], s[4]) for s in spans.get("spans", [])
                   if named.fullmatch(s[0]))
    starts = [s for s, _ in filed]
    kept, work = [], {}
    for i, (s, e) in enumerate(launches):
        j = bisect.bisect_left(starts, e - SLACK_NS)
        before = launches[i + 1][0] if i + 1 < len(launches) else float("inf")
        if j == len(filed) or starts[j] >= before:
            continue
        kept.append((s, e))
        for key, value in filed[j][1].items():
            try:
                work[key] = work.get(key, 0.0) + float(value)
            except (TypeError, ValueError):
                pass
    if not kept:
        return None
    ops = sorted((s, s + d) for name, s, d in trace_reduce.leaves(dev["ops"])
                 if kern.search(trace_reduce.op_label(name)))
    inside, j = 0.0, 0
    for s, e in ops:
        while j < len(kept) and kept[j][1] <= s:
            j += 1
        if j < len(kept) and kept[j][0] <= s and e <= kept[j][1]:
            inside += e - s
    return inside * 1e-9, work, len(kept)


@functools.lru_cache(maxsize=None)
def counting(counts):
    """The ``COUNTS`` of the counts file at ``counts``, a path from the root
    of the checkout to a file under the benchmark's ``paths``: the operations
    and bytes are the yardstick's to count, never the program's."""
    root = program_spans.ROOT
    path = harness.yardstick_module(root, harness.load_manifest(root), counts)
    if path is None:
        raise harness.ConfigurationError(
            f"'counts' names no module under paths: {counts!r}")
    return harness.load_module(
        path, "benchmark_counts_" + re.sub(r"\W", "_", counts)).COUNTS


def share(ctx, spans, program, kernel, count, span, counts=DEFAULT_COUNTS):
    got = timed(ctx["trace_raw"], spans, program, kernel, span)
    if got is None or got[0] <= 0:
        return None
    seconds, work, _ = got
    try:
        ops, moved = counting(counts)[count](ctx["config"], work)
    except KeyError:
        return None
    peak = peaks.peaks_for(ctx["memory"]["kind"])
    return 100.0 * max(ops / seconds / peak["bf16_flops_per_s"],
                       moved / seconds / peak["hbm_bytes_per_s"])


def read(ctx, program, kernel, count, span, counts=DEFAULT_COUNTS):
    spans = program_spans.load() if ctx.get("trace_raw") else None
    if not spans:
        return None
    return share(ctx, spans, program, kernel, count, span, counts)
