"""What a launch costs the decode engine's thread OFF the CPU, in ms a decode
step: the time it had host work and was not running, plus the time it took to
come back once the device had finished.

While a profiler session is live the engine's two launch spans,
``decode::step`` and ``decode::prefill``, and their ``.wait`` children carry
``cpu_ns``, the CPU time of their thread between their two ends
(``paddle_tpu/observability/trace.py cpu_span``).  On the engine thread's line
(the one that holds ``decode::step``), for every launch span wholly inside the
window that carries it and whose ``.wait`` does:

- ``off(s)`` = a span's duration less its ``cpu_ns``.  NOT floored a span:
  the thread clock may tick coarsely (on the v5e's hosts it advances 10 ms
  at a time, so one span reads 0 or a whole tick) and only a sum over the
  window's launches means anything — everything below is summed before it
  is subtracted, and the line says how many steps of the clock the sums rest
  on (``ticks``: a mean CPU time is good to ``step x sqrt(ticks) /
  launches``).  The spans whose ``cpu_ns`` exceeds their duration are
  counted and printed: on a fine clock they say the two clocks disagree;
- **queued** = ``off(launch) - off(its .wait)``: outside the wait the thread
  always has work, so this is the queue for the interpreter, whatever blocks
  inside the packed transfer and the launch, and the OS taking the core away;
- **hand-back** = the ``.wait``'s end less the later of its start and the end
  of the launch's last device operation, less the ``.wait``'s own ``cpu_ns``
  (the mean floored at 0): the device had finished and the thread had not
  come back.  One launch is in flight at a time, so the first device's busy
  intervals are dealt out in order: a launch's are those of the stretches
  (intervals less than ``STRETCH_GAP`` apart) that start before its ``.wait``
  ends and belong to no earlier one.  The device plane's clock is off the
  host's by a fraction of a millisecond either way, a session: where it
  lags, the launch seems to outlast its wait and the hand-back reads 0;
  where it leads, the hand-back reads that much long (``dispatch to first
  op`` below holds the offset plus the launch's own latency);
- the metric is the mean over the ``decode::step`` launches of queued +
  hand-back, floored at 0.

One ``bench spans:`` line a launch kind, once a trace, gives the two parts,
the device's idle time BETWEEN the launch's operations (what the idle
partition's ``wait`` term holds that is not the host's), the thread's own CPU
time and the part of it inside the wait, and the median time from the opening
of ``executor::dispatch`` to the launch's first operation (negative: the
device's clock is ahead).

Nothing where no ``decode::step`` carries ``cpu_ns`` (a program from before
the argument).  Cost: a sort of the thread's spans and of the device's
operations, then bisections a launch."""
import bisect
import itertools
import statistics

from benchmark import trace_reduce as tr
from benchmark.metrics import program_spans

STEP, PREFILL = "decode::step", "decode::prefill"
CPU = "cpu_ns"
# ns.  A launch's own operations follow one another within tens of µs; two
# launches are at least a dispatch (0.4 ms) apart
STRETCH_GAP = 2e5


def read(ctx):
    raw = program_spans.load()
    if not raw:
        return None
    step = program_spans.derived(raw, "engine_off_cpu", _split_printed).get(
        STEP)
    return step["total"] if step else None


def _split_printed(raw):
    """The split, printed where it is worked out: once for a trace, whichever
    entry reads it."""
    found = split(raw)
    for kind, m in found.items():
        print(f"bench spans: engine thread off the CPU, ms a "
              f"{kind.split('::')[1]}: total={m['total']:.4f} "
              f"queued={m['queued']:.4f} handback={m['handback']:.4f}"
              f" | in-launch gaps={m['gaps']:.4f} | on CPU={m['on_cpu']:.4f}"
              f" (in the wait {m['wait_cpu']:.4f})"
              f" | dispatch to first op={m['first_op']:.4f}"
              f" | {m['launches']} launches, cpu_ns over the duration in "
              f"{m['over']} of {m['spans']} spans, {m['ticks']} ticks of "
              f"{m['clock_step']:.4f}", flush=True)
    return found


def split(raw):
    """``{launch kind: means in ms}`` over the launches of each kind that lie
    wholly inside the window: ``total`` (the metric) = ``queued`` +
    ``handback``, ``gaps``, ``on_cpu`` and ``wait_cpu`` (the launch's CPU
    time and its wait's), ``first_op`` (a median), ``clock_step`` (the
    smallest ``cpu_ns`` above 0 that was read) and the counts ``launches``,
    ``spans`` read, ``over`` (``cpu_ns`` above the duration) and ``ticks``
    (the launches' CPU time in clock steps)."""
    thread = program_spans.thread_of(raw, STEP)
    if thread is None:
        return {}
    mine = sorted((s for s in raw["spans"] if s[1] == thread),
                  key=lambda s: s[2])
    starts = [s[2] for s in mine]
    busy = tr.union((s, s + d) for s, d in raw["device_ops"])
    device = ([b[0] for b in busy], [b[1] for b in busy],
              [0.0, *itertools.accumulate(e - s for s, e in busy)])
    stretch = []            # where the stretch an interval lies in begins
    for i, (b, _) in enumerate(busy):
        stretch.append(stretch[-1] if i and b - busy[i - 1][1] < STRETCH_GAP
                       else b)
    # every wait's share of the busy intervals, by the wait's start
    owns, dealt = {}, 0
    for w in sorted((s for s in mine if s[0].endswith(".wait")),
                    key=lambda s: s[2] + s[3]):
        upto = max(dealt, bisect.bisect_right(stretch, w[2] + w[3]))
        owns[w[2]], dealt = (dealt, upto), upto
    out = {}
    for kind in (STEP, PREFILL):
        rows = []
        for span in program_spans.inside(raw, kind):
            if span[1] != thread or CPU not in span[4]:
                continue
            lo, hi = span[2], span[2] + span[3]
            row = _launch(span, mine[bisect.bisect_left(starts, lo):
                                     bisect.bisect_left(starts, hi)],
                          owns, device)
            if row:
                rows.append(row)
        if rows:
            out[kind] = _means(rows)
    return out


def _launch(span, inside, owns, device):
    """One launch in ns; ``inside`` is its thread's spans that start in it,
    by start.  None where its wait carries no ``cpu_ns``."""
    kind = span[0]
    wait = next((s for s in inside if s[0] == kind + ".wait"), None)
    dispatch = next((s for s in inside if s[0] == "executor::dispatch"), None)
    cpu = float(span[4][CPU])
    row = {"off": span[3] - cpu, "on_cpu": cpu, "wait_off": 0.0,
           "wait_cpu": 0.0, "handback": 0.0, "gaps": 0.0, "first_op": None,
           "spans": 1, "over": int(cpu > span[3])}
    if wait is None:              # a step that launched nothing: all queued
        return row
    if CPU not in wait[4]:
        return None
    row["wait_cpu"] = float(wait[4][CPU])
    row["wait_off"] = wait[3] - row["wait_cpu"]
    row["spans"] += 1
    row["over"] += row["wait_off"] < 0
    begins, ends, summed = device
    w_lo, w_hi = wait[2], wait[2] + wait[3]
    first, after = owns[wait[2]]
    done = w_lo
    if after > first:
        done = min(max(w_lo, ends[after - 1]), w_hi)
        row["gaps"] = (ends[after - 1] - begins[first]) - (
            summed[after] - summed[first])
        if dispatch is not None:
            row["first_op"] = begins[first] - dispatch[2]
    row["handback"] = w_hi - done - row["wait_cpu"]
    return row


def _means(rows):
    n, ms = len(rows), 1e-6

    def mean(key):
        return sum(r[key] for r in rows) / n * ms

    queued = mean("off") - mean("wait_off")
    handback = max(0.0, mean("handback"))
    first = [r["first_op"] for r in rows if r["first_op"] is not None]
    tick = min((c for r in rows for c in (r["on_cpu"], r["wait_cpu"])
                if c > 0), default=None)
    return {
        "total": max(0.0, queued + handback),
        "queued": queued,
        "handback": handback,
        "gaps": mean("gaps"),
        "on_cpu": mean("on_cpu"),
        "wait_cpu": mean("wait_cpu"),
        "first_op": statistics.median(first) * ms if first else float("nan"),
        "launches": n,
        "spans": sum(r["spans"] for r in rows),
        "over": sum(r["over"] for r in rows),
        "clock_step": tick * ms if tick else 0.0,
        "ticks": round(sum(r["on_cpu"] for r in rows) / tick) if tick else 0,
    }
