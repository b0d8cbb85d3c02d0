"""The device's idle time by what the decode engine's thread was doing, as a
share of the traced window in %.

The engine's thread is the line that holds ``decode::step``.  Every idle
instant of the first device goes to exactly one of four terms:

- ``host``: the thread is inside a ``decode::`` span other than
  ``decode::wait_work`` and the ``*.wait`` spans — it is building a feed,
  dispatching, handing tokens out, admitting: the device waits for the host;
- ``no_work``: the thread is in ``decode::wait_work`` — no request pending,
  no stream live: the device waits for load;
- ``wait``: the thread is in a ``*.wait`` span, blocked on the device's
  result — idle there is the device-to-host copy after the program has ended;
- ``none``: the thread is in no ``decode::`` span (between two of them).

The four sum to ``device_idle_share`` by construction.  ``under`` picks the
term that is reported; the partition itself is worked out once for a trace
and kept with it.  Nothing where the trace has no ``decode::step``."""
from benchmark import trace_reduce as tr
from benchmark.metrics import program_spans

TERMS = ("host", "no_work", "wait", "none")


def read(ctx, under):
    raw = program_spans.load()
    if not raw:
        return None
    parts = program_spans.derived(raw, "idle_partition", _partition_printed)
    return parts[under] if parts else None


def _partition_printed(raw):
    """The partition, printed where it is worked out: once for a trace,
    however many metrics read a term of it."""
    parts = partition(raw)
    if parts:
        print("bench spans: device idle by the engine thread's span, % of the "
              "window: " + " ".join(f"{k}={v:.3f}" for k, v in parts.items()),
              flush=True)
    return parts


def partition(raw):
    win = program_spans.window(raw)
    thread = program_spans.thread_of(raw, "decode::step")
    if win is None or thread is None:
        return None
    mine = [s for s in raw["spans"]
            if s[1] == thread and s[0].startswith("decode::")]

    def cover(keep):
        return tr.union((s[2], s[2] + s[3]) for s in mine if keep(s[0]))

    no_work = cover(lambda n: n == "decode::wait_work")
    wait = cover(lambda n: n.endswith(".wait"))
    host = tr.subtract(tr.subtract(cover(lambda n: True), no_work), wait)
    idle = program_spans.device_idle(raw)
    out, left = {}, idle
    for name, where in (("host", host), ("no_work", no_work), ("wait", wait)):
        took = tr.intersect(left, where)
        out[name] = took
        left = tr.subtract(left, took)
    out["none"] = left
    span = win[1] - win[0]
    return {k: 100.0 * tr.total(out[k]) / span for k in TERMS}
