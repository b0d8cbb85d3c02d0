"""A percentile (the harness's rule) of one argument of the program's spans
called ``span`` that start inside the traced window — ``queue_ms`` of
``decode::prefill`` is what a request waited between ``submit`` and the start
of its prefill.  Nothing where no such span carries the argument."""
from benchmark import harness
from benchmark.metrics import program_spans


def read(ctx, span, arg, q):
    raw = program_spans.load()
    if not raw:
        return None
    return percentile(raw, span, arg, float(q))


def percentile(raw, span, arg, q):
    xs = [float(s[4][arg]) for s in program_spans.inside(raw, span, "start")
          if arg in s[4]]
    return harness.percentile(xs, q) if xs else None
