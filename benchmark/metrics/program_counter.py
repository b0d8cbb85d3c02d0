"""One of the program's own counters (``paddle_tpu.observability.stats``) as
it stands when the run is read, times ``scale``.  Nothing where the program
has no such counter."""
from benchmark import harness


def read(ctx, counter, scale=1.0):
    v = harness.program_counters().get(counter)
    return None if v is None else float(v) * float(scale)
