"""A percentile of one of the run's own sample lists (``ttft_ms``, ``tbt_ms``,
``lag_ms``), by the harness's rule; nothing where the list is empty."""
from benchmark import harness


def read(ctx, samples, q):
    xs = ctx.get(samples)
    return harness.percentile(xs, float(q)) if xs else None
