"""A ratio of sums of the program's counters over the window: the deltas the
driver took at the window's opening and closing (``ctx["window_counters"]``),
``scale * sum(num) / sum(den)``, times the configuration's number under
``times_config`` if given.  Nothing where the run took no such deltas or the
denominator is zero."""


def read(ctx, num, den, scale=1.0, times_config=None):
    deltas = ctx.get("window_counters") or {}
    if any(k not in deltas for k in list(num) + list(den)):
        return None
    bottom = sum(deltas[k] for k in den)
    if bottom <= 0:
        return None
    v = float(scale) * sum(deltas[k] for k in num) / bottom
    if times_config is not None:
        v *= float(ctx["config"][times_config])
    return v
