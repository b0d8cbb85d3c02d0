"""One of the run's own end-to-end numbers (``ctx["end_to_end"]``), as the
driver took it in this run: a quantity that is judged end to end in the cells
where it repeats and only recorded, under another name, in a cell where it
does not.  In a ``--trace 1`` run it is the traced run's own number, the
profiler's cost in it.  Nothing where the driver took none."""


def read(ctx, metric):
    return (ctx.get("end_to_end") or {}).get(metric)
