"""1 - (union of the device's operation intervals) / (traced window), in %."""


def read(ctx):
    t = ctx.get("trace")
    return 100.0 * t["idle_share"] if t else None
