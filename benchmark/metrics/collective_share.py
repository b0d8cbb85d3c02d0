"""Time in all-reduce / all-gather / reduce-scatter / all-to-all operations
over the device's busy time in the traced window, in %.  Whether compute
overlaps it cannot be told without spans inside the program."""


def read(ctx):
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0:
        return None
    return 100.0 * t["collective_s"] / t["busy_s"]
