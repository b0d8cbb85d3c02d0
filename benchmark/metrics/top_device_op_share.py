"""The device operation with the most time in the traced window, as a share
of the device's busy time, in %.  Which operation it is stands first in the
result line's ``breakdown``."""


def read(ctx):
    t = ctx.get("trace")
    if not t or not t["op_seconds"] or t["busy_s"] <= 0:
        return None
    return 100.0 * max(t["op_seconds"].values()) / t["busy_s"]
