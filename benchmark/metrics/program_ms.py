"""Device time per launch, in ms, of the programs whose name matches
``program``, from the ``XLA Modules`` line of the traced window.

The decode plane names its programs (``jit_fn_decode_lm_step``, one
``jit_fn_decode_lm_prefill_<rung>`` per rung of the ladder), so a metric's file
says which it means by a regular expression and nothing is guessed from how
often a program ran: an open loop's step rate is not uniform, and a faster
step changes every count.  All launches of all matching programs that lie
wholly inside the window count alike.  Nothing where none does.
"""
import re


def read(ctx, program):
    t = ctx.get("trace")
    if not t:
        return None
    picked = [v for k, v in t["modules"].items() if re.search(program, k)]
    launches = sum(v["launches"] for v in picked)
    if launches <= 0:
        return None
    return 1e3 * sum(v["seconds"] for v in picked) / launches
