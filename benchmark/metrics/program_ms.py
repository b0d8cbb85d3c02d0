"""Device time of one of the decode plane's programs per launch, in ms, from
the ``XLA Modules`` line of the traced window.

The engine jits its prefill (one program per rung of the ladder) and its
decode step under one function name, so the trace cannot tell them apart by
name until the program names its programs (PERF.md, Open questions).  Until
then: of the programs whose name matches ``program``, the one launched most
often in the traced window is the decode step — it runs once per token for
every stream, a prefill once per request — and the others are the prefills.
The count is checked against ``decodez()``'s own step rate over the window;
where they disagree by more than a quarter nothing is reported.
"""
import re


def read(ctx, role, program):
    t, z = ctx.get("trace"), ctx.get("decodez")
    if not t or not z or z["steps"] <= 0:
        return None
    mods = {k: v for k, v in t["modules"].items() if re.search(program, k)}
    if not mods:
        return None
    step = max(mods, key=lambda k: mods[k]["launches"])
    expect = z["steps"] * t["window_s"] / float(ctx["seconds"])
    if abs(mods[step]["launches"] - expect) > 0.25 * expect + 2:
        return None
    if role == "decode_step":
        picked = [mods[step]]
    else:
        picked = [v for k, v in mods.items() if k != step]
    launches = sum(v["launches"] for v in picked)
    if launches <= 0:
        return None
    return 1e3 * sum(v["seconds"] for v in picked) / launches
