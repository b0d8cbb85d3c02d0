"""Share of the device's busy time spent under named scopes of the program.

The program wraps its mechanisms in ``jax.named_scope`` (``moe_router``,
``moe_routed``, ``moe_shared``, ``mla_wq``, ``mla_attn``, ...), and XLA keeps
the scope path of an instruction in its ``op_name``, which the profiler
writes into the trace as the ``tf_op`` stat of the instruction's event
metadata.  ``jax.profiler.ProfileData`` does not expose metadata stats, so
this reader parses the ``.xplane.pb`` itself (the protobuf classes ship with
the installed TensorFlow) for one thing only: the map from an instruction's
text — the name the raw trace already has — to its ``tf_op``.  Then one pass
over the first device's leaf operations (``trace_reduce.leaves``), clipped to
the window: seconds of those whose ``tf_op`` matches any of ``scopes``, over
``busy_s``.  A fusion carries the ``op_name`` of its root instruction.

Nothing where the run has no trace, the trace no ``tf_op``, or no operation
lies under such a scope (a program from before the scopes).
"""
import functools
import re

from benchmark import trace_reduce
from benchmark.metrics import program_spans


@functools.lru_cache(maxsize=2)
def op_scopes(xplane_path: str) -> dict:
    """{instruction text: tf_op} of the first device plane."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(xplane_path, "rb") as f:
        space.ParseFromString(f.read())
    planes = sorted((p for p in space.planes
                     if trace_reduce.DEVICE_PLANE.match(p.name)),
                    key=lambda p: p.name)
    if not planes:
        return {}
    plane = planes[0]
    key = [k for k, v in plane.stat_metadata.items() if v.name == "tf_op"]
    if not key:
        return {}
    out = {}
    for em in plane.event_metadata.values():
        for s in em.stats:
            if s.metadata_id == key[0]:
                out[em.name] = s.str_value or \
                    plane.stat_metadata[s.ref_value].name
    return out


def read(ctx, scopes):
    raw, summary = ctx.get("trace_raw"), ctx.get("trace")
    path = ctx.get("xplane") or program_spans.find_trace()
    if not raw or not summary or not path or not raw.get("devices"):
        return None
    win = [h for h in raw.get("host", []) if h[0] == trace_reduce.WINDOW_SPAN]
    if not win or summary.get("busy_s", 0) <= 0:
        return None
    lo, hi = win[0][1], win[0][1] + win[0][2]
    try:
        scope_of = op_scopes(path)
    except Exception as e:      # no protobuf classes here: report nothing
        print(f"bench: scope_share cannot parse the trace: {e!r}", flush=True)
        return None
    want = re.compile("|".join(f"(?:{s})" for s in scopes))
    dev = raw["devices"][sorted(raw["devices"])[0]]
    under = 0.0
    for name, s, d in trace_reduce.leaves(dev["ops"]):
        got = min(s + d, hi) - max(s, lo)
        if got > 0 and want.search(scope_of.get(name, "")):
            under += got
    return 100.0 * under * 1e-9 / summary["busy_s"] if under > 0 else None
