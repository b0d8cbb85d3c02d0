"""The trainer's device time in the Fluid program's own words.

``core/lowering.py`` traces every op of a Fluid program under one
``jax.named_scope``, ``<role>/<op_namescope...>/<op.type>[/<param>]`` with
role ``fwd`` | ``bwd`` | ``opt``; XLA keeps the path as the instruction's
``op_name`` and the profiler writes it into the trace as ``tf_op``
(``scope_share.op_scopes`` reads that map, once a trace).  This reader makes
ONE pass over the first device's leaf operations (``trace_reduce.leaves``)
clipped to the window and files each under ``(role, name scope, op.type)`` —
the op type is the first element after the role that the program's registry
knows — with its seconds, of which in clones XLA's rematerialisation made
(``<instruction>.remat``: filed under the scope they recompute) and of which
in collectives.  Where XLA folded two instructions into one it joins their
names with ``;``: the first counts.  The table is worked out once for a trace
(``program_spans.derived``) and printed where it is, ``bench fluid ops:``.

``read(ctx, stat)``: ``top`` — the ``(role, op.type)`` pair with most time,
summed over its instances and layers; ``recompute`` — the clones; ``unscoped``
— time whose ``tf_op`` holds no role (an empty one counts): what the names do
not reach.  ``read(ctx, scopes=[...])`` is ``scope_share.read`` over the same
trace: the train driver's ``ctx`` carries the summary and not the raw trace,
so this reader extracts it and hands it on.  All in % of the first device's
busy time in the window, the sum of its leaf operations' seconds, so the three
roles and ``unscoped`` sum to 100.

Nothing where the run has no trace or the trace no operation under a role (a
program from before the scopes, or an executable a stale compile cache handed
back: a ``bench:`` line says so).
"""
import re

from benchmark import trace_reduce
from benchmark.metrics import program_spans, scope_share

ROLE = re.compile(r"(?:^|/)(fwd|bwd|opt)/(.*)")
REMAT = re.compile(r"\.remat\d*(?:\.\d+)?$")
ROWS = 10


def fluid_op_types():
    """What the program's registry calls an op, grad ops included."""
    try:
        from paddle_tpu.core import registry
        from paddle_tpu.ops import control_flow_ops
        ops = set(registry.all_ops()) | set(control_flow_ops.CONTROL_FLOW_OPS)
    except Exception as e:      # a program without them: file by role alone
        print(f"bench: fluid_op_table cannot list the program's op types: "
              f"{e!r}", flush=True)
        return frozenset()
    return frozenset(ops | {o + "_grad" for o in ops})


def file_under(tf_op: str, op_types) -> tuple:
    """``(role, name scope, op.type)`` of one ``tf_op``, or None without a
    role."""
    m = ROLE.search(tf_op.split(";")[0])
    if not m:
        return None
    parts = m.group(2).split("/")
    for i, p in enumerate(parts):
        if p in op_types:
            return m.group(1), "/".join(parts[:i]), p
    return m.group(1), "", parts[0]


def build(raw: dict, scope_of: dict, op_types) -> dict:
    """The table of one trace: ``raw`` as ``trace_reduce.extract`` gives it,
    ``scope_of`` the instruction-text-to-``tf_op`` map."""
    win = [h for h in raw.get("host", []) if h[0] == trace_reduce.WINDOW_SPAN]
    if not win or not raw.get("devices"):
        return {}
    lo, hi = win[0][1], win[0][1] + win[0][2]
    dev = raw["devices"][sorted(raw["devices"])[0]]
    rows, unscoped, filed = {}, {}, {}
    busy = 0.0
    for name, s, d in trace_reduce.leaves(dev["ops"]):
        got = (min(s + d, hi) - max(s, lo)) * 1e-9
        if got <= 0:
            continue
        busy += got
        if name not in filed:
            filed[name] = file_under(scope_of.get(name, ""), op_types)
        key = filed[name]
        label = trace_reduce.op_label(name)
        if key is None:
            unscoped[label] = unscoped.get(label, 0.0) + got
            continue
        row = rows.setdefault(key, [0.0, 0.0, 0.0, {}])
        row[0] += got
        if REMAT.search(label.split(" ")[0]):
            row[1] += got
        if trace_reduce.COLLECTIVE.search(label):
            row[2] += got
            row[3][label] = row[3].get(label, 0.0) + got
    return {"busy_s": busy, "rows": rows, "unscoped": unscoped}


def pairs(rows: dict) -> dict:
    """Seconds by ``(role, op.type)``, over all instances and layers."""
    out = {}
    for (role, _, op), v in rows.items():
        out[role, op] = out.get((role, op), 0.0) + v[0]
    return out


def _lines(table: dict) -> list:
    busy, rows = table["busy_s"], table["rows"]

    def longest(items, fmt):
        return "; ".join(fmt(k, v) for k, v in sorted(
            items, key=lambda kv: -kv[1])[:ROWS])

    out = ["bench fluid ops: role scope op.type seconds (of which recomputed, "
           f"collective), the longest of {len(rows)} rows in the first "
           f"device's {busy:.3f} busy s: " + "; ".join(
               f"{r} {s or '-'} {o} {v[0]:.4f} ({v[1]:.4f}, {v[2]:.4f})"
               for (r, s, o), v in sorted(
                   rows.items(), key=lambda kv: -kv[1][0])[:ROWS]),
           "bench fluid ops: by role and op.type over all layers: "
           + longest(pairs(rows).items(),
                     lambda k, v: f"{k[0]} {k[1]} {v:.4f}")]
    owners = {(label, "/".join(k for k in key if k)): sec
              for key, v in rows.items() for label, sec in v[3].items()}
    if owners:
        out.append("bench fluid ops: collectives by the op they belong to: "
                   + longest(owners.items(),
                             lambda k, v: f"{k[0]} {v:.4f} {k[1]}"))
    rest = table["unscoped"]
    if rest:
        kinds = {}
        for label, sec in rest.items():
            kind = re.sub(r"[.\d]*( .*)?$", "", label)
            n, s = kinds.get(kind, (0, 0.0))
            kinds[kind] = (n + 1, s + sec)
        out.append(
            f"bench fluid ops: {sum(rest.values()):.4f} s in {len(rest)} "
            "instructions under no role, by kind: " + "; ".join(
                f"{k} {s:.4f} ({n})" for k, (n, s) in sorted(
                    kinds.items(), key=lambda kv: -kv[1][1])[:ROWS])
            + "; the longest: "
            + longest(rest.items(), lambda k, v: f"{k} {v:.4f}"))
    return out


def _table_printed(raw: dict, path: str) -> dict:
    """The table of the run's own trace, printed where it is worked out:
    once, however many metrics read it."""
    try:
        scope_of = scope_share.op_scopes(path)
    except Exception as e:      # no protobuf classes here: report nothing
        print(f"bench: fluid_op_table cannot parse the trace: {e!r}",
              flush=True)
        return {}
    table = build(raw, scope_of, fluid_op_types())
    if not table:
        return {}
    if not table["rows"]:
        if table["busy_s"] > 0:
            print(f"bench: the trace holds {len(table['unscoped'])} device "
                  "instructions and no tf_op under fwd/, bwd/ or opt/: a "
                  "program lowered before its scopes, or an executable from a "
                  "compile cache written before them", flush=True)
        return {}
    for line in _lines(table):
        print(line, flush=True)
    return table


def load(ctx) -> tuple:
    """``(raw trace, its path, table)`` of the run, or three times None.  A
    driver that kept the raw trace hands it over in ``ctx``; the train driver
    keeps the summary only, so the trace is found and extracted here, once."""
    nothing = None, None, None
    raw = ctx.get("trace_raw")
    path = ctx.get("xplane") or program_spans.find_trace()
    if not path:
        return nothing
    if raw is None:
        spans = program_spans.load()
        if not spans:
            return nothing
        raw = program_spans.derived(
            spans, "trace_raw", lambda _: trace_reduce.extract(path))
    table = program_spans.derived(
        raw, "fluid_op_table", lambda r: _table_printed(r, path))
    return (raw, path, table) if table else nothing


def read(ctx, stat=None, scopes=None):
    raw, path, table = load(ctx)
    if not table:
        return None
    busy, rows = table["busy_s"], table["rows"]
    if scopes is not None:
        return scope_share.read({"trace_raw": raw, "xplane": path,
                                 "trace": {"busy_s": busy}}, scopes)
    if stat == "top":
        return 100.0 * max(pairs(rows).values()) / busy
    if stat == "recompute":
        return 100.0 * sum(v[1] for v in rows.values()) / busy
    if stat == "unscoped":
        return 100.0 * sum(table["unscoped"].values()) / busy
    raise ValueError(f"fluid_op_table: unknown stat {stat!r}")
