"""The benchmark's loader, accounting and result line.

Everything a cell is made of is data found by name: ``BENCHMARK.json``
names a cell's configuration and traffic mix, ``benchmark/configs/<config>.json``
and ``benchmark/traffic/<mix>.json`` hold them, a configuration's ``kind``
names its driver module (``benchmark/drivers/<kind>.py``) and every per-layer
metric has ``benchmark/metrics/<metric>.json`` naming its reader.  No cell,
configuration, mix or metric is known to this code by a literal, so a later
PR adds any of them as new files and entries and edits nothing that is here.

A metric is entered once.  Its entry in ``BENCHMARK.json`` says what it is
(unit, direction, source, layer, the end-to-end metric it moves) and which
cells read it; its file says how it is read (``what``, ``reader``, ``args``)
and repeats nothing of the entry.  An entry with no ``workloads`` list is
read in every cell that reports the end-to-end metric it moves, so a new cell
joins those metrics by being listed under that end-to-end metric alone.

Where cells read one metric with arguments that differ in a word (the counts
file of a kernel's roofline, the scopes of a share, the configuration's key a
counter is scaled by), the word is the cell's configuration's to supply: its
file may carry ``"metric_args": {"<metric>": {<key>: <value>}}``, laid key by
key over the ``args`` of the metric's file, which are the defaults.  A cell
joins such an entry by its name in the entry's ``workloads`` and a line in the
configuration file it brings; no metric file, reader or test is edited.

Importing this module imports neither JAX nor the program.
"""
from __future__ import annotations

import ast
import importlib.util
import json
import math
import os
import re
import statistics
import time
from typing import Callable, Dict, List, Optional, Sequence

MANIFEST = "BENCHMARK.json"
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

# An operation fails only for one of these named classes (ISSUE 24, rule 2).
# A token that differs from a reference is never one of them.
FAILURE_CLASSES = ("shed", "too_long", "error", "timeout", "short")


class ConfigurationError(ValueError):
    """The manifest or one of the files it names cannot be run as written:
    raised before anything is sent or trained."""


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ConfigurationError(f"{path}: no such file") from None
    except json.JSONDecodeError as e:
        raise ConfigurationError(f"{path}: not JSON ({e})") from None


def load_manifest(root: str) -> dict:
    return _read_json(os.path.join(root, MANIFEST))


def _under_paths(manifest: dict, rel: str) -> bool:
    """Whether ``rel``, a path from the root written with no ``..`` and no
    detour, lies in one of the benchmark's own directories."""
    return isinstance(rel, str) and os.path.normpath(rel) == rel and any(
        rel == p or rel.startswith(p.rstrip("/") + "/")
        for p in manifest["paths"])


def yardstick_module(root: str, manifest: dict, rel) -> Optional[str]:
    """The module a metric's file names (its reader, or an argument that
    ends in ``.py``), where it is a file under ``paths``: a metric may run no
    code the benchmark does not own.  None otherwise."""
    if not _under_paths(manifest, rel):
        return None
    path, top = os.path.join(root, rel), os.path.realpath(root)
    inside = os.path.realpath(path).startswith(top + os.sep)   # no link out
    return path if inside and os.path.isfile(path) else None


# what a metric's own file holds; everything else about it is its entry's
METRIC_FILE_KEYS = ("what", "reader", "args")
# where a configuration's file keeps the arguments its cells supply
METRIC_ARGS = "metric_args"


def metric_args(config: dict, where: str) -> Dict[str, dict]:
    """``{metric: {key: value}}`` as the configuration at ``where`` supplies
    it; nothing where it supplies none."""
    given = config.get(METRIC_ARGS, {})
    if not (isinstance(given, dict)
            and all(isinstance(v, dict) for v in given.values())):
        raise ConfigurationError(
            f"{where}: {METRIC_ARGS!r} is not an object of objects, a metric "
            f"a key")
    return given


def resolved(root: str, manifest: dict, spec: dict, given: Optional[dict],
             where: str, faults: List[str]) -> Optional[dict]:
    """A metric's file ``spec`` as a cell reads it: its ``args`` overlaid key
    by key with what the cell's configuration (at ``where``) gives the
    metric.  An argument that arrives this way and names a module is held to
    what one in the file is held to; None with the fault filed otherwise."""
    given = given or {}
    stray = _stray_module(root, manifest, given, where)
    if stray:
        faults.append(stray)
        return None
    return dict(spec, args={**(spec.get("args") or {}), **given})


def _stray_module(root: str, manifest: dict, args: dict,
                  where: str) -> Optional[str]:
    """The fault of the first of ``args`` that names a module (a string
    ending in ``.py``: the roofline reader's ``counts``) which is no file
    under ``paths``; None where every one is."""
    for key, value in sorted(args.items()):
        if isinstance(value, str) and value.endswith(".py") \
                and yardstick_module(root, manifest, value) is None:
            return f"{where}: {key!r} names no module under paths: {value!r}"
    return None


def read_signature(path: str) -> Optional[tuple]:
    """(the arguments ``read(ctx, ...)`` of the reader at ``path`` cannot do
    without, those it takes — None for any), from the file's text: checking a
    manifest runs no reader's code.  None where the file defines no ``read``."""
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, ast.FunctionDef) and node.name == "read":
            a = node.args
            names = [x.arg for x in a.posonlyargs + a.args][1:]     # less ctx
            needs = names[:len(names) - len(a.defaults)] + [
                x.arg for x, d in zip(a.kwonlyargs, a.kw_defaults)
                if d is None]
            takes = None if a.kwarg else set(names) | {
                x.arg for x in a.kwonlyargs}
            return set(needs), takes
    return None


def metric_applies(metric: dict, workload: str,
                   end_to_end: Sequence[dict] = ()) -> bool:
    """Whether ``workload`` reports ``metric``.  A list of cells decides
    where there is one.  Without one, an end-to-end metric is every cell's,
    and a per-layer metric belongs to every cell that reports the end-to-end
    metric it ``moves`` (one of ``end_to_end``)."""
    cells = metric.get("workloads")
    if cells is not None:
        return workload in cells
    moved = metric.get("moves")
    return moved is None or any(
        m.get("name") == moved and metric_applies(m, workload)
        for m in end_to_end)


def cell_metrics(manifest: dict, workload: str) -> tuple:
    """The end-to-end and the per-layer entries that ``workload`` reports."""
    e2e = [m for m in manifest["end_to_end"] if metric_applies(m, workload)]
    return e2e, [m for m in manifest["per_layer"]
                 if metric_applies(m, workload, e2e)]


def bench_dir(root: str, config_file: str) -> str:
    """The benchmark's own directory: where a configuration's file lives,
    one level up."""
    return os.path.dirname(os.path.dirname(os.path.join(root, config_file)))


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, root: str, manifest: dict, workload: str):
        by_name = {w["name"]: w for w in manifest["workloads"]}
        if workload not in by_name:
            raise ConfigurationError(
                f"no workload {workload!r} in {MANIFEST}; it has "
                f"{sorted(by_name)}")
        self.root = root
        self.manifest = manifest
        self.entry = by_name[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in manifest["configs"]}.get(
            self.entry["config"])
        if cfg_entry is None:
            raise ConfigurationError(
                f"workload {workload!r} names configuration "
                f"{self.entry['config']!r}, which {MANIFEST} does not list")
        self.config = _read_json(os.path.join(root, cfg_entry["file"]))
        self.config_name = cfg_entry["name"]
        self.config_file = cfg_entry["file"]
        self.metric_args = metric_args(self.config, self.config_file)
        self.bench_dir = bench_dir(root, cfg_entry["file"])
        self.mix_name = self.entry["traffic"]
        self.mix = _read_json(os.path.join(self.bench_dir, "traffic",
                                           self.mix_name + ".json"))
        kind = self.config.get("kind")
        if not isinstance(kind, str) or not NAME_RE.match(kind):
            raise ConfigurationError(
                f"{cfg_entry['file']}: 'kind' must name a driver module")
        self.kind = kind
        self.end_to_end, self.per_layer = cell_metrics(manifest, workload)

    def driver(self):
        path = os.path.join(self.bench_dir, "drivers", self.kind + ".py")
        return load_module(path, f"benchmark_driver_{self.kind}")

    def metric_file(self, metric: str) -> dict:
        """A per-layer metric's own file as this cell reads it — its ``args``
        overlaid with the configuration's ``metric_args`` of the metric — held
        to what ``check_manifest`` holds it to."""
        faults: List[str] = []
        spec = _metric_file(self.root, self.manifest, os.path.join(
            self.bench_dir, "metrics", metric + ".json"), faults)
        if spec is not None:
            spec = resolved(
                self.root, self.manifest, spec, self.metric_args.get(metric),
                f"{self.config_file}, {METRIC_ARGS} of {metric!r}", faults)
        if faults:
            raise ConfigurationError("; ".join(faults))
        return spec

    def reader(self, metric: str) -> Callable:
        """The ``read(ctx)`` function of a per-layer metric, found through
        the metric's own file."""
        spec = self.metric_file(metric)
        rel = spec["reader"]
        mod = load_module(os.path.join(self.root, rel),
                          "benchmark_reader_" + re.sub(r"\W", "_", rel))
        return lambda ctx: mod.read(ctx, **spec["args"])


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise ConfigurationError(f"{path}: no such module")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_manifest(root: str, manifest: dict) -> List[str]:
    """What the contract fixes about ``BENCHMARK.json`` that can be checked
    without a chip; returns the faults found (none for a sound file)."""
    faults: List[str] = []
    need = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != need:
        faults.append(f"keys {sorted(manifest)} are not exactly {sorted(need)}")
        return faults

    def name_ok(what, n):
        if not isinstance(n, str) or not NAME_RE.match(n):
            faults.append(f"{what} {n!r} is not a name")

    seen: Dict[str, set] = {k: set() for k in
                            ("config", "workload", "metric")}

    def once(kind, n):
        if n in seen[kind]:
            faults.append(f"{kind} {n!r} appears twice")
        seen[kind].add(n)

    for c in manifest["configs"]:
        name_ok("config", c.get("name"))
        once("config", c.get("name"))
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            faults.append(f"config {c.get('name')!r} has keys {sorted(c)}")
        if not _under_paths(manifest, c.get("file", "")):
            faults.append(f"config file {c.get('file')!r} is outside paths")
        elif not os.path.isfile(os.path.join(root, c["file"])):
            faults.append(f"config file {c['file']!r} does not exist")
        for k in c.get("reduced", []):
            name_ok("reduced key", k)
    pairs = set()
    for w in manifest["workloads"]:
        name_ok("workload", w.get("name"))
        once("workload", w.get("name"))
        for k in ("config", "traffic"):
            name_ok(k, w.get(k))
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            faults.append(f"workload {w.get('name')!r} has keys {sorted(w)}")
        if w.get("chips") not in (1, 4):
            faults.append(f"workload {w.get('name')!r}: chips {w.get('chips')}")
        if not (1 <= len(w.get("why", "")) <= 200):
            faults.append(f"workload {w.get('name')!r}: why is not 1-200 chars")
        if (w.get("config"), w.get("traffic")) in pairs:
            faults.append(f"pair {(w.get('config'), w.get('traffic'))} twice")
        pairs.add((w.get("config"), w.get("traffic")))
        if w.get("config") not in seen["config"]:
            faults.append(f"workload {w.get('name')!r}: unknown config")
    four = sum(1 for w in manifest["workloads"] if w.get("chips") == 4)
    if four > max(1, len(manifest["workloads"]) // 4):
        faults.append(f"{four} four-chip cells of {len(manifest['workloads'])}")
    used = {w.get("config") for w in manifest["workloads"]}
    for c in seen["config"] - used:
        faults.append(f"config {c!r} is used by no cell")
    e2e = set()
    for m in manifest["end_to_end"]:
        name_ok("metric", m.get("name"))
        once("metric", m.get("name"))
        e2e.add(m.get("name"))
        if set(m) - {"workloads"} != {"name", "unit", "better", "bound",
                                     "source"}:
            faults.append(f"metric {m.get('name')!r} has keys {sorted(m)}")
        if not (isinstance(m.get("bound"), (int, float))
                and 0 < m["bound"] <= 0.1):
            faults.append(f"metric {m.get('name')!r}: bound {m.get('bound')}")
        if m.get("source") not in ("host_clock", "device_trace"):
            faults.append(f"metric {m.get('name')!r}: source {m.get('source')}")
    if "setup_s" not in e2e:
        faults.append("no setup_s among end_to_end")
    for m in manifest["per_layer"]:
        name_ok("metric", m.get("name"))
        once("metric", m.get("name"))
        if set(m) - {"workloads"} != {"name", "unit", "better", "source",
                                     "layer", "moves"}:
            faults.append(f"metric {m.get('name')!r} has keys {sorted(m)}")
        if m.get("source") not in SOURCES:
            faults.append(f"metric {m.get('name')!r}: source {m.get('source')}")
        if m.get("moves") not in e2e:
            faults.append(f"metric {m.get('name')!r} moves {m.get('moves')!r}")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT_RE.match(str(m.get("unit", ""))):
            faults.append(f"metric {m.get('name')!r}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            faults.append(f"metric {m.get('name')!r}: better {m.get('better')!r}")
        for w in m.get("workloads", []):
            if w not in seen["workload"]:
                faults.append(f"metric {m.get('name')!r} lists cell {w!r}")
    files = {c.get("name"): c.get("file") for c in manifest["configs"]
             if isinstance(c.get("file"), str)}
    read_in: Dict[str, list] = {}
    for w in manifest["workloads"]:
        name = w.get("name")
        e2e_here, mine_pl = cell_metrics(manifest, name)
        mine_e2e = {m["name"] for m in e2e_here}
        if "setup_s" not in mine_e2e or len(mine_e2e) < 2:
            faults.append(f"cell {name!r} reports {sorted(mine_e2e)}")
        if not mine_pl:
            faults.append(f"cell {name!r} reports no per-layer metric")
        for m in mine_pl:
            read_in.setdefault(m.get("name"), []).append(w)
            if m["moves"] not in mine_e2e:
                faults.append(f"cell {name!r}: {m['name']!r} moves "
                              f"{m['moves']!r}, which the cell does not report")
    # what each configuration gives the metrics its cells read
    given: Dict[str, dict] = {}
    for config, rel in files.items():
        try:
            given[config] = metric_args(
                _read_json(os.path.join(root, rel)), rel)
        except ConfigurationError as e:
            faults.append(str(e))
            continue
        reads = {n for n, cells in read_in.items()
                 if any(w.get("config") == config for w in cells)}
        for n in sorted(set(given[config]) - reads):
            # (a misspelt name would read the file's defaults in silence)
            faults.append(f"{rel}: {METRIC_ARGS} names {n!r}, which no cell "
                          f"of {config!r} reads")
    entered: Dict[tuple, str] = {}
    signatures: Dict[str, Optional[tuple]] = {}
    for m in manifest["per_layer"]:
        name, cells = m.get("name"), read_in.get(m.get("name"))
        if not cells:
            faults.append(f"metric {name!r} is read in no cell")
            continue
        cells = [w for w in cells if w.get("config") in given]
        if not cells:
            continue
        spec = _metric_file(root, manifest, os.path.join(
            bench_dir(root, files[cells[0]["config"]]), "metrics",
            str(name) + ".json"), faults)
        if spec is None:
            continue
        reader = spec["reader"]
        if reader not in signatures:
            signatures[reader] = read_signature(os.path.join(root, reader))
        if signatures[reader] is None:
            faults.append(f"metric {name!r}: {reader} defines no read(ctx, "
                          f"...)")
            continue
        needs, takes = signatures[reader]
        found: List[str] = []
        for w in cells:
            config = w["config"]
            got = resolved(root, manifest, spec, given[config].get(name),
                           f"{files[config]}, {METRIC_ARGS} of {name!r}",
                           found)
            if got is None:
                continue
            args = got["args"]
            # what the reader cannot do without, and what it does not take:
            # found here, not as a metric that is silently missing on the chip
            lacks = sorted(needs - set(args))
            extra = sorted(set(args) - takes) if takes is not None else []
            if lacks:
                found.append(f"metric {name!r} as {config!r} reads it: no "
                             f"{lacks} for {reader}'s read")
            if extra:
                found.append(f"metric {name!r} as {config!r} reads it: "
                             f"{extra} that it does not take")
            # one reader with the same arguments that moves the same metric
            # is one metric, whichever cell reads it and wherever its words
            # come from: a cell joins it by its list, not by a copy of it
            same = (reader, json.dumps(args, sort_keys=True), m.get("moves"))
            if entered.setdefault(same, name) != name:
                found.append(
                    f"metric {name!r} is read with the reader, the args and "
                    f"the 'moves' of {entered[same]!r}: a copy")
        faults.extend(dict.fromkeys(found))     # once, however many cells
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        faults.append(f"run_seconds {rs!r}")
    return faults


def _metric_file(root: str, manifest: dict, path: str,
                 faults: List[str]) -> Optional[dict]:
    """A per-layer metric's own file, or None with the fault filed: it holds
    ``what``, ``reader`` and, where the reader takes any, ``args`` (the
    defaults of what a cell's configuration may supply: ``resolved``) — and
    nothing of the manifest's entry, so the two cannot disagree.  The reader,
    and any argument that names a module (a string ending in ``.py``: the
    roofline reader's ``counts``), is a file under ``paths``."""
    rel = os.path.relpath(path, root)
    try:
        spec = _read_json(path)
    except ConfigurationError as e:
        faults.append(str(e))
        return None
    extra = sorted(set(spec) - set(METRIC_FILE_KEYS))
    if extra:
        faults.append(f"{rel} carries {extra}: the manifest's entry says "
                      f"that, the file only {list(METRIC_FILE_KEYS)}")
    reader = spec.get("reader")
    if not (isinstance(spec.get("what"), str) and spec["what"]):
        faults.append(f"{rel} does not say 'what' it reads")
    if not isinstance(spec.get("args", {}), dict):
        faults.append(f"{rel}: 'args' is not an object")
        return None
    if yardstick_module(root, manifest, reader) is None:
        faults.append(f"{rel} names no reader under paths: {reader!r}")
        return None
    stray = _stray_module(root, manifest, spec.get("args", {}), rel)
    if stray:
        faults.append(stray)
        return None
    return spec


# ---------------------------------------------------------------------------
# arithmetic kept with the yardstick
# ---------------------------------------------------------------------------

def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-quantile (0..1) by linear interpolation between order
    statistics (numpy's default rule), on plain Python floats."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)`` — the driver's rule."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


# ---------------------------------------------------------------------------
# failure accounting (one implementation, every cell)
# ---------------------------------------------------------------------------

class Accounting:
    """Operations sent within the window and what became of them.

    ``attempted`` counts operations sent in the window; ``failed`` counts an
    operation only under one of :data:`FAILURE_CLASSES`.  Operations sent
    outside the window (a lead-in) are counted apart: a failure there makes
    the run incorrect but is not one of the window's operations."""

    def __init__(self):
        self.attempted = 0
        self.outside = 0
        self.by_class = {c: 0 for c in FAILURE_CLASSES}
        self.outside_by_class = {c: 0 for c in FAILURE_CLASSES}
        self.examples: List[str] = []

    def record(self, in_window: bool, failure: Optional[str],
               detail: str = "") -> None:
        if failure is not None and failure not in self.by_class:
            raise ValueError(f"unknown failure class {failure!r}")
        if in_window:
            self.attempted += 1
        else:
            self.outside += 1
        if failure is not None:
            (self.by_class if in_window else self.outside_by_class)[failure] += 1
            if len(self.examples) < 8:
                self.examples.append(f"{failure}: {detail}"[:300])

    @property
    def failed(self) -> int:
        return sum(self.by_class.values())

    @property
    def failed_outside(self) -> int:
        return sum(self.outside_by_class.values())

    def line(self) -> str:
        cls = " ".join(f"{c}={n}" for c, n in self.by_class.items())
        out = (f"bench failures: attempted={self.attempted} "
               f"failed={self.failed} {cls}")
        if self.outside:
            out += (f" | outside the window: sent={self.outside} "
                    f"failed={self.failed_outside}")
        return out


class Checks:
    """The conditions of ``correct``; every one is printed, all must hold."""

    def __init__(self):
        self.items: List[tuple] = []

    def add(self, name: str, ok: bool, detail: str = "") -> None:
        self.items.append((name, bool(ok), detail))

    @property
    def ok(self) -> bool:
        return all(ok for _, ok, _ in self.items)

    def lines(self) -> List[str]:
        return [f"bench check: {'ok  ' if ok else 'FAIL'} {n}"
                + (f" — {d}" if d else "") for n, ok, d in self.items]


class Phases:
    """Where a run's wall time went.  The driver stops a run at a fixed limit
    and its report names the run, not what it was doing; the ``bench time:``
    line, printed by every run, names the phase.  ``mark`` closes the phase
    that began at the mark before it; ``within`` notes a time taken inside
    one of them (on another thread, or by one reader among many)."""

    def __init__(self, t_start: float):
        self.t_start = self.last = t_start
        self.phases: List[tuple] = []
        self.inside: List[tuple] = []

    def mark(self, name: str, at: Optional[float] = None) -> None:
        now = time.perf_counter() if at is None else at
        self.phases.append((name, now - self.last))
        self.last = now

    def within(self, name: str, seconds: float) -> None:
        self.inside.append((name, float(seconds)))

    def line(self) -> str:
        def fmt(items):
            return " ".join(f"{n}={s:.1f}" for n, s in items)

        out = (f"bench time: {time.perf_counter() - self.t_start:.1f} s since "
               f"the process started: {fmt(self.phases)}")
        return out + (f" | inside those: {fmt(self.inside)}"
                      if self.inside else "")


# ---------------------------------------------------------------------------
# what jax compiled, and what its persistent cache served
# ---------------------------------------------------------------------------

class CompileLog:
    """Backend compiles and persistent-cache hits through ``jax.monitoring``,
    as ``chip_smoke.CompileLog`` counts them (a copy: the yardstick may not
    change with the program)."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self._event = BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **_kw):
        if event == self._event:
            self.compiles += 1
            self.compile_s += float(duration)

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> tuple:
        return (self.compiles, self.cache_hits)


# fallback counters of the main paths (chip_smoke.FALLBACK_COUNTERS, copied):
# any non-zero value makes a run incorrect
FALLBACK_COUNTERS = (
    "decode.attn_fallbacks",
    "sparse_fused.gather_fallbacks",
    "sparse_fused.update_fallbacks",
    "sparse_fused.runtime_disables",
    "quant.matmul_fallbacks",
    "quant.lower_fallbacks",
    "quant.runtime_disables",
    "compile_cache.faults",
)

ATTRIBUTION_FLAGS = ("perf_attribution", "phase_attribution",
                     "capacity_attribution", "memory_attribution")


def program_counters() -> dict:
    from paddle_tpu.observability import stats
    return stats.to_dict()


def check_program_state(checks: Checks, window_mark: tuple,
                        window_end_mark: tuple) -> int:
    """Zero compiles inside the window, every fallback counter zero, every
    attribution flag off.  Returns the compiles counted inside the window."""
    from paddle_tpu.core import flags
    window_compiles = window_end_mark[0] - window_mark[0]
    checks.add("no compile inside the window", window_compiles == 0,
               f"{window_compiles} backend compile(s)")
    c = program_counters()
    bad = {n: int(c.get(n, 0)) for n in FALLBACK_COUNTERS if c.get(n, 0)}
    checks.add("every fallback counter zero", not bad, json.dumps(bad))
    on = [f for f in ATTRIBUTION_FLAGS if flags.get_flags(f)]
    checks.add("every attribution flag off", not on, ",".join(on))
    return window_compiles


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

def device_facts(devices, chips: int) -> dict:
    """The device as JAX reports it and the peak memory of the fullest chip.
    A TPU keeps two books: ``peak_bytes_in_use`` for live buffers (weights,
    state, the KV pool, results) and ``peak_bytes_reserved`` for the
    temporaries the largest program reserved while it ran.  What the chip had
    to have free is their sum, and that is ``memory_peak_bytes``; the two
    parts ride along for the per-layer readers."""
    used = list(devices)[:chips]
    rows = []
    for d in used:
        st = d.memory_stats() or {}
        live = int(st.get("peak_bytes_in_use", 0))
        temp = int(st.get("peak_bytes_reserved", 0))
        rows.append((live + temp, live, temp))
    total, live, temp = max(rows)
    print(f"bench memory: fullest chip peak_bytes_in_use {live} + "
          f"peak_bytes_reserved {temp} = {total}", flush=True)
    return {"platform": str(used[0].platform), "kind": str(used[0].device_kind),
            "count": len(devices), "memory_peak_bytes": total,
            "live_peak_bytes": live, "temp_peak_bytes": temp}


def result_line(correct: bool, acct: Accounting, metrics: Dict[str, dict],
                device: dict, breakdown: Optional[dict] = None,
                checks: Optional[Checks] = None) -> str:
    """The contract's one JSON object.  ``checks`` rides last, under a key of
    its own: every condition of ``correct`` with what it read — a comparison's
    name says its limit, its detail the number — so that the record of a run
    that is not correct says which number went over."""
    device = {k: v for k, v in device.items()
              if k in ("platform", "kind", "count", "memory_peak_bytes",
                       "busy_s", "window_s")}
    out = {"correct": bool(correct), "attempted": int(acct.attempted),
           "failed": int(acct.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if checks is not None:
        out["checks"] = [[n, ok, d] for n, ok, d in checks.items]
    return json.dumps(out)


def select_metrics(wanted: List[dict], values: Dict[str, float]) -> dict:
    """``{name: {"value", "unit"}}`` for the manifest's metrics of this cell
    that have a value; a value that is missing is left out, never invented."""
    out = {}
    for m in wanted:
        v = values.get(m["name"])
        if v is None or not math.isfinite(float(v)):
            continue
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def read_per_layer(cell: Cell, ctx: dict, phases: Phases) -> Dict[str, float]:
    """Every per-layer metric of the cell through its own reader; a reader
    that took more than a second is named on the ``bench time:`` line."""
    values = {}
    for m in cell.per_layer:
        t0 = time.perf_counter()
        try:
            v = cell.reader(m["name"])(ctx)
        except ConfigurationError:
            raise
        except Exception as e:  # one reader's fault must not lose the run
            print(f"bench: reader of {m['name']} failed: {e!r}", flush=True)
            v = None
        took = time.perf_counter() - t0
        if took > 1.0:
            phases.within("reader:" + m["name"], took)
        if v is not None:
            values[m["name"]] = float(v)
    phases.mark("readers")
    return values
