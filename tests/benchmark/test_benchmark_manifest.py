"""BENCHMARK.json and the files it names: the contract's limits that need no
chip, every cell's configuration, mix and metric files found by name, and a
configuration, a mix, per-layer metrics and a cell added as new files with
no existing file edited — under ``benchmark/`` and under ``tests/benchmark/``.

The rule for a configuration's test module, which the guard at the end of this
file holds every ``test_benchmark_*.py`` to:
it asserts on its own entries, found by name; it never indexes ``configs``,
``workloads``, ``per_layer`` or ``end_to_end`` by position, and never compares
their length, or a whole list of them, with a literal; the contract's limits
(128 entries, 24 cells, a quarter of the cells on four chips) are asserted in
this file, and no other module needs them."""
import ast
import functools
import glob
import hashlib
import importlib.util
import inspect
import itertools
import json
import os
import shutil
import sys
import time
import traceback

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402

MANIFEST = harness.load_manifest(REPO)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
PER_LAYER = [m["name"] for m in MANIFEST["per_layer"]]
# every (per-layer metric, cell that reads it): what a traced run prints
PAIRS = [(m["name"], w) for w in CELLS
         for m in harness.cell_metrics(MANIFEST, w)[1]]
# the entries with no list of cells: a cell joins them through the
# end-to-end metric they move
LISTLESS = [m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m]
# what a saturated serve cell reads by being listed under served_tokens_per_s
# alone: the list-less entries that move it, and those of setup_s
SERVED_FAMILY = {m["name"] for m in MANIFEST["per_layer"]
                 if "workloads" not in m
                 and m["moves"] in ("served_tokens_per_s", "setup_s")}


def test_manifest_meets_the_contract():
    assert harness.check_manifest(REPO, MANIFEST) == []
    assert os.path.getsize(os.path.join(REPO, harness.MANIFEST)) <= 64 * 1024
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    for p in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))


def test_check_manifest_catches_a_bad_name_and_a_bad_unit():
    bad = json.loads(json.dumps(MANIFEST))
    (cell,) = [w for w in bad["workloads"] if w["name"] == min(CELLS)]
    (metric,) = [m for m in bad["end_to_end"] if m["name"] == "setup_s"]
    cell["name"], metric["unit"] = "has space", "tokens per second"
    faults = harness.check_manifest(REPO, bad)
    assert any("has space" in f for f in faults)
    assert any("tokens per second" in f for f in faults)


@pytest.mark.parametrize("name", [m["name"] for m in
                                  MANIFEST["end_to_end"] + MANIFEST["per_layer"]])
def test_metric_names_and_units_use_only_the_allowed_characters(name):
    (m,) = [m for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]
            if m["name"] == name]
    assert harness.NAME_RE.match(m["name"])
    assert harness.UNIT_RE.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in harness.SOURCES


@pytest.mark.parametrize("workload", CELLS)
def test_a_cells_files_are_found_by_name(workload):
    cell = harness.Cell(REPO, MANIFEST, workload)
    assert cell.config["kind"] == cell.kind
    assert cell.mix["why"] and cell.mix["who"]
    assert isinstance(cell.config["reduced"], list)
    assert isinstance(cell.config["assumed"], list) and cell.config["assumed"]
    driver = cell.driver()
    driver.validate(cell, float(MANIFEST["run_seconds"]))
    assert callable(driver.run)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in names
        assert callable(cell.reader(m["name"]))


@pytest.mark.parametrize("metric, workload", PAIRS)
def test_a_cell_finds_a_metrics_file_its_reader_and_its_args(metric, workload):
    """One case a (metric, cell) pair: the cell resolves the metric's file,
    which says how the metric is read and repeats nothing of the manifest's
    entry, so the two cannot disagree and a cell joins by the entry alone."""
    cell = harness.Cell(REPO, MANIFEST, workload)
    (entry,) = [m for m in cell.per_layer if m["name"] == metric]
    spec = cell.metric_file(metric)
    assert set(spec) <= set(harness.METRIC_FILE_KEYS), sorted(spec)
    assert not set(spec) & set(entry)
    assert spec["what"]
    assert os.path.isfile(os.path.join(REPO, spec["reader"]))
    assert isinstance(spec.get("args", {}), dict)
    assert callable(cell.reader(metric))
    assert entry["moves"] in {m["name"] for m in cell.end_to_end}


def test_every_per_layer_entry_has_its_file_and_every_file_its_entry():
    assert len(PER_LAYER) <= 128                # the contract's limits
    assert len(CELLS) <= 24
    files = [f for f in os.listdir(os.path.join(REPO, "benchmark", "metrics"))
             if f.endswith(".json")]
    assert sorted(files) == sorted(n + ".json" for n in PER_LAYER)


@pytest.mark.parametrize("metric", LISTLESS)
def test_an_entry_with_no_list_is_read_where_the_metric_it_moves_is(metric):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    (moved,) = [m for m in MANIFEST["end_to_end"]
                if m["name"] == entry["moves"]]
    reports = set(moved.get("workloads", CELLS))
    assert {w for m, w in PAIRS if m == metric} == reports
    for w in CELLS:
        assert harness.metric_applies(entry, w, MANIFEST["end_to_end"]) \
            == (w in reports)


def test_check_manifest_faults_a_copy_and_a_file_that_repeats_its_entry(tmp_path):
    root = str(tmp_path)
    _copy_of(root, "benchmark")
    metrics = os.path.join(root, "benchmark", "metrics")
    # (the same reader moving ANOTHER end-to-end metric, as decode_step_ms.tbt50
    # beside .served, is a metric of its own: the manifest as it stands passes)
    assert harness.check_manifest(root, MANIFEST) == []
    # a second entry with the reader, the args and the 'moves' of one that is
    # there is how the list filled up: a cell is listed instead
    (entry,) = [m for m in MANIFEST["per_layer"]
                if m["name"] == "prefill_pad_share.served"]
    copy = dict(entry, name="prefill_pad_share.served_again",
                workloads=entry["workloads"][:1])
    shutil.copy(os.path.join(metrics, entry["name"] + ".json"),
                os.path.join(metrics, copy["name"] + ".json"))
    bad = json.loads(json.dumps(MANIFEST))
    bad["per_layer"].append(copy)
    faults = harness.check_manifest(root, bad)
    assert len(faults) == 1 and "a copy" in faults[0] \
        and copy["name"] in faults[0]
    # a file that says what the entry says can come to disagree with it
    path = os.path.join(metrics, entry["name"] + ".json")
    with open(path) as f:
        spec = json.load(f)
    with open(path, "w") as f:
        json.dump(dict(spec, unit="%", workloads=entry["workloads"]), f)
    faults = harness.check_manifest(root, MANIFEST)
    assert len(faults) == 1 and "'unit', 'workloads'" in faults[0]
    # a reader, or an argument that names a module (the roofline reader's
    # counts file), outside the benchmark's own directories: the program's
    # tree, a detour through "..", a path from "/"
    for reader in ("bench.py", "paddle_tpu/profiler.py",
                   "benchmark/../bench.py", os.path.join(REPO, "bench.py")):
        with open(path, "w") as f:
            json.dump({"what": spec["what"], "reader": reader}, f)
        assert any("no reader under paths" in f
                   for f in harness.check_manifest(root, MANIFEST)), reader
    for counts in ("paddle_tpu/kernels/moe.py",
                   "benchmark/../paddle_tpu/kernels/moe.py",
                   "../kernel_counts.py", "benchmark/kernel_counts_none.py",
                   os.path.join(REPO, "benchmark", "kernel_counts.py")):
        with open(path, "w") as f:
            json.dump(dict(spec, args=dict(spec.get("args", {}),
                                           counts=counts)), f)
        faults = harness.check_manifest(root, MANIFEST)
        assert len(faults) == 1 and "'counts' names no module under paths" \
            in faults[0], counts
        with pytest.raises(harness.ConfigurationError):
            harness.Cell(root, MANIFEST, entry["workloads"][0]).reader(
                entry["name"])
    # nor through a link that leaves the checkout
    os.symlink(os.path.join(REPO, "bench.py"),
               os.path.join(root, "benchmark", "linked_counts.py"))
    with open(path, "w") as f:
        json.dump(dict(spec, args=dict(
            spec.get("args", {}), counts="benchmark/linked_counts.py")), f)
    assert any("'counts' names no module under paths" in f
               for f in harness.check_manifest(root, MANIFEST))
    os.remove(os.path.join(root, "benchmark", "linked_counts.py"))
    os.remove(path)
    assert any("no such file" in f
               for f in harness.check_manifest(root, MANIFEST))
    # an entry that no cell reads measures nothing
    bad = json.loads(json.dumps(MANIFEST))
    bad["per_layer"].append(dict(entry, name="unread.served", workloads=[]))
    assert any("'unread.served' is read in no cell" in f
               for f in harness.check_manifest(root, bad))


def _with_metric_args(root, config, change):
    """Rewrites ``config``'s file in ``root``'s copy of the benchmark with
    ``change`` applied to its ``metric_args`` (what a later PR would have
    written into the configuration file it brings)."""
    path = os.path.join(root, "benchmark", "configs", config + ".json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["metric_args"] = change(cfg.get("metric_args", {}))
    with open(path, "w") as f:
        json.dump(cfg, f)


@functools.lru_cache(maxsize=None)
def _shared_entry(reader):
    """(an entry that the cells of two configurations or more read with
    ``reader``, a cell whose configuration gives it words of its own, that
    configuration): found by what the manifest and the files say, so that the
    controls below name no model."""
    for m in MANIFEST["per_layer"]:
        cells = [harness.Cell(REPO, MANIFEST, w) for n, w in PAIRS
                 if n == m["name"]]
        if len({c.config_name for c in cells}) < 2 \
                or cells[0].metric_file(m["name"])["reader"] != reader:
            continue
        for cell in cells:
            if m["name"] in cell.metric_args:
                return m, cell, cell.config_name
    raise AssertionError(f"no shared entry is read with {reader}")


def _control_unread(root):
    m, cell, config = _shared_entry(_SCOPES)
    misspelt = m["name"] + "x"
    _with_metric_args(root, config, lambda a: dict(a, **{misspelt: {}}))
    return MANIFEST, [f"names {misspelt!r}, which no cell of {config!r} reads"]


def _control_not_an_object(root):
    m, cell, config = _shared_entry(_SCOPES)
    _with_metric_args(root, config, lambda a: [m["name"]])
    return MANIFEST, ["'metric_args' is not an object of objects"]


def _control_a_copy_by_what_a_cell_resolves(root):
    # the FILES of the two entries differ; the cell's configuration gives the
    # shared one the scopes of the cell's own one
    m, cell, config = _shared_entry(_SCOPES)
    (own,) = [e for e in cell.per_layer if e["name"] != m["name"]
              and e.get("workloads") == [cell.name]
              and cell.metric_file(e["name"])["reader"] == _SCOPES][:1]
    scopes = cell.metric_file(own["name"])["args"]["scopes"]
    _with_metric_args(root, config, lambda a: dict(
        a, **{m["name"]: dict(a[m["name"]], scopes=scopes)}))
    return MANIFEST, ["a copy"]


def _control_files_alike_that_resolve_apart(root):
    # a second entry whose FILE is the shared one's, byte for byte, read by
    # one cell whose configuration gives each of the two its own scopes: not
    # a copy (the rule before PR 58 compared the files and called it one)
    m, cell, config = _shared_entry(_SCOPES)
    twin = dict(m, name=m["name"] + "_b", workloads=[cell.name])
    metrics = os.path.join(root, "benchmark", "metrics")
    shutil.copy(os.path.join(metrics, m["name"] + ".json"),
                os.path.join(metrics, twin["name"] + ".json"))
    _with_metric_args(root, config, lambda a: dict(
        a, **{twin["name"]: {"scopes": ["/somewhere_else/"]}}))
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["per_layer"].append(twin)
    return manifest, []


def _control_too_few_arguments(root):
    # the shared file loses its defaults: the cells whose configurations give
    # the entry nothing resolve to no `scopes`, those that give theirs do
    m, cell, config = _shared_entry(_SCOPES)
    path = os.path.join(root, "benchmark", "metrics", m["name"] + ".json")
    with open(path) as f:
        spec = json.load(f)
    with open(path, "w") as f:
        json.dump({k: v for k, v in spec.items() if k != "args"}, f)
    bare = sorted({w["config"] for w in MANIFEST["workloads"]
                   if (m["name"], w["name"]) in PAIRS
                   and m["name"] not in harness.Cell(
                       REPO, MANIFEST, w["name"]).metric_args})
    assert bare and config not in bare
    return MANIFEST, [f"as {c!r} reads it: no ['scopes'] for {_SCOPES}'s read"
                      for c in bare]


def _control_an_argument_the_reader_does_not_take(root):
    m, cell, config = _shared_entry(_RATIO)
    _with_metric_args(root, config, lambda a: dict(
        a, **{m["name"]: dict(a[m["name"]], time_config="n")}))
    return MANIFEST, [f"as {config!r} reads it: ['time_config'] that it "
                      f"does not take"]


CONTROLS = [_control_unread, _control_not_an_object,
            _control_a_copy_by_what_a_cell_resolves,
            _control_files_alike_that_resolve_apart,
            _control_too_few_arguments,
            _control_an_argument_the_reader_does_not_take]


@pytest.mark.parametrize("control", CONTROLS,
                         ids=[c.__name__[len("_control_"):] for c in CONTROLS])
def test_check_manifest_holds_what_a_configuration_supplies(control, tmp_path):
    """What a later PR writes into the configuration file it brings is held
    before a chip is asked for: each control is ONE named fault a
    configuration it touches (none where the rule must not fire)."""
    root = str(tmp_path)
    _copy_of(root, "benchmark")
    assert harness.check_manifest(root, MANIFEST) == []
    manifest, expect = control(root)
    faults = harness.check_manifest(root, manifest)
    assert len(faults) == len(expect), faults
    for fault, words in zip(sorted(faults), sorted(expect)):
        assert words in fault, (fault, words)


BAD_COUNTS = ["paddle_tpu/kernels/moe.py",
              "benchmark/../paddle_tpu/kernels/moe.py", "../kernel_counts.py",
              "benchmark/kernel_counts_none.py",
              os.path.join(REPO, "benchmark", "kernel_counts.py"),
              "benchmark/linked_counts.py"]


@pytest.mark.parametrize("counts", BAD_COUNTS)
def test_a_counts_file_that_a_configuration_names_is_held_as_a_metric_files_is(
        counts, tmp_path):
    """The program's tree, a detour through "..", a path from "/", a file
    that is not there, a link out of the checkout: ``check_manifest`` faults
    it once, naming the configuration's file, and the reader is refused at
    run time, before a number is printed."""
    root = str(tmp_path)
    _copy_of(root, "benchmark")
    os.symlink(os.path.join(REPO, "bench.py"),
               os.path.join(root, "benchmark", "linked_counts.py"))
    m, cell, config = _shared_entry(_ROOFLINE)
    assert cell.metric_args[m["name"]]["counts"].startswith("benchmark/")
    _with_metric_args(root, config, lambda a: dict(
        a, **{m["name"]: dict(a[m["name"]], counts=counts)}))
    faults = harness.check_manifest(root, MANIFEST)
    assert len(faults) == 1 and "'counts' names no module under paths" \
        in faults[0] and f"configs/{config}.json" in faults[0] \
        and m["name"] in faults[0], faults
    with pytest.raises(harness.ConfigurationError, match="no module under"):
        harness.Cell(root, MANIFEST, cell.name).reader(m["name"])


def test_the_harness_knows_no_cell_config_mix_or_metric_by_a_literal():
    names = set(CELLS) | set(PER_LAYER)
    names |= {c["name"] for c in MANIFEST["configs"]}
    names |= {w["traffic"] for w in MANIFEST["workloads"]}
    bench = os.path.join(REPO, "benchmark")
    code = [os.path.join(bench, f) for f in os.listdir(bench) if f.endswith(".py")]
    code += [os.path.join(bench, "drivers", f)
             for f in os.listdir(os.path.join(bench, "drivers"))]
    for path in code:
        with open(path) as f:
            text = f.read()
        for n in names:
            assert f'"{n}"' not in text and f"'{n}'" not in text, (path, n)


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


# What a later ``model_config`` PR brings, shaped as the accepted ones were: a
# configuration, a mix, a cell, a reader and a counts file of its own, and
# twelve per-layer entries that its cell alone reads — {name: (reader, args)};
# what else an entry says follows from its reader.  And, since PR 58, what it
# JOINS: a roofline, a share and a counter that the models of experts share,
# by its cell's name at the end of their lists and by the words of its own in
# the ``metric_args`` of the configuration file it brings — JOINED.
NEW_CONFIG, NEW_MIX, NEW_CELL = "tlm-new", "new_mix", "lm_new_cell"
NEW_READER, NEW_COUNTS = "benchmark/metrics/new_metric.py", \
    "benchmark/kernel_counts_new.py"
_IN_STEPS = {"program": "^jit_fn_decode_lm_step\\b",
             "span": "decode::step\\.observe", "counts": NEW_COUNTS}
_IN_PREFILLS = {"program": "^jit_fn_decode_lm_prefill_",
                "span": "decode::prefill\\.observe", "counts": NEW_COUNTS}
_ROOFLINE, _SCOPES, _RATIO = ("benchmark/metrics/" + f for f in (
    "kernel_roofline.py", "scope_share.py", "counter_ratio.py"))
NEW_METRICS = {
    "steps_twice.served_new": (NEW_READER, {"scale": 2.0}),
    "mixer_share.served_new": (_SCOPES, {"scopes": ["/new_mixer/"]}),
    "experts_share.served_new": (_SCOPES, {"scopes": ["/new_experts/"]}),
    "attn_share.served_new": (_SCOPES, {"scopes": ["/new_attn/"]}),
    "experts_prefill_roofline.served_new": (_ROOFLINE, dict(
        _IN_PREFILLS, kernel="^new_grouped", count="experts_prefill")),
    "experts_step_roofline.served_new": (_ROOFLINE, dict(
        _IN_STEPS, kernel="^new_grouped", count="experts_step")),
    "prefill_attn_roofline.served_new": (_ROOFLINE, dict(
        _IN_PREFILLS, kernel="^new_flash_fwd", count="prefill_attn")),
    "decode_attn_roofline.served_new": (_ROOFLINE, dict(
        _IN_STEPS, kernel="^new_paged_attn", count="decode_attn")),
    "expert_load_max_over_mean.served_new": (_RATIO, {
        "num": ["step_new_load_max_sum"], "den": ["step_new_assignments"],
        "times_config": "n_layer"}),
    "experts_touched_per_step.served_new": (_RATIO, {
        "num": ["step_new_touched"], "den": ["steps"]}),
    "tile_pad_share.served_new": (_RATIO, {
        "num": ["prefill_new_pad_rows"], "den": ["prefill_new_rows"],
        "scale": 100.0}),
    "state_live_share.served_new": (_RATIO, {
        "num": ["step_new_rows_live"], "den": ["step_new_rows_held"],
        "scale": 100.0}),
}
JOINED = {
    "moe_prefill_roofline.served": {"counts": NEW_COUNTS,
                                    "kernel": "^new_grouped_all"},
    "moe_share.served": {"scopes": ["/new_route/", "/new_experts/"]},
    "expert_load_max_over_mean.served": {"times_config": "n_layer"},
}
_SAYS = {      # unit, better, source, layer: the accepted entries' own words
    NEW_READER: ("count", "higher", "program_counter", "decode plane"),
    _RATIO: ("ratio", "lower", "program_counter", "decode plane"),
    _SCOPES: ("%", "higher", "device_trace", "kernels and XLA ops"),
    _ROOFLINE: ("%", "higher", "device_trace", "kernels and XLA ops")}


def _copy_of(root, *dirs):
    for d in dirs:
        shutil.copytree(os.path.join(REPO, d), os.path.join(root, d),
                        ignore=shutil.ignore_patterns("__pycache__"))


def _a_later_prs_files(root):
    """Writes what that PR adds into ``root``'s copy of ``benchmark/`` — 16
    files, none that was there — and returns its entries of the manifest: the
    configuration, the cell and the per-layer metrics."""
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "tlm-gpt1w.json")) as f:
        cfg = json.load(f)
    cfg.update(name=NEW_CONFIG, max_seq_len=256, metric_args=JOINED)
    with open(os.path.join(bench, "configs", NEW_CONFIG + ".json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "batch_sat.json")) as f:
        mix = json.load(f)
    mix.update(callers=40)
    mix["prompt_tokens"]["max"] = 128
    mix["engine"]["prefill_buckets"] = [64, 128]
    with open(os.path.join(bench, "traffic", NEW_MIX + ".json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, NEW_READER), "w") as f:
        f.write("def read(ctx, scale):\n"
                "    return scale * ctx['decodez']['steps']\n")
    counts = {a["count"] for _, a in NEW_METRICS.values() if "count" in a}
    for name in JOINED:         # the joined roofline's count, by its file
        with open(os.path.join(bench, "metrics", name + ".json")) as f:
            counts |= {json.load(f)["args"].get("count")} - {None}
    counts = sorted(counts)
    with open(os.path.join(root, NEW_COUNTS), "w") as f:
        f.write("def _nothing(cfg, w):\n    return 0.0, 0.0\n\n\n"
                f"COUNTS = dict.fromkeys({counts!r}, _nothing)\n")
    per_layer = []
    for name, (reader, args) in NEW_METRICS.items():
        with open(os.path.join(bench, "metrics", name + ".json"), "w") as f:
            json.dump(dict(what="a new metric", reader=reader, args=args), f)
        unit, better, source, layer = _SAYS[reader]
        per_layer.append({
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "served_tokens_per_s",
            "workloads": [NEW_CELL]})
    config = {"name": NEW_CONFIG, "source": "https://example.org/config.json",
              "file": f"benchmark/configs/{NEW_CONFIG}.json", "reduced": [],
              "why": "a configuration added by a later PR"}
    cell = {"name": NEW_CELL, "config": NEW_CONFIG, "traffic": NEW_MIX,
            "chips": 1, "why": "a cell added by a later PR"}
    return config, cell, per_layer


def _appended(config, cell, per_layer):
    """``BENCHMARK.json`` as that PR leaves it: its entries at the END of
    ``configs``, ``workloads`` and ``per_layer``, the cell's name under
    ``served_tokens_per_s`` and — a cell of the configuration it brings — at
    the end of the lists of the entries it JOINS; no other line of the
    manifest."""
    manifest = json.loads(json.dumps(MANIFEST))
    if config is not None:
        manifest["configs"].append(config)
    manifest["workloads"].append(cell)
    joins = JOINED if config is not None else ()
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] == "served_tokens_per_s" or m["name"] in joins:
            m["workloads"] = m["workloads"] + [cell["name"]]
    manifest["per_layer"] += per_layer
    return manifest


def _less_the_joins(per_layer):
    """``per_layer`` with the later PR's cell taken off the ends of the lists
    it joined: what must be the manifest's own, entry for entry."""
    return [dict(m, workloads=m["workloads"][:-1])
            if m["name"] in JOINED and m["workloads"][-1] == NEW_CELL else m
            for m in per_layer]


def test_a_config_a_mix_a_metric_and_a_cell_are_added_by_files_alone(tmp_path):
    root = str(tmp_path)
    _copy_of(root, "benchmark")
    before = _digests(os.path.join(root, "benchmark"))
    config, new_cell, entries = _a_later_prs_files(root)

    # one more cell of a configuration that is there: one entry of workloads
    # and its name under served_tokens_per_s, no other line of the manifest
    ninth = _appended(None, {
        "name": "lm_ninth", "config": "tlm-gpt1w", "traffic": NEW_MIX,
        "chips": 1, "why": "a cell added by a later PR"}, [])
    assert harness.check_manifest(root, ninth) == []
    assert {m["name"] for m in harness.Cell(root, ninth, "lm_ninth").per_layer} \
        == SERVED_FAMILY

    # a cell of a new configuration listed under served_tokens_per_s — no
    # per-layer entry added, no file under metrics/ written — reads the serve
    # family of twelve and the two compile-cache counts, and the three shared
    # entries it is listed under with the words its configuration gives
    manifest = _appended(config, new_cell, [])
    assert harness.check_manifest(root, manifest) == []
    joined = {m["name"] for m in
              harness.Cell(root, manifest, NEW_CELL).per_layer}
    assert joined == SERVED_FAMILY | set(JOINED)
    assert joined >= {
        "decode_step_ms.served", "prefill_ms.served", "step_host_ms.served",
        "step_emit_ms.served", "device_idle_share.served",
        "idle_engine_host_share.served", "idle_no_work_share.served",
        "hbm_peak_gb.served", "hbm_temp_gb.served",
        "tokens_per_decode_step.served", "top_device_op_share.served",
        "program_build_s"}
    assert _less_the_joins(manifest["per_layer"]) == MANIFEST["per_layer"]
    # a metric of its own is one more entry and one more file
    manifest = _appended(config, new_cell, entries)
    assert harness.check_manifest(root, manifest) == []

    cell = harness.Cell(root, manifest, NEW_CELL)
    assert cell.config["max_seq_len"] == 256 and cell.mix["callers"] == 40
    cell.driver().validate(cell, float(manifest["run_seconds"]))
    assert {m["name"] for m in cell.per_layer} == joined | set(NEW_METRICS)
    # (the family's program_build_s reads the program's counter: importing
    # the program is not a reader's time, so it is done before the clock)
    harness.program_counters()
    phases = harness.Phases(time.perf_counter())
    values = harness.read_per_layer(cell, {
        "decodez": {"steps": 21, "tokens": 0, "prefills": 0},
        "compile": {"in_window": 0, "cache_hits_in_setup": 3}}, phases)
    assert [n for n, _ in phases.phases] == ["readers"]
    assert not phases.inside                    # no reader slow enough to name
    assert values["steps_twice.served_new"] == 42.0
    assert values["warm_cache_hits"] == 3.0
    # the cells that were there still load, and no file that was there changed
    for w in CELLS:
        harness.Cell(root, manifest, w)
    after = _digests(os.path.join(root, "benchmark"))
    assert {k: after[k] for k in before} == before
    # the configuration, the mix, the reader, the counts; a file an entry
    assert len(after) == len(before) + 4 + len(entries)


def test_select_metrics_leaves_out_what_has_no_value():
    wanted = [{"name": "a", "unit": "ms"}, {"name": "b", "unit": "s"},
              {"name": "c", "unit": "%"}]
    got = harness.select_metrics(wanted, {"a": 1.5, "c": float("nan")})
    assert got == {"a": {"value": 1.5, "unit": "ms"}}


def test_result_line_has_exactly_the_contracts_keys():
    acct = harness.Accounting()
    acct.record(True, None)
    line = json.loads(harness.result_line(
        True, acct, {"x": {"value": 1.0, "unit": "s"}},
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "memory_peak_bytes": 5, "live_peak_bytes": 3, "temp_peak_bytes": 2},
        {"device_ops": [], "idle_gaps": []}))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["attempted"] == 1 and line["failed"] == 0
    # what was compared rides last, under a key of its own
    checks = harness.Checks()
    checks.add("reference comparison: logit_err within 0.02", True, "0.0071")
    checks.add("no compile inside the window", False, "1 backend compile(s)")
    line = json.loads(harness.result_line(
        False, acct, {}, {"platform": "tpu", "kind": "TPU v5 lite",
                          "count": 1, "memory_peak_bytes": 5}, None, checks))
    assert list(line)[-1] == "checks" and line["correct"] is False
    assert line["checks"] == [
        ["reference comparison: logit_err within 0.02", True, "0.0071"],
        ["no compile inside the window", False, "1 backend compile(s)"]]


def test_percentile_and_spread_are_the_stated_rules():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert harness.percentile(xs, 0.5) == 30.0
    assert harness.percentile(xs, 0.9) == pytest.approx(46.0)
    assert harness.percentile([7.0], 0.95) == 7.0
    # statistics.quantiles(n=4) of 1..6: quartiles 1.75 and 5.25, median 3.5
    assert harness.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# the guard: the tests under tests/benchmark/ take a new cell as new files and
# entries too (the rule is in this file's first lines)
# ---------------------------------------------------------------------------
HERE = os.path.relpath(os.path.dirname(os.path.abspath(__file__)), REPO)
MODULES = sorted(os.path.basename(p) for p in
                 glob.glob(os.path.join(REPO, HERE, "test_benchmark_*.py")))


@pytest.fixture(scope="module")
def later_checkout(tmp_path_factory):
    """The checkout that later PR leaves: every directory of ``paths`` as it
    is here, the PR's files beside them, and ``BENCHMARK.json`` with the PR's
    entries at the end of its lists.  (root, manifest)."""
    root = str(tmp_path_factory.mktemp("later_pr"))
    _copy_of(root, *MANIFEST["paths"])
    manifest = _appended(*_a_later_prs_files(root))
    with open(os.path.join(root, harness.MANIFEST), "w") as f:
        json.dump(manifest, f, indent=1)
    return root, manifest


def test_a_later_prs_entries_fit_and_change_no_accepted_cells_metrics(
        later_checkout):
    root, manifest = later_checkout
    assert harness.check_manifest(root, manifest) == []
    assert harness.load_manifest(root) == manifest
    # the next served configuration, at twelve entries, still fits
    assert len(NEW_METRICS) == 12
    assert len(manifest["per_layer"]) == len(PER_LAYER) + 12 <= 128
    assert [w["name"] for w in manifest["workloads"]] == CELLS + [NEW_CELL]
    assert len(manifest["workloads"]) <= 24
    # (the rate's own entry and the three joined ones have one more name at
    # the end of their lists; nothing else about any entry differs)
    assert _less_the_joins(manifest["per_layer"][:len(PER_LAYER)]) \
        == MANIFEST["per_layer"]
    for w in CELLS:
        (e2e, per_layer), (was_e2e, was) = (
            harness.cell_metrics(m, w) for m in (manifest, MANIFEST))
        assert _less_the_joins(per_layer) == was \
            and [m["name"] for m in e2e] == [m["name"] for m in was_e2e], w
        # and every accepted cell reads every entry with the reader and the
        # arguments it read it with: the joined entries' too
        later, here = harness.Cell(root, manifest, w), \
            harness.Cell(REPO, MANIFEST, w)
        for m in was:
            assert later.metric_file(m["name"]) \
                == here.metric_file(m["name"]), (w, m["name"])
    # and its cell reads the list-less served family through its name under
    # served_tokens_per_s alone, beside its own twelve and the three it joined
    # with its own words
    cell = harness.Cell(root, manifest, NEW_CELL)
    assert {m["name"] for m in cell.per_layer} \
        == SERVED_FAMILY | set(NEW_METRICS) | set(JOINED)
    for name, words in JOINED.items():
        with open(os.path.join(REPO, "benchmark", "metrics",
                               name + ".json")) as f:
            shared = json.load(f)
        got = cell.metric_file(name)
        assert got["reader"] == shared["reader"]
        assert got["args"] == {**shared["args"], **words}
        assert callable(cell.reader(name))


def _manifest_readers(source):
    """The test functions in a module's source that read the manifest: the
    names of those that mention ``MANIFEST``, ``load_manifest`` or anything
    the module derives from them at its top level (a list of cells, a helper
    that loads one), in their body or their decorators."""
    tree, derived = ast.parse(source), {"MANIFEST", "load_manifest"}

    def mentions(node):
        return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)} \
            | {n.attr for n in ast.walk(node) if isinstance(n, ast.Attribute)}

    def defined(node):
        if isinstance(node, ast.FunctionDef):
            return {node.name}
        if isinstance(node, ast.Assign):
            return {n.id for t in node.targets for n in ast.walk(t)
                    if isinstance(n, ast.Name)}
        return set()

    while True:
        more = {name for node in tree.body if mentions(node) & derived
                for name in defined(node)} - derived
        if not more:
            break
        derived |= more
    return [node.name for node in tree.body
            if isinstance(node, ast.FunctionDef)
            and node.name.startswith("test_") and node.name in derived]


def _calls(fn):
    """The calls pytest makes of a test function whose every argument a
    ``parametrize`` mark supplies — one, of nothing, where it takes none;
    None where it takes a fixture."""
    calls = [{}]
    for mark in getattr(fn, "pytestmark", []):
        if mark.name != "parametrize":
            continue
        names, rows = mark.args
        if isinstance(names, str):
            names = [n.strip() for n in names.split(",")]
        rows = [r.values if isinstance(r, type(pytest.param()))
                else r if len(names) > 1 else (r,) for r in rows]
        calls = [dict(c, **dict(zip(names, r)))
                 for c, r in itertools.product(calls, rows)]
    if calls and set(inspect.signature(fn).parameters) != set(calls[0]):
        return None
    return calls


def _failures(path):
    """Imports the test module at ``path`` — its ``REPO`` is the checkout it
    lies in, so its ``MANIFEST`` and every list it derives at import are that
    checkout's — and makes pytest's calls of its test functions that read the
    manifest and take no fixture.  Functions that need an engine, a trace, a
    chip or a temporary directory are not held to anything here, which in
    this module leaves the guard itself out; of the others none is skipped.
    Returns (what failed with their failing lines, how many calls were
    made)."""
    from benchmark.metrics import program_spans
    with open(path) as f:
        names = _manifest_readers(f.read())
    failed, made, sys_path = [], 0, list(sys.path)
    # (the readers import this process's benchmark package, which knows its
    # checkout by program_spans.ROOT: a counts file is looked for under it)
    root, program_spans.ROOT = program_spans.ROOT, os.path.dirname(
        os.path.dirname(os.path.dirname(path)))
    try:
        spec = importlib.util.spec_from_file_location(
            "guarded_" + os.path.basename(path)[:-3], path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert not names or mod.REPO == program_spans.ROOT
        for name in names:
            fn = getattr(mod, name)
            for kwargs in _calls(fn) or []:
                made += 1
                try:
                    fn(**kwargs)
                except Exception as e:      # noqa: BLE001 - each is reported
                    at = [fr for fr in traceback.extract_tb(e.__traceback__)
                          if fr.filename == path][-1]
                    failed.append(f"{name}{list(kwargs.values())}"
                                  f" line {at.lineno}: {at.line}"
                                  f" -> {type(e).__name__}: {e}"[:600])
    finally:
        sys.path[:], program_spans.ROOT = sys_path, root
    return failed, made


@pytest.mark.parametrize("module", MODULES)
def test_a_module_takes_a_configuration_and_a_cell_appended_by_a_later_pr(
        module, later_checkout):
    """One case a module under ``tests/benchmark/``, found by a glob: the
    next configuration's module is held to the rule the day it is added."""
    root, _ = later_checkout
    failed, _ = _failures(os.path.join(root, HERE, module))
    assert failed == [], "\n".join(
        [f"{module} pins the manifest's size or order: with a configuration, "
         "a cell and twelve per-layer entries appended,"] + failed)


def test_the_guard_fails_a_module_that_pins_where_the_lists_end(
        later_checkout):
    """The guard's own control: the pins of the module that stopped PR 48,
    true of the manifest as it is here, fail in the later checkout, each named
    with its line; what reads by name passes, and what takes a fixture or
    reads no manifest is not called."""
    root, _ = later_checkout
    # (where the lists end today, read here for once, to plant pins on it)
    last_cell, last_config = CELLS[-1], MANIFEST["configs"][-1]["name"]
    path = os.path.join(root, HERE, "pinning.py")
    with open(path, "w") as f:
        f.write(f'''import os
from benchmark import harness
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MANIFEST = harness.load_manifest(REPO)
NAMES = [w["name"] for w in MANIFEST["workloads"]]
def _family():
    return [m for m in MANIFEST["per_layer"] if "workloads" not in m]
def test_by_name():
    assert NAMES.count({last_cell!r}) == 1 and len(NAMES) <= 24
def test_the_count():
    assert len(NAMES) == {len(CELLS)}
def test_the_last_cell():
    assert MANIFEST["workloads"][-1]["name"] == {last_cell!r}
def test_the_last_configuration():
    assert MANIFEST["configs"][-1]["name"] == {last_config!r}
def test_room_for_three():
    assert len(MANIFEST["per_layer"]) <= {len(PER_LAYER) + 3}
def test_a_family_by_a_helper():
    assert len(_family()) == {len(LISTLESS)}
def test_with_a_fixture(tmp_path):
    assert MANIFEST["workloads"][-1]["name"] == {last_cell!r}
def test_of_something_else():
    assert os.path.basename(REPO) == "repo"
''')
    try:
        failed, made = _failures(path)
    finally:
        os.remove(path)
    assert made == 6
    assert [f.split("[")[0] for f in failed] == [
        "test_the_count", "test_the_last_cell", "test_the_last_configuration",
        "test_room_for_three"]
    assert 'MANIFEST["workloads"][-1]["name"] ==' in failed[1]
