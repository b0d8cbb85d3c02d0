"""BENCHMARK.json and the files it names: the contract's limits that need no
chip, every cell's configuration, mix and metric files found by name, and a
configuration, a mix, a per-layer metric and a cell added as new files with
no existing file edited."""
import hashlib
import json
import os
import shutil
import sys
import time

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
    bad["workloads"][0]["name"] = "has space"
    bad["end_to_end"][0]["unit"] = "tokens per second"
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
    assert len(PER_LAYER) <= 128                # the contract's limit
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
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    metrics = os.path.join(root, "benchmark", "metrics")
    # (the same reader moving ANOTHER end-to-end metric, as decode_step_ms.tbt
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


def test_a_config_a_mix_a_metric_and_a_cell_are_added_by_files_alone(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(os.path.join(root, "benchmark"))
    bench = os.path.join(root, "benchmark")

    with open(os.path.join(bench, "configs", "tlm-gpt1w.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tlm-new", max_seq_len=256)
    with open(os.path.join(bench, "configs", "tlm-new.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "batch_sat.json")) as f:
        mix = json.load(f)
    mix.update(callers=40)
    mix["prompt_tokens"]["max"] = 128
    mix["engine"]["prefill_buckets"] = [64, 128]
    with open(os.path.join(bench, "traffic", "new_mix.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "metrics", "new_metric.py"), "w") as f:
        f.write("def read(ctx, scale):\n    return scale * ctx['decodez']['steps']\n")
    entry = {"name": "steps_twice.served", "unit": "count", "better": "higher",
             "source": "program_counter", "layer": "decode plane",
             "moves": "served_tokens_per_s", "workloads": ["lm_new_cell"]}
    with open(os.path.join(bench, "metrics", "steps_twice.served.json"), "w") as f:
        json.dump(dict(what="a new reader", args={"scale": 2.0},
                       reader="benchmark/metrics/new_metric.py"), f)

    # a ninth cell of a configuration that is there: one entry of workloads
    # and its name under served_tokens_per_s, no other line of the manifest
    ninth = json.loads(json.dumps(MANIFEST))
    ninth["workloads"].append({
        "name": "lm_ninth", "config": "tlm-gpt1w", "traffic": "new_mix",
        "chips": 1, "why": "a cell added by a later PR"})
    for m in ninth["end_to_end"]:
        if m["name"] == "served_tokens_per_s":
            m["workloads"] = m["workloads"] + ["lm_ninth"]
    assert harness.check_manifest(root, ninth) == []
    assert {m["name"] for m in harness.Cell(root, ninth, "lm_ninth").per_layer} \
        == SERVED_FAMILY

    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append({
        "name": "tlm-new", "source": "https://example.org/config.json",
        "file": "benchmark/configs/tlm-new.json", "reduced": [],
        "why": "a configuration added by a later PR"})
    manifest["workloads"].append({
        "name": "lm_new_cell", "config": "tlm-new", "traffic": "new_mix",
        "chips": 1, "why": "a cell added by a later PR"})
    for m in manifest["end_to_end"]:
        if m["name"] == "served_tokens_per_s":
            m["workloads"] = m["workloads"] + ["lm_new_cell"]
    # a ninth cell listed under served_tokens_per_s ALONE — no per-layer
    # entry edited or added, no file under metrics/ touched — reads the
    # serve family of twelve and the two compile-cache counts
    assert harness.check_manifest(root, manifest) == []
    joined = {m["name"] for m in
              harness.Cell(root, manifest, "lm_new_cell").per_layer}
    assert joined == SERVED_FAMILY
    assert joined >= {
        "decode_step_ms.served", "prefill_ms.served", "step_host_ms.served",
        "step_emit_ms.served", "device_idle_share.served",
        "idle_engine_host_share.served", "idle_no_work_share.served",
        "hbm_peak_gb.served", "hbm_temp_gb.served",
        "tokens_per_decode_step.served", "top_device_op_share.served",
        "program_build_s"}
    assert manifest["per_layer"] == MANIFEST["per_layer"]
    # a metric of its own is one more entry and one more file
    manifest["per_layer"].append(entry)
    assert harness.check_manifest(root, manifest) == []

    cell = harness.Cell(root, manifest, "lm_new_cell")
    assert cell.config["max_seq_len"] == 256 and cell.mix["callers"] == 40
    cell.driver().validate(cell, float(manifest["run_seconds"]))
    assert [m["name"] for m in cell.per_layer if m["name"] == entry["name"]]
    # (the family's program_build_s reads the program's counter: importing
    # the program is not a reader's time, so it is done before the clock)
    harness.program_counters()
    phases = harness.Phases(time.perf_counter())
    values = harness.read_per_layer(cell, {
        "decodez": {"steps": 21, "tokens": 0, "prefills": 0},
        "compile": {"in_window": 0, "cache_hits_in_setup": 3}}, phases)
    assert [n for n, _ in phases.phases] == ["readers"]
    assert not phases.inside                    # no reader slow enough to name
    assert values["steps_twice.served"] == 42.0
    assert values["warm_cache_hits"] == 3.0
    # the cells that were there still load, and no file that was there changed
    for w in CELLS:
        harness.Cell(root, manifest, w)
    after = _digests(os.path.join(root, "benchmark"))
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 4


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


def test_percentile_and_spread_are_the_stated_rules():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert harness.percentile(xs, 0.5) == 30.0
    assert harness.percentile(xs, 0.9) == pytest.approx(46.0)
    assert harness.percentile([7.0], 0.95) == 7.0
    # statistics.quantiles(n=4) of 1..6: quartiles 1.75 and 5.25, median 3.5
    assert harness.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)
