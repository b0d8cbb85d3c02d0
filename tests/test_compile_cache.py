"""Persistent cross-process compile cache (core/compile_cache.py) +
``Executor.warm_start``.

Covers the robustness contract — corrupted/truncated/version-skewed
entries degrade to a *counted* miss and are evicted (a cache fault must
never fail a run), concurrent same-key writers are atomic, the LRU byte
cap prunes oldest-used first — and the acceptance numbers: a second
process hydrates the fc/LeNet program from disk with persistent-cache
hits and a >= 2x faster time-to-first-run than the cold process.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import compile_cache as cc
from paddle_tpu.core import flags as _flags
from paddle_tpu.core import unique_name
from paddle_tpu.core.executor import Executor, Scope
from paddle_tpu.core.program import Program, program_guard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir(tmp_path):
    d = tmp_path / "cc"
    d.mkdir()
    _flags.set_flags({"compile_cache_dir": str(d)})
    try:
        yield str(d)
    finally:
        _flags.set_flags({"compile_cache_dir": ""})


def _fc_program(width=8, seed=0):
    prog, startup = Program(), Program()
    prog.random_seed = seed
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [width])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.01).minimize(loss)
    return prog, startup, loss


def _feed(width=8, bs=4, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(bs, width).astype("float32"),
            "y": rng.randn(bs, 1).astype("float32")}


def _counters():
    m = cc._cm()
    return {"hits": m.hits.value, "misses": m.misses.value,
            "faults": m.faults.value, "skews": m.version_skews.value,
            "evictions": m.evictions.value,
            "store_errors": m.store_errors.value}


def _train_once(prog, startup, loss, feed):
    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)
    (lv,) = exe.run(prog, feed=feed, fetch_list=[loss], scope=scope,
                    sync=True)
    return float(np.asarray(lv))


# ---------------------------------------------------------------------------
# flag unset: current behavior, no persistence anywhere
# ---------------------------------------------------------------------------

def test_flag_unset_no_persistence(tmp_path):
    assert not cc.enabled()
    before = _counters()
    prog, startup, loss = _fc_program(width=3)
    _train_once(prog, startup, loss, _feed(width=3))
    after = _counters()
    assert after == before  # no persistent path was even consulted
    assert cc.store("deadbeef", None) is None  # store is a no-op unguarded


# ---------------------------------------------------------------------------
# in-process round trip + counters
# ---------------------------------------------------------------------------

def test_fresh_executor_hydrates_from_disk(cache_dir):
    prog, startup, loss = _fc_program(width=5)
    feed = _feed(width=5)
    before = _counters()
    l1 = _train_once(prog, startup, loss, feed)
    mid = _counters()
    assert mid["misses"] > before["misses"]  # cold: counted disk misses
    assert len(cc.list_entries(cache_dir)) >= 2  # startup + train step

    # a FRESH executor (empty in-memory cache) hydrates from disk
    l2 = _train_once(prog, startup, loss, feed)
    after = _counters()
    assert after["hits"] >= mid["hits"] + 2
    assert after["faults"] == before["faults"]
    assert l2 == pytest.approx(l1, rel=1e-5)


def test_run_steps_hydrates_from_disk(cache_dir):
    prog, startup, loss = _fc_program(width=4)
    K, bs = 3, 4
    rng = np.random.RandomState(1)
    feed = {"x": rng.randn(K, bs, 4).astype("float32"),
            "y": rng.randn(K, bs, 1).astype("float32")}

    def steps_once():
        scope, exe = Scope(), Executor()
        exe.run(startup, scope=scope)
        (ls,) = exe.run_steps(prog, feed=feed, fetch_list=[loss],
                              scope=scope)
        return np.asarray(ls)

    l1 = steps_once()
    before = _counters()
    l2 = steps_once()
    after = _counters()
    assert after["hits"] > before["hits"]
    np.testing.assert_allclose(l1, l2, rtol=1e-5)


# ---------------------------------------------------------------------------
# robustness: every fault class degrades to a counted miss + eviction
# ---------------------------------------------------------------------------

def _sole_train_entry(cache_dir, before_keys=()):
    keys = {e["key"] for e in cc.list_entries(cache_dir)}
    fresh = keys - set(before_keys)
    assert fresh, "expected a new cache entry"
    return sorted(fresh)


def test_corrupted_entry_counted_miss_and_evicted(cache_dir):
    prog, startup, loss = _fc_program(width=6)
    feed = _feed(width=6)
    l1 = _train_once(prog, startup, loss, feed)
    entries = cc.list_entries(cache_dir)
    assert entries
    for e in entries:  # corrupt EVERY entry: garbage past the magic
        with open(e["path"], "wb") as f:
            f.write(b"not a cache entry at all")
    before = _counters()
    l2 = _train_once(prog, startup, loss, feed)  # must not raise
    after = _counters()
    assert l2 == pytest.approx(l1, rel=1e-5)
    assert after["faults"] >= before["faults"] + 2
    assert after["misses"] > before["misses"]
    # bad files were evicted, then re-stored by the recompile
    for e in entries:
        if os.path.exists(e["path"]):
            cc.read_header(e["path"])  # whatever is there now is valid


def test_truncated_entry_counted_miss_and_evicted(cache_dir):
    prog, startup, loss = _fc_program(width=7)
    feed = _feed(width=7)
    l1 = _train_once(prog, startup, loss, feed)
    for e in cc.list_entries(cache_dir):
        data = open(e["path"], "rb").read()
        with open(e["path"], "wb") as f:
            f.write(data[:len(data) // 2])
    before = _counters()
    l2 = _train_once(prog, startup, loss, feed)
    after = _counters()
    assert l2 == pytest.approx(l1, rel=1e-5)
    assert after["faults"] >= before["faults"] + 2


def test_version_skew_counted_and_evicted(cache_dir):
    prog, startup, loss = _fc_program(width=9)
    feed = _feed(width=9)
    _train_once(prog, startup, loss, feed)
    # rewrite every entry's header as if a different jax had written it
    for e in cc.list_entries(cache_dir):
        hdr, blob = cc._read_entry(e["path"])
        hdr["jax"] = "0.0.1-somethingelse"
        hb = json.dumps(hdr, sort_keys=True).encode()
        with open(e["path"], "wb") as f:
            f.write(cc.MAGIC + cc._HEADER_LEN.pack(len(hb)) + hb + blob)
    before = _counters()
    _train_once(prog, startup, loss, feed)
    after = _counters()
    assert after["skews"] >= before["skews"] + 2
    assert after["faults"] == before["faults"]  # skew is its own counter
    # skewed entries were evicted and replaced by current-env ones
    for e in cc.list_entries(cache_dir):
        assert cc.read_header(e["path"])["jax"] != "0.0.1-somethingelse"


def test_wrong_executable_under_right_key_falls_back(cache_dir):
    """Fingerprint blind spot drill: the entry file for program A's key
    holds program B's executable — load succeeds, the FIRST dispatch
    faults, and the executor falls back to a fresh compile instead of
    failing the run (the bad file is evicted)."""
    prog_a, startup_a, loss_a = _fc_program(width=10)
    prog_b, startup_b, loss_b = _fc_program(width=11)
    feed_a = _feed(width=10)
    l_cold = _train_once(prog_a, startup_a, loss_a, feed_a)
    _train_once(prog_b, startup_b, loss_b, _feed(width=11))
    entries = {e["key"]: e for e in cc.list_entries(cache_dir)}
    assert len(entries) >= 4
    # overwrite every entry payload with some OTHER entry's payload
    keys = sorted(entries)
    blobs = {k: open(entries[k]["path"], "rb").read() for k in keys}
    for k, other in zip(keys, keys[1:] + keys[:1]):
        hdr, blob = cc._read_entry(entries[other]["path"])
        hdr2 = dict(hdr)
        hdr2["key"] = k
        hb = json.dumps(hdr2, sort_keys=True).encode()
        with open(entries[k]["path"], "wb") as f:
            f.write(cc.MAGIC + cc._HEADER_LEN.pack(len(hb)) + hb + blob)
    before = _counters()
    l_warm = _train_once(prog_a, startup_a, loss_a, feed_a)  # must not raise
    after = _counters()
    assert l_warm == pytest.approx(l_cold, rel=1e-5)
    assert after["faults"] > before["faults"]


def test_lru_prune_under_max_bytes(cache_dir):
    # store 4 programs' entries, then cap the dir at roughly 2 entries
    progs = [_fc_program(width=12 + i) for i in range(4)]
    for i, (p, s, l) in enumerate(progs):
        _train_once(p, s, l, _feed(width=12 + i))
        time.sleep(0.02)  # distinct mtimes for a deterministic LRU order
    entries = cc.list_entries(cache_dir)
    total = sum(e["bytes"] for e in entries)
    cap = total // 2
    before = _counters()
    old_flag = _flags.get_flags("compile_cache_max_bytes")
    try:
        _flags.set_flags({"compile_cache_max_bytes": cap})
        evicted = cc.prune_lru(cache_dir)
    finally:
        _flags.set_flags({"compile_cache_max_bytes": old_flag})
    after = _counters()
    assert evicted
    assert after["evictions"] >= before["evictions"] + len(evicted)
    left = cc.list_entries(cache_dir)
    assert sum(e["bytes"] for e in left) <= cap
    # oldest-used went first: survivors are the newest entries
    evicted_mtimes = [e["mtime"] for e in entries if e["key"] in evicted]
    kept_mtimes = [e["mtime"] for e in left]
    assert max(evicted_mtimes) <= min(kept_mtimes) + 1e-6


def test_store_respects_cap_inline(cache_dir):
    old = _flags.get_flags("compile_cache_max_bytes")
    try:
        _flags.set_flags({"compile_cache_max_bytes": 1})  # absurdly small
        prog, startup, loss = _fc_program(width=16)
        _train_once(prog, startup, loss, _feed(width=16))
        # every store immediately pruned itself down to <= 1 byte total
        assert cc.store_stats(cache_dir)["bytes"] <= 1
    finally:
        _flags.set_flags({"compile_cache_max_bytes": old})


# ---------------------------------------------------------------------------
# warm_start
# ---------------------------------------------------------------------------

def test_warm_start_precompiles_and_run_hits(cache_dir):
    prog, startup, loss = _fc_program(width=17)
    feed = _feed(width=17)
    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)
    res = exe.warm_start(prog,
                         feed_specs={n: v for n, v in feed.items()},
                         fetch_list=[loss], scope=scope)
    assert res["warmed"] == 1 and res["segments"] == 1
    assert res["compiled"] + res["persistent_hits"] == 1
    hits_before = cc._cm().hits.value
    from paddle_tpu.observability import stats as _stats
    mem_hits = _stats.scope("executor").counter("cache_hits")
    v0 = mem_hits.value
    (lv,) = exe.run(prog, feed=feed, fetch_list=[loss], scope=scope,
                    sync=True)
    assert np.isfinite(float(np.asarray(lv)))
    # the real run found the precompiled executable in MEMORY
    assert mem_hits.value == v0 + 1
    assert cc._cm().hits.value == hits_before


def test_warm_start_spec_forms(cache_dir):
    prog, startup, loss = _fc_program(width=18)
    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)
    # (shape, dtype) pair + bare shape tuple (dtype from the program var)
    res = exe.warm_start(
        prog,
        feed_specs={"x": ((4, 18), "float32"), "y": (4, 1)},
        fetch_list=[loss], scope=scope)
    assert res["warmed"] == 1
    feed = _feed(width=18)
    from paddle_tpu.observability import stats as _stats
    mem_hits = _stats.scope("executor").counter("cache_hits")
    v0 = mem_hits.value
    exe.run(prog, feed=feed, fetch_list=[loss], scope=scope, sync=True)
    assert mem_hits.value == v0 + 1


def test_warm_start_without_cache_flag_still_precompiles():
    assert not cc.enabled()
    prog, startup, loss = _fc_program(width=19)
    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)
    res = exe.warm_start(prog, feed_specs=_feed(width=19),
                         fetch_list=[loss], scope=scope)
    assert res["compiled"] == 1 and res["persistent_hits"] == 0
    from paddle_tpu.observability import stats as _stats
    mem_hits = _stats.scope("executor").counter("cache_hits")
    v0 = mem_hits.value
    exe.run(prog, feed=_feed(width=19), fetch_list=[loss], scope=scope,
            sync=True)
    assert mem_hits.value == v0 + 1


def test_warm_start_dynamic_shape_rejected():
    prog, startup, loss = _fc_program(width=20)
    exe = Executor()
    with pytest.raises(ValueError, match="dynamic"):
        exe.warm_start(prog, feed_specs={"x": (-1, 20), "y": (4, 1)},
                       fetch_list=[loss])


def test_warm_start_missing_state_skips_segment(cache_dir):
    prog, startup, loss = _fc_program(width=21)
    exe = Executor()
    # startup never ran and 'x'/'y' widths declared -1 batch: params are
    # declared though — warm compiles from decls; RNG state path etc.
    # But an empty scope with undeclared shapes must SKIP, not raise.
    scope = Scope()
    res = exe.warm_start(prog, feed_specs=_feed(width=21),
                         fetch_list=[loss], scope=scope)
    # fc params are statically declared, so this actually warms; the
    # contract under test: no exception, and a summary either way
    assert res["segments"] == 1
    assert res["warmed"] + len(res["skipped"]) == 1


# ---------------------------------------------------------------------------
# statusz provider
# ---------------------------------------------------------------------------

def test_statusz_provider(cache_dir):
    prog, startup, loss = _fc_program(width=22)
    _train_once(prog, startup, loss, _feed(width=22))
    st = cc._statusz()
    assert st["enabled"] and st["entries"] >= 2 and st["bytes"] > 0
    _flags.set_flags({"compile_cache_dir": ""})
    assert cc._statusz() == {"enabled": False}


# ---------------------------------------------------------------------------
# cache_admin operator CLI
# ---------------------------------------------------------------------------

def test_cache_admin_cli(cache_dir):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import cache_admin
    finally:
        sys.path.pop(0)
    # the CLI parses the frame with its own stdlib constants (so it
    # runs on hosts without jax) — they must stay in sync with the
    # runtime's
    assert cache_admin.MAGIC == cc.MAGIC
    assert cache_admin.FORMAT_VERSION == cc.FORMAT_VERSION
    assert cache_admin.ENTRY_SUFFIX == cc.ENTRY_SUFFIX
    prog, startup, loss = _fc_program(width=23)
    _train_once(prog, startup, loss, _feed(width=23))

    lines = list(cache_admin.entry_lines(cache_dir))
    assert len(lines) >= 2 and all("jax=" in l for l in lines)

    st = cache_admin.stat_dir(cache_dir)
    assert st["tier_a_entries"] >= 2 and st["tier_a_bytes"] > 0

    res = cache_admin.verify_dir(cache_dir, deep=True)
    assert res["bad"] == [] and res["ok"] >= 2

    # corrupt one entry: verify flags it, --fix removes it
    victim = cc.list_entries(cache_dir)[0]
    with open(victim["path"], "wb") as f:
        f.write(b"garbage")
    res = cache_admin.verify_dir(cache_dir)
    assert len(res["bad"]) == 1 and res["bad"][0]["key"] == victim["key"]
    res = cache_admin.verify_dir(cache_dir, fix=True)
    assert not os.path.exists(victim["path"])

    pruned = cache_admin.prune_dir(cache_dir, cap=1)
    assert pruned["tier_a_entries"] == 0 and pruned["evicted"]


# ---------------------------------------------------------------------------
# cross-process acceptance: second process hydrates, >= 2x faster TTFR
# ---------------------------------------------------------------------------

_CHILD = r"""
import json, os, sys, time
import numpy as np
import jax
import paddle_tpu as fluid
from paddle_tpu.core.executor import Executor, Scope
from paddle_tpu.models import mnist
from paddle_tpu.core.program import Program, program_guard
from paddle_tpu.core import unique_name, compile_cache as cc

mode = sys.argv[1]
if mode == "plain":
    assert not cc.enabled()

prog, startup = Program(), Program()
with program_guard(prog, startup), unique_name.guard():
    feeds, loss, acc = mnist.build()
B = 64
rng = np.random.RandomState(0)
feed = {"pixel": rng.randn(B, 1, 28, 28).astype("float32"),
        "label": rng.randint(0, 10, (B, 1)).astype("int64")}
scope, exe = Scope(), Executor()
exe.run(startup, scope=scope)
t0 = time.perf_counter()
(lv,) = exe.run(prog, feed=feed, fetch_list=[loss.name], scope=scope,
                sync=True)
ttfr = time.perf_counter() - t0
m = cc._cm()
print("CHILD=" + json.dumps({
    "ttfr_s": ttfr, "loss": float(np.asarray(lv)),
    "persistent_hits": m.hits.value,
    "persistent_misses": m.misses.value,
    "faults": m.faults.value}), flush=True)
"""


def _child_env(cache=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("FLAGS_compile_cache_dir", None)
    env.pop("JAX_ENABLE_X64", None)
    if cache:
        env["FLAGS_compile_cache_dir"] = cache
    return env


def _run_child(script, mode, cache=None, extra_env=None):
    env = _child_env(cache)
    if extra_env:
        env.update(extra_env)
    out = subprocess.run([sys.executable, script, mode], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    for line in out.stdout.splitlines():
        if line.startswith("CHILD="):
            return json.loads(line[len("CHILD="):])
    raise AssertionError(f"no CHILD line:\n{out.stdout}\n{out.stderr[-800:]}")


def test_second_process_gets_persistent_hits_and_2x_ttfr(tmp_path):
    """THE acceptance number: subprocess A compiles the LeNet train
    program cold; subprocess B (fresh interpreter, same cache dir)
    hydrates from disk — persistent hits > 0, zero faults, and a
    time-to-first-run at least 2x faster."""
    script = tmp_path / "cc_child.py"
    script.write_text(_CHILD)
    d = tmp_path / "cache"
    d.mkdir()
    cold = _run_child(str(script), "cold", cache=str(d))
    warm = _run_child(str(script), "warm", cache=str(d))
    assert cold["persistent_misses"] > 0 and cold["persistent_hits"] == 0
    assert warm["persistent_hits"] >= 2, warm
    assert warm["persistent_misses"] == 0, warm
    assert warm["faults"] == 0
    assert warm["loss"] == pytest.approx(cold["loss"], rel=1e-5)
    assert warm["ttfr_s"] * 2.0 <= cold["ttfr_s"], (
        f"warm {warm['ttfr_s']:.3f}s not >=2x faster than "
        f"cold {cold['ttfr_s']:.3f}s")


def test_flag_unset_process_behaves_as_before(tmp_path):
    script = tmp_path / "cc_child.py"
    script.write_text(_CHILD)
    res = _run_child(str(script), "plain")  # asserts inside the child
    assert res["persistent_hits"] == 0 and res["persistent_misses"] == 0


def test_concurrent_two_process_writers_atomic(tmp_path):
    """Two fresh processes compile the SAME programs into the same
    cache dir simultaneously: last rename wins per key, both runs
    succeed, no torn/tmp files survive, and a third process gets clean
    hits."""
    script = tmp_path / "cc_child.py"
    script.write_text(_CHILD)
    d = tmp_path / "cache"
    d.mkdir()
    env = _child_env(cache=str(d))
    procs = [subprocess.Popen([sys.executable, str(script), f"race{i}"],
                              env=env, cwd=REPO, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for i in range(2)]
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-2000:]
        assert "CHILD=" in out
    names = os.listdir(str(d))
    assert not [n for n in names if n.startswith(".tmp-")]
    for e in cc.list_entries(str(d)):
        cc.read_header(e["path"])  # every surviving entry is well-formed
    third = _run_child(str(script), "verify", cache=str(d))
    assert third["persistent_hits"] >= 2 and third["faults"] == 0


# ---------------------------------------------------------------------------
# where jax's own persistent cache lives: decided in ONE place
# ---------------------------------------------------------------------------

_DIR_CHILD = r"""
import json
import jax
import paddle_tpu as fluid
from paddle_tpu.core import compile_cache as cc
fluid.Executor()
print("DIR=" + json.dumps({"jax": jax.config.jax_compilation_cache_dir,
                           "wired": cc.wire_jax_cache()}), flush=True)
"""


def _dir_child(tmp_path, cwd, **env_extra):
    script = tmp_path / "dir_child.py"
    script.write_text(_DIR_CHILD)
    env = _child_env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    out = subprocess.run([sys.executable, str(script)], env=env, cwd=cwd,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("DIR="))
    return json.loads(line[len("DIR="):])


@pytest.mark.parametrize("with_flag", [False, True])
def test_env_var_places_jax_cache_and_nothing_moves_it(tmp_path, with_flag):
    """``JAX_COMPILATION_CACHE_DIR`` set: jax's cache lives there after
    an Executor is built — also when ``FLAGS_compile_cache_dir`` is set
    (whose ``.ptcc`` tier keeps its own directory) — and nothing is
    written under the in-checkout default."""
    default = cc.default_jax_cache_dir()
    before = sorted(os.listdir(default)) if os.path.isdir(default) else None
    x = str(tmp_path / "x")
    extra = {"JAX_COMPILATION_CACHE_DIR": x}
    if with_flag:
        extra["FLAGS_compile_cache_dir"] = str(tmp_path / "ptcc")
    got = _dir_child(tmp_path, REPO, **extra)
    assert got == {"jax": x, "wired": x}
    after = sorted(os.listdir(default)) if os.path.isdir(default) else None
    assert after == before


def test_default_jax_cache_dir_is_fixed_and_in_the_checkout(tmp_path):
    """Unset: the same path in two processes started from different
    directories, inside the checkout, derived from the package's
    location (never a temp name, a pid or a time)."""
    a = _dir_child(tmp_path, REPO)
    b = _dir_child(tmp_path, str(tmp_path),
                   FLAGS_compile_cache_dir=str(tmp_path / "ptcc"))
    want = os.path.join(REPO, ".jax_compile_cache")
    assert a == b == {"jax": want, "wired": want}
    assert cc.default_jax_cache_dir() == want


def test_executables_jax_cache_served_are_not_stored_again(tmp_path):
    """With both tiers on, an executable that jax's own cache loaded is
    not serialized into a ``.ptcc`` entry: on XLA:CPU such an entry has
    lost its kernels and kills the NEXT process at readback ("Function
    ... not found").  Process 1 fills both tiers; the tier-A entries are
    removed; process 2 compiles from jax's cache and must store nothing;
    process 3 still runs clean."""
    script = tmp_path / "cc_child.py"
    script.write_text(_CHILD)
    d = tmp_path / "ptcc"
    d.mkdir()
    both = {"JAX_ENABLE_COMPILATION_CACHE": "1",
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax"),
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
            "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1"}
    first = _run_child(str(script), "fill", cache=str(d), extra_env=both)
    assert cc.list_entries(str(d))
    for e in cc.list_entries(str(d)):
        os.remove(e["path"])
    second = _run_child(str(script), "jaxhit", cache=str(d), extra_env=both)
    assert second["persistent_hits"] == 0
    assert cc.list_entries(str(d)) == []
    third = _run_child(str(script), "again", cache=str(d), extra_env=both)
    assert third["faults"] == 0
    assert third["loss"] == pytest.approx(first["loss"], rel=1e-5)
