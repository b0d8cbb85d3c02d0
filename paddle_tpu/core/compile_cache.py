"""Persistent cross-process compilation cache + AOT warm start.

The executor's in-memory executable cache (``executor.py`` — the
reference's program cache, ``python/paddle/fluid/executor.py:207``
``_get_program_cache_key``) dies with its process, so every fresh
trainer — first launch, elastic kill-restart, bench worker respawn —
re-pays full lowering + XLA compilation (PERF.md: 8.6 s for one first
call at seq-64k).  On TPU the compile IS the cold-start bound, which is
why JAX grew its own persistent compilation cache; this module is the
framework-level equivalent, keyed by our own ProgramDesc fingerprint:

- **Tier A** — whole-executable reuse: ``jax.jit(fn).lower(...)
  .compile()`` AOT executables serialized via
  ``jax.experimental.serialize_executable`` into content-addressed
  entry files.  A warm process skips lowering-trace AND XLA compile;
  first step costs one deserialize (~ms).
- **Tier B** — XLA-level reuse: JAX's own persistent compilation
  cache, which is ALWAYS on and whose directory is decided in exactly
  one place (:func:`wire_jax_cache`): ``JAX_COMPILATION_CACHE_DIR``
  when the environment sets it, else a fixed directory inside the
  checkout.  It never lives under ``FLAGS_compile_cache_dir``.

Store discipline is robustness-grade: entries are written atomically
(unique tmp + ``os.replace``); loads of corrupted / truncated /
version-skewed entries degrade to a *counted miss* (never an
exception out of :func:`load`) and evict the bad file; an LRU size cap
(``FLAGS_compile_cache_max_bytes``, mtime = last use) bounds the dir.
Every fault leaves a flight-recorder note (``observability/flight.py``)
so a post-mortem explains a recompile storm.

Keying: :func:`fingerprint` hashes the canonical ProgramDesc (block
ops/attrs + var dtypes/shapes via ``Program.to_dict``), the feed
signature, fetch list, lowering mode (train/infer, run/run_steps), the
mesh spec, and an environment digest (jax/jaxlib versions, backend
platform, device count, x64 mode, lowering-relevant FLAGS).  Entries
from a different environment are skipped with a counted
``version_skew`` — a jax upgrade invalidates the cache instead of
crashing it.

Tier A is gated on ``FLAGS_compile_cache_dir``: unset (default) ⇒ no
entry files, no threads.

SECURITY: entry payloads deserialize through pickle (the transport
``jax.experimental.serialize_executable`` uses), so loading an entry
executes code from the file.  The cache directory must be PRIVATE to
the training user — it is created 0700 — and must never point at a
world-writable shared location; anyone who can write the directory can
run code in every process that reads it.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import struct
import threading
import time
from typing import Dict, List, Optional, Tuple

from . import flags as _flags
from ..observability import debug_server as _debug_server
from ..observability import stats as _obs_stats
from ..observability import trace as _obs_trace

MAGIC = b"PTCC1\0"
FORMAT_VERSION = 1
# Version of the scope grammar ``core/lowering.py op_scope`` names every
# lowered instruction by.  The names are metadata: the ProgramDesc of a
# program without ``name_scope`` does not change with them, and JAX leaves
# metadata out of its persistent cache's key
# (``jax_compilation_cache_include_metadata_in_key`` is off; on, every
# source line that moves would miss).  Without this an executable compiled
# before its scopes is handed back in their place and a trace of it names
# nothing.  Tier A carries the version in :func:`_env_digest`, tier B in the
# compiled program's name (:func:`program_name`).  Bump it with the grammar.
SCOPE_GRAMMAR = 1
ENTRY_SUFFIX = ".ptcc"
_HEADER_LEN = struct.Struct("<I")

_metrics = None
_lock = threading.Lock()
_tmp_counter = 0
_env_digest_cache: Optional[str] = None
_jax_hit_listener = False
_jax_hits = 0


def _cm():
    """Metric handles (module-wide, survive observability.reset()).

    The persistent hit/miss/serialize/deserialize series live in the
    ``executor`` scope next to the in-memory cache counters (one
    dashboard row answers "did the restart hydrate?"); store-level
    faults/evictions live under ``compile_cache``.
    """
    global _metrics
    m = _metrics
    if m is None:
        ex = _obs_stats.scope("executor")
        cc = _obs_stats.scope("compile_cache")
        import types as _t
        m = _t.SimpleNamespace(
            hits=ex.counter(
                "persistent_hits",
                "executable cache misses served from the persistent "
                "disk cache (no lowering trace, no XLA compile)"),
            misses=ex.counter(
                "persistent_misses",
                "executable cache misses that also missed the "
                "persistent disk cache (full compile paid)"),
            serialize_ms=ex.histogram("persistent_serialize_ms"),
            deserialize_ms=ex.histogram("persistent_deserialize_ms"),
            store_errors=cc.counter(
                "store_errors",
                "failed entry serializations/writes (cache stays "
                "consistent; the run continues uncached)"),
            faults=cc.counter(
                "faults",
                "corrupted/truncated/unloadable entries hit at read "
                "time — each one degraded to a miss and was evicted"),
            version_skews=cc.counter(
                "version_skews",
                "entries skipped because they were written by a "
                "different jax/jaxlib/platform environment"),
            evictions=cc.counter("evictions",
                                 "entry files pruned by the LRU size cap"),
            stored_bytes=cc.counter("stored_bytes"),
        )
        _metrics = m
    return m


def _flight_note(msg: str, **fields) -> None:
    try:
        from ..observability import flight as _flight
        _flight.note(msg, **fields)
    except Exception:  # the recorder must never take a run down
        pass


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

def cache_dir() -> str:
    try:
        return str(_flags.get_flags("compile_cache_dir") or "")
    except KeyError:  # pragma: no cover - flag always defined
        return ""


def enabled() -> bool:
    return bool(cache_dir())


def max_bytes() -> int:
    try:
        return int(_flags.get_flags("compile_cache_max_bytes") or 0)
    except KeyError:  # pragma: no cover
        return 0


def default_jax_cache_dir() -> str:
    """Where JAX's persistent cache lives when the environment does not
    say: ``<checkout>/.jax_compile_cache`` (git-ignored), derived from
    this package's location and from nothing else — the path is part of
    the cache key's world, so a directory named after a pid, a time or
    a temp name would never hit."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_compile_cache")


def wire_jax_cache() -> str:
    """Tier B, and THE one place this program decides where JAX's
    persistent compilation cache lives.  ``JAX_COMPILATION_CACHE_DIR``
    set: JAX reads it itself and nothing here (or anywhere else) sets
    another directory.  Unset: :func:`default_jax_cache_dir`.  Returns
    the directory in use; idempotent."""
    global _jax_hit_listener
    import jax
    if not _jax_hit_listener:
        _jax_hit_listener = True
        jax.monitoring.register_event_listener(_on_jax_event)
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if d:
        return d
    d = default_jax_cache_dir()
    if jax.config.jax_compilation_cache_dir != d:
        jax.config.update("jax_compilation_cache_dir", d)
    return d


def program_name(base: str) -> str:
    """The name a lowered block is jitted under: JAX's persistent cache keys
    on the module's name and not on its metadata, so the scope grammar's
    version rides in the name (``jit_fn_s1``)."""
    return f"{base}_s{SCOPE_GRAMMAR}"


def _on_jax_event(event: str, **_kw) -> None:
    global _jax_hits
    if event == "/jax/compilation_cache/cache_hits":
        _jax_hits += 1


def jax_cache_hits() -> int:
    """Compiles served by JAX's persistent cache so far in this process
    (counted from the first :func:`wire_jax_cache`)."""
    return _jax_hits


# ---------------------------------------------------------------------------
# fingerprinting
# ---------------------------------------------------------------------------

def _env_digest() -> str:
    """Environment part of every key: an executable only loads into the
    jax/jaxlib/platform world that built it."""
    global _env_digest_cache
    if _env_digest_cache is None:
        import jax
        import jaxlib
        env = {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "platform": jax.default_backend(),
            "device_count": jax.device_count(),
            "x64": bool(jax.config.jax_enable_x64),
            "scope_grammar": SCOPE_GRAMMAR,
        }
        _env_digest_cache = hashlib.sha256(
            json.dumps(env, sort_keys=True).encode()).hexdigest()
    return _env_digest_cache


def _lowering_flags() -> dict:
    """Trace-time flags that change the lowered program — read LIVE
    (not cached with the env digest) so a mid-process ``set_flags``
    can't alias two different lowerings under one fingerprint."""
    return {"sparse_dense_update_max_elems":
            _flags.get_flags("sparse_dense_update_max_elems")}


def env_info() -> dict:
    """The human-readable environment stamp written into entry headers
    (and checked, field by field, at load time)."""
    import jax
    import jaxlib
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "platform": jax.default_backend(),
            "device_count": jax.device_count()}


def program_digest(program) -> str:
    """Stable content hash of a ProgramDesc (blocks: ops, attrs, var
    dtypes+shapes).  Memoized per (program, version): mutation bumps
    ``_version`` which invalidates the memo along with the executor
    caches."""
    cached = getattr(program, "_fp_digest", None)
    if cached is not None and cached[0] == program._version:
        return cached[1]
    doc = json.dumps(program.to_dict(), sort_keys=True, default=repr)
    digest = hashlib.sha256(doc.encode()).hexdigest()
    program._fp_digest = (program._version, digest)
    return digest


def mesh_spec(mesh) -> Optional[list]:
    if mesh is None:
        return None
    try:
        kinds = sorted({d.device_kind for d in mesh.devices.flat})
    except Exception:
        kinds = []
    return [list(mesh.axis_names), list(mesh.devices.shape), kinds]


def fingerprint(program, sig, fetch_names, training: bool, mode: str,
                mesh=None, extra=None) -> str:
    """The canonical cache key: hex digest of everything that determines
    the compiled executable."""
    doc = {
        "program": program_digest(program),
        "sig": [[n, list(s), str(d)] for n, s, d in sig],
        "fetch": list(fetch_names),
        "training": bool(training),
        "mode": mode,
        "mesh": mesh_spec(mesh),
        "env": _env_digest(),
        "flags": _lowering_flags(),
    }
    if extra:
        doc["extra"] = extra
    return hashlib.sha256(
        json.dumps(doc, sort_keys=True).encode()).hexdigest()


# ---------------------------------------------------------------------------
# entry file format
# ---------------------------------------------------------------------------

def entry_path(key: str, d: Optional[str] = None) -> str:
    return os.path.join(d or cache_dir(), key + ENTRY_SUFFIX)


def read_header(path: str) -> dict:
    """Parse one entry file's framed JSON header (stdlib-only — the
    operator CLI uses this without importing jax).  Raises ValueError
    on any framing problem."""
    size = os.path.getsize(path)
    with open(path, "rb") as f:
        if f.read(len(MAGIC)) != MAGIC:
            raise ValueError("bad magic")
        (hlen,) = _HEADER_LEN.unpack(f.read(_HEADER_LEN.size))
        if hlen <= 0 or hlen > 1 << 20:
            raise ValueError(f"implausible header length {hlen}")
        hdr = json.loads(f.read(hlen).decode("utf-8"))
        if not isinstance(hdr, dict):
            raise ValueError("header is not an object")
    payload = size - len(MAGIC) - _HEADER_LEN.size - hlen
    if payload < 0 or payload != int(hdr.get("payload_bytes", payload)):
        raise ValueError("truncated entry (payload size mismatch)")
    return hdr


def _read_entry(path: str) -> Tuple[dict, bytes]:
    with open(path, "rb") as f:
        data = f.read()
    if not data.startswith(MAGIC):
        raise ValueError("bad magic")
    off = len(MAGIC)
    (hlen,) = _HEADER_LEN.unpack(data[off:off + _HEADER_LEN.size])
    off += _HEADER_LEN.size
    if hlen <= 0 or off + hlen > len(data):
        raise ValueError("truncated header")
    hdr = json.loads(data[off:off + hlen].decode("utf-8"))
    payload = data[off + hlen:]
    if len(payload) != int(hdr.get("payload_bytes", -1)):
        raise ValueError("truncated entry (payload size mismatch)")
    return hdr, payload


def _atomic_write(d: str, name: str, blob: bytes) -> str:
    """Unique-tmp + rename: concurrent writers of the same key race
    benignly (last rename wins, both files are complete)."""
    global _tmp_counter
    with _lock:
        _tmp_counter += 1
        n = _tmp_counter
    tmp = os.path.join(d, f".tmp-{os.getpid()}-{n}-{name}")
    path = os.path.join(d, name)
    with open(tmp, "wb") as f:
        f.write(blob)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def _evict_file(path: str) -> None:
    try:
        os.remove(path)
    except OSError:
        pass


# ---------------------------------------------------------------------------
# store / load
# ---------------------------------------------------------------------------

def store(key: str, compiled, meta: Optional[dict] = None) -> Optional[str]:
    """Serialize one AOT-compiled executable (``jax.stages.Compiled``)
    under ``key``.  Never raises: serialization failures (platforms
    without executable serialization) and I/O errors are counted in
    ``compile_cache.store_errors`` and the run continues uncached
    (tier B still applies).  Returns the entry path or None."""
    d = cache_dir()
    if not d:
        return None
    m = _cm()
    try:
        from jax.experimental import serialize_executable as _se
        t0 = time.perf_counter_ns()
        payload, in_tree, out_tree = _se.serialize(compiled)
        blob = pickle.dumps((payload, in_tree, out_tree),
                            protocol=pickle.HIGHEST_PROTOCOL)
        # the devices the executable was compiled for, in assignment
        # order: load() must hand exactly these back to jax (see there)
        hdr = {"format": FORMAT_VERSION, "key": key,
               "created": time.time(), "payload_bytes": len(blob),
               "devices": [d.id for d in
                           compiled.runtime_executable().local_devices()]}
        hdr.update(env_info())
        if meta:
            hdr["meta"] = meta
        hdr_bytes = json.dumps(hdr, sort_keys=True).encode("utf-8")
        framed = (MAGIC + _HEADER_LEN.pack(len(hdr_bytes)) + hdr_bytes
                  + blob)
        # 0700: entries execute as pickle on load — the dir must stay
        # private to the training user (see the module docstring)
        os.makedirs(d, mode=0o700, exist_ok=True)
        path = _atomic_write(d, key + ENTRY_SUFFIX, framed)
        m.serialize_ms.observe((time.perf_counter_ns() - t0) / 1e6)
        m.stored_bytes.inc(len(framed))
        prune_lru(d)
        return path
    except Exception as e:
        m.store_errors.inc()
        _flight_note("compile_cache_store_error", key=key[:16],
                     error=repr(e)[:200])
        return None


def _env_matches(hdr: dict) -> bool:
    info = env_info()
    return (int(hdr.get("format", -1)) == FORMAT_VERSION
            and all(hdr.get(k) == v for k, v in info.items()))


def deserialize_entry(hdr: dict, blob: bytes):
    """``jax.stages.Compiled`` from one entry's header + payload, loaded
    onto the devices it was compiled for: left to its default, jax loads
    an executable over EVERY device of the backend, and a one-device
    executable in a multi-device process then dies at its first
    dispatch ("expected ... to have N shards")."""
    import jax
    from jax.experimental import serialize_executable as _se
    payload, in_tree, out_tree = pickle.loads(blob)
    by_id = {d.id: d for d in jax.devices()}
    return _se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in hdr["devices"]])


def load(key: str, count_miss: bool = True):
    """Load + deserialize the executable stored under ``key``.

    ``count_miss=False`` keeps a clean not-found out of the
    ``persistent_misses`` series (hydrate-only probes, whose miss is
    counted by the real compile that follows); faults and skews are
    always counted.

    Returns a callable ``jax.stages.Compiled`` or None.  NEVER raises:
    a missing file is a plain miss; a corrupted/truncated/unloadable
    entry is a *counted* miss (``compile_cache.faults``) that evicts
    the bad file; an entry from a different jax/jaxlib/platform world
    is a counted ``version_skew`` (also evicted — it can never load
    here).  Hits touch the file's mtime (the LRU clock).

    All counters here increment unconditionally (unlike the per-run
    hot-path telemetry, which FLAGS_runtime_stats gates): loads happen
    only on compile-path misses, and the hit/miss/fault series must
    stay consistent with each other for the restart-win accounting.
    """
    d = cache_dir()
    if not d:
        return None
    path = entry_path(key, d)
    m = _cm()
    try:
        hdr, blob = _read_entry(path)
    except FileNotFoundError:
        if count_miss:
            m.misses.inc()
        return None
    except Exception as e:
        m.faults.inc()
        m.misses.inc()
        _flight_note("compile_cache_corrupt_entry", key=key[:16],
                     error=repr(e)[:200])
        _evict_file(path)
        return None
    if not _env_matches(hdr):
        m.version_skews.inc()
        m.misses.inc()
        _flight_note("compile_cache_version_skew", key=key[:16],
                     entry_env={k: hdr.get(k) for k in
                                ("format", "jax", "jaxlib", "platform")})
        _evict_file(path)
        return None
    try:
        t0 = time.perf_counter_ns()
        compiled = deserialize_entry(hdr, blob)
        ms = (time.perf_counter_ns() - t0) / 1e6
    except Exception as e:
        # payload unpickles garbage / XLA refuses the executable: same
        # contract as corruption — counted miss, evict, carry on
        m.faults.inc()
        m.misses.inc()
        _flight_note("compile_cache_deserialize_fault", key=key[:16],
                     error=repr(e)[:200])
        _evict_file(path)
        return None
    m.hits.inc()
    m.deserialize_ms.observe(ms)
    try:
        os.utime(path, None)  # LRU touch
    except OSError:
        pass
    return compiled


def dispatch_fault(key: Optional[str], exc) -> None:
    """A disk-hydrated executable failed its first dispatch (the
    executor falls back to a fresh compile): count the fault, evict
    the entry it came from, leave a flight note."""
    _cm().faults.inc()
    _flight_note("compile_cache_dispatch_fault",
                 key=(key or "")[:16], error=repr(exc)[:200])
    if key:
        d = cache_dir()
        if d:
            _evict_file(entry_path(key, d))


# ---------------------------------------------------------------------------
# occupancy / LRU prune
# ---------------------------------------------------------------------------

def list_entries(d: Optional[str] = None) -> List[dict]:
    """[{key, path, bytes, mtime}] for every tier-A entry file (sorted
    oldest-used first — prune order)."""
    d = d or cache_dir()
    out = []
    try:
        names = os.listdir(d)
    except OSError:
        return out
    for n in names:
        if not n.endswith(ENTRY_SUFFIX) or n.startswith(".tmp-"):
            continue
        p = os.path.join(d, n)
        try:
            st = os.stat(p)
        except OSError:
            continue  # racing another process's prune
        out.append({"key": n[:-len(ENTRY_SUFFIX)], "path": p,
                    "bytes": st.st_size, "mtime": st.st_mtime})
    out.sort(key=lambda e: e["mtime"])
    return out


def store_stats(d: Optional[str] = None) -> dict:
    entries = list_entries(d)
    return {"entries": len(entries),
            "bytes": sum(e["bytes"] for e in entries)}


def register_memory_pool() -> None:
    """Register the on-disk store on the MemoryLedger (kind ``disk``)
    so /allocz answers how many bytes the persistent cache holds
    against its ``FLAGS_compile_cache_max_bytes`` cap.  No-op unless
    both the cache and ``FLAGS_memory_attribution`` are on."""
    from ..observability import memory as _memory
    if not _memory.enabled() or not enabled():
        return

    def _snap() -> dict:
        st = store_stats()
        return {"used": st["bytes"], "entries": st["entries"],
                "cap_bytes": max_bytes()}

    _memory.pool("compile_cache_disk", "disk", _snap)


def prune_lru(d: Optional[str] = None,
              cap: Optional[int] = None) -> List[str]:
    """Evict oldest-used entries until the tier-A files fit under the
    byte cap.  Concurrent-process safe: a file deleted under us is
    someone else's eviction."""
    d = d or cache_dir()
    cap = max_bytes() if cap is None else cap
    if not d:
        return []
    # reap tmp files a crashed writer left behind (old enough that no
    # live writer can still be between write and rename) — even when
    # the byte cap is 0/unbounded, these must not accumulate
    try:
        now = time.time()
        for n in os.listdir(d):
            if n.startswith(".tmp-"):
                p = os.path.join(d, n)
                try:
                    if now - os.stat(p).st_mtime > 3600:
                        os.remove(p)
                except OSError:
                    pass
    except OSError:
        pass
    if not cap:
        return []
    entries = list_entries(d)
    total = sum(e["bytes"] for e in entries)
    evicted = []
    for e in entries:
        if total <= cap:
            break
        _evict_file(e["path"])
        total -= e["bytes"]
        evicted.append(e["key"])
        _cm().evictions.inc()
    if evicted:
        _flight_note("compile_cache_lru_prune", evicted=len(evicted),
                     cap=cap)
    return evicted


def _statusz() -> dict:
    d = cache_dir()
    if not d:
        return {"enabled": False}
    out = {"enabled": True, "dir": d, "max_bytes": max_bytes()}
    out.update(store_stats(d))
    return out


_debug_server.register_provider("compile_cache", _statusz)
