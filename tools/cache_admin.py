"""Operator CLI for the persistent compile cache (core/compile_cache.py).

Inspect and maintain a ``FLAGS_compile_cache_dir`` directory from the
command line — the companion to ``tools/dump_metrics.py`` for the
on-disk half of the cache:

    python tools/cache_admin.py ls     /path/to/cache
    python tools/cache_admin.py stat   /path/to/cache
    python tools/cache_admin.py verify /path/to/cache [--deep]
    python tools/cache_admin.py prune  /path/to/cache --max-bytes 1000000
    python tools/cache_admin.py prune  /path/to/cache   # env/default cap

``ls`` prints one line per tier-A entry (key, size, age, last use, the
environment stamp that gates loads); ``stat`` summarizes occupancy
(entries/bytes, oldest/newest use).
``verify`` checks every entry's framing + header and reports
corrupted/truncated files (exit code 1 if any; ``--fix`` deletes them,
``--deep`` additionally unpickles and loads each executable — requires
jax and the paddle_tpu environment).  ``prune`` applies the LRU byte
cap (``--max-bytes`` overrides ``FLAGS_compile_cache_max_bytes`` from
the environment, default 2 GiB).

Everything except ``verify --deep`` is stdlib-only: the entry framing
(MAGIC + u32 header length + JSON header + payload) is parsed locally,
so the CLI runs on any host that can see the cache directory — a
storage box with no jax installed included.
"""
from __future__ import annotations

import argparse
import json
import os
import struct
import sys
import time

__all__ = ["entry_lines", "stat_dir", "verify_dir", "prune_dir", "main"]

# entry framing — kept in sync with paddle_tpu/core/compile_cache.py
# (the header carries format/jax/jaxlib/platform; FORMAT_VERSION gates
# loads at runtime, the CLI only needs the frame)
MAGIC = b"PTCC1\0"
FORMAT_VERSION = 1
ENTRY_SUFFIX = ".ptcc"
_HEADER_LEN = struct.Struct("<I")
_DEFAULT_CAP = 2 << 30


def _read_header(path: str) -> dict:
    """Parse one entry file's framed JSON header and check the payload
    size accounting.  Raises ValueError on any framing problem."""
    size = os.path.getsize(path)
    with open(path, "rb") as f:
        if f.read(len(MAGIC)) != MAGIC:
            raise ValueError("bad magic")
        raw = f.read(_HEADER_LEN.size)
        if len(raw) != _HEADER_LEN.size:
            raise ValueError("truncated header length")
        (hlen,) = _HEADER_LEN.unpack(raw)
        if hlen <= 0 or hlen > 1 << 20:
            raise ValueError(f"implausible header length {hlen}")
        body = f.read(hlen)
        if len(body) != hlen:
            raise ValueError("truncated header")
        hdr = json.loads(body.decode("utf-8"))
        if not isinstance(hdr, dict):
            raise ValueError("header is not an object")
    payload = size - len(MAGIC) - _HEADER_LEN.size - hlen
    if payload < 0 or payload != int(hdr.get("payload_bytes", payload)):
        raise ValueError("truncated entry (payload size mismatch)")
    return hdr


def _list_entries(d: str):
    """[{key, path, bytes, mtime}] oldest-used first (the prune order;
    mtime is touched on every runtime cache hit)."""
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


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n}B"


def _fmt_age(seconds: float) -> str:
    if seconds < 120:
        return f"{seconds:.0f}s"
    if seconds < 7200:
        return f"{seconds / 60:.0f}m"
    if seconds < 2 * 86400:
        return f"{seconds / 3600:.1f}h"
    return f"{seconds / 86400:.1f}d"


def entry_lines(d):
    """One formatted line per entry, newest-used last (unreadable
    headers are flagged in-line, not fatal)."""
    now = time.time()
    for e in _list_entries(d):
        try:
            hdr = _read_header(e["path"])
            env = (f"jax={hdr.get('jax')} platform={hdr.get('platform')} "
                   f"mode={hdr.get('meta', {}).get('mode', '?')}")
            created = _fmt_age(now - float(hdr.get("created", now)))
        except Exception as exc:
            env = f"UNREADABLE ({exc})"
            created = "?"
        yield (f"{e['key'][:16]}…  {_fmt_bytes(e['bytes']):>10}  "
               f"created {created:>6} ago  "
               f"used {_fmt_age(now - e['mtime']):>6} ago  {env}")


def stat_dir(d):
    entries = _list_entries(d)
    now = time.time()
    out = {
        "dir": d,
        "tier_a_entries": len(entries),
        "tier_a_bytes": sum(e["bytes"] for e in entries),
    }
    if entries:
        out["oldest_use_age_s"] = round(now - entries[0]["mtime"], 1)
        out["newest_use_age_s"] = round(now - entries[-1]["mtime"], 1)
    return out


def verify_dir(d, deep=False, fix=False):
    """Check every entry's framing, header JSON, size accounting and
    format version; ``deep`` also unpickles + loads the executable the
    way the runtime would (needs the paddle_tpu/jax environment).
    Returns {ok, bad: [{key, error}], fixed}."""
    bad = []
    ok = 0
    for e in _list_entries(d):
        try:
            hdr = _read_header(e["path"])
            if int(hdr.get("format", -1)) != FORMAT_VERSION:
                raise ValueError(
                    f"format {hdr.get('format')} != {FORMAT_VERSION}")
            if deep:
                _deep_verify(e["path"], hdr)
            ok += 1
        except Exception as exc:
            bad.append({"key": e["key"], "error": repr(exc)[:200]})
            if fix:
                try:
                    os.remove(e["path"])
                except OSError:
                    pass
    return {"ok": ok, "bad": bad, "fixed": fix and len(bad) or 0}


def _deep_verify(path: str, hdr: dict) -> None:
    """Load the executable exactly like the runtime would (the only
    jax-dependent corner of this tool)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from paddle_tpu.core import compile_cache as cc
    env = cc.env_info()
    skew = {k: (hdr.get(k), v) for k, v in env.items()
            if hdr.get(k) != v}
    if skew:
        raise ValueError(f"environment skew {skew}")
    cc.deserialize_entry(*cc._read_entry(path))


def prune_dir(d, cap=None):
    """Apply the LRU byte cap: delete oldest-used tier-A entries until
    the rest fit.  Stdlib-only (mirrors compile_cache.prune_lru)."""
    if cap is None:
        env = os.environ.get("FLAGS_compile_cache_max_bytes")
        cap = int(env) if env else _DEFAULT_CAP
    # reap stale tmp files from crashed writers (mirrors the runtime:
    # old enough that no live writer is between write and rename)
    now = time.time()
    for n in os.listdir(d):
        if n.startswith(".tmp-"):
            p = os.path.join(d, n)
            try:
                if now - os.stat(p).st_mtime > 3600:
                    os.remove(p)
            except OSError:
                pass
    entries = _list_entries(d)
    total = sum(e["bytes"] for e in entries)
    evicted = []
    for e in entries:
        if not cap or total <= cap:
            break
        try:
            os.remove(e["path"])
        except OSError:
            continue
        total -= e["bytes"]
        evicted.append(e["key"])
    out = stat_dir(d)
    out["evicted"] = evicted
    out["cap"] = cap
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="persistent compile cache admin (ls/stat/verify/prune)")
    ap.add_argument("cmd", choices=("ls", "stat", "verify", "prune"))
    ap.add_argument("dir", help="the FLAGS_compile_cache_dir directory")
    ap.add_argument("--deep", action="store_true",
                    help="verify: also unpickle + load each executable")
    ap.add_argument("--fix", action="store_true",
                    help="verify: delete entries that fail")
    ap.add_argument("--max-bytes", type=int, default=None,
                    help="prune: byte cap (default "
                         "FLAGS_compile_cache_max_bytes env, else 2 GiB)")
    args = ap.parse_args(argv)
    if not os.path.isdir(args.dir):
        print(f"not a directory: {args.dir}", file=sys.stderr)
        return 2
    if args.cmd == "ls":
        n = 0
        for line in entry_lines(args.dir):
            print(line)
            n += 1
        if not n:
            print("(no tier-A entries)")
        return 0
    if args.cmd == "stat":
        print(json.dumps(stat_dir(args.dir), indent=2, sort_keys=True))
        return 0
    if args.cmd == "verify":
        res = verify_dir(args.dir, deep=args.deep, fix=args.fix)
        print(json.dumps(res, indent=2, sort_keys=True))
        return 1 if res["bad"] else 0
    if args.cmd == "prune":
        print(json.dumps(prune_dir(args.dir, args.max_bytes), indent=2,
                         sort_keys=True))
        return 0
    return 2  # pragma: no cover


if __name__ == "__main__":
    sys.exit(main())
