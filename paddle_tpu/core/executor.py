"""Scope + Executor: the single-device runtime.

Reference: ``paddle/fluid/framework/scope.h:41`` (hierarchical name→Variable
map) and ``executor.cc`` / ``python/paddle/fluid/executor.py:256-474``.

TPU-native redesign: ``Executor.run`` does NOT interpret ops.  It analyzes
the requested (program, feed-signature, fetch-list) once, lowers the whole
block to a pure JAX function (core/lowering.py), ``jax.jit``s it with the
updated persistable state *donated* (so parameters update in-place in HBM),
and caches the compiled executable — the analogue of the reference's
program cache (``executor.py:207`` ``_get_program_cache_key``) plus kernel
dispatch, replaced by one XLA compile.  Feed batches with new shapes
trigger a recompile (cached per shape bucket), which is the
static-shape/recompile-cache policy SURVEY.md §7 calls out.
"""
from __future__ import annotations

import functools
import math
import re
import threading
import time
import weakref
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import compile_cache as _compile_cache
from . import flags as _flags
from . import host_ops as _host_ops
from .lowering import analyze_block, build_block_fn
from .program import EMPTY_VAR, Program, Variable, default_main_program
from .selected_rows import SelectedRows
from .types import np_dtype
from .. import platform as _platform
from ..observability import debug_server as _debug_server
from ..observability import perf as _obs_perf
from ..observability import runlog as _obs_runlog
from ..observability import stats as _obs_stats
from ..observability import step_stats as _obs_step
from ..observability import trace as _obs_trace

RNG_STATE_VAR = "@RNG_STATE@"

# depth > 0 while _run_segmented drives per-segment inner runs on this
# thread: those runs suppress their own runlog records (the segmented
# step logs ONE aggregate record) — thread-local, executors are shared
_SEGMENT_TLS = threading.local()

_exec_metrics = None

# live executors for the debug server's /statusz (weak: the provider
# must never keep a notebook's discarded executor — and its compiled
# executables — alive)
_live_executors: "weakref.WeakSet" = weakref.WeakSet()


def _executor_statusz() -> dict:
    cap = _flags.get_flags("executor_cache_capacity")
    return {
        "cache_capacity": cap,
        "executors": [
            {"training": e._training,
             "cache_entries": len(e._cache),
             "seen_shape_buckets": len(e._seen_shapes)}
            for e in list(_live_executors)],
    }


_debug_server.register_provider("executors", _executor_statusz)


def _executor_pool_snapshot() -> dict:
    """MemoryLedger callback: the persistent-state scope's device
    bytes (shape × itemsize — no LazyFetch materialization, no sync)
    plus the live executors' executable-cache entry count."""
    scope_bytes = 0
    nvars = 0
    for v in list(global_scope().vars.values()):
        if isinstance(v, SelectedRows):
            v = v.values
        shape = getattr(v, "shape", None)
        dt = getattr(v, "dtype", None)
        if shape is None or dt is None:
            continue
        try:
            scope_bytes += int(np.prod(shape)) * np.dtype(dt).itemsize
            nvars += 1
        except (TypeError, ValueError):  # pragma: no cover - odd var
            continue
    entries = sum(len(e._cache) for e in list(_live_executors))
    return {"used": scope_bytes, "scope_vars": nvars,
            "cache_entries": entries}


def _register_memory_pools() -> None:
    """Register the executor's byte holders on the MemoryLedger —
    called from ``Executor.__init__`` so a flag-off process pays one
    flag read and never creates a pool."""
    from ..observability import memory as _memory
    if not _memory.enabled():
        return
    _memory.pool("executor_scope", "device", _executor_pool_snapshot)
    _compile_cache.register_memory_pool()


def _program_name(key: str) -> str:
    """The function name ``run_callable`` compiles ``key`` under:
    ``decode/lm/prefill/128`` → ``fn_decode_lm_prefill_128``, which jax
    shows as ``jit_fn_decode_lm_prefill_128`` in a device trace."""
    return "fn_" + re.sub(r"\W+", "_", key).strip("_")


# the feed dtypes ``run_callable`` packs, each with the name it signs
# under: four bytes wide, so an entry is a run of int32 words whatever
# it holds
_PACKED_DTYPES = {np.dtype(t): t for t in ("int32", "uint32", "float32")}


@functools.lru_cache(maxsize=None)
def _sig_names(prefix: str, n: int) -> tuple:
    """The names ``run_callable`` signs its n feed / state / const
    entries under: built once a length, not once a launch."""
    return tuple(f"{prefix}{i}" for i in range(n))


def _pack_feed(feed):
    """One host-to-device transfer for a ``run_callable`` feed.

    Host entries (NumPy arrays and scalars) whose canonical dtype is
    int32 / uint32 / float32 are viewed as int32 words and laid end to
    end in one buffer; ``_unpack_feed`` cuts it apart again inside the
    compiled program, bit for bit.  Every other entry goes ``loose``: a
    ``jax.Array`` as it is, anything else through its own
    ``jnp.asarray`` as before.  Returns ``(sig, layout, packed, loose,
    nbytes, transfers)``: ``sig`` the logical signature — the entries'
    own shapes and canonical dtypes, what a feed converted entry by entry
    would sign as; ``layout`` the static ``(offset, shape, dtype)`` per
    packed entry, None per loose one; ``packed`` the device buffer (None
    when nothing was packed); ``nbytes`` the logical entries' bytes."""
    sig, layout, pieces, loose = [], [], [], []
    words = nbytes = transfers = 0
    for name, v in zip(_sig_names("", len(feed)), feed):
        if isinstance(v, (np.ndarray, np.generic)):
            dt = v.dtype if v.dtype in _PACKED_DTYPES \
                else jax.dtypes.canonicalize_dtype(v.dtype)
            dtype = _PACKED_DTYPES.get(dt)
            if dtype is not None:
                a = np.asarray(v, dt)
                sig.append((name, a.shape, dtype))
                layout.append((words, a.shape, dtype))
                pieces.append(a.reshape(-1).view(np.int32))
                words += a.size
                continue
        if not isinstance(v, jax.Array):
            v = jnp.asarray(v)
            transfers += 1
        sig.append((name, tuple(v.shape), str(v.dtype)))
        layout.append(None)
        loose.append(v)
        nbytes += _obs_step.approx_nbytes(v)
    packed = None
    if pieces:
        packed = jax.device_put(np.concatenate(pieces))
        transfers += 1
    return (tuple(sig), tuple(layout), packed, loose, nbytes + 4 * words,
            transfers)


def _unpack_feed(layout, packed, loose):
    """Inside the compiled program: the feed list ``_pack_feed`` took
    apart, each packed entry a static slice of ``packed`` reshaped and
    bitcast back to its dtype."""
    loose = iter(loose)
    feed = []
    for spec in layout:
        if spec is None:
            feed.append(next(loose))
            continue
        off, shape, dtype = spec
        piece = jax.lax.slice(packed, (off,), (off + math.prod(shape),))
        piece = piece.reshape(shape)
        if dtype != "int32":
            piece = jax.lax.bitcast_convert_type(piece, dtype)
        feed.append(piece)
    return feed


def _em():
    """Cached executor metric handles: registering through the registry
    on every run costs a lock + dict round trip per metric; the handles
    are process-wide and survive ``observability.reset()``, so create
    them once (hot-path budget: the whole telemetry cost per cached run
    must stay under 5% of a dispatch)."""
    global _exec_metrics
    m = _exec_metrics
    if m is None:
        sc = _obs_stats.scope("executor")
        import types as _t
        m = _t.SimpleNamespace(
            steps=sc.counter("steps"),
            hits=sc.counter("cache_hits"),
            misses=sc.counter("cache_misses"),
            shape_recompiles=sc.counter(
                "shape_recompiles",
                "compile-cache misses caused by a new feed-shape bucket "
                "for an already-compiled program"),
            evictions=sc.counter("cache_evictions"),
            const_sig_reuses=sc.counter(
                "const_sig_reuses",
                "run_callable launches that signed their constants by "
                "identity with the last launch's instead of walking them"),
            feed_transfers=sc.counter(
                "feed_transfers",
                "host-to-device transfers run_callable made for feeds: one "
                "for the packed buffer and one per host entry it cannot "
                "pack (beside steps: 1.0 a launch for an engine's feed)"),
            feed_bytes=sc.counter("feed_bytes"),
            fetch_bytes=sc.counter("fetch_bytes"),
            wall=sc.histogram("run_wall_ms"),
            build=sc.counter(
                "build_ms",
                "host ms spent building executables, added to only on an "
                "executable-cache miss: lowering + jax.jit construction + "
                "the first, synchronous call (trace, compile or "
                "persistent-cache load)"),
        )
        _exec_metrics = m
    return m


_numerics_metrics = None


def _nm():
    """Cached numerics-sentinel metric handles (see ``_em``)."""
    global _numerics_metrics
    m = _numerics_metrics
    if m is None:
        sc = _obs_stats.scope("numerics")
        import types as _t
        m = _t.SimpleNamespace(
            nan=sc.counter("nan", "variables with NaN values caught by "
                           "the FLAGS_numerics_check post-step sentinel"),
            inf=sc.counter("inf", "variables with Inf values caught by "
                           "the FLAGS_numerics_check post-step sentinel"),
            checked=sc.counter("checked_steps"),
        )
        _numerics_metrics = m
    return m


def _numerics_mode() -> str:
    """'' (off) / 'warn' / 'fatal' from ``FLAGS_numerics_check``."""
    try:
        v = str(_flags.get_flags("numerics_check") or "").strip().lower()
    except KeyError:  # pragma: no cover - flag always defined
        return ""
    if v in ("", "0", "false", "off", "no", "none"):
        return ""
    return "fatal" if v == "fatal" else "warn"


class _CacheEntry:
    """One compiled-executable cache slot.  ``meta`` memoizes the
    telemetry constants of the executable (program_key string, feed and
    fetch byte totals) so the cached-run record path never re-hashes the
    big nested cache key or walks array metadata.

    Persistent-cache bookkeeping: a dispatch failure of an AOT
    executable (``from_disk`` set, or ``aot_ms`` not None — avals
    pinned at build time by disk hydration, inline AOT compile, or
    warm_start specs) falls back to a fresh lazy jit instead of
    failing the run (``Executor._recover_disk_entry``);
    ``fingerprint`` is the disk key; ``aot_ms`` the measured AOT
    compile cost (0.0 for disk hits — no compile was paid)."""

    __slots__ = ("plan", "jitted", "meta", "from_disk", "fingerprint",
                 "aot_ms", "perf", "fused_disabled", "fused_used")

    def __init__(self, plan, jitted):
        self.plan = plan
        self.jitted = jitted
        self.meta = None
        self.from_disk = False
        self.fingerprint = None
        self.aot_ms = None
        # set by _recover_fused_fault: this entry was re-lowered without
        # the int8 kernels after a dispatch-level compile fault
        # (recovery is once-per-entry — a second fault re-raises)
        self.fused_disabled = False
        # the lowering's trace-time latch dict ({"int8_fused": bool},
        # build_block_fn._int8_fused_used): did THIS entry's lowering
        # actually emit int8 kernels?  None for executables with no
        # reachable trace (disk hydrates).  Recovery gates on it
        self.fused_used = None
        # cost/memory attribution record (observability/perf.py) when
        # FLAGS_perf_attribution harvested this executable; else None
        self.perf = None

    def __iter__(self):
        # (plan, jitted) unpacking compatibility for cache introspection
        return iter((self.plan, self.jitted))


class Scope:
    """Name → device-value map with parent fallback (scope.h:41)."""

    def __init__(self, parent: Optional["Scope"] = None):
        self.vars: Dict[str, object] = {}
        self.parent = parent
        self.kids: List[Scope] = []

    def new_scope(self) -> "Scope":
        kid = Scope(self)
        self.kids.append(kid)
        return kid

    def drop_kids(self) -> None:
        self.kids.clear()

    def find_var(self, name: str):
        s: Optional[Scope] = self
        while s is not None:
            if name in s.vars:
                return s.vars[name]
            s = s.parent
        return None

    def has_var(self, name: str) -> bool:
        return self.find_var(name) is not None

    def set_var(self, name: str, value) -> None:
        self.vars[name] = value

    def erase(self, name: str) -> None:
        self.vars.pop(name, None)

    def local_names(self) -> List[str]:
        return list(self.vars)


class LazyFetch(np.lib.mixins.NDArrayOperatorsMixin):
    """Deferred ``Executor.run`` fetch: holds the device value and
    materializes to numpy on first host access, so back-to-back ``run``
    calls pipeline their dispatches instead of paying the host<->device
    round trip per step (the reference's async stream-execution role,
    ``details/threaded_ssa_graph_executor.cc:36``).

    Reading ANY pending fetch flushes ALL pending fetches in one batched
    ``jax.device_get`` — a whole training run's losses cost one round
    trip at the first read.  Shape/dtype/ndim are served without a sync.
    Acts as an ndarray for ufuncs/indexing/float()/format; anything else
    delegates to the materialized array."""

    _PENDING: List = []          # weakrefs: a dropped fetch frees its buffer
    _LOCK = threading.Lock()     # Executor.run is called from many threads
    _MAX_PENDING = 512  # flush backstop so unread fetches can't pile up

    def __init__(self, dev):
        self._dev = dev
        self._np = None
        self._err = None
        self._done = threading.Event()
        backstop = None
        with LazyFetch._LOCK:
            if len(LazyFetch._PENDING) >= LazyFetch._MAX_PENDING:
                backstop = LazyFetch._snapshot_locked()
            LazyFetch._PENDING.append(weakref.ref(self))
        if backstop:  # materialize OUTSIDE the lock (see _flush)
            LazyFetch._materialize(backstop)

    @classmethod
    def _snapshot_locked(cls):
        batch = [f for ref in cls._PENDING
                 if (f := ref()) is not None
                 and f._np is None and f._err is None]
        cls._PENDING.clear()
        return batch

    @classmethod
    def _flush(cls):
        # snapshot under the lock, read back OUTSIDE it: holding the lock
        # across the device_get would serialize every concurrent
        # Executor.run on LazyFetch construction
        with cls._LOCK:
            batch = cls._snapshot_locked()
        cls._materialize(batch)

    @classmethod
    def _materialize(cls, batch):
        if not batch:
            return
        try:
            vals = jax.device_get([f._dev for f in batch])
        except Exception:
            # isolate the poisoned buffer: fetch one by one so a single
            # failed read cannot lose every other pending value
            for f in batch:
                try:
                    cls._assign(f, jax.device_get(f._dev))
                except Exception as e:
                    f._err = e
                    f._dev = None
                f._done.set()
            return
        for f, v in zip(batch, vals):
            cls._assign(f, v)
            f._done.set()

    @staticmethod
    def _assign(f, v):
        arr = np.asarray(v)
        if not arr.flags.writeable:
            arr = arr.copy()
        # ONE mutable array per fetch, like the sync path's returned
        # ndarray: user mutation through __setitem__/__array__ is visible
        # to later reads of the same fetch, never to other fetches
        f._np = arr
        f._dev = None

    def _val(self):
        if self._np is None and self._err is None:
            LazyFetch._flush()
            # raced another thread's in-flight snapshot: its device_get
            # will assign and signal; wait instead of double-fetching
            if self._np is None and self._err is None:
                if not self._done.wait(timeout=600.0):
                    raise RuntimeError(
                        "deferred fetch timed out waiting for another "
                        "thread's in-flight device readback")
        if self._err is not None:
            raise RuntimeError(
                f"deferred fetch failed: {self._err!r}") from self._err
        return self._np

    # metadata without sync (snapshot fields first: a concurrent flush
    # may assign _np and null _dev between attribute reads)
    @property
    def shape(self):
        a, dev = self._np, self._dev
        if a is not None:
            return a.shape
        if dev is not None:
            return tuple(dev.shape)
        return self._val().shape

    @property
    def dtype(self):
        a, dev = self._np, self._dev
        if a is not None:
            return a.dtype
        if dev is not None:
            return np.dtype(dev.dtype)
        return self._val().dtype

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def size(self):
        n = 1
        for d in self.shape:
            n *= d
        return n

    def __array__(self, dtype=None, *args, **kwargs):
        # identity semantics like the sync path (np.asarray of the one
        # returned ndarray is that ndarray): hand out the fetch's own
        # mutable array; dtype conversion or an explicit numpy-2
        # copy=True request returns a private copy
        a = self._val()
        if kwargs.get("copy") or (args and args[0]):
            return np.array(a, dtype=dtype, copy=True)
        return np.asarray(a, dtype=dtype) if dtype is not None else a

    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        inputs = tuple(np.asarray(i) if isinstance(i, LazyFetch) else i
                       for i in inputs)
        return getattr(ufunc, method)(*inputs, **kwargs)

    def __getitem__(self, idx):
        return self._val()[idx]

    def __setitem__(self, idx, value):
        self._val()[idx] = value

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        return iter(self._val())

    def __float__(self):
        return float(self._val())

    def __int__(self):
        return int(self._val())

    def __bool__(self):
        return bool(self._val())

    def __format__(self, spec):
        return format(self._val(), spec)

    def __repr__(self):
        return repr(self._val())

    def __str__(self):
        return str(self._val())

    def item(self, *args):
        return self._val().item(*args)

    def __getattr__(self, name):
        # anything beyond the fast-path surface: materialize and delegate.
        # Dunder protocols must NOT leak through (numpy would find the
        # ml_dtypes array's __array_interface__ and reinterpret bf16
        # buffers as void bytes; __array__ above is the one true door).
        if name.startswith("__") and name.endswith("__"):
            raise AttributeError(name)
        return getattr(self._val(), name)


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


def scope_guard(scope: Scope):
    import contextlib

    @contextlib.contextmanager
    def guard():
        global _global_scope
        saved = _global_scope
        _global_scope = scope
        try:
            yield
        finally:
            _global_scope = saved

    return guard()


def _expand_lod_feeds(feed):
    """A fed LoDTensor splits into its padded array + the ``@LEN``
    companion (the reference's LoD travels inside the tensor; the padded
    contract carries lengths as a separate feed).  Nested (level-2)
    tensors additionally carry the inner [B, S] lengths as ``@LEN2``."""
    from ..lod_tensor import LoDTensor

    out = {}
    for name, val in feed.items():
        if isinstance(val, LoDTensor):
            out[name] = val.data
            out.setdefault(name + "@LEN", val.seq_lens)
            if val.inner_lens is not None:
                out.setdefault(name + "@LEN2", val.inner_lens)
        else:
            out[name] = val
    return out


def _as_device_array(value, var: Optional[Variable]):
    if isinstance(value, (jax.Array,)):
        return value
    if isinstance(value, SelectedRows):
        return SelectedRows(jnp.asarray(np.asarray(value.rows)),
                            jnp.asarray(np.asarray(value.values)),
                            value.height)
    arr = np.asarray(value)
    if var is not None and var.dtype is not None:
        arr = arr.astype(np_dtype(var.dtype), copy=False)
    return jnp.asarray(arr)


class Executor:
    """Single-device program runner (executor.py:256 equivalent).

    ``place``: a ``paddle_tpu.TPUPlace`` must name a TPU device JAX
    can see — the constructor raises otherwise, so a program can never
    run "on TPUPlace" on the CPU without a word.  ``None``/``CPUPlace``
    run on JAX's default backend.  Which device of that backend the
    computation lands on stays JAX's decision.
    """

    def __init__(self, place=None, training: bool = True):
        if _platform.is_tpu_place(place):
            _platform.tpu_device(place)
        self.place = place
        self._cache: Dict = {}
        # telemetry: feed signatures seen per (program, fetch, mode) base
        # key, to distinguish shape-bucket recompiles from first compiles
        self._seen_shapes: Dict = {}
        # (weakrefs of the constants run_callable's last launch brought,
        # their signature): see _const_sig
        self._const_memo = None
        # lowering mode: inference executors (the Predictor) pass
        # training=False so ctx.training-gated lowerings (dropout off
        # without an is_test attr, Pallas RNN cells inside the fusion ops
        # whose training path needs the vjp-friendly scan) pick the test
        # branch; part of the executable cache key
        self._training = training
        # latched by _build_entry once a call's arrays lie across several
        # devices (a scope a ParallelExecutor placed, run through a plain
        # Executor): jit then compiles ONE program over those devices and
        # GSPMD partitions it, which no Mosaic kernel survives — lowerings
        # that have no mesh to wrap a kernel over read it (ctx.spans_devices)
        self._spans_devices = False
        _live_executors.add(self)
        # fleet observability opt-in: FLAGS_debug_server_port=0 (default)
        # makes this a flag read — no socket, no thread; same deal for
        # the crash flight recorder (FLAGS_flight_record_dir empty)
        _debug_server.maybe_start_from_flags()
        from ..observability import flight as _flight
        _flight.arm_from_flags()
        # jax's persistent compilation cache: JAX_COMPILATION_CACHE_DIR
        # when set, else the fixed in-checkout directory
        _compile_cache.wire_jax_cache()
        # memory anatomy: register the executable-cache + persistent-
        # scope pool (and the compile cache's disk pool) on the
        # MemoryLedger — one flag read when FLAGS_memory_attribution
        # is off, idempotent when on
        _register_memory_pools()
        # HA promotion awareness: last fleet-topology epoch this executor
        # acted on (see _refresh_promoted_endpoints)
        self._promo_epoch = 0

    def _refresh_promoted_endpoints(self) -> None:
        """Promotion-aware endpoint refresh: when any RPC client failed
        over to a NEW physical address since our last host-op dispatch
        (a backup was promoted / a replacement re-registered — the
        transport bumps a process-wide epoch), drop every cached
        logical→physical resolution before running this program's RPC
        host ops.  Endpoints that did not fail a request yet re-resolve
        through the registry instead of timing out into serial failovers
        mid-step.  One int compare when nothing moved."""
        from ..distributed import transport as _transport
        epoch = _transport.promotion_epoch()
        if epoch != self._promo_epoch:
            self._promo_epoch = epoch
            _transport.refresh_resolutions()

    # -- public API --------------------------------------------------------
    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, object]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
        sync: bool = False,
    ):
        # one step-root span per top-level run (head-sampled by
        # FLAGS_trace_sample_rate): everything below — lowering, the
        # jitted dispatch, and every RPC the host ops issue — stitches
        # under this trace id, across processes (distributed/transport
        # carries the context on the wire).  Nested runs (device
        # segments, pserver optimize blocks) become child spans.
        with _obs_trace.start_span("executor::step", cat="executor"), \
                _obs_trace.span("executor::run"):
            return self._run_traced(program, feed, fetch_list, scope,
                                    return_numpy, use_program_cache, sync)

    def _run_traced(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, object]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
        sync: bool = False,
    ):
        program = program if program is not None else default_main_program()
        feed = _expand_lod_feeds(feed or {})
        fetch_names = [v.name if isinstance(v, Variable) else str(v) for v in (fetch_list or [])]
        scope = scope or global_scope()
        program = self._prepare_program(program, feed)

        if any(_host_ops.is_host_op(op.type) for op in program.global_block.ops):
            return self._run_segmented(program, feed, fetch_names, scope, return_numpy)

        tel = _obs_trace.flags_on()
        rl = _obs_runlog.enabled() and \
            not getattr(_SEGMENT_TLS, "depth", 0)
        if rl:
            # before this dispatch donates buffers: queued records
            # whose fetches alias persistable state land while those
            # buffers are still alive (previous dispatch has
            # typically completed by now, so no blocking)
            _obs_runlog.drain_pending()
        pf = _obs_perf.enabled()
        t_run0 = time.perf_counter_ns() if (tel or rl or pf) else None

        feed_names = sorted(feed)
        block = program.global_block
        feed_vals = []
        with _obs_trace.span("executor::feed"):
            for n in feed_names:
                var = block.var_or_none(n)
                feed_vals.append(
                    self._put_feed(_as_device_array(feed[n], var)))

        sig = self._feed_sig(feed_names, feed_vals)
        base = (program._uid, program._version, tuple(fetch_names),
                self._training)
        key = self._mem_key(program, sig, fetch_names)
        entry = self._cache.get(key) if use_program_cache else None
        cache_hit = entry is not None
        lowering_ms = 0.0
        if entry is None:
            # analysis first: the state-read sets below are the plan's,
            # and (persistent cache) the state values double as the AOT
            # lowering's avals
            t_an0 = time.perf_counter_ns()
            plan = analyze_block(program, 0, feed_names, fetch_names)
            lowering_ms = (time.perf_counter_ns() - t_an0) / 1e6
        else:
            plan = entry.plan

        donated_state = [self._state_val(scope, block, n) for n in plan.donated_reads]
        const_state = [self._state_val(scope, block, n) for n in plan.const_reads]
        rng = scope.find_var(RNG_STATE_VAR)
        if rng is None:
            rng = jax.random.PRNGKey(program.random_seed or 0)
        rng = self._put_rng(rng)

        if entry is None:
            t_low0 = time.perf_counter_ns()
            with _obs_trace.start_span("executor::lower", cat="executor",
                                       root=False), \
                    _obs_trace.span("executor::lower"):
                entry = self._build_entry(
                    program, plan, sig, tuple(fetch_names), "run",
                    (feed_vals, donated_state, const_state, rng))
            build_ms = lowering_ms + (time.perf_counter_ns() - t_low0) / 1e6
            # the AOT compile (entry.aot_ms) reports as compile_ms below;
            # keep it out of lowering_ms or a cold first step counts it twice
            lowering_ms = max(0.0, build_ms - (entry.aot_ms or 0.0))
            if use_program_cache:
                self._cache[key] = entry
                self._evict_cache_overflow()
            if tel:
                self._note_cache_miss(base, sig)
        elif tel:
            _em().hits.inc()
        plan, jitted = entry.plan, entry.jitted

        t0 = time.perf_counter() if _flags.get_flags("benchmark") else None

        nc = _numerics_mode()
        state_backup = None
        if nc == "fatal":
            # the dispatch DONATES the state buffers, so "raise before
            # the poisoned step applies" needs a pre-step copy to
            # restore into the scope (fatal is an opt-in debugging
            # mode; one state copy per step is its price)
            state_backup = [self._copy_state_val(v) for v in donated_state]

        compile_ms = 0.0
        t_disp0 = time.perf_counter_ns() if not cache_hit else None
        with _obs_trace.start_span("executor::dispatch", cat="executor",
                                   root=False), \
                _obs_trace.span("executor::dispatch"):
            try:
                fetches, new_state, rng_out = jitted(feed_vals, donated_state,
                                                     const_state, rng)
            except Exception as e:
                jitted = self._recover_disk_entry(entry, program, e,
                                                  donated_state)
                try:
                    fetches, new_state, rng_out = jitted(
                        feed_vals, donated_state, const_state, rng)
                except Exception as e2:
                    # an AOT/disk entry recovered to a lazy re-lower that
                    # STILL faults: last chance is an int8-kernel compile
                    # fault — drop the kernels once, counted
                    jitted = self._recover_fused_fault(entry, program, e2,
                                                       donated_state)
                    fetches, new_state, rng_out = jitted(
                        feed_vals, donated_state, const_state, rng)
        if t_disp0 is not None:
            # first call of a fresh executable: the synchronous part
            # is jax trace + XLA compile (execution is async), so this
            # wall time is the compile cost to within dispatch noise.
            # AOT-compiled entries (persistent cache) measured their
            # compile in the lower phase instead; disk hits paid none.
            first_ms = (time.perf_counter_ns() - t_disp0) / 1e6
            compile_ms = (entry.aot_ms if entry.aot_ms is not None
                          else first_ms)
            if tel:
                _em().build.inc(build_ms + first_ms)

        self._numerics_guard(nc, state_backup, fetch_names, fetches,
                             plan, new_state, scope)

        for name, val in zip(plan.persist_writes, new_state):
            self._note_state_write(name)
            scope.set_var(name, val)
        if plan.has_stateful:
            scope.set_var(RNG_STATE_VAR, rng_out)

        if _flags.get_flags("check_nan_inf"):
            # post-block NaN/Inf scan (FLAGS_check_nan_inf, operator.cc:31
            # post-kernel check at whole-block granularity)
            for name, val in list(zip(fetch_names, fetches)) + \
                    list(zip(plan.persist_writes, new_state)):
                arr = np.asarray(val.values if isinstance(val, SelectedRows)
                                 else val)
                # jnp.issubdtype: ml_dtypes floats (bfloat16, float8_*)
                # are invisible to np.issubdtype — the flagship bf16
                # workloads must not bypass the guard
                if jnp.issubdtype(arr.dtype, jnp.floating) and \
                        not np.all(np.isfinite(arr)):
                    raise FloatingPointError(
                        f"NaN/Inf detected in {name!r} "
                        f"(FLAGS_check_nan_inf)")
        if t0 is not None:
            sync_ref = next((v for v in list(fetches) + list(new_state)
                             if v is not None), None)
            if sync_ref is not None:
                np.asarray(sync_ref.values
                           if isinstance(sync_ref, SelectedRows)
                           else sync_ref)
            print(f"[benchmark] executor run: "
                  f"{(time.perf_counter() - t0) * 1e3:.3f} ms")

        if not return_numpy:
            out = list(fetches)
        elif sync:
            with _obs_trace.span("executor::fetch"):
                out = [self._fetch_to_numpy(v) for v in fetches]
        else:
            # async dispatch: wrap plain-array fetches lazily so user
            # step loops pipeline (one batched readback at first
            # access).  Fetches that alias persistable state
            # materialize NOW — the next run() donates that state's
            # buffer, and a deferred read of a donated buffer would
            # raise.
            persist = set(plan.persist_writes) | set(plan.donated_reads)
            out = []
            with _obs_trace.span("executor::fetch"):
                for name, v in zip(fetch_names, fetches):
                    if (isinstance(v, jax.Array) and name not in persist):
                        out.append(LazyFetch(v))
                    else:
                        out.append(self._fetch_to_numpy(v))
        if tel:
            self._record_step(entry, key, cache_hit, lowering_ms,
                              compile_ms, feed_vals, fetches, t_run0, plan,
                              donated_state, program=program)
        if pf and entry.perf is not None and t_run0 is not None:
            # feed the measured wall back into the cost/memory record
            # (roofline position) and sample the live device-memory
            # gauges — both ride the FLAGS_perf_attribution opt-in.
            # A cold step's wall subtracts the one-time lowering/compile
            # cost so the roofline rates reflect execution, not build
            _obs_perf.observe_step(
                entry.perf, self._program_key(key),
                self._perf_wall_ms(t_run0, cache_hit, lowering_ms,
                                   compile_ms, entry))
            _obs_perf.sample_device_memory()
        if rl:
            _obs_runlog.log_run(
                fetch_names, out,
                wall_ms=(time.perf_counter_ns() - t_run0) / 1e6,
                batch=_obs_runlog.batch_of(feed_vals))
        return out

    def run_callable(self, key: str, build_fn, feed: Sequence,
                     state: Sequence = (), const: Sequence = ()):
        """Dispatch a pure JAX callable through THIS executor's
        executable cache — the decode plane's entry point, and the
        general mechanism for cache-resident device state across
        dispatches.

        ``build_fn()`` returns ``fn(feed, state, const) -> (outs,
        new_state)`` (lists in, lists out).  The compiled executable is
        cached per ``(key, feed/state/const shape-dtype signature)`` in
        the SAME cache as program runs, and counts against the same
        ``executor.*`` telemetry (cache_hits / cache_misses /
        shape_recompiles / steps / run_wall_ms) — so a serving plane
        can pin "zero recompiles under mixed traffic" for callable
        dispatches exactly as it does for program dispatches.

        ``feed`` reaches the device in ONE transfer (``_pack_feed``):
        every host entry — a NumPy array or scalar — whose canonical
        dtype is int32, uint32 or float32 is laid as int32 words in one
        buffer, and the compiled program cuts the buffer by static
        offsets and bitcasts each piece back, so ``fn`` gets the list it
        was sent, bit for bit.  What passes through: a ``jax.Array``
        entry as it is; a host entry of any other width (or a Python
        value) through its own ``jnp.asarray``.  The signature is taken
        from the LOGICAL entries — each one's own shape and canonical
        dtype, read off the host values before anything is converted —
        so the same arrays under the same key hit the same executable,
        whoever sends them in that form (which entries were packed is
        part of the cache key: a feed that signs the same but arrives
        otherwise, one entry already a ``jax.Array``, is a miss and
        builds its own).  ``const``'s part of it is not rebuilt while
        every array IS the one the last launch brought
        (``executor.const_sig_reuses``); ``executor.feed_transfers``
        beside ``executor.steps`` counts the transfers a launch.

        ``state`` buffers are DONATED: they stay device-resident and
        update in place in HBM across dispatches (a paged KV cache
        never round-trips to host); the caller must carry the returned
        ``new_state`` handles forward — the old ones are consumed.
        ``const`` values (model params) are neither donated nor copied.
        No persistent-cache tier: a callable has no canonical program
        fingerprint to key a disk entry by.

        The compiled program is named after ``key`` (``decode/lm/step``
        → ``jit_fn_decode_lm_step``), so a device trace shows every
        callable under its own name.

        Returns ``(outs, new_state)`` as device arrays (wrap in
        ``np.asarray`` to materialize)."""
        with _obs_trace.span("executor::run_callable", key=key):
            return self._run_callable(key, build_fn, feed, state, const)

    def _run_callable(self, key, build_fn, feed, state, const):
        tel = _obs_trace.flags_on()
        t_run0 = time.perf_counter_ns() if tel else None
        with _obs_trace.span("executor::feed"):
            feed_sig, layout, packed, loose, feed_bytes, transfers = \
                _pack_feed(feed)
        state = list(state)
        const = list(const)
        const_sig, reused = self._const_sig(const)
        sig = (feed_sig
               + self._feed_sig(_sig_names("s", len(state)), state)
               + const_sig)
        # which entries were packed is part of the key: the program is
        # built round one layout, so a feed that signs the same but
        # arrives otherwise (an entry already on the device) is a miss
        # that is counted, not a silent second compile under a hit
        arrival = tuple(spec is not None for spec in layout)
        base = ("callable", key, self._training)
        mem_key = ("callable", key, sig, self._training, arrival)
        entry = self._cache.get(mem_key)
        cache_hit = entry is not None
        lowering_ms = 0.0
        if entry is None:
            t_low0 = time.perf_counter_ns()
            with _obs_trace.span("executor::lower", key=key):
                fn = build_fn()

                def run(packed, loose, state, const):
                    return fn(_unpack_feed(layout, packed, loose), state,
                              const)

                run.__name__ = run.__qualname__ = _program_name(key)
                jitted = jax.jit(run, donate_argnums=(2,))
            lowering_ms = (time.perf_counter_ns() - t_low0) / 1e6
            entry = _CacheEntry(None, jitted)
            self._cache[mem_key] = entry
            self._evict_cache_overflow()
            if tel:
                self._note_cache_miss(base, (sig, arrival))
        elif tel:
            _em().hits.inc()
        compile_ms = 0.0
        t_disp0 = time.perf_counter_ns() if not cache_hit else None
        with _obs_trace.start_span("executor::dispatch", cat="executor",
                                   root=False), \
                _obs_trace.span("executor::dispatch", key=key):
            outs, new_state = entry.jitted(packed, loose, state, const)
        if t_disp0 is not None:
            # first call of a fresh executable: the synchronous part
            # is jax trace + XLA compile (execution is async)
            compile_ms = (time.perf_counter_ns() - t_disp0) / 1e6
            if tel:
                _em().build.inc(lowering_ms + compile_ms)
        if tel:
            m = _em()
            m.steps.inc()
            m.feed_transfers.inc(transfers)
            if reused:
                m.const_sig_reuses.inc()
            wall_ms = (time.perf_counter_ns() - t_run0) / 1e6
            m.wall.observe(wall_ms)
            _obs_step.record(_obs_step.StepStats(
                program_key=f"callable:{key}",
                cache_hit=cache_hit,
                lowering_ms=round(lowering_ms, 3),
                compile_ms=round(compile_ms, 3),
                feed_bytes=feed_bytes,
                fetch_bytes=sum(_obs_step.approx_nbytes(v) for v in outs),
                wall_ms=round(wall_ms, 3)))
        return outs, new_state

    def _const_sig(self, const):
        """``const``'s part of a callable's signature, and whether it
        was reused.  The constants are the same arrays launch after
        launch whatever the key (an engine's weights, under its step and
        every prefill rung), so the last launch's are remembered with
        their signature and a launch whose every array IS the one
        remembered does not walk them again.  Remembered weakly: a dead
        reference matches nothing, so an ``id`` cannot be recycled under
        it, and weights a caller swapped in for one launch are not kept
        on the device for this memo's sake."""
        if not const:
            return (), False
        memo = self._const_memo
        if memo is not None and len(memo[0]) == len(const):
            for ref, v in zip(memo[0], const):
                if ref() is not v:
                    break
            else:
                return memo[1], True
        sig = self._feed_sig(_sig_names("c", len(const)), const)
        try:
            self._const_memo = ([weakref.ref(v) for v in const], sig)
        except TypeError:       # a constant no weakref can watch
            self._const_memo = None
        return sig, False

    def run_steps(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, object]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
    ):
        """Run K training steps in ONE device dispatch via ``lax.scan``.

        ``feed`` maps each feed name to a *stacked* array with a leading
        step dimension ``[K, ...]``; step i consumes slice i (fresh data
        per step, unlike repeating ``run`` which pays per-step dispatch).
        Fetches come back stacked ``[K, ...]``.  Persistable state
        (params, optimizer moments, BN stats, RNG) advances exactly as K
        ``run`` calls would.  The TPU-native replacement for the
        reference's C++ executor loop over a pre-fed data queue — and the
        loop the one-chip train cell times (benchmark/drivers/train.py).
        """
        with _obs_trace.span("executor::run_steps"):
            return self._run_steps(program, feed, fetch_list, scope,
                                   return_numpy)

    def _run_steps(self, program, feed, fetch_list, scope, return_numpy):
        program = program if program is not None else default_main_program()
        feed = feed or {}
        from ..lod_tensor import LoDTensor
        for n, v in feed.items():
            if isinstance(v, LoDTensor):
                raise TypeError(
                    f"run_steps feed {n!r} is a LoDTensor — the leading "
                    "dim of a run_steps feed is the STEP count, not the "
                    "batch; stack padded arrays + '@LEN' vectors per "
                    "step instead")
        if not feed:
            raise ValueError("run_steps needs at least one stacked feed "
                             "to define the step count")
        fetch_names = [v.name if isinstance(v, Variable) else str(v)
                       for v in (fetch_list or [])]
        scope = scope or global_scope()
        program = self._prepare_program(program, feed)
        if any(_host_ops.is_host_op(op.type)
               for op in program.global_block.ops):
            raise NotImplementedError(
                "run_steps cannot scan programs with host ops (RPC/IO); "
                "use run() per step")

        tel = _obs_trace.flags_on()
        rl = _obs_runlog.enabled() and \
            not getattr(_SEGMENT_TLS, "depth", 0)
        if rl:
            # before this dispatch donates buffers: queued records
            # whose fetches alias persistable state land while those
            # buffers are still alive (previous dispatch has
            # typically completed by now, so no blocking)
            _obs_runlog.drain_pending()
        pf = _obs_perf.enabled()
        t_run0 = time.perf_counter_ns() if (tel or rl or pf) else None

        feed_names = sorted(feed)
        block = program.global_block
        ks = {np.asarray(feed[n]).shape[0] for n in feed_names}
        if len(ks) != 1:
            raise ValueError(
                f"stacked feeds disagree on the step count: { {n: np.asarray(feed[n]).shape[0] for n in feed_names} }")
        (K,) = ks
        stacked = []
        with _obs_trace.span("executor::feed"):
            for n in feed_names:
                var = block.var_or_none(n)
                arr = np.asarray(feed[n])
                steps = [_as_device_array(a, var) for a in arr]
                stacked.append(jax.device_put(np.stack(steps)))

        sig = self._feed_sig(feed_names, stacked)
        base = (program._uid, program._version, tuple(fetch_names),
                "run_steps", self._training)
        key = self._mem_key(program, sig, fetch_names, mode="run_steps")
        entry = self._cache.get(key)
        cache_hit = entry is not None
        lowering_ms = 0.0
        if entry is None:
            # analysis timed apart from the state gathering below: the
            # H2D transfer of params must not inflate lowering_ms
            t_an0 = time.perf_counter_ns()
            plan = analyze_block(program, 0, feed_names, fetch_names)
            lowering_ms = (time.perf_counter_ns() - t_an0) / 1e6
        else:
            plan = entry.plan

        donated_state = [self._state_val(scope, block, n)
                         for n in plan.donated_reads]
        const_state = [self._state_val(scope, block, n)
                       for n in plan.const_reads]
        rng = scope.find_var(RNG_STATE_VAR)
        if rng is None:
            rng = jax.random.PRNGKey(program.random_seed or 0)
        rng = self._put_rng(rng)

        if entry is None:
            t_low0 = time.perf_counter_ns()
            with _obs_trace.span("executor::lower"):
                build = self._make_scan_builder(program, plan)
                entry = self._build_entry(
                    program, plan, sig, tuple(fetch_names), "run_steps",
                    (stacked, donated_state, const_state, rng),
                    build_fn=build)
            self._cache[key] = entry
            self._evict_cache_overflow()
            build_ms = lowering_ms + (time.perf_counter_ns() - t_low0) / 1e6
            # AOT compile time reports as compile_ms, not lowering.
            # Unconditional like run()'s: _perf_wall_ms subtracts
            # lowering_ms from cold perf-record walls even when tel off
            lowering_ms = max(0.0, build_ms - (entry.aot_ms or 0.0))
            if tel:
                self._note_cache_miss(base, sig)
        elif tel:
            _em().hits.inc()
        plan, jitted = entry.plan, entry.jitted

        nc = _numerics_mode()
        state_backup = None
        if nc == "fatal":
            # donation consumes the pre-step buffers; see run()
            state_backup = [self._copy_state_val(v) for v in donated_state]

        compile_ms = 0.0
        t_disp0 = time.perf_counter_ns() if not cache_hit else None
        # run_steps admits no host ops, so the K-step dispatch IS the
        # step: one root span (head-sampled like run()'s)
        with _obs_trace.start_span("executor::step", cat="executor",
                                   tags={"k_steps": K}), \
                _obs_trace.span("executor::dispatch", k_steps=K):
            try:
                fetches, new_state, rng_out = jitted(stacked, donated_state,
                                                     const_state, rng)
            except Exception as e:
                jitted = self._recover_disk_entry(
                    entry, program, e, donated_state,
                    build_fn=self._make_scan_builder(program, entry.plan))
                try:
                    fetches, new_state, rng_out = jitted(
                        stacked, donated_state, const_state, rng)
                except Exception as e2:
                    # see run(): AOT/disk recovery faulting again can
                    # only be saved by dropping the int8 kernels once
                    jitted = self._recover_fused_fault(
                        entry, program, e2, donated_state,
                        build_fn=self._make_scan_builder(program,
                                                         entry.plan))
                    fetches, new_state, rng_out = jitted(
                        stacked, donated_state, const_state, rng)
        if t_disp0 is not None:
            first_ms = (time.perf_counter_ns() - t_disp0) / 1e6
            compile_ms = (entry.aot_ms if entry.aot_ms is not None
                          else first_ms)
            if tel:
                _em().build.inc(build_ms + first_ms)
        self._numerics_guard(nc, state_backup, fetch_names, fetches,
                             plan, new_state, scope)
        for name, val in zip(plan.persist_writes, new_state):
            self._note_state_write(name)
            scope.set_var(name, val)
        if plan.has_stateful:
            scope.set_var(RNG_STATE_VAR, rng_out)
        if return_numpy:
            with _obs_trace.span("executor::fetch"):
                out = [np.asarray(v) for v in fetches]
        else:
            out = list(fetches)
        if tel:
            self._record_step(entry, key, cache_hit, lowering_ms,
                              compile_ms, stacked, fetches, t_run0, plan,
                              donated_state, program=program)
        if pf and entry.perf is not None and t_run0 is not None:
            # dispatch wall covers K steps, and so does the record's
            # flops/bytes — the roofline rates normalize consistently
            _obs_perf.observe_step(
                entry.perf, self._program_key(key),
                self._perf_wall_ms(t_run0, cache_hit, lowering_ms,
                                   compile_ms, entry))
            _obs_perf.sample_device_memory()
        if rl:
            _obs_runlog.log_run_steps(
                fetch_names, out if return_numpy else fetches, K,
                wall_ms=(time.perf_counter_ns() - t_run0) / 1e6,
                batch=_obs_runlog.batch_of(stacked, axis=1))
        return out

    def _fetch_to_numpy(self, v):
        return np.asarray(v)

    # -- persistent compile cache (core/compile_cache.py) ------------------
    def _make_scan_builder(self, program: Program, plan):
        """Builder for run_steps' K-step ``lax.scan`` wrapper (the
        executable the cache stores for mode="run_steps")."""
        def build(disable_int8_fused=False):
            fn = build_block_fn(program, plan, training=self._training,
                                mesh=self._mesh(),
                                spans_devices=self._spans_devices,
                                disable_int8_fused=disable_int8_fused)
            refeed = plan.donated_write_indices
            n_writes = len(plan.persist_writes)
            extra_idx = [i for i in range(n_writes)
                         if i not in set(refeed)]

            def multi(stacked, donated, const, rng):
                # All persistable writes ride the scan CARRY; only
                # fetches are stacked as ys.  Stacking state would
                # allocate O(K x full model state) HBM per dispatch.
                # Write-only slots (not refed) are seeded with zeros —
                # the block never reads them, each step overwrites.
                if extra_idx:
                    _, ns, _ = jax.eval_shape(
                        fn, [s[0] for s in stacked], donated, const, rng)
                    extra0 = [jnp.zeros(ns[i].shape, ns[i].dtype)
                              for i in extra_idx]
                else:
                    extra0 = []

                def one(carry, xs):
                    donated, _, rng = carry
                    fetches, new_state, rng = fn(list(xs), donated, const,
                                                 rng)
                    return ([new_state[i] for i in refeed],
                            [new_state[i] for i in extra_idx],
                            rng), fetches
                (donated, extra, rng), fetches = jax.lax.scan(
                    one, (donated, extra0, rng), tuple(stacked))
                final_state = [None] * n_writes
                for slot, i in enumerate(refeed):
                    final_state[i] = donated[slot]
                for slot, i in enumerate(extra_idx):
                    final_state[i] = extra[slot]
                return fetches, final_state, rng

            multi._int8_fused_used = fn._int8_fused_used
            multi.__name__ = multi.__qualname__ = \
                _compile_cache.program_name("multi")
            return multi
        return build

    @staticmethod
    def _feed_sig(feed_names, vals) -> tuple:
        """Feed-signature component of the executable cache key; ``vals``
        are device arrays or ShapeDtypeStructs (warm_start) — both carry
        the shape/dtype the compiled executable is pinned to."""
        return tuple((n, tuple(v.shape), str(v.dtype))
                     for n, v in zip(feed_names, vals))

    def _mem_key(self, program: Program, sig, fetch_names,
                 mode: str = "run") -> tuple:
        """THE in-memory executable-cache key.  warm_start precompiles
        install entries under this same key, so every component lives
        here — run()/run_steps()/_warm_one must never reassemble it by
        hand (a drifted copy silently defeats warm starts)."""
        if mode == "run":
            return (program._uid, program._version, sig,
                    tuple(fetch_names), self._training)
        return (program._uid, program._version, sig, tuple(fetch_names),
                mode, self._training)

    def _build_entry(self, program: Program, plan, sig, fetch_names: tuple,
                     mode: str, args, build_fn=None,
                     force_aot: bool = False,
                     hydrate_only: bool = False) -> _CacheEntry:
        """Resolve the executable for a fresh cache slot.

        Persistent cache enabled: disk load (tier A hit — no trace, no
        compile) → AOT ``lower(...).compile()`` + serialize to disk.
        Disabled (default): lazy ``jax.jit``, byte-for-byte the
        pre-cache behavior, unless ``force_aot`` (warm_start) asks for
        an eager compile anyway.  ``hydrate_only`` returns None on a
        disk miss instead of compiling (a restarting worker that wants
        the restart win but must not block its startup on cold-cache
        compiles).  ``args`` are the concrete call args or
        ShapeDtypeStructs — the AOT lowering's avals; any aval guessed
        wrong is recovered at dispatch (``_recover_disk_entry``).
        """
        self._spans_devices = self._spans_devices or any(
            isinstance(v, jax.Array) and len(v.sharding.device_set) > 1
            for v in jax.tree_util.tree_leaves(args))
        raw_make = build_fn or (lambda: build_block_fn(
            program, plan, training=self._training, mesh=self._mesh(),
            spans_devices=self._spans_devices))
        used_cell = []  # the raw fn's _int8_fused_used dict, once built

        def make(**kw):
            fn = raw_make(**kw)
            cell = getattr(fn, "_int8_fused_used", None)
            if cell is not None:
                used_cell[:] = [cell]
            return fn

        if _compile_cache.enabled():
            fp = _compile_cache.fingerprint(program, sig, fetch_names,
                                            self._training, mode,
                                            self._mesh())
            compiled = _compile_cache.load(fp, count_miss=not hydrate_only)
            if compiled is not None:
                entry = _CacheEntry(plan, compiled)
                entry.from_disk = True
                entry.fingerprint = fp
                entry.aot_ms = 0.0
                entry.perf = _obs_perf.harvest(compiled, "disk", mode,
                                               compile_ms=0.0)
                return entry
            if hydrate_only:
                return None
            jitted = jax.jit(make(), donate_argnums=(1,))
            jax_hits0 = _compile_cache.jax_cache_hits()
            t0 = time.perf_counter_ns()
            compiled = jitted.lower(*args).compile()
            aot_ms = (time.perf_counter_ns() - t0) / 1e6
            if _compile_cache.jax_cache_hits() == jax_hits0:
                # only executables XLA built HERE are stored: one that
                # jax's own cache loaded is already on disk there, and
                # serializing a loaded XLA:CPU executable again yields
                # an entry without its kernels that dies at readback
                # ("Function wrapped_tanh not found", jax 0.9)
                _compile_cache.store(fp, compiled,
                                     meta={"mode": mode,
                                           "fetches": list(fetch_names)})
            entry = _CacheEntry(plan, compiled)
            entry.fused_used = used_cell[0] if used_cell else None
            entry.fingerprint = fp
            entry.aot_ms = aot_ms
            entry.perf = _obs_perf.harvest(compiled, "compile", mode,
                                           compile_ms=aot_ms)
            return entry
        if hydrate_only:
            return None
        jitted = jax.jit(make(), donate_argnums=(1,))
        if force_aot or _obs_perf.enabled():
            # perf attribution needs the compiled handle (cost/memory
            # analysis lives on jax.stages.Compiled): compile the SAME
            # executable eagerly instead of at first dispatch.  A
            # dispatch fault of this AOT entry recovers to a lazy jit
            # like every other AOT entry (_recover_disk_entry)
            t0 = time.perf_counter_ns()
            jitted = jitted.lower(*args).compile()
            entry = _CacheEntry(plan, jitted)
            entry.fused_used = used_cell[0] if used_cell else None
            entry.aot_ms = (time.perf_counter_ns() - t0) / 1e6
            entry.perf = _obs_perf.harvest(jitted, "compile", mode,
                                           compile_ms=entry.aot_ms)
            return entry
        entry = _CacheEntry(plan, jitted)
        entry.fused_used = used_cell[0] if used_cell else None
        return entry

    def _recover_disk_entry(self, entry: _CacheEntry, program: Program,
                            exc, donated_state, build_fn=None):
        """An AOT executable whose dispatch fails is replaced in-place
        by a fresh lazy jit and the call retried: disk-hydrated entries
        and warm_start precompiles can mismatch the live scope
        (fingerprint blind spot, stale device assignment, wrong spec),
        and even a long-validated AOT ``Compiled`` is pinned to state
        avals the lazy jit would simply have retraced for (a user
        resizing a persistable var in the scope).  The fault is
        counted, the entry file evicted (stale for this key either
        way), and the run proceeds as a plain compile.

        Failures of lazy-jit entries — which already retrace per call —
        re-raise untouched UNLESS their lowering emitted int8 kernels
        (entry.fused_used latch): a Mosaic/XLA compile fault of such a
        kernel only surfaces at this layer (the try/except in
        kernels/quant.py covers trace time only), so the counted-fallback
        contract is completed here by ONE re-lower with the int8 kernels
        disabled.  A fault AFTER execution started (donated buffers
        already consumed: a retry would read deleted arrays) always
        re-raises; aval/sharding and compile faults raise before any
        donation."""
        if any(isinstance(v, jax.Array) and v.is_deleted()
               for v in donated_state):
            raise exc
        if entry.aot_ms is None and not entry.from_disk:
            return self._recover_fused_fault(entry, program, exc,
                                             donated_state, build_fn)
        if entry.fingerprint is not None:
            # a cache-keyed executable (disk-hydrated or stored): count
            # the fault against the cache and evict the stale entry.
            # warm_start force-AOT entries with the cache OFF recompile
            # silently — there is no cache to blame
            _compile_cache.dispatch_fault(entry.fingerprint, exc)
        jitted = jax.jit(self._entry_builder(entry, program, build_fn)(),
                         donate_argnums=(1,))
        entry.jitted = jitted
        entry.from_disk = False
        entry.aot_ms = None
        return jitted

    def _entry_builder(self, entry, program, build_fn=None):
        """Block-fn builder for fault-recovery re-lowers; accepts
        ``disable_int8_fused`` (both producers — the default
        build_block_fn closure and _make_scan_builder's build — do).
        The rebuilt fn's trace-time used-latch replaces the entry's (a
        disk-hydrated entry has none until its lazy rebuild traces)."""
        def mk(disable_int8_fused=False):
            if build_fn is not None:
                fn = build_fn(disable_int8_fused=disable_int8_fused)
            else:
                fn = build_block_fn(
                    program, entry.plan, training=self._training,
                    mesh=self._mesh(),
                    spans_devices=self._spans_devices,
                    disable_int8_fused=disable_int8_fused)
            cell = getattr(fn, "_int8_fused_used", None)
            if cell is not None:
                entry.fused_used = cell
            return fn
        return mk

    def _recover_fused_fault(self, entry, program, exc, donated_state,
                             build_fn=None):
        """Last line of the int8 kernels' counted-fallback contract: a
        compile fault that only surfaces at dispatch (Mosaic on a real
        TPU — invisible to the trace-time try/except in kernels/quant.py)
        re-lowers the step ONCE with the int8 kernels disabled, counted
        in quant.runtime_disables.  Reached for lazy-jit entries directly
        from _recover_disk_entry, and from the run()/run_steps()
        second-level retry when an AOT/disk entry's re-lower faults
        again.  Gated on the ENTRY's trace-time latch
        (entry.fused_used): anything whose lowering emitted no int8
        kernels re-raises untouched."""
        from ..kernels import quant as _quant_kernels
        cell = entry.fused_used
        if entry.fused_disabled or not (cell and cell.get("int8_fused")):
            raise exc
        if any(isinstance(v, jax.Array) and v.is_deleted()
               for v in donated_state):
            raise exc
        _quant_kernels.count_runtime_disable()
        mk = self._entry_builder(entry, program, build_fn)
        jitted = jax.jit(mk(disable_int8_fused=True), donate_argnums=(1,))
        entry.jitted = jitted
        entry.from_disk = False
        entry.aot_ms = None
        entry.fused_disabled = True
        return jitted

    def warm_start(self, program: Optional[Program] = None,
                   feed_specs: Optional[Dict[str, object]] = None,
                   fetch_list: Optional[Sequence] = None,
                   scope: Optional[Scope] = None,
                   hydrate_only: bool = False) -> dict:
        """AOT-precompile ``(program, feed_specs, fetch_list)`` and
        hydrate this executor's executable cache *before the first
        batch* — from the persistent disk cache when
        ``FLAGS_compile_cache_dir`` is set (an elastic-restarted worker
        skips the whole compile), else by compiling now (and, with the
        cache enabled, storing for the next process).

        ``feed_specs`` maps feed names to shape tuples, ``(shape,
        dtype)`` pairs (shape itself a tuple/list), numpy/jax arrays,
        or ``jax.ShapeDtypeStruct``s — only shape/dtype are read, no
        feed data is needed.  A LIST of such dicts warms one executable
        per entry (the serving plane precompiles a whole batch-size
        bucket ladder this way); the returned counts aggregate over
        all of them.  Shapes must be concrete.  Names are the
        post-expansion feed names (a LoD feed contributes its padded
        array plus the ``<name>@LEN`` length vector).  The scope must
        already hold the program's persistable state (run the startup
        program / restore the checkpoint first): state shapes are part
        of the executable.

        Programs containing host ops (the transpiled trainer program)
        warm every device segment whose inputs are covered by
        ``feed_specs`` + scope; segments fed by an earlier host op's
        runtime output are skipped (reported in ``skipped``).

        ``hydrate_only=True`` takes disk hits but never compiles on a
        miss — for restart paths that want the warm-cache win without
        blocking startup on cold-cache compiles (the pserver hydrates
        before binding its port; a cold cache keeps the old lazy
        compile-at-first-round behavior).

        Returns {"segments", "warmed", "persistent_hits", "compiled",
        "skipped": [...], "ms"}.
        """
        program = program if program is not None else default_main_program()
        scope = scope or global_scope()
        if isinstance(feed_specs, (list, tuple)):
            # one warm per spec-set (a serving bucket ladder): aggregate
            # the counts, keep every skip reason
            agg = {"segments": 0, "warmed": 0, "persistent_hits": 0,
                   "compiled": 0, "skipped": [], "ms": 0.0}
            for fs in feed_specs:
                one = self.warm_start(program, fs, fetch_list, scope,
                                      hydrate_only=hydrate_only)
                for k in ("segments", "warmed", "persistent_hits",
                          "compiled"):
                    agg[k] += one[k]
                agg["skipped"].extend(one["skipped"])
                agg["ms"] = round(agg["ms"] + one["ms"], 3)
            return agg
        feed_specs = dict(feed_specs or {})
        fetch_names = [v.name if isinstance(v, Variable) else str(v)
                       for v in (fetch_list or [])]
        t0 = time.perf_counter()
        program = self._prepare_program(program, feed_specs)
        out = {"segments": 0, "warmed": 0, "persistent_hits": 0,
               "compiled": 0, "skipped": [], "ms": 0.0}

        if any(_host_ops.is_host_op(op.type)
               for op in program.global_block.ops):
            segs = self._segment_plan(program, tuple(sorted(feed_specs)),
                                      tuple(fetch_names))
            for i, seg in enumerate(segs):
                if seg[0] != "device":
                    continue
                _, sub, seg_fetches, reads = seg
                sub_specs = {n: v for n, v in feed_specs.items()
                             if n in reads}
                self._warm_one(sub, sub_specs, seg_fetches, scope, out,
                               label=f"segment[{i}]",
                               hydrate_only=hydrate_only)
        else:
            self._warm_one(program, feed_specs, fetch_names, scope, out,
                           label="program", hydrate_only=hydrate_only)
        out["ms"] = round((time.perf_counter() - t0) * 1e3, 3)
        return out

    def aot_hlo(self) -> List[str]:
        """Optimized-HLO text of every ahead-of-time compiled executable
        this executor holds (:meth:`warm_start` precompiles and
        persistent-cache entries) — the executables ``run`` will
        dispatch, so a check can read what a lowering really emitted (a
        Mosaic custom call, a collective) instead of trusting that it
        took the branch it claims."""
        return [e.jitted.as_text() for e in self._cache.values()
                if isinstance(e, _CacheEntry) and e.aot_ms is not None]

    def _warm_one(self, program: Program, feed_specs: Dict, fetch_names,
                  scope: Scope, out: dict, label: str,
                  hydrate_only: bool = False) -> None:
        out["segments"] += 1
        feed_names = sorted(feed_specs)
        block = program.global_block
        feed_avals = [self._spec_aval(feed_specs[n], block.var_or_none(n))
                      for n in feed_names]
        sig = self._feed_sig(feed_names, feed_avals)
        key = self._mem_key(program, sig, fetch_names)
        if key in self._cache:
            out["warmed"] += 1
            return
        plan = analyze_block(program, 0, feed_names, fetch_names)
        try:
            donated_state = [self._warm_state_aval(scope, block, n)
                             for n in plan.donated_reads]
            const_state = [self._warm_state_aval(scope, block, n)
                           for n in plan.const_reads]
        except RuntimeError as e:
            # state produced at runtime by an earlier host op with no
            # static declaration (and the scope doesn't hold it yet):
            # nothing to precompile
            out["skipped"].append(f"{label}: {e}")
            return
        rng = scope.find_var(RNG_STATE_VAR)
        if rng is None:
            rng = jax.random.PRNGKey(program.random_seed or 0)
        rng = self._put_rng(rng)
        entry = self._build_entry(
            program, plan, sig, tuple(fetch_names), "run",
            (feed_avals, donated_state, const_state, rng), force_aot=True,
            hydrate_only=hydrate_only)
        if entry is None:  # hydrate_only + disk miss: leave it lazy
            out["skipped"].append(f"{label}: persistent-cache miss "
                                  "(hydrate_only)")
            return
        self._cache[key] = entry
        self._evict_cache_overflow()
        out["warmed"] += 1
        if entry.from_disk:
            out["persistent_hits"] += 1
        else:
            out["compiled"] += 1

    def _warm_state_aval(self, scope: Scope, block, name: str):
        """State input for a warm_start lowering: the live scope value
        when present (exact avals), else an abstract aval from the
        program's static var declaration (a pserver's grad inputs exist
        only at runtime but are fully declared).  Raises RuntimeError
        when neither is available."""
        if scope.find_var(name) is not None:
            return self._state_val(scope, block, name)
        var = block.var_or_none(name)
        from .types import VarType
        if var is None or var.shape is None or var.dtype is None or \
                any(s < 0 for s in var.shape) or \
                var.type != VarType.DENSE_TENSOR:
            raise RuntimeError(
                f"variable {name!r} is neither in the scope nor "
                f"statically declared (shape/dtype) in the program")
        return jax.ShapeDtypeStruct(
            tuple(int(s) for s in var.shape),
            jax.dtypes.canonicalize_dtype(np_dtype(var.dtype)))

    @staticmethod
    def _spec_aval(spec, var: Optional[Variable]) -> "jax.ShapeDtypeStruct":
        """Normalize one warm_start feed spec to the aval the real run
        will produce: the executor casts host arrays to the program
        var's dtype (``_as_device_array``), so a declared var dtype
        wins over a host spec's — but a ``jax.Array`` spec is fed
        through UNCAST by the real path, so its dtype stands."""
        dtype = None
        if isinstance(spec, jax.Array):
            return jax.ShapeDtypeStruct(tuple(spec.shape),
                                        np.dtype(spec.dtype))
        if isinstance(spec, jax.ShapeDtypeStruct):
            shape, dtype = tuple(spec.shape), np.dtype(spec.dtype)
        elif hasattr(spec, "shape") and hasattr(spec, "dtype"):
            shape, dtype = tuple(spec.shape), np.dtype(spec.dtype)
        elif isinstance(spec, (tuple, list)) and len(spec) == 2 and \
                isinstance(spec[0], (tuple, list)):
            shape, dtype = tuple(spec[0]), np.dtype(spec[1])
        elif isinstance(spec, (tuple, list)):
            shape = tuple(spec)
        else:
            raise TypeError(
                f"warm_start feed spec must be a shape tuple, "
                f"(shape, dtype) pair, array, or ShapeDtypeStruct; "
                f"got {spec!r}")
        shape = tuple(int(s) for s in shape)
        if any(s < 0 for s in shape):
            raise ValueError(
                f"warm_start feed shape {shape} has a dynamic (-1) dim; "
                "precompilation needs concrete shapes")
        if var is not None and var.dtype is not None:
            dtype = np.dtype(np_dtype(var.dtype))
        elif dtype is None:
            dtype = np.dtype("float32")
        # the device array the real run feeds is jnp.asarray's view of
        # the cast value: canonicalized (x64 off ⇒ int64→int32 etc.)
        return jax.ShapeDtypeStruct(shape,
                                    jax.dtypes.canonicalize_dtype(dtype))

    # -- host-op segmented execution ---------------------------------------
    # Blocks containing host ops (core/host_ops.py: RPC, pserver loop, IO)
    # are partitioned into maximal device segments — each lowered + jitted
    # exactly like a plain block — interleaved with host-op calls against
    # the scope.  This is the TPU translation of the reference op loop
    # running send/recv/listen_and_serv kernels in program order
    # (executor.cc:390, operators/send_op.cc:29, listen_and_serv_op.cc:102).

    def _segment_plan(self, program: Program, feed_names: tuple, fetch_names: tuple):
        key = ("seg", program._uid, program._version, feed_names, fetch_names)
        segs = self._cache.get(key)
        if segs is not None:
            return segs
        block = program.global_block
        runs: List = []  # (kind, start, end) over block.ops
        for i, op in enumerate(block.ops):
            kind = "host" if _host_ops.is_host_op(op.type) else "device"
            if runs and runs[-1][0] == kind:
                runs[-1][2] = i + 1
            else:
                runs.append([kind, i, i + 1])
        segs = []
        for idx, (kind, a, b) in enumerate(runs):
            if kind == "host":
                segs.append(("host", block.ops[a:b]))
                continue
            needed_later = set(fetch_names)
            for _, a2, b2 in runs[idx + 1:]:
                for op in block.ops[a2:b2]:
                    needed_later.update(op.input_arg_names())
            produced = set()
            for op in block.ops[a:b]:
                produced.update(op.output_arg_names())
            seg_fetches = sorted((produced & needed_later) - {EMPTY_VAR, ""})
            sub = program.clone()
            sub.global_block.ops = sub.global_block.ops[a:b]
            reads, defined = set(), set()
            for op in sub.global_block.ops:
                reads.update(n for n in op.input_arg_names() if n not in defined)
                defined.update(op.output_arg_names())
            segs.append(("device", sub, seg_fetches, reads))
        self._cache[key] = segs
        return segs

    def _run_segmented(self, program, feed, fetch_names, scope, return_numpy):
        self._refresh_promoted_endpoints()
        rl = _obs_runlog.enabled()
        t_seg0 = time.perf_counter_ns() if rl else None
        backup = None
        if _numerics_mode() == "fatal":
            # the per-segment sentinel restore only covers ONE segment's
            # donated state; 'scope restored intact' needs every
            # persistable snapshotted before the FIRST segment runs
            backup = [
                (v.name, self._copy_state_val(scope.find_var(v.name)))
                for v in program.global_block.vars.values()
                if getattr(v, "persistable", False)
                and scope.find_var(v.name) is not None]
        _SEGMENT_TLS.depth = getattr(_SEGMENT_TLS, "depth", 0) + 1
        try:
            out = self._run_segments(program, feed, fetch_names, scope,
                                     return_numpy)
        except FloatingPointError:
            if backup is not None:
                for name, val in backup:
                    scope.set_var(name, val)
            raise
        finally:
            _SEGMENT_TLS.depth -= 1
        if rl:
            # ONE record per step: the inner per-segment runs suppressed
            # theirs (per-segment step_ms/boundary fetches would corrupt
            # the series), this one carries the user's fetches and the
            # whole-step wall including host ops
            _obs_runlog.log_run(
                fetch_names, out,
                wall_ms=(time.perf_counter_ns() - t_seg0) / 1e6,
                batch=_obs_runlog.batch_of(list(feed.values())))
        return out

    def _run_segments(self, program, feed, fetch_names, scope,
                      return_numpy):
        segs = self._segment_plan(program, tuple(sorted(feed)), tuple(fetch_names))
        fetched: Dict[str, object] = {}
        # host ops read their inputs from the scope; make fed values visible
        for seg in segs:
            if seg[0] == "host":
                for op in seg[1]:
                    for n in op.input_arg_names():
                        if n in feed:
                            scope.set_var(n, feed[n])
        for seg in segs:
            if seg[0] == "host":
                for op in seg[1]:
                    # one child span per host op: in a stitched trace
                    # the send/recv/barrier rows sit between the device
                    # segments, with the pserver's server spans hanging
                    # under them via the wire context
                    with _obs_trace.start_span("host_op::" + op.type,
                                               cat="executor", root=False):
                        _host_ops.run_host_op(self, program, op, scope)
                continue
            _, sub, seg_fetches, reads = seg
            sub_feed = {n: v for n, v in feed.items() if n in reads}
            # keyword form: ParallelExecutor.run's positional signature
            # differs (reference parity), but both accept program=/scope=
            vals = self.run(program=sub, feed=sub_feed,
                            fetch_list=seg_fetches,
                            scope=scope, return_numpy=False)
            for n, v in zip(seg_fetches, vals):
                fetched[n] = v
                scope.set_var(n, v)
        out = []
        for n in fetch_names:
            v = fetched.get(n)
            if v is None:
                v = scope.find_var(n)
            if v is None:
                raise RuntimeError(
                    f"fetch target {n!r} was not produced by any program "
                    f"segment and is not in the scope")
            if return_numpy and not isinstance(v, SelectedRows):
                v = self._fetch_to_numpy(v)  # PE: process_allgather of
                # non-addressable multi-host shards; plain Executor: asarray
            out.append(v)
        return out

    # -- telemetry (paddle_tpu/observability) ------------------------------
    def _note_cache_miss(self, base, sig) -> None:
        m = _em()
        m.misses.inc()
        if len(self._seen_shapes) > 1024:
            # bound the side-table (telemetry only: a clear just makes
            # the next miss per base count as a first compile, not a
            # shape recompile) — shape churn must not leak memory here
            # while the executable cache itself is capped
            self._seen_shapes.clear()
        seen = self._seen_shapes.setdefault(base, set())
        if seen and sig not in seen:
            # same program+fetches, new feed signature: a shape-bucket
            # recompile (the static-shape policy's cost made visible —
            # a storm of these means feed shapes are churning)
            m.shape_recompiles.inc()
        if len(seen) > 1024:  # same leak bound, per-base
            seen.clear()
        seen.add(sig)

    def _evict_cache_overflow(self) -> None:
        cap = _flags.get_flags("executor_cache_capacity")
        while cap and len(self._cache) > cap:
            oldest = next(iter(self._cache))  # insertion order = FIFO
            del self._cache[oldest]
            if _obs_trace.flags_on():
                _em().evictions.inc()

    def _record_step(self, entry, key, cache_hit: bool, lowering_ms: float,
                     compile_ms: float, feed_vals, fetches,
                     t_run0_ns: int, plan, donated_state,
                     program: Optional[Program] = None) -> None:
        wall_ms = (time.perf_counter_ns() - t_run0_ns) / 1e6
        meta = entry.meta
        if meta is None:
            # once per executable: the cache key pins every feed/fetch
            # shape, so program_key and the transfer byte totals are
            # constants — re-deriving them per step (nested-tuple hash +
            # jax metadata property chains) dominated the cached-run
            # telemetry cost
            nbytes = _obs_step.approx_nbytes
            meta = (self._program_key(key),
                    sum(nbytes(v) for v in feed_vals),
                    sum(nbytes(v) for v in fetches))
            entry.meta = meta
        pk, feed_bytes, fetch_bytes = meta
        ss = _obs_step.StepStats(
            program_key=pk,
            cache_hit=cache_hit,
            lowering_ms=round(lowering_ms, 3),
            compile_ms=round(compile_ms, 3),
            feed_bytes=feed_bytes,
            fetch_bytes=fetch_bytes,
            wall_ms=round(wall_ms, 3),
            extras=self._step_stat_extras(program, plan, fetches))
        _obs_step.record(ss)
        m = _em()
        m.steps.inc()
        m.wall.observe(wall_ms)
        m.feed_bytes.inc(ss.feed_bytes)
        m.fetch_bytes.inc(ss.fetch_bytes)
        self._post_step_telemetry(ss, plan, donated_state)

    def _post_step_telemetry(self, ss, plan, donated_state) -> None:
        """Hook for subclasses (ParallelExecutor adds mesh-level stats)."""

    @staticmethod
    def _step_stat_extras(program, plan, fetches):
        """Model-health scalars for the StepStats record: any fetch
        registered in ``Program.step_stat_vars`` (switch_moe wires its
        aux loss / dropped-token fraction there) lands in the record's
        ``extras`` and a same-named gauge — so EP health shows per step
        on ``/stepz`` and ``/metrics``.  Scalar-only, and only when the
        var is actually fetched; the float() forces a (tiny) device
        readback, paid solely under FLAGS_runtime_stats.  For
        ``run_steps`` the stacked [K] fetch reports the LAST step."""
        reg = getattr(program, "step_stat_vars", None)
        if not reg:
            return None
        out = {}
        for name, val in zip(plan.fetch_names, fetches):
            key = reg.get(name)
            if key is None:
                continue
            try:
                arr = np.asarray(val)
                if arr.size < 1:
                    continue
                v = float(arr.reshape(-1)[-1])
            except Exception:
                continue
            out[key] = v
            _obs_stats.gauge(key).set(v)
        return out or None

    @staticmethod
    def _perf_wall_ms(t_run0, cache_hit, lowering_ms, compile_ms,
                      entry) -> float:
        """Wall time for the perf-record roofline: the full run wall
        minus the one-time build costs a COLD step paid (lowering,
        in-dispatch first-call XLA compile, AOT compile) — otherwise a
        1–2-step run's achieved FLOP/s is dominated by the compile,
        understating the roofline by orders of magnitude."""
        wall = (time.perf_counter_ns() - t_run0) / 1e6
        if not cache_hit:
            # compile_ms REPORTS entry.aot_ms for AOT entries (see the
            # dispatch block) — max(), not sum, or it subtracts twice
            wall -= (lowering_ms or 0.0) + max(compile_ms or 0.0,
                                               entry.aot_ms or 0.0)
        return max(wall, 0.0)

    @staticmethod
    def _program_key(key) -> str:
        """Short telemetry id of an executable-cache key (the StepStats
        ``program_key`` and the /profilez record key share it)."""
        return f"{key[0]:x}v{key[1]}:{abs(hash(key)) % (16 ** 8):08x}"

    # -- numerics sentinel (FLAGS_numerics_check) --------------------------
    @staticmethod
    def _copy_state_val(v):
        """Device copy of one donated-state value (fatal-mode pre-step
        snapshot — the original buffer is consumed by donation)."""
        if isinstance(v, SelectedRows):
            return SelectedRows(jnp.asarray(v.rows).copy(),
                                jnp.asarray(v.values).copy(), v.height)
        cp = getattr(v, "copy", None)
        return cp() if callable(cp) else v

    def _numerics_guard(self, mode: str, state_backup, fetch_names,
                        fetches, plan, new_state, scope) -> None:
        """Run the sentinel BEFORE the state writes (run and run_steps
        share this): a fatal verdict keeps the poisoned post-optimizer
        state out of the scope — the pre-step copy goes back in, since
        donation consumed the live buffers."""
        if not mode:
            return
        try:
            self._check_numerics(fetch_names, fetches,
                                 plan.persist_writes, new_state, mode)
        except FloatingPointError:
            if state_backup is not None:
                for name, val in zip(plan.donated_reads, state_backup):
                    scope.set_var(name, val)
            raise

    def _check_numerics(self, fetch_names, fetches, persist_names,
                        new_state, mode: str) -> None:
        """Post-dispatch NaN/Inf sentinel over every float fetch and
        updated persistable var.  Device-side ``jnp.isnan``/``jnp.isinf``
        reductions, ONE batched readback of the tiny flags — never a
        full-tensor host scan (that is FLAGS_check_nan_inf's job).

        Runs BEFORE the state writes: at ``mode='fatal'`` a poisoned
        step dumps a flight record and raises while the scope still
        holds the pre-step parameters — the optimizer never applies the
        poison.  ``mode='warn'`` names the variables, bumps
        ``numerics.{nan,inf}`` and notes the flight ring, then lets the
        step land (the counters make a slow-motion blow-up visible
        without killing a run that might recover)."""
        names: List[str] = []
        flags = []
        seen = set()
        for name, val in list(zip(fetch_names, fetches)) + \
                list(zip(persist_names, new_state)):
            if name in seen:  # a fetched persistable counts once
                continue
            v = val.values if isinstance(val, SelectedRows) else val
            dt = getattr(v, "dtype", None)
            if dt is None or not jnp.issubdtype(dt, jnp.floating):
                continue
            seen.add(name)
            names.append(name)
            flags.append(jnp.any(jnp.isnan(v)))
            flags.append(jnp.any(jnp.isinf(v)))
        m = _nm()
        m.checked.inc()
        if not names:
            return
        host = jax.device_get(flags)  # one batched tiny-flag readback
        nan_vars = [n for n, f in zip(names, host[0::2]) if bool(f)]
        inf_vars = [n for n, f in zip(names, host[1::2]) if bool(f)]
        if not nan_vars and not inf_vars:
            return
        m.nan.inc(len(nan_vars))
        m.inf.inc(len(inf_vars))
        from ..observability import flight as _flight
        _flight.note("numerics_sentinel", mode=mode,
                     nan_vars=nan_vars[:16], inf_vars=inf_vars[:16])
        msg = (f"numerics sentinel (FLAGS_numerics_check={mode}): "
               f"NaN in {nan_vars or '[]'}, Inf in {inf_vars or '[]'}")
        if mode == "fatal":
            # full post-mortem BEFORE the raise (the step's spans and
            # the poisoned-step note are still in the rings)
            _flight.dump("numerics_fatal")
            raise FloatingPointError(
                msg + " — step NOT applied (the pre-step state snapshot "
                "is restored into the scope)")
        import sys as _sys
        print("[numerics] " + msg, file=_sys.stderr, flush=True)

    # -- placement hooks (overridden by ParallelExecutor) ------------------
    def _prepare_program(self, program: Program, feed: Dict) -> Program:
        return program

    def _mesh(self):
        return None

    def _put_feed(self, arr):
        return arr

    def _put_rng(self, rng):
        return rng

    def _put_state(self, name: str, val):
        return val

    def _note_state_write(self, name: str) -> None:
        pass

    # -- helpers -----------------------------------------------------------
    def _state_val(self, scope: Scope, block, name: str):
        val = scope.find_var(name)
        if val is None:
            raise RuntimeError(
                f"variable {name!r} is not initialized in the scope — run the "
                f"startup program first (fluid.default_startup_program())"
            )
        val = _as_device_array(val, block.var_or_none(name))
        placed = self._put_state(name, val)
        if placed is not val:
            scope.set_var(name, placed)
        return placed

    def close(self) -> None:
        self._cache.clear()
