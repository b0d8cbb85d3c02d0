"""DecodeEngine: token-level continuous batching over the paged cache.

The decode-plane hot loop.  One scheduler thread drives two kinds of
dispatch against one :class:`~paddle_tpu.core.executor.Executor`:

- **prefill** — one dispatch per JOINING request, prompt padded to the
  smallest bucket on the prefill ladder (``prefill_buckets`` — the
  serving batcher's bucket discipline applied to the time axis).
  It writes the prompt's K/V into the request's cache blocks and samples
  the first token, so a joining stream emits immediately.  Prefill is a
  SEPARATE executable from the decode step: a long new prompt costs the
  in-flight streams exactly one prefill dispatch of latency, never a
  recompile or a batch-shape change.
- **decode step** — ONE dispatch advances every active slot by one
  token: fixed ``[max_slots]`` shapes, inactive slots ride along into
  the reserved trash block.  Requests join (slot assigned at admission)
  and leave (slot freed the moment eos/length finishes it) at token
  granularity — the running batch never drains to reshape.

Both dispatches ride ``Executor.run_callable`` with the cache arrays as
donated cache-resident state, so the executor's compile counters cover
the decode plane: after the ladder + step are warm, a mixed join/leave
load of varying prompt and output lengths is ZERO compiles — the
acceptance pin.

The model protocol is a class:
:class:`~paddle_tpu.decode.adapter.LMAdapter` (its cache, its two programs,
its observer, ``supports``, and ``slot_state`` for a model that keeps rows
by SLOT beside its paged blocks).  The engine knows no kind of state by
name, and nothing but the feed of a ``slot_state`` model's prefill differs:
same admission, same ladder, same step pipeline.

Admission control (the batcher discipline): a bounded pending queue
(``max_queue``) sheds with the serving plane's typed
:class:`Overloaded`; an over-budget prompt/output (off the ladder, or
past the block-table context bound) is a typed
:class:`RequestTooLong`.  Block reservation happens at admission —
``ceil((prompt+max_new)/block_tokens)`` blocks up front — so a running
stream can never hit cache OOM mid-generation.

One block lifecycle, two admission policies.  Every engine draws its
blocks from the refcounted allocator (:mod:`paddle_tpu.decode.cache`),
checks before each step that every live slot's write target is present
and private (:meth:`DecodeEngine._ensure_blocks`), keeps the pool gauges
(``blocks_referenced`` / ``blocks_cached`` / ``blocks_leaked``) and
shows ``block_pool`` on ``/decodez``.  With neither policy a block has
one owner from admission to retirement, so that check finds nothing to
do; an engine is built with either or both of (refused at construction
where ``model.supports`` lacks the name):

- ``prefix_cache=True`` — admission walks the prompt's
  block-aligned prefix against a content-addressed
  :class:`~paddle_tpu.decode.cache.PrefixCache` and ADOPTS hits as
  refcounted references, so a shared system prompt prefills once and
  later requests dispatch only a suffix prefill
  (:meth:`TransformerLM.prefill_suffix`).  Full prompt blocks register
  after prefill; zero-ref cached blocks park in an LRU reclaimed under
  pool pressure.  Hits are capped one block short of the prompt so the
  suffix is never empty (the last position's logits seed the stream).
- ``overcommit=True`` — admission reserves only
  ``ceil((P+1)/block_tokens)`` blocks and the decode step grows one
  block as a stream crosses each block boundary; when growth cannot
  allocate, the NEWEST running stream is preempted (blocks decref'd,
  generated tokens kept host-side on its handle) and re-queued
  head-of-line for re-prefill of ``prompt + generated[:-1]`` — the
  counter-hash sampler is positional, so a resumed stream's remaining
  tokens are identical to an uninterrupted run.  The oldest stream is
  never evicted: it finishes, frees blocks, and the FIFO head (the
  preempted request) re-admits — no livelock.

Writes into a block that is shared (refcount > 1) or advertised by the
prefix cache fork it first — device block-copy plus a block-table
remap (copy-on-write).  Inside this engine streams only ever append
past their adopted prefix, so forks are the beam decoder's path
(:mod:`paddle_tpu.decode.beam`); the step-side check is the safety
invariant that makes that true by construction.

Token fan-out.  Reading a step and telling its streams are two
things.  At the read the engine BOOKS the step (span
``decode::step.book``): counters, slot state, each token onto its
handle's record, retirement — so the admission sweep that follows sees
the freed slots and blocks.  Telling the streams is left on ``_fanout``
and done under ``decode::step.emit`` right AFTER the next step's
dispatch: the readers it wakes then run while the device computes and
this thread waits with the interpreter released, instead of holding the
next dispatch back with the device idle.  The order ``… wait n → book n →
admit → prefill(s) → retire → feed → dispatch n+1 → emit n → wait n+1``
keeps step n read and observed before step n+1 is dispatched.  When no
step will follow (the last stream left, an error, ``close()``) the list
goes out at once, before anything else is told to a handle; a FIN queues
behind whatever is pending, so a stream always gets token … token, FIN;
a prefill's own first token goes out at once (it is the TTFT).  The
price: a step's tokens reach their streams one feed + dispatch and the
prefills admitted in between later — ``fanout_delay_ms`` says how much.

Who writes a token's frame.  A stream served by :mod:`.server` on a
native connection with ``chunk_tokens`` 1 is PUSHED: its handle carries
the connection as a frame sink from ``submit`` on, and ``.emit`` hands
the step's tokens and their connections to ONE foreign call
(``transport.push_frames``) — a template and four bytes a token, the
bytes the queue path sends — that does no I/O and keeps the interpreter:
the native library's writer thread, which needs none, writes every
T-frame behind it with sends that cannot block.  No connection thread
wakes for a token: it sleeps from the request to the FIN, which (with
the typed error) stays its to write.
Every other reader keeps the queue path, one ``queue.put`` a token:
in-process readers (``for tok in handle``, ``next_token``, the beam
decoder, the canary), ``rpc_transport=python`` connections, chunked
streams.  A pushed stream whose socket would not take a frame whole (a
slow reader) moves to the queue path ONCE and FOR GOOD — the transport
keeps what the socket refused, the stream's own connection thread writes
it and drains the queue from there on, so no frame overtakes another and
a slow reader never holds this thread; a push that finds the peer gone
cancels the handle, so the slot and its blocks are freed at the next
step.  The push lock orders the two writers: it is held across a push,
and by whoever takes a stream off its sink.

Observability: ``decode.<name>.*`` counters/gauges/histograms plus the
``/decodez`` debug page (:func:`DecodeEngine.decodez`); among them
``fanout_delay_ms`` (histogram: read of a step → its tokens handed out,
for the steps handed out behind a dispatch), ``fanout_immediate``
(hand-outs with no step in flight), ``pushed_frames`` (token frames this
thread wrote itself; ``decode::step.emit`` carries ``pushed=<frames>``)
and ``push_fallbacks`` (streams moved to the queue path).
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from .cache import PrefixCache, blocks_for
from .model import TransformerLM
from ..core.executor import Executor
from ..distributed import faults as _faults
from ..distributed import transport as _transport
from ..kernels import quant as _quant_kernels
from ..observability import audit as _audit
from ..observability import capacity as _capacity
from ..observability import debug_server as _debug_server
from ..observability import memory as _memory
from ..observability import phase as _phase
from ..observability import stats as _obs_stats
from ..observability import tenant as _tenant
from ..observability import trace as _trace
from ..serving.batcher import BucketLadder, Overloaded, RequestTooLong

# what an engine (and a beam session) is built with where its constructor
# is given nothing; ``prefix_cache`` and ``overcommit`` default to off
DEFAULT_MAX_SLOTS = 8
DEFAULT_MAX_QUEUE = 64
DEFAULT_BLOCK_TOKENS = 16
DEFAULT_PREFILL_BUCKETS = (16, 32, 64, 128)
DEFAULT_CACHE_DTYPE = "float32"

# decode request phases (FLAGS_phase_attribution): queue = submit ->
# slot claimed, prefill = slot -> first token emitted (the TTFT tail
# minus queue wait), decode = first token -> stream finished.  The
# three sum to the request's end-to-end wall by construction
DECODE_PHASES = ("queue", "prefill", "decode")

# a step's tokens wait for one feed + dispatch and the prefills admitted
# before it: a few ms to a few tens, which the default ladder's 5/10/25/50
# would not tell apart
_FANOUT_MS_BUCKETS = (0.5, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 8.0, 10.0, 12.5,
                      15.0, 20.0, 25.0, 35.0, 50.0, 75.0, 100.0, 150.0,
                      250.0, 500.0, 1000.0, 5000.0)


class SamplingParams:
    """Per-request sampling config.  ``temperature <= 0`` is greedy;
    ``top_k == 0`` samples the full vocab (under the compiled
    ``TOPK_MAX`` ceiling).

    What it costs (:func:`paddle_tpu.decode.model._sample`): a greedy
    request's token is an argmax and sorts nothing; one
    ``temperature > 0`` request puts every launch it takes part in — its
    prefill, and each decode step it shares with the batch — on the
    whole-vocabulary sort.  Every request's tokens are the same either
    way; counters ``greedy_steps`` / ``greedy_prefills`` beside
    ``steps`` / ``prefills`` say how many launches went without."""

    def __init__(self, temperature: float = 0.0, top_k: int = 0,
                 max_new_tokens: int = 32, eos_id: Optional[int] = None,
                 seed: int = 0):
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.seed = int(seed)
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")

    def to_dict(self) -> dict:
        return {"temperature": self.temperature, "top_k": self.top_k,
                "max_new_tokens": self.max_new_tokens,
                "eos_id": self.eos_id, "seed": self.seed}

    @classmethod
    def from_dict(cls, d: dict) -> "SamplingParams":
        return cls(temperature=d.get("temperature", 0.0),
                   top_k=d.get("top_k", 0),
                   max_new_tokens=d.get("max_new_tokens", 32),
                   eos_id=d.get("eos_id"), seed=d.get("seed", 0) or 0)


class DecodeRequest:
    __slots__ = ("rid", "prompt", "sampling", "t_enq", "handle", "tl",
                 "tenant", "resume_tokens")

    def __init__(self, rid: int, prompt: np.ndarray,
                 sampling: SamplingParams,
                 tenant: Optional[str] = None):
        self.rid = rid
        self.prompt = prompt
        self.sampling = sampling
        self.tenant = tenant
        # set by preemption: the tokens generated before eviction; a
        # non-None value marks a queued request as a RESUME (re-prefill
        # prompt + resume_tokens[:-1], then continue token-exact)
        self.resume_tokens: Optional[List[int]] = None
        self.t_enq = time.perf_counter()   # the engine's one clock
        self.handle = DecodeHandle(rid)
        # phase timeline sharing the enqueue stamp (flag-gated; None
        # keeps the flag-off path allocation-free)
        self.tl = (_phase.PhaseTimeline(t0=self.t_enq)
                   if _phase.enabled() else None)


class DecodeHandle:
    """Client-side view of one generation: iterate for the token
    stream, or :meth:`result` for the aggregate."""

    _DONE = object()
    _UNSUNK = object()

    def __init__(self, rid: int):
        self.rid = rid
        # a served stream's frame sink (module doc, "Token fan-out"): while
        # it is set the engine's thread writes this stream's token frames
        # itself and ``_q`` carries no token.  ``submit`` sets both before
        # the request is queued; the sink is cleared — under the engine's
        # push lock, for good — when the stream moves to the queue path
        self._sink = None
        self._push_lock: Optional[threading.Lock] = None
        self._n_pushed = 0
        self._q: "queue.Queue" = queue.Queue()
        self._tokens: List[int] = []
        self._logits: List[np.ndarray] = []   # capture_logits engines only
        self._final: Optional[dict] = None
        self._err: Optional[BaseException] = None
        self._done = threading.Event()
        self._cancelled = threading.Event()

    # -- engine side -------------------------------------------------------
    # A token is BOOKED the moment the engine has read it (the record
    # that preemption, the audit hash and ``result()`` read) and EMITTED
    # when its reader is woken; a decode step's tokens are emitted one
    # dispatch later than they are booked (module doc, "Token fan-out")
    def _book(self, token: int, logits: Optional[np.ndarray]) -> None:
        self._tokens.append(int(token))
        if logits is not None:
            self._logits.append(logits)

    def _emit(self, token: int) -> None:
        self._q.put(int(token))

    def _unsink(self) -> None:
        """The engine's thread, under the push lock: the sink would not take
        a frame whole, so from here to its FIN this stream's tokens go
        through ``_q`` and its connection's thread writes them."""
        self._sink = None
        self._q.put(self._UNSUNK)

    def _finish(self, reason: str) -> None:
        self._final = {"tokens": list(self._tokens), "finish": reason,
                       "n_tokens": len(self._tokens)}
        self._done.set()
        self._q.put(self._DONE)

    def _fail(self, exc: BaseException) -> None:
        self._err = exc
        self._done.set()
        self._q.put(self._DONE)

    # -- client side -------------------------------------------------------
    def __iter__(self):
        while True:
            item = self._q.get()
            if item is self._DONE:
                if self._err is not None:
                    raise self._err
                return
            yield item

    def next_token(self, timeout: Optional[float] = None):
        """One token id, or None when the stream is finished; raises
        TimeoutError if the engine produces nothing for ``timeout``
        seconds (the streaming server's bounded wait — a wedged engine
        must surface as a typed error frame, not a parked connection)."""
        try:
            item = self._q.get(timeout=timeout)
        except queue.Empty:
            raise TimeoutError(
                f"decode request {self.rid}: no token within {timeout}s")
        if item is self._DONE:
            if self._err is not None:
                raise self._err
            return None
        return item

    def await_sink(self, timeout: float) -> bool:
        """The wait of a pushed stream's connection thread, which writes no
        token: True once the stream has finished (FIN is the caller's to
        write; an engine error is raised), False once it has moved to the
        queue path (:meth:`next_token` from here on).  Either way nothing
        more is pushed when this returns or raises.  It wakes once a
        ``timeout``; a stream whose pushed count did not advance in that
        time is taken off its sink and raises TimeoutError — the wedged
        engine's typed error frame, as :meth:`next_token` gives it."""
        seen = self._n_pushed
        while True:
            try:
                item = self._q.get(timeout=timeout)
            except queue.Empty:
                if self._n_pushed != seen:
                    seen = self._n_pushed
                    continue
                with self._push_lock:
                    self._sink = None
                raise TimeoutError(
                    f"decode request {self.rid}: no token within {timeout}s")
            if item is self._UNSUNK:
                return False
            if self._err is not None:
                raise self._err
            return True

    def cancel(self) -> None:
        """Abandon the generation: the engine retires the request's
        slot (freeing its cache blocks) at the next step boundary, or
        drops it from the pending queue at the next admission sweep.
        No-op once the stream already finished.  Called by the
        streaming server when a client disconnects mid-stream — a
        vanished reader must not keep generating into the void."""
        if not self._done.is_set():
            self._cancelled.set()

    @property
    def cancelled(self) -> bool:
        return self._cancelled.is_set()

    def result(self, timeout: Optional[float] = None) -> dict:
        if not self._done.wait(timeout=timeout):
            raise TimeoutError(f"decode request {self.rid} still running")
        if self._err is not None:
            raise self._err
        return dict(self._final)

    @property
    def tokens(self) -> List[int]:
        return list(self._tokens)

    @property
    def logits(self) -> List[np.ndarray]:
        return list(self._logits)


class _Slot:
    __slots__ = ("req", "blocks", "pos_next", "n_generated", "last_token",
                 "t_last", "cached_tokens", "seq")

    def __init__(self, req: DecodeRequest, blocks: List[int],
                 prompt_len: int, first_token: int,
                 cached_tokens: int, seq: np.ndarray):
        self.req = req
        self.blocks = blocks
        self.pos_next = prompt_len   # where the last sampled token's
        self.n_generated = 1         # K/V lands on the next step
        self.last_token = first_token
        self.t_last = time.perf_counter()
        # positions [0, cached_tokens) are already resident in adopted
        # blocks (prefix hits; else 0); ``seq`` is the full token
        # sequence prefill must make resident (the prompt, or
        # prompt + generated[:-1] on a preemption resume)
        self.cached_tokens = cached_tokens
        self.seq = seq


class _LatencyStats:
    """The flag-gated token-level latency + goodput bundle
    (``FLAGS_phase_attribution``): created on first use so a flag-off
    process never registers these series.

    - ``ttft_ms``: submit -> first token emitted (queue + prefill; what
      a streaming client perceives as time-to-first-token);
    - ``tbt_ms``: per-stream inter-token interval (time between
      tokens), the token-level tail SLO metric — an SLO rule on
      ``decode.<name>.ttft_ms:p99`` / ``tbt_ms:p99`` reads these;
    - goodput accounting: every decode-step lane is either useful
      (live stream) or padding (inactive slot riding into the trash
      block), every prefill token either real prompt or bucket pad,
      and cancelled streams generated into the void — the counters
      say how much of the device time bought tokens a client kept.
      (Preemption re-prefill compute is accounted separately in
      :class:`_PrefixStats` — ``preempt_reprefill_tokens``.)
    """

    def __init__(self, name: str):
        sc = _obs_stats.scope(f"decode.{name}")
        self.ttft_ms = sc.histogram(
            "ttft_ms", help_str="time to first token: submit -> first "
            "token emitted (queue wait + prefill dispatch)")
        self.tbt_ms = sc.histogram(
            "tbt_ms", help_str="time between tokens, per stream (the "
            "client-perceived per-token latency)")
        self.live_slot_steps = sc.counter(
            "goodput_live_slot_steps", "decode-step lanes that advanced "
            "a live stream (useful device work)")
        self.pad_slot_steps = sc.counter(
            "goodput_pad_slot_steps", "decode-step lanes dispatched for "
            "INACTIVE slots (padding riding into the trash block)")
        self.prefill_tokens = sc.counter(
            "goodput_prefill_tokens", "real prompt tokens prefilled")
        self.pad_prefill_tokens = sc.counter(
            "goodput_pad_prefill_tokens", "pad tokens added snapping "
            "prompts onto the prefill bucket ladder")
        self.cancelled = sc.counter(
            "cancelled", "streams abandoned by their client (engine "
            "retired the slot / dropped the queued request)")
        self.cancelled_tokens = sc.counter(
            "cancelled_tokens", "tokens generated for streams later "
            "cancelled (device work no client kept)")
        self.phases = _phase.PhaseRecorder(f"decode.{name}",
                                           DECODE_PHASES)

    def goodput(self) -> dict:
        live = self.live_slot_steps.value
        pad = self.pad_slot_steps.value
        pre = self.prefill_tokens.value
        pre_pad = self.pad_prefill_tokens.value
        return {
            "live_slot_steps": live, "pad_slot_steps": pad,
            "slot_utilization": round(live / max(live + pad, 1), 4),
            "prefill_tokens": pre, "pad_prefill_tokens": pre_pad,
            "prefill_efficiency": round(pre / max(pre + pre_pad, 1), 4),
            "cancelled": self.cancelled.value,
            "cancelled_tokens": self.cancelled_tokens.value,
        }


class _PrefixStats:
    """Block-pool metric bundle of every engine: prefix-cache hit
    accounting, copy-on-write forks, preemption/resume accounting and
    the pool leak invariant (the counters of a policy an engine was not
    built with stay 0)."""

    def __init__(self, name: str):
        sc = _obs_stats.scope(f"decode.{name}")
        self.prefix_lookups = sc.counter(
            "prefix_lookups", "full prompt blocks walked against the "
            "prefix cache at admission (the hit-rate denominator)")
        self.prefix_hits = sc.counter(
            "prefix_hits", "blocks adopted from the prefix cache — "
            "prompt positions that did NOT re-prefill")
        self.prefix_inserts = sc.counter(
            "prefix_inserts", "freshly prefilled full blocks registered "
            "into the prefix cache")
        self.prefix_evictions = sc.counter(
            "prefix_evictions", "parked zero-ref cached blocks reclaimed "
            "to the free list under pool pressure (LRU order)")
        self.prefix_collisions = sc.counter(
            "prefix_collisions", "hash hits rejected by the full "
            "token-id verify (served as a miss, never as wrong K/V)")
        self.saved_prefill_tokens = sc.counter(
            "prefix_saved_prefill_tokens", "prompt tokens whose prefill "
            "compute was skipped via adopted cached blocks")
        self.cow_forks = sc.counter(
            "cow_forks", "shared blocks forked (device block-copy + "
            "table remap) on the first divergent write")
        self.preempts = sc.counter(
            "preempts", "running streams evicted by overcommit pressure "
            "(blocks freed, generated tokens kept host-side)")
        self.preempt_resumes = sc.counter(
            "preempt_resumes", "preempted streams re-admitted via "
            "re-prefill")
        self.reprefill_tokens = sc.counter(
            "preempt_reprefill_tokens", "tokens re-prefilled resuming "
            "preempted streams (overcommit's compute cost)")
        self.blocks_referenced = sc.gauge("blocks_referenced")
        self.blocks_cached = sc.gauge("blocks_cached")
        self.blocks_leaked = sc.gauge(
            "blocks_leaked", "pool invariant: usable blocks neither "
            "free, referenced nor cached — MUST be zero")


class _EngineStats:
    def __init__(self, name: str):
        self._name = name
        self._lat_lock = threading.Lock()
        self._lat: Optional[_LatencyStats] = None
        sc = _obs_stats.scope(f"decode.{name}")
        self.tokens = sc.counter("tokens", "generated tokens (all streams)")
        self.prefills = sc.counter("prefills")
        self.greedy_prefills = sc.counter(
            "greedy_prefills", "prefills of a temperature <= 0 request: "
            "sampled by argmax, no sort of the vocabulary")
        self.joins = sc.counter(
            "joins", "requests admitted into the running decode batch")
        self.leaves = sc.counter(
            "leaves", "requests retired from the running batch (eos/length)")
        self.shed = sc.counter(
            "shed", "requests refused by admission control (typed "
            "Overloaded/RequestTooLong)")
        self.steps = sc.counter("steps", "decode-step dispatches")
        self.greedy_steps = sc.counter(
            "greedy_steps", "decode steps with no temperature > 0 slot: "
            "sampled by argmax, no sort of the vocabulary")
        self.queue = sc.gauge("queue_depth")
        self.active = sc.gauge("slots_active")
        self.blocks_free = sc.gauge("blocks_free")
        self.step_ms = sc.histogram("step_ms")
        self.prefill_ms = sc.histogram("prefill_ms")
        self.queue_ms = sc.histogram(
            "queue_ms",
            help_str="submit -> the engine thread starts the request's "
                     "prefill: wait for a slot, for blocks and for the "
                     "dispatches ahead of it")
        self.token_ms = sc.histogram(
            "token_ms",
            help_str="per-stream inter-token interval (what a client "
                     "perceives as per-token latency)")
        self.fanout_delay_ms = sc.histogram(
            "fanout_delay_ms", buckets=_FANOUT_MS_BUCKETS,
            help_str="read of a decode step -> its tokens handed to "
                     "their streams, for the steps handed out behind "
                     "the next step's dispatch (steps - count went out "
                     "at once)")
        self.fanout_immediate = sc.counter(
            "fanout_immediate", "token hand-outs made with no step in "
            "flight (the last stream left, an error, close)")
        self.pushed_frames = sc.counter(
            "pushed_frames", "token frames the engine's thread wrote to "
            "their connections itself (transport.push_frames)")
        self.push_fallbacks = sc.counter(
            "push_fallbacks", "streams moved from the pushed path to the "
            "queue path for good: their socket would not take a frame "
            "whole")

    def latency(self) -> _LatencyStats:
        """The flag-gated bundle (lazy: see :class:`_LatencyStats`)."""
        with self._lat_lock:
            if self._lat is None:
                self._lat = _LatencyStats(self._name)
            return self._lat

    @property
    def lat(self) -> Optional[_LatencyStats]:
        return self._lat

    def capacity_tracker(self) -> "_capacity.CapacityTracker":
        """Get-or-create this engine's capacity tracker (callers gate
        on ``_capacity.enabled()`` so a flag-off process never
        registers ``decode.<name>.util.*`` series)."""
        return _capacity.tracker(f"decode.{self._name}",
                                 ("prefill", "decode"))

    def capacity(self) -> Optional["_capacity.CapacityTracker"]:
        return _capacity.get(f"decode.{self._name}")


class DecodeEngine:
    """One model's stateful generative scheduler (module doc)."""

    def __init__(self, model: TransformerLM, params: Dict,
                 name: str = "lm",
                 max_slots: Optional[int] = None,
                 block_tokens: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 prefill_buckets=None,
                 max_queue: Optional[int] = None,
                 executor: Optional[Executor] = None,
                 capture_logits: bool = False,
                 attn_impl: Optional[str] = None,
                 cache_dtype: Optional[str] = None,
                 prefix_cache: Optional[bool] = None,
                 overcommit: Optional[bool] = None):
        self.model = model
        self.name = name
        cfg = model.config
        self.max_slots = int(DEFAULT_MAX_SLOTS if max_slots is None
                             else max_slots)
        self.max_queue = int(DEFAULT_MAX_QUEUE if max_queue is None
                             else max_queue)
        bs = int(DEFAULT_BLOCK_TOKENS if block_tokens is None
                 else block_tokens)
        # block TABLE width: enough blocks per slot for a full-length
        # context — a compiled shape, so it derives from max_seq_len
        self.max_blocks_per_seq = blocks_for(cfg.max_seq_len, bs)
        if num_blocks is None:
            num_blocks = 1 + self.max_slots * self.max_blocks_per_seq
        # KV storage dtype latches at engine build (the compiled state
        # shape)
        if cache_dtype is None:
            cache_dtype = DEFAULT_CACHE_DTYPE
        # the model describes its cache and owns the state list its entry
        # points thread: the engine passes ``cache.state()`` through.  A
        # ``slot_state`` model's cache is sized by the slot count, and its
        # prefill's feed says which slot the prompt fills (LMAdapter)
        self._slot_state = bool(getattr(model, "slot_state", False))
        self.cache = model.make_cache(
            num_blocks, bs, dtype=cache_dtype,
            **({"slots": self.max_slots} if self._slot_state else {}))
        ladder = (prefill_buckets if prefill_buckets is not None
                  else DEFAULT_PREFILL_BUCKETS)
        sizes = sorted({int(b) for b in
                        (ladder.sizes if isinstance(ladder, BucketLadder)
                         else ladder) if int(b) <= cfg.max_seq_len})
        if not sizes:
            sizes = [cfg.max_seq_len]
        self.prefill_ladder = BucketLadder(sizes)
        self.capture_logits = capture_logits
        self._attn_impl = attn_impl
        self._exe = executor if executor is not None \
            else Executor(training=False)
        self._plist = model.param_list(params)
        self.stats = _EngineStats(name)
        # what the model's programs return beside token and logits (a
        # routed model's load figures) goes to the model's own observer
        self._observer = model.observer(
            name, self.cache, (self.max_slots, self.max_blocks_per_seq))
        # the two admission policies (module doc), latched here: an
        # engine with a prefix cache has ``self.prefix``
        self._overcommit_on = bool(overcommit)
        asked = {"prefix_cache": bool(prefix_cache),
                 "overcommit": self._overcommit_on}
        refused = sorted(k for k, on in asked.items()
                         if on and k not in model.supports)
        if refused:
            raise ValueError(
                f"decode engine {name!r}: {type(model).__name__} does not "
                f"support {', '.join(refused)} (it has no suffix prefill "
                f"over its cache)")
        self.prefix = (PrefixCache(
            self.cache.allocator, bs,
            model_key=f"{name}/{cfg.vocab}x{cfg.d_model}x{cfg.n_layer}")
            if prefix_cache else None)
        self._pstats = _PrefixStats(name)
        # suffix / resume bucket ladder: a prefix-hit suffix (or a
        # preemption re-prefill, whose length can exceed the prefill
        # ladder) snaps onto block-size doublings so a handful of
        # executables cover every residual length
        limit = self.max_context()
        sizes2 = set(self.prefill_ladder.sizes)
        b2 = bs
        while b2 < limit:
            sizes2.add(b2)
            b2 *= 2
        sizes2.add(limit)
        self._resume_ladder = BucketLadder(sorted(sizes2))

        self._lock = threading.Condition()
        self._pending: List[DecodeRequest] = []
        self._slots: List[Optional[_Slot]] = [None] * self.max_slots
        # decode-step feed rows (host mirrors of the fixed-shape feeds)
        self._tables = np.zeros((self.max_slots, self.max_blocks_per_seq),
                                np.int32)
        self._rid = itertools.count(1)
        self._closed = False
        # token fan-out (module doc): what the last step booked and no
        # stream has been told yet, in order, as ``(handle, token, None)``
        # or ``(handle, None, finish reason)``; only the engine thread
        # touches it
        self._fanout: List[tuple] = []
        self._fanout_t_read = 0.0
        # held across a push (sinks read, the foreign call, its verdicts) and
        # by whoever takes a stream off its sink: a connection's thread never
        # writes while a push to it can be under way
        self._push_lock = threading.Lock()
        # memory anatomy (FLAGS_memory_attribution): the KV block pool
        # registers on the process MemoryLedger — pool bytes, per-state
        # block counts (incl. parked LRU blocks), bytes-per-resident-
        # stream — and its refcount invariant feeds the leak sentinel.
        # Flag off: no pool, no series, no thread, _mem_pool stays None
        # so every event-filing site is one attribute check
        self._block_bytes = self.cache.nbytes // max(self.cache.num_blocks,
                                                     1)
        if self.cache.quantized:
            # /quantz: advertise the quantized pool (dtype-aware bytes
            # per block INCLUDING the parallel scale pools)
            _quant_kernels.note_kv_cache(name, {
                "dtype": self.cache.dtype,
                "num_blocks": self.cache.num_blocks,
                "block_tokens": bs,
                "bytes_per_block": self._block_bytes,
                "pool_bytes": self.cache.nbytes,
            })
        self._mem_pool: Optional[str] = None
        if _memory.enabled():
            self._mem_pool = f"decode_kv.{name}"
            _memory.pool(self._mem_pool, "device",
                         self._mem_pool_snapshot,
                         audit=self._mem_pool_audit)
            _memory.maybe_start_sentinel()
        _debug_server.register_decodez(name, self.decodez)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"decode-sched-{name}")
        self._thread.start()

    # -- admission ---------------------------------------------------------
    def max_context(self) -> int:
        return min(self.model.config.max_seq_len,
                   self.cache.max_context(self.max_blocks_per_seq))

    def submit(self, prompt, sampling: Optional[SamplingParams] = None,
               tenant: Optional[str] = None, sink=None) -> DecodeHandle:
        """Enqueue one generation.  ``tenant`` is an optional
        client-supplied id for per-tenant usage metering
        (``FLAGS_tenant_accounting``; ignored when off).  ``sink`` is the
        decode server's: where this thread writes the stream's token
        frames itself (module doc, "Token fan-out"; ``.io`` a native
        connection, ``.head`` a frame's bytes before the token's four);
        in-process readers give none.  Raises
        :class:`RequestTooLong` (prompt off the prefill ladder or
        prompt+budget past the context bound) or :class:`Overloaded`
        (queue bound) — both typed, never queued."""
        sampling = sampling or SamplingParams()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        limit = self.max_context()
        if prompt.size > self.prefill_ladder.max:
            self.stats.shed.inc()
            raise RequestTooLong(self.name, "prompt", prompt.size,
                                 self.prefill_ladder.max)
        if prompt.size + sampling.max_new_tokens > limit:
            self.stats.shed.inc()
            raise RequestTooLong(
                self.name, "prompt+max_new_tokens",
                prompt.size + sampling.max_new_tokens, limit)
        need = blocks_for(prompt.size + sampling.max_new_tokens,
                          self.cache.block_tokens)
        if need > self.cache.num_blocks - 1:
            # could never be admitted even with the pool idle — typed
            # rejection now, not a head-of-line livelock later
            self.stats.shed.inc()
            raise RequestTooLong(
                self.name, "blocks",
                need * self.cache.block_tokens,
                (self.cache.num_blocks - 1) * self.cache.block_tokens)
        req = DecodeRequest(next(self._rid), prompt, sampling,
                            tenant=tenant)
        req.handle._sink, req.handle._push_lock = sink, self._push_lock
        if _tenant.enabled():
            _tenant.account(tenant, requests=1)
        with self._lock:
            if self._closed:
                raise RuntimeError(f"decode engine {self.name!r} is closed")
            if len(self._pending) >= self.max_queue:
                self.stats.shed.inc()
                raise Overloaded(self.name, len(self._pending),
                                 self.max_queue)
            self._pending.append(req)
            self.stats.queue.set(len(self._pending))
            self._lock.notify_all()
        return req.handle

    def generate(self, prompt, timeout: Optional[float] = 120.0,
                 **sampling_kw) -> dict:
        """Blocking convenience over :meth:`submit`."""
        return self.submit(
            prompt, SamplingParams(**sampling_kw)).result(timeout=timeout)

    # -- scheduler loop ----------------------------------------------------
    def _loop(self) -> None:
        while True:
            with self._lock:
                if not self._closed and not self._pending and \
                        not any(self._slots):
                    with _trace.span("decode::wait_work"):
                        while not self._closed and not self._pending \
                                and not any(self._slots):
                            self._lock.wait()
                if self._closed:
                    pending = self._pending
                    self._pending = []
                    break_slots = [s for s in self._slots if s is not None]
                    break
            with _trace.span("decode::admit") as sp:
                with self._lock:
                    waiting = len(self._pending)
                    admit = self._admissible_locked()
                sp.annotate(admitted=len(admit), pending=waiting)
            for req in admit:
                try:
                    self._prefill(req)
                except Exception as e:   # noqa: BLE001 — fail ONE stream
                    _memory.oom_forensics(e, "decode_prefill")
                    self._release(req, None, error=e)
            if any(s is not None for s in self._slots):
                try:
                    self._decode_step()
                except Exception as e:   # noqa: BLE001
                    # no step is in flight: what was computed goes out
                    # before a stream is requeued or failed
                    self._flush_fanout()
                    if not self._recover_oom(e):
                        self._fail_all(e)
        self._flush_fanout()
        for req in pending:
            req.handle._fail(RuntimeError("decode engine closed"))
        for slot in break_slots:
            slot.req.handle._fail(RuntimeError("decode engine closed"))

    def _admissible_locked(self) -> List[DecodeRequest]:
        """Pop every pending request that has a free slot AND a full
        block reservation right now (called under the lock)."""
        out = []
        # cancelled-before-admission requests drop from the queue head
        # (a vanished client must not hold a queue slot); they never
        # joined, so they count neither join nor leave
        while self._pending and self._pending[0].handle.cancelled:
            dropped = self._pending.pop(0)
            if dropped.tl is not None:
                self.stats.latency().cancelled.inc()
            self._tell_finish(dropped.handle, "cancelled")
        bs = self.cache.block_tokens
        for i, slot in enumerate(self._slots):
            if slot is not None or not self._pending:
                continue
            req = self._pending[0]
            resume = req.resume_tokens is not None
            if resume and len(req.resume_tokens) > 1:
                # re-prefill target: prompt + generated[:-1]; the LAST
                # generated token's K/V is written by the next decode
                # step (exactly the post-prefill slot contract)
                seq = np.concatenate(
                    [req.prompt,
                     np.asarray(req.resume_tokens[:-1], np.int32)])
            else:
                seq = req.prompt
            L = int(seq.size)
            if self._overcommit_on:
                # lazy reservation: enough for the resident sequence
                # plus the next write position; the decode step grows
                # one block per boundary crossing (or preempts)
                need = blocks_for(L + 1, bs)
                if self._mem_pool is not None and \
                        not self._admit_headroom_ok(need):
                    break   # measured bytes say no room: FIFO head
            else:           # waits for a release, like an alloc miss
                need = blocks_for(
                    req.prompt.size + req.sampling.max_new_tokens, bs)
            acquired: List[int] = []
            start = 0
            if self.prefix is not None:
                # cap one block short of the sequence: prefill must
                # compute >= 1 real position (the stream's next logits)
                cap = min((L - 1) // bs, need)
                if cap > 0:
                    c0 = self.prefix.collisions
                    hits = self.prefix.match(seq, cap)
                    self._pstats.prefix_lookups.inc(cap)
                    dc = self.prefix.collisions - c0
                    if dc:
                        self._pstats.prefix_collisions.inc(dc)
                    # acquire BEFORE the fresh alloc: a referenced hit
                    # cannot be stolen by the LRU reclaim that alloc
                    # may trigger under pressure
                    acquired = [self.prefix.acquire(k) for k, _ in hits]
                    start = len(acquired) * bs
            blocks = self._alloc_blocks(need - len(acquired))
            if blocks is None:
                for b in acquired:       # re-park the hits; FIFO head
                    self.cache.allocator.decref(b)   # waits for blocks
                break
            if self._mem_pool is not None and blocks:
                _memory.note_event("alloc", self._mem_pool,
                                   len(blocks) * self._block_bytes,
                                   rid=req.rid)
            blocks = acquired + blocks
            if _tenant.enabled():
                # resident KV attribution: the stream now holds a ref
                # on every one of its blocks (prefix hits included);
                # the matching negative delta files at retire/preempt
                _tenant.account(req.tenant, resident_kv_bytes=(
                    len(blocks) * self._block_bytes))
            if start:
                self._pstats.prefix_hits.inc(len(acquired))
                self._pstats.saved_prefill_tokens.inc(start)
            self._pending.pop(0)
            # the slot is claimed NOW (table row filled) so a later
            # admission in the same sweep can't take it
            row = self._tables[i]
            row[:] = 0
            row[:len(blocks)] = blocks
            self._slots[i] = _Slot(req, blocks, L,
                                   first_token=-1,   # token set by prefill
                                   cached_tokens=start, seq=seq)
            if req.tl is not None and not resume:
                # queue wait ends at slot claim
                req.tl.stamp("queue", t=time.perf_counter())
            if not resume:
                self.stats.joins.inc()   # every join has a matching
            out.append(req)              # leave through _retire
        self.stats.queue.set(len(self._pending))
        self.stats.blocks_free.set(self.cache.allocator.free_blocks)
        self.stats.active.set(sum(s is not None for s in self._slots))
        self._update_pool_gauges()
        return out

    def _alloc_blocks(self, n: int) -> Optional[List[int]]:
        """Allocator alloc with prefix-cache backpressure: a miss
        reclaims parked (zero-ref cached) blocks LRU-first and retries
        — a cached block is only ever a loan from the free pool."""
        got = self.cache.allocator.alloc(n)
        if got is None and self.prefix is not None:
            freed = self.prefix.reclaim(
                n - self.cache.allocator.free_blocks)
            if freed:
                self._pstats.prefix_evictions.inc(freed)
                if self._mem_pool is not None:
                    _memory.note_event("reclaim", self._mem_pool,
                                       freed * self._block_bytes)
                got = self.cache.allocator.alloc(n)
        return got

    def _slot_of(self, req: DecodeRequest):
        for i, s in enumerate(self._slots):
            if s is not None and s.req is req:
                return i, s
        raise KeyError(f"request {req.rid} has no slot")

    # -- dispatches --------------------------------------------------------
    def _prefill(self, req: DecodeRequest) -> None:
        """Make positions ``[start, L)`` of the slot's sequence resident
        and sample what follows.  A fresh prompt is ``start == 0`` on the
        prefill ladder; behind prefix hits (``start > 0``) only the
        suffix dispatches (:meth:`TransformerLM.prefill_suffix`); a
        preemption resume re-prefills ``prompt + generated[:-1]`` on the
        wider resume ladder and DISCARDS the sampled token, restoring
        the slot to its pre-eviction state — the next decode step
        re-samples token index ``n_generated``, which the positional
        counter-hash makes identical to the token the stream would have
        produced uninterrupted."""
        t0 = time.perf_counter()
        i, slot = self._slot_of(req)
        if req.handle.cancelled:   # client vanished between admit and here
            self._retire(i, slot, "cancelled")
            return
        queue_ms = (t0 - req.t_enq) * 1e3
        self.stats.queue_ms.observe(queue_ms)
        start = slot.cached_tokens
        fresh = start == 0 and req.resume_tokens is None
        ladder = self.prefill_ladder if fresh else self._resume_ladder
        bucket = ladder.snap(slot.seq.size - start)
        with _trace.cpu_span("decode::prefill", rid=req.rid, bucket=bucket,
                             prompt=int(slot.seq.size),
                             queue_ms=queue_ms) as sp:
            if not fresh:
                sp.annotate(start=start)
            self._prefill_traced(i, slot, req, t0, bucket)

    def _prefill_traced(self, i: int, slot: _Slot, req: DecodeRequest,
                        t0: float, bucket: int) -> None:
        seq, start, resume = slot.seq, slot.cached_tokens, req.resume_tokens
        L = int(seq.size)
        n = L - start             # the positions this dispatch computes
        if start == 0:
            entry, program = self.model.prefill, "prefill"
            where = [np.int32(L)] + ([np.int32(i)] if self._slot_state
                                     else [])
        else:
            entry, program = self.model.prefill_suffix, "prefill_sfx"
            where = [np.int32(start), np.int32(L)]

        def build():
            def fn(feed, state, const):
                return entry(const, state, *feed)
            return fn

        with _trace.span("decode::prefill.feed"):
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :n] = seq[start:]
            feed = [tokens,
                    *where,
                    self._tables[i].copy(),
                    np.uint32(req.sampling.seed & 0xFFFFFFFF),
                    np.float32(req.sampling.temperature),
                    np.int32(req.sampling.top_k)]
            _debug_server.note_activity("decode")
            # chaos hook: `delay:decode_prefill` sleeps here, inside the
            # prefill phase / TTFT window (the SLO-watchdog test's lever)
            _faults.event("decode_prefill")
        (tok, logits, *extra), new_state = self._exe.run_callable(
            f"decode/{self.name}/{program}/{bucket}", build, feed,
            state=self.cache.state(), const=self._plist)
        self.cache.update(new_state)
        with _trace.cpu_span("decode::prefill.wait"):
            first = int(np.asarray(tok))
            logits_np = np.asarray(logits) if self.capture_logits else None
            self._observer.prefill(extra, n, bucket)
        with _trace.span("decode::prefill.emit"):
            slot.t_last = time.perf_counter()
            self.stats.prefills.inc()
            if not (req.sampling.temperature > 0.0):
                self.stats.greedy_prefills.inc()
            prefill_ms = (slot.t_last - t0) * 1e3
            self.stats.prefill_ms.observe(prefill_ms)
            if _capacity.enabled():
                # the engine thread is serial: prefill wall IS busy time
                self.stats.capacity_tracker().note(
                    "prefill", prefill_ms, bucket=bucket, work=1)
            if _tenant.enabled():
                # a prefill serves exactly one request: its whole wall is
                # that tenant's device time
                _tenant.account(req.tenant, prefill_tokens=n,
                                device_ms=prefill_ms)
            if req.tl is not None and resume is None:
                req.tl.stamp("prefill", t=slot.t_last)
                lat = self.stats.latency()
                lat.ttft_ms.observe((slot.t_last - req.t_enq) * 1e3)
                lat.prefill_tokens.inc(n)
                lat.pad_prefill_tokens.inc(bucket - n)
            self._register_prefix(slot, seq)
            if resume is not None:
                # restore the evicted stream's exact slot state; the
                # freshly sampled token is a DISCARD (the client already
                # has its successor, resume[-1])
                slot.pos_next = L
                slot.n_generated = len(resume)
                slot.last_token = int(resume[-1])
                req.resume_tokens = None
                self._pstats.preempt_resumes.inc()
                self._pstats.reprefill_tokens.inc(n)
                return
            slot.last_token = first
            self.stats.tokens.inc()
            req.handle._book(first, logits_np)
            # at once (this is the TTFT), as a step's: pushed or queued
            for handle, tok, _ in self._push_tokens(
                    [(req.handle, first, None)]):
                handle._emit(tok)
            self._maybe_finish(i, slot, first)

    def _register_prefix(self, slot: _Slot, seq: np.ndarray) -> None:
        """Advertise the slot's freshly prefilled FULL blocks in the
        prefix cache (content is immutable from here: the stream only
        ever appends past them).  Hit blocks [0, cached_tokens) are
        already registered."""
        if self.prefix is None:
            return
        bs = self.cache.block_tokens
        toks = [int(t) for t in seq]
        keys = self.prefix.chain_keys(toks)
        inserted = 0
        for bi in range(slot.cached_tokens // bs, len(seq) // bs):
            if self.prefix.insert(keys[bi], toks[:(bi + 1) * bs],
                                  slot.blocks[bi]):
                inserted += 1
        if inserted:
            self._pstats.prefix_inserts.inc(inserted)

    def _decode_step(self) -> None:
        # the two launch spans and their waits carry the thread's CPU time
        # while a profiler listens: step_off_cpu_ms reads queueing from it
        with _trace.cpu_span("decode::step") as sp:
            self._decode_step_traced(sp)

    def _decode_step_traced(self, sp) -> None:
        t0 = time.perf_counter()
        with _trace.span("decode::step.retire"):
            # retire cancelled slots FIRST: their blocks free before this
            # step's admission sweep ran, and they must not burn a batch
            # lane generating for a vanished reader
            for i, slot in enumerate(self._slots):
                if slot is not None and slot.req.handle.cancelled:
                    self._retire(i, slot, "cancelled")
            # overcommit growth + copy-on-write forks (may preempt)
            self._ensure_blocks()
        with _trace.span("decode::step.feed"):
            tokens = np.zeros((self.max_slots,), np.int32)
            positions = np.zeros((self.max_slots,), np.int32)
            seeds = np.zeros((self.max_slots,), np.uint32)
            steps = np.zeros((self.max_slots,), np.int32)
            temps = np.zeros((self.max_slots,), np.float32)
            topks = np.zeros((self.max_slots,), np.int32)
            tables = self._tables.copy()
            live = []
            for i, slot in enumerate(self._slots):
                if slot is None:
                    tables[i, :] = 0   # trash block: masked garbage
                    continue
                live.append(i)
                tokens[i] = slot.last_token
                positions[i] = slot.pos_next
                seeds[i] = slot.req.sampling.seed & 0xFFFFFFFF
                steps[i] = slot.n_generated   # this dispatch samples token
                temps[i] = slot.req.sampling.temperature  # index n_generated
                topks[i] = slot.req.sampling.top_k
        sp.annotate(live=len(live))
        if not live:
            self._flush_fanout()   # every stream was retired: no dispatch
            return
        model, impl = self.model, self._attn_impl

        def build():
            def fn(feed, state, const):
                return model.decode_step(const, state, *feed,
                                         attn_impl=impl)
            return fn

        _debug_server.note_activity("decode")
        # chaos hook: `delay:decode_step` sleeps inside the decode
        # phase (per-token latency); cheap active() guard when off.
        # `oom:decode_step` raises a realistic RESOURCE_EXHAUSTED here
        # — exactly where a real allocation failure would surface — so
        # the OOM-forensics + preempt-and-recover path is drillable
        # without real HBM pressure
        _faults.event("decode_step")
        _faults.oom_fault("decode_step")
        (toks, logits, *extra), new_state = self._exe.run_callable(
            f"decode/{self.name}/step", build,
            [tokens, positions, tables, seeds, steps, temps, topks],
            state=self.cache.state(), const=self._plist)
        self.cache.update(new_state)
        # the PREVIOUS step's tokens go out now: the wake-ups, and the
        # stream and reader threads they set off, run while the device
        # computes this step and this thread waits for it below with
        # the interpreter released
        self._flush_fanout(step_in_flight=True)
        with _trace.cpu_span("decode::step.wait"):
            toks_np = np.asarray(toks)
            logits_np = np.asarray(logits) if self.capture_logits else None
            self._observer.step(extra, positions[live] + 1)
        with _trace.span("decode::step.book"):
            self._book_step(live, toks_np, logits_np, t0,
                            greedy=not (temps > 0.0).any())
        if not any(s is not None for s in self._slots):
            self._flush_fanout()   # the last stream left: no step follows

    def _book_step(self, live: List[int], toks_np: np.ndarray,
                   logits_np: Optional[np.ndarray], t0: float,
                   greedy: bool) -> None:
        """Book one step's tokens at the read: counters, the per-slot
        state, ``handle._book``, retirement (slot and blocks free for
        the admission sweep that follows).  Waking the streams is left
        on ``self._fanout`` for :meth:`_flush_fanout`."""
        now = time.perf_counter()
        self._fanout_t_read = now
        self.stats.steps.inc()
        if greedy:     # the predicate _sample's cond took on the device
            self.stats.greedy_steps.inc()
        step_ms = (now - t0) * 1e3
        self.stats.step_ms.observe(step_ms)
        if _capacity.enabled():
            self.stats.capacity_tracker().note(
                "decode", step_ms, work=len(live))
        if _tenant.enabled():
            # the fixed-width step's wall splits evenly over the LIVE
            # slots (pad lanes belong to nobody), so per-tenant
            # device-ms sums to the measured step wall
            share = step_ms / len(live)
            for i in live:
                _tenant.account(self._slots[i].req.tenant,
                                decode_tokens=1, device_ms=share)
        lat = self.stats.latency() if _phase.enabled() else None
        if lat is not None:
            lat.live_slot_steps.inc(len(live))
            lat.pad_slot_steps.inc(self.max_slots - len(live))
        for i in live:
            slot = self._slots[i]
            tok = int(toks_np[i])
            slot.pos_next += 1
            slot.n_generated += 1
            slot.last_token = tok
            self.stats.tokens.inc()
            self.stats.token_ms.observe((now - slot.t_last) * 1e3)
            if lat is not None:
                lat.tbt_ms.observe((now - slot.t_last) * 1e3)
            slot.t_last = now
            handle = slot.req.handle
            handle._book(
                tok, logits_np[i] if logits_np is not None else None)
            self._fanout.append((handle, tok, None))
            self._maybe_finish(i, slot, tok)

    def _flush_fanout(self, step_in_flight: bool = False) -> None:
        """Hand out what ``self._fanout`` holds, in order.  Behind a
        step's dispatch (``step_in_flight``) this costs the device
        nothing; everywhere else no step will follow soon enough, and
        the tokens go out before anything else is told to a handle."""
        if not self._fanout:
            return
        with _trace.span("decode::step.emit") as sp:
            queued = self._push_tokens(self._fanout)
            for handle, tok, reason in queued:
                if reason is None:
                    handle._emit(tok)
                else:
                    handle._finish(reason)
            sp.annotate(pushed=len(self._fanout) - len(queued))
            self._fanout.clear()
        if step_in_flight:
            self.stats.fanout_delay_ms.observe(
                (time.perf_counter() - self._fanout_t_read) * 1e3)
        else:
            self.stats.fanout_immediate.inc()
            with self._lock:
                self._lock.notify_all()   # drain() waits for the hand-out

    def _push_tokens(self, entries: List[tuple]) -> List[tuple]:
        """Write the token frames of the ``entries`` whose handle has a sink
        — every one in ONE foreign call that never blocks, a template and
        four bytes a token — and return the entries left for the queue
        path, in their order (a stream's FIN is among them, so it still
        reads token … token, FIN).  The call does no I/O: a native thread
        writes the frames behind it, so a verdict is on a stream's EARLIER
        frames.  A sink that would not take one whole has the rest — and
        this frame behind it — kept by the transport, and the stream is
        moved to the queue path for good; a dead peer cancels the handle,
        so its slot and blocks go at the next step."""
        if not any(r is None and h._sink is not None for h, _, r in entries):
            return entries          # in-process readers: no lock, no call
        with self._push_lock:
            sunk, queued = [], []
            for e in entries:
                (sunk if e[2] is None and e[0]._sink is not None
                 else queued).append(e)
            if not sunk:            # taken off their sinks a moment ago
                return queued
            verdicts = _transport.push_frames(
                [h._sink.io for h, _, _ in sunk],
                [h._sink.head + t.to_bytes(4, "little", signed=True)
                 for h, t, _ in sunk])
            for (handle, _, _), rc in zip(sunk, verdicts):
                if rc == _transport.PUSH_DEAD:
                    handle._sink = None
                    handle.cancel()
                    continue
                handle._n_pushed += 1
                if rc == _transport.PUSH_WOULD_BLOCK:
                    handle._unsink()
                    self.stats.push_fallbacks.inc()
            self.stats.pushed_frames.inc(
                len(verdicts) - verdicts.count(_transport.PUSH_DEAD))
        return queued

    def _tell_finish(self, handle: DecodeHandle, reason: str) -> None:
        """FIN, never ahead of a token: behind whatever is pending."""
        if self._fanout:
            self._fanout.append((handle, None, reason))
        else:
            handle._finish(reason)

    # -- refcounted block lifecycle (prefix cache / overcommit) ------------
    def _ensure_blocks(self) -> None:
        """Make every live slot's write-target block PRESENT (overcommit
        growth: one block per boundary crossing) and PRIVATE (fork a
        block that is shared or advertised by the prefix cache before
        writing into it).  Runs before each step dispatch; allocation
        failure preempts the newest stream and retries — bounded by the
        live-slot count, and the oldest stream is never evicted, so the
        engine always makes forward progress."""
        bs = self.cache.block_tokens
        alloc = self.cache.allocator
        for i in range(self.max_slots):
            slot = self._slots[i]
            if slot is None:
                continue
            j = slot.pos_next // bs
            while j >= len(slot.blocks):
                got = self._alloc_blocks(1)
                if got is not None:
                    with self._lock:
                        slot.blocks.append(got[0])
                        self._tables[i, len(slot.blocks) - 1] = got[0]
                    if self._mem_pool is not None:
                        _memory.note_event("alloc", self._mem_pool,
                                           self._block_bytes,
                                           rid=slot.req.rid, grow=True)
                    if _tenant.enabled():
                        _tenant.account(slot.req.tenant,
                                        resident_kv_bytes=self._block_bytes)
                    break
                self._preempt_newest()
                if self._slots[i] is None:   # preempted itself
                    break
            slot = self._slots[i]
            if slot is None or j >= len(slot.blocks):
                continue
            b = slot.blocks[j]
            if alloc.refcount(b) > 1 or (self.prefix is not None
                                         and self.prefix.holds(b)):
                nb: Optional[int] = None
                while nb is None:
                    got = self._alloc_blocks(1)
                    if got is not None:
                        nb = got[0]
                        break
                    self._preempt_newest()
                    if self._slots[i] is None:
                        break
                if self._slots[i] is None or nb is None:
                    continue
                self._copy_block(b, nb)
                with self._lock:
                    slot.blocks[j] = nb
                    self._tables[i, j] = nb
                alloc.decref(b)
                self._pstats.cow_forks.inc()
                if self._mem_pool is not None:
                    # net-zero for the tenant (block swap), but the
                    # timeline names the fork
                    _memory.note_event("alloc", self._mem_pool,
                                       self._block_bytes,
                                       rid=slot.req.rid, cow=True)
        self._update_pool_gauges()

    def _preempt_newest(self) -> None:
        """Evict the NEWEST (highest rid) live stream: free its blocks,
        keep its generated tokens host-side on the handle, and requeue
        it head-of-line for re-prefill.  Newest-victim keeps the oldest
        stream running to completion — freed blocks then admit the FIFO
        head (the preempted request), the no-livelock argument."""
        v = None
        for j, s in enumerate(self._slots):
            if s is not None and (v is None or
                                  s.req.rid > self._slots[v].req.rid):
                v = j
        if v is None:
            return
        slot = self._slots[v]
        req = slot.req
        # chaos hook: `kill_after:decode_preempt` dies HERE, mid-
        # eviction — the replica vanishes with the pool half-mutated;
        # the supervisor-respawned replica must come back with a clean
        # pool invariant (the chaos_lite pin)
        _faults.event("decode_preempt")
        parked_before = self._parked()
        with self._lock:
            self._slots[v] = None
            self.cache.allocator.release(slot.blocks)
            self._tables[v, :] = 0
            req.resume_tokens = list(req.handle._tokens)
            self._pending.insert(0, req)
            self.stats.queue.set(len(self._pending))
            self.stats.active.set(
                sum(s is not None for s in self._slots))
            self.stats.blocks_free.set(self.cache.allocator.free_blocks)
            self._lock.notify_all()
        self._pstats.preempts.inc()
        self._note_blocks_released(len(slot.blocks), parked_before,
                                   "preempt", rid=req.rid)
        if _tenant.enabled():
            _tenant.account(req.tenant, resident_kv_bytes=-(
                len(slot.blocks) * self._block_bytes))

    def _copy_block(self, src: int, dst: int) -> None:
        """Device block-copy (the COW fork): one tiny jitted callable
        on the donated cache state — K/V never round-trip to host.
        Every cache pool (codes AND, when quantized, the per-block
        scale pools) keeps its block axis at dim 1, so one generic
        loop forks them all — a forked block carries its scales."""
        def build():
            def fn(feed, state, const):
                s, d = feed
                return [], [a.at[:, d].set(a[:, s]) for a in state]
            return fn

        with _trace.span("decode::copy_block", src=src, dst=dst):
            _, new_state = self._exe.run_callable(
                f"decode/{self.name}/blkcopy", build,
                [np.int32(src), np.int32(dst)],
                state=self.cache.state(), const=[])
            self.cache.update(new_state)

    def _parked(self) -> int:
        """Zero-ref blocks the prefix cache holds (none without one)."""
        return self.prefix.parked_blocks if self.prefix is not None else 0

    def _update_pool_gauges(self) -> None:
        alloc = self.cache.allocator
        parked = self._parked()
        self._pstats.blocks_referenced.set(alloc.referenced_blocks)
        self._pstats.blocks_cached.set(parked)
        self._pstats.blocks_leaked.set(alloc.leaked(parked))

    # -- retirement --------------------------------------------------------
    def _maybe_finish(self, i: int, slot: _Slot, token: int) -> None:
        s = slot.req.sampling
        if s.eos_id is not None and token == s.eos_id:
            self._retire(i, slot, "eos")
        elif slot.n_generated >= s.max_new_tokens:
            self._retire(i, slot, "length")

    def _retire(self, i: int, slot: _Slot, reason: str) -> None:
        """Free the slot + its cache blocks and finish the stream
        (eos / length / cancelled all leave through here)."""
        parked_before = self._parked()
        with self._lock:
            self._slots[i] = None
            self.cache.allocator.release(slot.blocks)
            self._tables[i, :] = 0
            self.stats.leaves.inc()
            self.stats.active.set(sum(x is not None for x in self._slots))
            self.stats.blocks_free.set(self.cache.allocator.free_blocks)
            self._update_pool_gauges()
            self._lock.notify_all()   # blocks freed: admit the queue head
        req = slot.req
        self._note_blocks_released(len(slot.blocks), parked_before,
                                   "free", rid=req.rid, reason=reason)
        if _capacity.enabled():
            self.stats.capacity_tracker().note_done(1)
        if _tenant.enabled():
            _tenant.account(
                req.tenant,
                cancellations=1 if reason == "cancelled" else 0,
                resident_kv_bytes=-(len(slot.blocks) * self._block_bytes),
                latency_ms=(time.perf_counter() - req.t_enq) * 1e3)
        if req.tl is not None:
            lat = self.stats.latency()
            if reason == "cancelled":
                lat.cancelled.inc()
                lat.cancelled_tokens.inc(slot.n_generated)
            # close the decode phase (zero-width for a stream finished
            # at its first token) and fold the timeline in: the three
            # phases sum to this request's end-to-end wall
            req.tl.stamp("decode", t=time.perf_counter())
            lat.phases.observe(req.tl, rid=req.rid, finish=reason,
                               tokens=slot.n_generated)
        if _audit.enabled() and reason != "cancelled":
            # per-stream token-id rolling hash into the audit ring,
            # keyed by the prompt's content hash so replicas that
            # decoded the SAME prompt are comparable fleet-wide.
            # Cancelled streams truncate at client timing, never at
            # model output — they are not comparable and stay out
            h = _audit.fnv1a64(b"")
            for t in req.handle._tokens:
                h = _audit.fold_token(h, t)
            _audit.note_stream(self.name, "",
                               _audit.request_hash(req.prompt), h)
        self._tell_finish(req.handle, reason)

    def _release(self, req: DecodeRequest, slot_idx, error) -> None:
        self._flush_fanout()
        parked_before = self._parked()
        released = 0
        with self._lock:
            for i, s in enumerate(self._slots):
                if s is not None and s.req is req:
                    self.cache.allocator.release(s.blocks)
                    released += len(s.blocks)
                    self._tables[i, :] = 0
                    self._slots[i] = None
                    self.stats.leaves.inc()
            self.stats.blocks_free.set(self.cache.allocator.free_blocks)
            self.stats.active.set(sum(x is not None for x in self._slots))
            self._update_pool_gauges()
        if released:
            self._note_blocks_released(released, parked_before, "free",
                                       rid=req.rid, reason="error")
            if _tenant.enabled():
                _tenant.account(req.tenant, resident_kv_bytes=-(
                    released * self._block_bytes))
        req.handle._fail(error)

    def _fail_all(self, error) -> None:
        parked_before = self._parked()
        released = 0
        with self._lock:
            slots, self._slots = (list(self._slots),
                                  [None] * self.max_slots)
            for s in slots:
                if s is not None:
                    self.cache.allocator.release(s.blocks)
                    released += len(s.blocks)
                    self.stats.leaves.inc()
            self._tables[:] = 0
            self._update_pool_gauges()
        if released:
            self._note_blocks_released(released, parked_before, "free",
                                       reason="fail_all")
        for s in slots:
            if s is not None:
                if _tenant.enabled():
                    _tenant.account(s.req.tenant, resident_kv_bytes=-(
                        len(s.blocks) * self._block_bytes))
                s.req.handle._fail(error)

    # -- memory anatomy ----------------------------------------------------
    def _mem_pool_snapshot(self) -> dict:
        """The MemoryLedger callback: this engine's KV pool bytes by
        state.  Lock-light (counter reads race admission by at most one
        block — the ledger is a snapshot, not a barrier)."""
        alloc = self.cache.allocator
        parked = self._parked()
        bb = self._block_bytes
        resident = sum(s is not None for s in self._slots)
        out = {"reserved": self.cache.nbytes,
               "used": alloc.referenced_blocks * bb,
               "parked": parked * bb,
               "block_bytes": bb,
               "blocks": {"size": self.cache.num_blocks,
                          "free": alloc.free_blocks,
                          "referenced": alloc.referenced_blocks,
                          "parked": parked},
               "resident_streams": resident}
        if resident:
            out["bytes_per_resident_stream"] = (
                alloc.referenced_blocks * bb // resident)
        return out

    def _mem_pool_audit(self) -> int:
        """The leak sentinel's refcount invariant: blocks neither free
        nor referenced nor parked nor the trash block — must be 0."""
        return self.cache.allocator.leaked(self._parked())

    def _note_blocks_released(self, n_blocks: int, parked_before: int,
                              kind: str, **extra) -> None:
        """File block-release events: blocks the prefix cache kept
        (refcount hit zero while advertised) park, the rest free."""
        if self._mem_pool is None or n_blocks <= 0:
            return
        parked_now = self._parked()
        d = min(max(parked_now - parked_before, 0), n_blocks)
        bb = self._block_bytes
        if d:
            _memory.note_event("park", self._mem_pool, d * bb)
        if n_blocks - d:
            _memory.note_event(kind, self._mem_pool,
                               (n_blocks - d) * bb, **extra)

    def _admit_headroom_ok(self, need_blocks: int) -> bool:
        """Overcommit admission's measured-bytes consult: admit only
        while the ledger's byte view of the pool agrees there is room
        (reserved − used; parked bytes are reclaimable so they count
        as headroom).  Attribution that disagrees with the allocator
        would be a bug, so this is a cross-check, not a second
        allocator — and it only exists when the ledger does."""
        p = _memory.get(self._mem_pool)
        if p is None:
            return True
        s = p.snapshot()
        return s["reserved"] - s["used"] >= need_blocks * self._block_bytes

    def _recover_oom(self, error) -> bool:
        """OOM forensics + recovery: a RESOURCE_EXHAUSTED escaping the
        step dispatch dumps a named post-mortem (full ledger, top
        holders, event tail) and — when the engine was built with an
        admission policy and a stream is live — sheds the NEWEST stream
        through the existing preemption path (counted), so the engine
        keeps serving instead of failing every slot.  Returns False (caller
        falls through to _fail_all) when unarmed or not an OOM."""
        if self._mem_pool is None or not _memory.is_oom(error):
            return False
        _memory.oom_forensics(error, "decode_step")
        if not (self.prefix is not None or self._overcommit_on) or \
                not any(s is not None for s in self._slots):
            return False
        self._preempt_newest()
        _obs_stats.scope(f"decode.{self.name}").counter(
            "oom_recovered", "RESOURCE_EXHAUSTED step dispatches "
            "survived by preempting the newest stream").inc()
        return True

    # -- observability -----------------------------------------------------
    def decodez(self) -> dict:
        """The /decodez payload: slots, cache, queue, recent rates."""
        with self._lock:
            slots = [
                None if s is None else {
                    "rid": s.req.rid, "prompt_len": int(s.req.prompt.size),
                    "generated": s.n_generated,
                    "context_len": int(s.pos_next),
                    "max_new_tokens": s.req.sampling.max_new_tokens}
                for s in self._slots]
            pending = len(self._pending)
        out = {
            "model": self.name,
            "config": self.model.config.to_dict(),
            "cache": self.cache.snapshot(),
            "max_blocks_per_seq": self.max_blocks_per_seq,
            "prefill_buckets": list(self.prefill_ladder.sizes),
            "max_slots": self.max_slots,
            "slots": slots,
            "queue_depth": pending,
            "tokens": self.stats.tokens.value,
            "steps": self.stats.steps.value,
            "greedy_steps": self.stats.greedy_steps.value,
            "prefills": self.stats.prefills.value,
            "greedy_prefills": self.stats.greedy_prefills.value,
            "joins": self.stats.joins.value,
            "leaves": self.stats.leaves.value,
            "shed": self.stats.shed.value,
            "fanout_immediate": self.stats.fanout_immediate.value,
            "pushed_frames": self.stats.pushed_frames.value,
            "push_fallbacks": self.stats.push_fallbacks.value,
        }
        out.update(self._observer.decodez())
        alloc = self.cache.allocator
        parked = self._parked()
        ps = self._pstats
        out["block_pool"] = {
            "size": self.cache.num_blocks,
            "free": alloc.free_blocks,
            "referenced": alloc.referenced_blocks,
            "cached": parked,
            "leaked": alloc.leaked(parked),
            "cow_forks": ps.cow_forks.value,
            "overcommit": self._overcommit_on,
        }
        if self.prefix is not None:
            lk, ht = ps.prefix_lookups.value, ps.prefix_hits.value
            out["prefix_cache"] = {
                "entries": len(self.prefix),
                "cached_blocks": parked,
                "lookups": lk,
                "hits": ht,
                "hit_rate": round(ht / max(lk, 1), 4),
                "saved_prefill_tokens": ps.saved_prefill_tokens.value,
                "inserts": ps.prefix_inserts.value,
                "evictions": ps.prefix_evictions.value,
                "collisions": self.prefix.collisions,
            }
        if self._overcommit_on:
            out["preemption"] = {
                "preempts": ps.preempts.value,
                "resumes": ps.preempt_resumes.value,
                "reprefill_tokens": ps.reprefill_tokens.value,
            }
        snap = self.stats.step_ms.snapshot()
        if snap.get("count"):
            out["step_p50_ms"] = self.stats.step_ms.percentile(0.50)
            out["step_p99_ms"] = self.stats.step_ms.percentile(0.99)
        tsnap = self.stats.token_ms.snapshot()
        if tsnap.get("count"):
            out["token_p50_ms"] = self.stats.token_ms.percentile(0.50)
            out["token_p99_ms"] = self.stats.token_ms.percentile(0.99)
        if self.stats.queue_ms.count:
            out["queue_p50_ms"] = self.stats.queue_ms.percentile(0.50)
            out["queue_p99_ms"] = self.stats.queue_ms.percentile(0.99)
        fan = self.stats.fanout_delay_ms
        if fan.count:
            out["fanout_delay_p50_ms"] = fan.percentile(0.50)
            out["fanout_delay_p99_ms"] = fan.percentile(0.99)
        lat = self.stats.lat
        if lat is not None:
            # the FLAGS_phase_attribution plane: TTFT/TBT tails,
            # goodput accounting, per-phase attribution
            if lat.ttft_ms.count:
                out["ttft_p50_ms"] = lat.ttft_ms.percentile(0.50)
                out["ttft_p99_ms"] = lat.ttft_ms.percentile(0.99)
            if lat.tbt_ms.count:
                out["tbt_p50_ms"] = lat.tbt_ms.percentile(0.50)
                out["tbt_p99_ms"] = lat.tbt_ms.percentile(0.99)
            out["goodput"] = lat.goodput()
            out["phases"] = lat.phases.snapshot()
        cap = self.stats.capacity()
        if cap is not None:
            out["capacity"] = cap.snapshot()
        return out

    # -- lifecycle ---------------------------------------------------------
    def drain(self, timeout: float = 60.0) -> bool:
        """Wait until every accepted request has finished."""
        deadline = time.monotonic() + timeout
        with self._lock:
            while self._pending or self._fanout or \
                    any(s is not None for s in self._slots):
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._lock.wait(timeout=min(left, 0.2))
        return True

    def close(self, timeout: float = 30.0) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._lock.notify_all()
        self._thread.join(timeout=timeout)
        if self._mem_pool is not None:
            _memory.unregister(self._mem_pool)
        _debug_server.unregister_decodez(self.name)
        _capacity.unregister(f"decode.{self.name}")
