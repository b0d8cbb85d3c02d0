"""Refcounted block lifecycle (ISSUE 18): refcounted allocator +
copy-on-write, the hash-keyed prefix cache (verify-on-hit collision
safety, LRU park/revive/reclaim), overcommit admission with preemption
+ token-exact re-prefill resume, beam forking on the shared pool, what
an engine with neither policy does and reports, the one prefill path's
four cases, the ``decode.<name>.blocks_leaked`` invariant, and the
chaos drill: a replica hard-killed mid-preemption while its siblings'
in-flight streams keep going and the supervisor's replacement comes back
with a clean pool."""
import os
import sys
import threading
import time

import numpy as np
import pytest

from dist_model import retry_flaky
from paddle_tpu import observability as obs
from paddle_tpu.decode import (BlockAllocator, DecodeClient, DecodeEngine,
                               LMConfig, PagedBeamDecoder, PrefixCache,
                               SamplingParams, TransformerLM)
from paddle_tpu.decode import server as dserver
from paddle_tpu.distributed import registry as reg_mod
from paddle_tpu.distributed import transport

HERE = os.path.dirname(os.path.abspath(__file__))
DECODE_RUNNER = os.path.join(HERE, "decode_replica_runner.py")

TINY = LMConfig(vocab=48, d_model=32, n_head=2, d_ffn=48, n_layer=2,
                max_seq_len=32)


def _engine(name, **kw):
    lm = TransformerLM(TINY)
    params = lm.init_params(seed=5)
    kw.setdefault("max_slots", 3)
    kw.setdefault("block_tokens", 4)
    kw.setdefault("prefill_buckets", (8, 16))
    return lm, params, DecodeEngine(lm, params, name=name, **kw)


def _wait(cond, timeout=20.0, poll=0.03, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(poll)
    pytest.fail(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------------
# allocator: refcounts + the flags-off free-list order pin
# ---------------------------------------------------------------------------

def test_allocator_refcount_shared_block_survives_decref():
    a = BlockAllocator(4)                  # blocks 1..3 usable
    (b,) = a.alloc(1)
    assert a.refcount(b) == 1
    a.incref(b)
    assert a.refcount(b) == 2
    free0 = a.free_blocks
    a.decref(b)                            # one sharer left: NOT freed
    assert a.refcount(b) == 1 and a.free_blocks == free0
    a.decref(b)                            # last reference: freed
    assert a.refcount(b) == 0 and a.free_blocks == free0 + 1
    assert a.leaked() == 0


def test_allocator_reference_errors_are_typed():
    a = BlockAllocator(4)
    with pytest.raises(ValueError):
        a.incref(2)                        # never allocated
    with pytest.raises(ValueError):
        a.decref(2)
    (b,) = a.alloc(1)
    a.decref(b)
    with pytest.raises(ValueError):
        a.decref(b)                        # double free
    with pytest.raises(ValueError):
        a.release([0])                     # the trash block is never owned
    assert a.alloc(99) is None             # never a partial grant
    assert a.leaked() == 0


def test_allocator_single_owner_free_list_order_is_the_legacy_order():
    """Flags-off pin: with every block at refcount 1 (the legacy
    reservation policy), release returns blocks in DROP order and
    alloc hands them back FIFO — byte-identical to the pre-refcount
    free list, so flags-off engines place blocks identically."""
    a = BlockAllocator(8)                  # free: [1..7]
    g1 = a.alloc(3)
    g2 = a.alloc(2)
    assert g1 == [1, 2, 3] and g2 == [4, 5]
    a.release(g1)                          # free: [6, 7, 1, 2, 3]
    assert a._free == [6, 7, 1, 2, 3]
    assert a.alloc(4) == [6, 7, 1, 2]
    a.release(g2)
    assert a._free == [3, 4, 5]
    assert a.leaked() == 0


# ---------------------------------------------------------------------------
# prefix cache: chain keys, verify-on-hit, LRU park/revive/reclaim
# ---------------------------------------------------------------------------

def test_prefix_cache_chain_keys_cover_block_boundaries():
    a = BlockAllocator(8)
    pc = PrefixCache(a, 4, model_key="m1")
    toks = list(range(10))
    keys = pc.chain_keys(toks)
    assert len(keys) == 2                  # 10 tokens -> 2 full blocks
    # the chain is rolling: key[1] depends on key[0]'s tokens
    assert pc.chain_keys(toks[:8]) == keys and keys[0] != keys[1]
    assert pc.chain_keys([9] + toks[1:])[0] != keys[0]
    # model identity is part of the key (same tokens, other model)
    assert PrefixCache(BlockAllocator(8), 4,
                       model_key="m2").chain_keys(toks) != keys


def test_prefix_cache_match_acquire_insert_roundtrip():
    a = BlockAllocator(8)
    pc = PrefixCache(a, 4, model_key="m")
    toks = list(range(8))
    k1, k2 = pc.chain_keys(toks)
    b1, b2 = a.alloc(2)
    assert pc.insert(k1, toks[:4], b1)
    assert pc.insert(k2, toks, b2)
    assert not pc.insert(k1, toks[:4], b1)         # first writer wins
    # live-entry hit: acquire increfs (the stream still owns it)
    hits = pc.match(toks + [40, 41], max_blocks=2)
    assert [k for k, _ in hits] == [k1, k2]
    got = [pc.acquire(k) for k, _ in hits]
    assert got == [b1, b2] and a.refcount(b1) == 2
    # a different prompt shares only the first block
    assert [b for _, b in pc.match(toks[:4] + [30, 31, 32, 33], 2)] == [b1]
    for b in (b1, b2):
        a.decref(b)
        a.decref(b)                                # zero-ref: parked, not freed
    assert pc.parked_blocks == 2 and a.free_blocks == 5
    assert a.leaked(pc.parked_blocks) == 0
    # revive from the LRU: parked -> referenced again
    (hit,) = pc.match(toks[:4], 1)
    assert pc.acquire(hit[0]) == b1 and a.refcount(b1) == 1
    assert pc.parked_blocks == 1


def test_prefix_cache_hash_collision_served_as_miss():
    """A 64-bit chain-hash collision must NEVER serve another prefix's
    K/V: the stored token ids are compared on every hash hit, and a
    mismatch counts a collision and stops the walk."""
    a = BlockAllocator(8)
    pc = PrefixCache(a, 4, model_key="m")
    toks = list(range(4))
    (key,) = pc.chain_keys(toks)
    (blk,) = a.alloc(1)
    assert pc.insert(key, toks, blk)
    # simulate the collision: same key, different covered tokens
    pc._entries[key] = (blk, tuple(range(100, 104)))
    assert pc.match(toks, 1) == []
    assert pc.collisions == 1
    assert pc.snapshot()["collisions"] == 1


def test_prefix_cache_lru_reclaims_oldest_and_repark_refreshes():
    a = BlockAllocator(8)
    pc = PrefixCache(a, 2, model_key="m")
    toks = [7, 8, 9, 10, 11, 12]
    k1, k2, k3 = pc.chain_keys(toks)
    b1, b2, b3 = a.alloc(3)
    assert pc.insert(k1, toks[:2], b1)
    assert pc.insert(k2, toks[:4], b2)
    assert pc.insert(k3, toks[:6], b3)
    for b in (b1, b2, b3):                 # park in age order 1, 2, 3
        a.decref(b)
    assert pc.parked_blocks == 3 and a.leaked(3) == 0
    # revive the middle block and re-park it: moves to the LRU tail
    assert pc.match(toks[:4], 2)[-1] == (k2, b2)
    assert pc.acquire(k2) == b2
    a.decref(b2)
    # reclaim evicts oldest-first: b1 then b3, never the re-parked b2
    assert pc.reclaim(2) == 2
    assert pc.parked_blocks == 1 and a._free[-2:] == [b1, b3]
    assert a.leaked(pc.parked_blocks) == 0
    # the chain property: with block 1 evicted, deeper entries are
    # unreachable even though k2 is still registered
    assert pc.match(toks, 3) == []


# ---------------------------------------------------------------------------
# engine: prefix hits (token parity + exact saved counter), leaks,
# the plain engine, the one prefill path
# ---------------------------------------------------------------------------

def test_engine_prefix_hit_parity_and_exact_saved_tokens():
    """The acceptance pin: a prompt whose block-aligned prefix is
    cached generates IDENTICAL tokens to a flags-off engine, and the
    saved-prefill accounting is exact (2 adopted blocks == 8 tokens)."""
    pA = np.arange(1, 9, dtype=np.int32)            # 8 = 2 full blocks
    pB = np.concatenate([pA, [9, 10]]).astype(np.int32)
    _, _, ref = _engine("tpfx_ref")
    try:
        refA = ref.generate(pA, max_new_tokens=4)
        refB = ref.generate(pB, max_new_tokens=4)
    finally:
        ref.close()
    _, _, eng = _engine("tpfx_hit", prefix_cache=True)
    try:
        outA = eng.generate(pA, max_new_tokens=4)
        outB = eng.generate(pB, max_new_tokens=4)
        assert outA["tokens"] == refA["tokens"]
        assert outB["tokens"] == refB["tokens"]
        ps = eng._pstats
        assert ps.prefix_hits.value == 2
        assert ps.saved_prefill_tokens.value == 8   # exactly 2 blocks
        assert ps.prefix_inserts.value == 2         # pA's full blocks
        assert eng.prefix.collisions == 0
        z = eng.decodez()
        assert z["block_pool"]["leaked"] == 0
        assert z["prefix_cache"]["hits"] == 2
        assert z["prefix_cache"]["lookups"] == 3    # cap 1 (pA) + 2 (pB)
        assert z["prefix_cache"]["saved_prefill_tokens"] == 8
        assert eng.cache.allocator.leaked(eng.prefix.parked_blocks) == 0
    finally:
        eng.close()


def test_engine_prefix_reclaim_under_pressure_and_no_leak():
    """Parked cached blocks are a loan: when a new admission can't be
    served from the free list, the LRU gives them back (counted as
    evictions) and the pool invariant holds through finish, cancel and
    reclaim paths."""
    pA = np.arange(1, 9, dtype=np.int32)
    pB = np.arange(20, 28, dtype=np.int32)          # disjoint content
    _, _, eng = _engine("tpfx_evict", prefix_cache=True, max_slots=2,
                        num_blocks=5)               # 4 usable blocks
    try:
        eng.generate(pA, max_new_tokens=4)          # parks 2 full blocks
        assert eng.prefix.parked_blocks == 2
        # pB needs 3 blocks; only 2 free -> reclaim 1 parked block
        eng.generate(pB, max_new_tokens=4)
        assert eng._pstats.prefix_evictions.value >= 1
        # cancel mid-stream releases the slot's blocks too
        h = eng.submit(np.arange(30, 36, dtype=np.int32),
                       SamplingParams(max_new_tokens=8))
        assert h.next_token(timeout=30) is not None
        h.cancel()
        _wait(lambda: eng.decodez()["slots"] == [None] * 2,
              msg="cancelled stream retired")
        parked = eng.prefix.parked_blocks
        assert eng.cache.allocator.leaked(parked) == 0
        assert eng._pstats.blocks_leaked.value == 0
        assert eng.decodez()["block_pool"]["leaked"] == 0
    finally:
        eng.close()


def test_a_plain_engine_reports_a_whole_pool():
    """Neither policy: the engine still keeps the pool's gauges and the
    ``block_pool`` card, and after a mixed join/leave load every usable
    block is free or referenced, none forked, none lost; the cards of
    the two policies stay tied to their own policy."""
    _, _, eng = _engine("tpfx_off")
    try:
        rng = np.random.RandomState(3)
        handles = [eng.submit(rng.randint(1, TINY.vocab, size=n),
                              SamplingParams(max_new_tokens=m))
                   for n, m in ((6, 3), (11, 9), (3, 5), (16, 2), (8, 7))]
        handles[2].cancel()
        for h in handles:
            assert h.result(timeout=120)["finish"] in ("length", "cancelled")
        assert eng.drain(timeout=60)
        assert eng.prefix is None
        z = eng.decodez()
        pool = z["block_pool"]
        assert pool["leaked"] == 0 and pool["cow_forks"] == 0
        assert pool["free"] + pool["referenced"] == pool["size"] - 1
        assert pool["cached"] == 0 and pool["overcommit"] is False
        assert "prefix_cache" not in z and "preemption" not in z
        gauges = obs.stats.default_registry().to_dict()
        assert gauges["decode.tpfx_off.blocks_leaked"] == 0
        assert gauges["decode.tpfx_off.blocks_cached"] == 0
        assert gauges["decode.tpfx_off.blocks_referenced"] == 0
    finally:
        eng.close()


def test_a_plain_engine_places_blocks_as_the_free_list_always_did(
        monkeypatch):
    """Neither policy: the blocks a request sequence is given, in order,
    are the ones the engine before the one-lifecycle change (PR 31) gave
    (recorded from it); no prompt is hashed, no block copied, no stream
    preempted."""
    from paddle_tpu.decode import cache as cache_mod
    called = []
    for name in ("chain_keys", "match", "insert"):
        monkeypatch.setattr(
            cache_mod.PrefixCache, name,
            lambda self, *a, _n=name, **k: called.append(_n))
    _, _, eng = _engine("tpfx_replay", num_blocks=14)   # 13 usable
    eng._copy_block = lambda *a: called.append("_copy_block")
    eng._preempt_newest = lambda: called.append("_preempt_newest")
    granted, alloc = [], eng.cache.allocator.alloc

    def recorded(n):
        got = alloc(n)
        granted.append(got)
        return got
    eng.cache.allocator.alloc = recorded
    rng = np.random.RandomState(11)

    def batch(shapes):
        # submitted under the engine's lock: ONE admission sweep sees
        # them all, so the order of grants is the order of submission
        with eng._lock:
            handles = [eng.submit(rng.randint(1, TINY.vocab, size=n),
                                  SamplingParams(max_new_tokens=m))
                       for n, m in shapes]
        for h in handles:
            assert h.result(timeout=120)["finish"] == "length"
        assert eng.drain(timeout=60)
    try:
        batch(((6, 3), (9, 10), (5, 6)))    # 3, 5 and 3 blocks; the first
        batch(((12, 4), (3, 2)))            # leaves first, the second last
        assert granted == REPLAYED_GRANTS
        assert called == []
        assert eng.cache.allocator._free == REPLAYED_FREE_LIST
    finally:
        eng.close()


# what the engine of PR 31 (commit 52aef04) granted for the sequence above
REPLAYED_GRANTS = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10, 11], [12, 13, 1, 2],
                   [3, 9]]
REPLAYED_FREE_LIST = [10, 11, 4, 5, 6, 7, 8, 3, 9, 12, 13, 1, 2]


def _filed_spans(monkeypatch):
    """Every ``trace.span`` and ``trace.cpu_span`` of the process, as (name,
    arguments), filed when it closes."""
    from paddle_tpu.observability import trace
    filed = []

    class Span:
        def __init__(self, name, args):
            self.name, self.args = name, dict(args)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            filed.append((self.name, self.args))

        def annotate(self, **args):
            self.args.update(args)

    for opener in ("span", "cpu_span"):
        monkeypatch.setattr(trace, opener, lambda name, **a: Span(name, a))
    return filed


def _preempt_once(eng, after_tokens):
    """Evict the one live stream, on the engine's own thread, before the
    first step that finds it with ``after_tokens`` tokens."""
    ensure, done = eng._ensure_blocks, []

    def ensure_after_one_eviction():
        live = [s for s in eng._slots if s is not None]
        if not done and live and live[0].n_generated >= after_tokens:
            done.append(live[0].req.rid)
            eng._preempt_newest()
        ensure()
    eng._ensure_blocks = ensure_after_one_eviction
    return done


@pytest.mark.parametrize("case,policies,starts", [
    ("fresh", {}, [None]),
    ("prefix_hit", {"prefix_cache": True}, [8]),
    ("resume", {"overcommit": True}, [None, 0]),
    ("resume_behind_hit", {"prefix_cache": True, "overcommit": True},
     [8, 8]),
])
def test_the_one_prefill_path_serves_every_case_token_for_token(
        case, policies, starts, monkeypatch):
    """Fresh prompt, suffix behind prefix hits, preemption resume from
    position 0 and resume behind prefix hits all go through
    ``DecodeEngine._prefill``: the stream's tokens are those of an
    uninterrupted plain engine, and every prefill files ONE
    ``decode::prefill`` span (children ``.feed`` / ``.wait`` / ``.emit``)
    whose ``start`` says where the dispatch began (absent: a fresh
    prompt)."""
    warm = np.arange(1, 9, dtype=np.int32)              # 2 full blocks
    prompt = np.concatenate([warm, [9, 10]]).astype(np.int32)
    _, _, ref = _engine(f"tone_ref_{case}")
    try:
        want = ref.generate(prompt, max_new_tokens=9)["tokens"]
    finally:
        ref.close()
    _, _, eng = _engine(f"tone_{case}", **policies)
    try:
        if "prefix_cache" in policies:
            eng.generate(warm, max_new_tokens=2)        # registers 2 blocks
        evicted = (_preempt_once(eng, after_tokens=4)
                   if "overcommit" in policies else None)
        before = eng.stats.prefills.value, eng.stats.tokens.value
        filed = _filed_spans(monkeypatch)
        handle = eng.submit(prompt, SamplingParams(max_new_tokens=9))
        assert list(handle) == want
        assert handle.result(timeout=60)["tokens"] == want
        assert eng.drain(timeout=60)
        monkeypatch.undo()
        if evicted is not None:
            assert evicted == [handle.rid]
            assert eng._pstats.preempt_resumes.value == 1
        prefills = [a for n, a in filed if n == "decode::prefill"]
        assert [a.get("start") for a in prefills] == starts
        for a in prefills:
            assert a["rid"] == handle.rid and a["queue_ms"] >= 0
            assert a["bucket"] >= a["prompt"] - a.get("start", 0) > 0
        # the first dispatch makes the prompt resident, a resume the
        # prompt and all but the last of the 4 tokens it had
        assert [a["prompt"] for a in prefills] == [10, 13][:len(starts)]
        names = [n for n, _ in filed if n.startswith("decode::prefill")]
        assert names == ["decode::prefill.feed", "decode::prefill.wait",
                         "decode::prefill.emit",
                         "decode::prefill"] * len(starts)
        # a resumed prefill counts a prefill and no token
        assert eng.stats.prefills.value - before[0] == len(starts)
        assert eng.stats.tokens.value - before[1] == len(want)
        assert eng.decodez()["block_pool"]["leaked"] == 0
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# overcommit: preemption + token-exact re-prefill resume
# ---------------------------------------------------------------------------

def test_overcommit_preempt_resume_is_loss_free():
    """Three greedy streams through a pool that can only hold two
    (4 blocks each at full length, 8 usable blocks) finish with tokens
    IDENTICAL to a fully reserved engine — preemption kept the
    generated tokens host-side and the re-prefill resumed the stream
    exactly where it stopped."""
    prompts = [np.arange(1 + 7 * i, 7 + 7 * i, dtype=np.int32)
               for i in range(3)]                   # 6 tokens each
    _, _, ref = _engine("toc_ref", prefill_buckets=(8,))
    try:
        want = [ref.generate(p, max_new_tokens=10)["tokens"]
                for p in prompts]
    finally:
        ref.close()
    _, _, eng = _engine("toc_small", prefill_buckets=(8,),
                        num_blocks=9, overcommit=True)
    preempt, pending_at_preempt = eng._preempt_newest, []

    def preempt_recorded():
        # (victim's tokens still to be handed out, victim's tokens booked)
        victim = max((s for s in eng._slots if s is not None),
                     key=lambda s: s.req.rid).req.handle
        pending_at_preempt.append(
            (sum(1 for handle, _, _ in eng._fanout if handle is victim),
             len(victim.tokens)))
        preempt()
    eng._preempt_newest = preempt_recorded
    try:
        handles = [eng.submit(p, SamplingParams(max_new_tokens=10))
                   for p in prompts]
        streamed = [list(h) for h in handles]
        got = [h.result(timeout=120) for h in handles]
        assert [g["tokens"] for g in got] == want
        assert streamed == want     # each token reached its reader once
        assert all(g["finish"] == "length" for g in got)
        # the eviction came between a step's read and its hand-out: the
        # victim's newest token was booked, not yet streamed, and is
        # part of what the re-prefill resumes from
        assert pending_at_preempt and all(
            n == 1 and booked >= 2 for n, booked in pending_at_preempt)
        ps = eng._pstats
        assert ps.preempts.value >= 1
        assert ps.preempt_resumes.value >= 1
        assert ps.reprefill_tokens.value >= 1
        assert eng.cache.allocator.leaked() == 0
        z = eng.decodez()
        assert z["block_pool"]["leaked"] == 0
        assert z["block_pool"]["overcommit"] is True
        assert z["preemption"]["preempts"] == ps.preempts.value
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# beam forking on the shared pool: COW bit-exactness
# ---------------------------------------------------------------------------

def test_beam_cow_forking_matches_eager_copy_bit_exact():
    lm = TransformerLM(TINY)
    params = lm.init_params(seed=5)
    prompt = np.array([3, 1, 4, 1, 5, 9, 2], dtype=np.int32)
    shared = PagedBeamDecoder(lm, params, beam_size=3,
                              end_id=TINY.vocab - 1, name="bx_cow",
                              block_tokens=4, share_prefix=True)
    eager = PagedBeamDecoder(lm, params, beam_size=3,
                             end_id=TINY.vocab - 1, name="bx_base",
                             block_tokens=4, share_prefix=False)
    try:
        rs = shared.decode(prompt, max_steps=6)
        re_ = eager.decode(prompt, max_steps=6)
        assert np.array_equal(rs.ids, re_.ids)
        assert np.allclose(rs.scores, re_.scores)
        # the point of COW: strictly fewer device block copies than
        # eager per-step private copies, forks only on divergent writes
        assert shared.cow_forks >= 1
        assert shared.block_copies < eager.block_copies
        assert shared.leaked() == 0 and eager.leaked() == 0
        # session reuse: a second decode starts from a clean pool
        rs2 = shared.decode(prompt, max_steps=6)
        assert np.array_equal(rs2.ids, rs.ids)
        assert shared.leaked() == 0
    finally:
        shared.close()
        eager.close()


# ---------------------------------------------------------------------------
# chaos: hard kill mid-preemption; siblings unaffected; the
# supervisor's replacement comes back with a clean pool
# ---------------------------------------------------------------------------

def _decode_eps(registry_ep):
    snap = reg_mod.fetch_snapshot(transport.RPCClient(0), registry_ep)
    out = {}
    for logical, lease in (snap.get("leases") or {}).items():
        parsed = dserver.parse_replica_key(logical)
        if parsed is not None and parsed[0] == "lm":
            out[parsed[1]] = lease["endpoint"]
    return out


@pytest.mark.chaos_lite
@retry_flaky()
def test_chaos_kill_mid_preemption_pool_recovers_siblings_unaffected():
    """The ISSUE-18 chaos drill: two overcommitted decode replicas;
    r1 is armed (``env_once``) to hard-die (``os._exit``) the first
    time its engine fires the ``decode_preempt`` fault site — mid
    eviction, the worst moment for pool bookkeeping.  Concurrent
    streams pinned to r0 must finish token-exact (its own preemptions
    resume loss-free), the supervisor must respawn r1 (clean, the
    fault arms first-spawn-only), and the replacement must serve
    correctly with a zero-leak block pool."""
    from paddle_tpu.distributed.supervisor import (LIVE, FleetSpec,
                                                   RoleSpec, Supervisor)
    PROMPT_A = np.array([1, 2, 3, 4, 5, 6], dtype=np.int32)
    PROMPT_B = np.array([7, 8, 9, 10, 11, 12], dtype=np.int32)
    # the truth: an uninterrupted engine with full reservations (greedy
    # decode is per-stream deterministic, so this is THE token stream)
    lm = TransformerLM(TINY)
    params = lm.init_params(seed=5)
    ref = DecodeEngine(lm, params, name="chaos_ref", max_slots=4,
                       block_tokens=4, prefill_buckets=(8,))
    try:
        refA = ref.generate(PROMPT_A, max_new_tokens=20)["tokens"]
        refB = ref.generate(PROMPT_B, max_new_tokens=20)["tokens"]
    finally:
        ref.close()

    keys = [dserver.replica_key("lm", f"r{i}") for i in range(2)]
    spec = FleetSpec(
        roles={"decode": RoleSpec(
            count=2, argv=[sys.executable, DECODE_RUNNER],
            env={"PADDLE_REGISTRY": "{registry}",
                 "REPLICA_ID": "r{index}",
                 "JAX_PLATFORMS": "cpu"},
            # only the FIRST spawn of worker 1 dies mid-preemption;
            # its replacement comes up clean (the chaos-suite idiom)
            env_once={1: {"FLAGS_fault_inject":
                          "kill_after:decode_preempt"}},
            logical=keys, health_role="DECODE", grace_s=10.0)},
        hysteresis=2, name="t_pfx")
    sup = Supervisor(spec, poll_s=0.1, registry_poll_s=0.25)
    sup.start()
    r0_out, r0_errs, r1_errs, r1_done = [], [], [], []
    try:
        _wait(lambda: sum(1 for w in sup.workers.values()
                          if w.state == LIVE) == 2,
              timeout=120, msg="2 decode replicas LIVE")
        _wait(lambda: len(_decode_eps(sup.registry_ep)) == 2,
              timeout=60, msg="both decode leases announced")
        eps = _decode_eps(sup.registry_ep)
        ep0, ep1 = eps["r0"], eps["r1"]

        def sibling(idx):
            c = DecodeClient(endpoints=[ep0])
            try:
                r0_out.append(
                    c.generate("lm", PROMPT_A, timeout=180,
                               max_new_tokens=20))
            except Exception as e:      # noqa: BLE001 — ANY error = a drop
                r0_errs.append(repr(e))

        def victim(idx):
            c = DecodeClient(endpoints=[ep1])
            try:
                r1_done.append(
                    c.generate("lm", PROMPT_B, timeout=180,
                               max_new_tokens=20))
            except Exception as e:      # noqa: BLE001 — expected: the kill
                r1_errs.append(repr(e))
        threads = [threading.Thread(target=sibling, args=(i,))
                   for i in range(2)]
        # 4 concurrent max_new=20 streams demand 4 x 7 = 28 blocks of
        # r1's 12-block pool: preemption (and so the kill) is certain
        threads += [threading.Thread(target=victim, args=(i,))
                    for i in range(4)]
        for t in threads:
            t.start()
        # the kill + respawn: r1 re-announces from a NEW endpoint
        _wait(lambda: _decode_eps(sup.registry_ep).get("r1")
              not in (None, ep1),
              timeout=180, msg="r1 killed and respawned")
        for t in threads:
            t.join(timeout=200)
        assert not any(t.is_alive() for t in threads)
        # siblings unaffected: every r0 stream finished, token-exact
        # (r0 preempts under its own overcommit too — loss-free)
        assert r0_errs == [], r0_errs
        assert [o["tokens"] for o in r0_out] == [refA, refA]
        # the kill severed r1's in-flight streams
        assert len(r1_errs) >= 1, (r1_errs, r1_done)

        new_ep = _decode_eps(sup.registry_ep)["r1"]
        c2 = DecodeClient(endpoints=[new_ep])

        def _status_pool():
            try:
                return c2.status(new_ep)["lm"]["block_pool"]
            except Exception:           # noqa: BLE001 — still booting
                return None
        _wait(lambda: _status_pool() is not None, timeout=60,
              msg="recovered r1 answers admin status")
        pool = _status_pool()
        assert pool["leaked"] == 0 and pool["overcommit"] is True
        # and the replacement actually serves, token-exact
        out = c2.generate("lm", PROMPT_B, timeout=180, max_new_tokens=20)
        assert out["tokens"] == refB
        assert _status_pool()["leaked"] == 0
        assert sup.workers["decode-1"].state == LIVE
    finally:
        sup.stop()
