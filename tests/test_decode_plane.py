"""Autoregressive decode plane (paddle_tpu/decode): paged KV cache,
token-level continuous batching, Pallas decode-attention kernel,
streaming DECODE transport, and the satellite serving-batcher
max_seq_len rejection.

The two acceptance pins live here: greedy decode through the paged
cache is argmax-token-identical (logits within fp tolerance) to the
full-sequence re-forward baseline on the tiny transformer INCLUDING
requests that join/leave mid-batch, and a warmed engine under a mixed
join/leave load of varying prompt/output lengths triggers zero XLA
recompiles (executor compile counters pinned)."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.decode import (BlockAllocator, DecodeClient, DecodeEngine,
                               DecodeServer, LMConfig, Overloaded,
                               PagedKVCache, RequestTooLong,
                               SamplingParams, TransformerLM, load_lm,
                               save_lm)
from paddle_tpu.kernels import attention as AK

from paged_walks import WALKS, dense_reference, walk_case

TINY = LMConfig(vocab=48, d_model=32, n_head=2, d_ffn=48, n_layer=2,
                max_seq_len=32)


def _engine(name, **kw):
    lm = TransformerLM(TINY)
    params = lm.init_params(seed=5)
    kw.setdefault("max_slots", 3)
    kw.setdefault("block_tokens", 4)
    kw.setdefault("prefill_buckets", (8, 16))
    return lm, params, DecodeEngine(lm, params, name=name, **kw)


# ---------------------------------------------------------------------------
# cache / allocator
# ---------------------------------------------------------------------------

def test_block_allocator_reserves_trash_and_refuses_partial():
    a = BlockAllocator(6)                 # blocks 1..5 usable
    assert a.free_blocks == 5
    got = a.alloc(3)
    assert got is not None and 0 not in got
    assert a.alloc(3) is None             # only 2 left: no partial grant
    assert a.free_blocks == 2
    a.release(got)
    assert a.free_blocks == 5
    with pytest.raises(ValueError):
        a.release([0])                    # the trash block is never owned


def test_paged_cache_state_roundtrip():
    c = PagedKVCache(num_layers=2, num_heads=2, head_dim=8,
                     num_blocks=5, block_tokens=4)
    k, v = c.state()
    # [L, NB, bs, H*Dh]: heads merged into the minor (lane) axis
    assert k.shape == (2, 5, 4, 16) and v.shape == k.shape
    c.update([k + 1, v])
    assert float(jnp.max(c.k)) == 1.0
    snap = c.snapshot()
    assert snap["free_blocks"] == 4 and snap["block_tokens"] == 4


# ---------------------------------------------------------------------------
# decode-attention kernel
# ---------------------------------------------------------------------------

def _rand_paged(rng, S=3, H=2, D=16, bs=4, MB=4, N=8, L=3):
    """A whole pool [L, N, bs, H*D] (every layer different), three
    slots: slot 0 holds ONE token and a block table full of trash
    block 0 (an inactive decode slot), slot 2 a full context."""
    kc = jnp.asarray(rng.randn(L, N, bs, H * D).astype("float32"))
    vc = jnp.asarray(rng.randn(L, N, bs, H * D).astype("float32"))
    q = jnp.asarray(rng.randn(S, H, D).astype("float32"))
    bt = rng.randint(0, N, (S, MB)).astype("int32")
    bt[0, :] = 0
    cl = jnp.asarray(np.array([1, 7, 16], "int32"))
    return q, kc, vc, jnp.asarray(bt), cl


# (H, D): every head in one odd-sized lane chunk (off-TPU sizes), two
# heads per 128-lane chunk over two chunks (the chip's GPT-1 geometry,
# 12 x 64, in small), one head per chunk
_GEOMETRIES = [(2, 16), (4, 64), (2, 128)]


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("H,D", _GEOMETRIES)
def test_decode_attention_pallas_matches_xla_and_dense(H, D, layer):
    """The kernel is handed the WHOLE pool and a static layer: for
    every layer of a 3-layer pool it must read that layer's blocks and
    no other's."""
    rng = np.random.RandomState(0)
    q, kc, vc, bt, cl = _rand_paged(rng, H=H, D=D)
    ox = AK.paged_attention_xla(q, kc, vc, bt, cl, layer)
    op = AK.decode_attention(q, kc, vc, bt, cl, layer, impl="pallas")
    assert ox.shape == op.shape == q.shape
    assert float(jnp.max(jnp.abs(ox - op))) < 1e-5
    # dense reference for the full-context slot and the one-token slot
    for slot in (2, 0):
        n = int(cl[slot])
        k_full = np.asarray(kc[layer, bt[slot]]).reshape(-1, H, D)[:n]
        v_full = np.asarray(vc[layer, bt[slot]]).reshape(-1, H, D)[:n]
        s = np.einsum("hd,thd->ht", np.asarray(q[slot]), k_full) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("ht,thd->hd", p, v_full)
        assert np.abs(ref - np.asarray(ox[slot])).max() < 1e-5
    # another layer's blocks give another answer: the layer index is read
    other = AK.decode_attention(q, kc, vc, bt, cl, (layer + 1) % 3,
                                impl="pallas")
    assert float(jnp.max(jnp.abs(other - op))) > 1e-3


# blocks a slot: not a multiple of the chunk (a ragged last chunk), a
# multiple of it, fewer than one chunk (the chunk is clipped to the table)
@pytest.mark.parametrize("MB", [20, 16, 5])
@pytest.mark.parametrize("walk", list(WALKS))
def test_decode_attention_walks_live_blocks_in_chunks(walk, MB):
    """Every layer of a 3-layer pool, against the gather path and the
    dense reference of EVERY slot."""
    bs = 4
    chunk = min(AK._DECODE_CHUNK_BLOCKS, MB)
    contexts = WALKS[walk](chunk * bs, MB * bs)
    q, kc, vc, bt, cl = walk_case(np.random.RandomState(3), contexts, MB)
    for layer in range(3):
        op = AK.decode_attention(q, kc, vc, bt, cl, layer, impl="pallas")
        ox = AK.paged_attention_xla(q, kc, vc, bt, cl, layer)
        assert float(jnp.max(jnp.abs(ox - op))) < 1e-5
        ref = dense_reference(q, kc, vc, bt, cl, layer)
        assert np.abs(ref - np.asarray(op)).max() < 1e-5


@pytest.mark.parametrize("MB", [20, 5])
def test_decode_attention_reads_no_block_past_a_context(MB):
    """Table entries past a slot's context name a block of NaN: a block
    that is copied or computed though dead shows in the output (the
    gather path reads them all: ``0 x NaN`` — it is no reference here)."""
    bs = 4
    chunk = min(AK._DECODE_CHUNK_BLOCKS, MB)
    contexts = WALKS["all_of_these_in_adjacent_slots"](chunk * bs, MB * bs)
    q, kc, vc, bt, cl = walk_case(np.random.RandomState(4), contexts, MB,
                                   poison=True)
    op = np.asarray(AK.decode_attention(q, kc, vc, bt, cl, 1, impl="pallas"))
    assert np.isfinite(op).all()
    assert np.abs(dense_reference(q, kc, vc, bt, cl, 1) - op).max() < 1e-5


def test_decode_attention_kernel_fault_is_an_error(monkeypatch):
    """No fallback between the two implementations: a kernel that
    cannot be built fails the call (on a TPU the compiler's refusal
    arrives at jit lowering, where no trace-time latch could see it),
    and only an explicit impl="xla" takes the gather path."""
    rng = np.random.RandomState(1)
    q, kc, vc, bt, cl = _rand_paged(rng)

    def boom(*a, **kw):
        raise RuntimeError("injected kernel build fault")
    monkeypatch.setattr(AK, "_paged_attn_pallas", boom)
    with pytest.raises(RuntimeError, match="injected kernel build fault"):
        AK.decode_attention(q, kc, vc, bt, cl, 1)
    out = AK.decode_attention(q, kc, vc, bt, cl, 1, impl="xla")
    ox = AK.paged_attention_xla(q, kc, vc, bt, cl, 1)
    assert float(jnp.max(jnp.abs(out - ox))) == 0.0
    with pytest.raises(ValueError, match="unknown decode attention impl"):
        AK.decode_attention(q, kc, vc, bt, cl, 1, impl="auto")


# ---------------------------------------------------------------------------
# acceptance: greedy paged decode == full re-forward, incl. join/leave
# ---------------------------------------------------------------------------

def test_greedy_paged_decode_matches_full_reforward_with_join_leave():
    lm, params, eng = _engine("parity", capture_logits=True)
    try:
        rng = np.random.RandomState(0)
        # 5 requests onto 3 slots with different prompt/output lengths:
        # some join only after earlier ones leave — mid-batch churn
        prompts = [rng.randint(0, TINY.vocab, n).astype(np.int32)
                   for n in (3, 7, 5, 11, 2)]
        budgets = (6, 3, 8, 4, 5)
        handles = [eng.submit(p, SamplingParams(max_new_tokens=m))
                   for p, m in zip(prompts, budgets)]
        results = [h.result(timeout=120) for h in handles]
        plist = lm.param_list(params)
        for p, r, h in zip(prompts, results, handles):
            assert len(r["tokens"]) == dict(zip(map(len, prompts),
                                                budgets))[len(p)]
            toks = list(p)
            for step, got_logits in enumerate(h.logits):
                full = lm.full_logits(
                    plist, jnp.asarray(np.asarray(toks, np.int32)[None]))
                ref = np.asarray(full[0, -1])
                assert np.abs(ref - got_logits).max() < 1e-4
                ref_tok = int(ref.argmax())
                assert ref_tok == r["tokens"][step], (
                    f"token {step} diverged: paged {r['tokens'][step]} "
                    f"vs re-forward {ref_tok}")
                toks.append(ref_tok)
    finally:
        eng.close()


def _assert_greedy_is_reforward_argmax(lm, params, prompt, tokens):
    """Teacher-forced: token k is the argmax of the full causal forward
    over prompt + tokens[:k] (one padded shape, so one compile)."""
    plist = lm.param_list(params)
    width = TINY.max_seq_len
    seq = np.zeros((1, width), np.int32)
    n = len(prompt) + len(tokens)
    seq[0, :n] = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    logits = np.asarray(lm.full_logits(
        plist, jnp.asarray(seq), jnp.asarray([n], jnp.int32)))[0]
    for k, tok in enumerate(tokens):
        row = logits[len(prompt) + k - 1]
        assert int(row.argmax()) == tok, (
            f"token {k}: paged {tok} vs re-forward {int(row.argmax())} "
            f"(gap {float(row.max() - row[tok]):.2e})")


def test_every_pool_writer_and_reader_matches_full_reforward():
    """One engine drives every program that touches the pool —
    ``prefill``, the decode step, ``prefill_suffix`` on a prefix-cache
    hit AND on a preemption resume, and a ``_copy_block`` COW fork —
    and every greedy token is still the full re-forward's argmax."""
    lm, params, eng = _engine("poolpaths", prefill_buckets=(8, 16),
                              num_blocks=9, overcommit=True,
                              prefix_cache=True)
    alloc, bs = eng.cache.allocator, eng.cache.block_tokens
    phantom = []
    ensure = eng._ensure_blocks

    def ensure_with_one_sharer():
        # once: a second reference on a stream's partly written block,
        # so this step must fork it (and carry its rows) before writing
        if not phantom:
            for slot in eng._slots:
                if slot is None or not slot.pos_next % bs:
                    continue
                j = slot.pos_next // bs
                if j < len(slot.blocks) and \
                        alloc.refcount(slot.blocks[j]) == 1:
                    alloc.incref(slot.blocks[j])
                    phantom.append(slot.blocks[j])
                    break
        ensure()
    eng._ensure_blocks = ensure_with_one_sharer
    try:
        pA = np.arange(1, 9, dtype=np.int32)            # 2 full blocks
        first = eng.generate(pA, max_new_tokens=4)      # prefill + steps
        prompts = [np.concatenate([pA, [9, 10]]).astype(np.int32),
                   np.arange(20, 26, dtype=np.int32),
                   np.arange(30, 36, dtype=np.int32)]
        handles = [eng.submit(p, SamplingParams(max_new_tokens=10))
                   for p in prompts]
        results = [h.result(timeout=120) for h in handles]
        assert all(r["finish"] == "length" for r in results)
        for p, r in zip([pA] + prompts, [first] + results):
            _assert_greedy_is_reforward_argmax(lm, params, p, r["tokens"])
        ps = eng._pstats
        assert ps.prefix_hits.value >= 1        # suffix prefill, cached
        assert ps.preempt_resumes.value >= 1    # suffix prefill, resume
        assert ps.cow_forks.value >= 1 and phantom
        alloc.decref(phantom[0])                # the sharer lets go
        assert alloc.leaked(eng.prefix.parked_blocks) == 0
    finally:
        eng.close()


def test_zero_recompiles_under_mixed_join_leave_load():
    lm, params, eng = _engine("pinned")
    try:
        rng = np.random.RandomState(7)
        # warm both prefill buckets + the decode step
        eng.generate(rng.randint(0, TINY.vocab, 6), max_new_tokens=2)
        eng.generate(rng.randint(0, TINY.vocab, 14), max_new_tokens=2)
        d = obs.stats.default_registry().to_dict()
        keys = ("executor.cache_misses", "executor.shape_recompiles")
        before = {k: d.get(k, 0) for k in keys}
        hs = []
        for i in range(10):
            n = int(rng.randint(2, 16))
            m = int(rng.randint(1, 6))
            hs.append(eng.submit(
                rng.randint(0, TINY.vocab, n),
                SamplingParams(max_new_tokens=m,
                               temperature=0.8 if i % 2 else 0.0,
                               top_k=4 if i % 3 else 0, seed=i)))
        for h in hs:
            h.result(timeout=120)
        d = obs.stats.default_registry().to_dict()
        after = {k: d.get(k, 0) for k in keys}
        assert before == after, (before, after)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# admission control / lifecycle
# ---------------------------------------------------------------------------

def test_typed_rejections():
    lm, params, eng = _engine("reject", max_queue=0)
    try:
        with pytest.raises(RequestTooLong):
            eng.submit(np.zeros(20, np.int32))       # off the ladder
        with pytest.raises(RequestTooLong):
            eng.submit(np.zeros(10, np.int32),
                       SamplingParams(max_new_tokens=30))  # past context
        with pytest.raises(Overloaded):
            eng.submit(np.zeros(4, np.int32),
                       SamplingParams(max_new_tokens=4))  # queue bound 0
        assert eng.stats.shed.value == 3
    finally:
        eng.close()


def test_eos_finishes_stream_early():
    lm, params, eng = _engine("eos")
    try:
        prompt = np.arange(5, dtype=np.int32)
        ref = eng.generate(prompt, max_new_tokens=6)
        assert ref["finish"] == "length"
        eos = ref["tokens"][2]
        out = eng.generate(prompt, max_new_tokens=6, eos_id=eos)
        assert out["finish"] == "eos"
        assert out["tokens"] == ref["tokens"][:3]
        # the slot and its blocks were released
        free = eng.cache.allocator.free_blocks
        assert free == eng.cache.num_blocks - 1
    finally:
        eng.close()


def test_decodez_payload_and_drain():
    lm, params, eng = _engine("dz")
    try:
        eng.generate(np.arange(4, dtype=np.int32), max_new_tokens=3)
        assert eng.drain(timeout=10)
        z = eng.decodez()
        assert z["tokens"] == 3 and z["leaves"] == 1
        # two steps: the first's token went out behind the second's
        # dispatch, the second's at once (no step followed)
        assert z["steps"] == 2 and z["fanout_immediate"] == 1
        assert eng.stats.fanout_delay_ms.count == 1
        assert 0 < z["fanout_delay_p50_ms"] <= z["fanout_delay_p99_ms"]
        assert z["cache"]["free_blocks"] == eng.cache.num_blocks - 1
        assert z["slots"] == [None] * eng.max_slots
        assert z["prefill_buckets"] == [8, 16]
    finally:
        eng.close()


def test_an_engine_given_nothing_has_the_documented_defaults():
    """``DecodeEngine(model, params)``: 8 slots, a queue of 64, 16-token
    blocks, the ladder 16/32/64/128 (cut to the model's context), an f32
    pool, neither admission policy — from nowhere but the constructor."""
    lm = TransformerLM(LMConfig(vocab=48, d_model=32, n_head=2, d_ffn=48,
                                n_layer=2, max_seq_len=128))
    eng = DecodeEngine(lm, lm.init_params(seed=5))
    try:
        assert eng.name == "lm"
        assert eng.max_slots == 8 and eng.max_queue == 64
        assert eng.cache.block_tokens == 16
        assert eng.prefill_ladder.sizes == (16, 32, 64, 128)
        assert eng.cache.dtype == "float32" and not eng.cache.quantized
        assert eng.prefix is None
        assert eng.decodez()["block_pool"]["overcommit"] is False
        assert eng.cache.num_blocks == 1 + 8 * (128 // 16)
    finally:
        eng.close()


def test_seeded_sampling_replays_across_batch_compositions():
    """A seeded sampled stream depends only on (seed, token index) —
    identical whether it runs alone or sharing the batch with other
    traffic (per-request counter-hash sampling, not an engine-global
    PRNG key)."""
    lm, params, eng = _engine("seeded")
    try:
        prompt = np.arange(5, dtype=np.int32)
        sp = dict(max_new_tokens=5, temperature=0.9, top_k=8, seed=42)
        alone = eng.generate(prompt, **sp)
        # same request again, now riding with concurrent neighbors
        rng = np.random.RandomState(3)
        noise = [eng.submit(rng.randint(0, TINY.vocab, 4),
                            SamplingParams(max_new_tokens=6,
                                           temperature=0.5, seed=i))
                 for i in range(2)]
        busy = eng.generate(prompt, **sp)
        for h in noise:
            h.result(timeout=60)
        assert busy["tokens"] == alone["tokens"]
        # a different seed must actually change a sampled stream
        other = eng.generate(prompt, max_new_tokens=5, temperature=0.9,
                             top_k=8, seed=43)
        assert other["tokens"] != alone["tokens"]
    finally:
        eng.close()


def _sample_top_k_then_select(logits, seeds, steps, temperature, top_k):
    """The sampling epilogue as it was before greedy rows took an argmax:
    sort every row's top slice, then choose — the plain reference
    :func:`_sample` is held to, token for token."""
    import jax
    from paddle_tpu.decode.adapter import TOPK_MAX, _hash_uniform
    kk = min(TOPK_MAX, logits.shape[1])
    vals, idx = jax.lax.top_k(logits.astype(jnp.float32), kk)
    lane = jnp.arange(kk, dtype=jnp.int32)[None, :]
    want = jnp.where(top_k > 0, jnp.minimum(top_k, kk), kk)[:, None]
    vals = jnp.where(lane < want, vals, -jnp.inf)
    g = -jnp.log(-jnp.log(_hash_uniform(seeds, steps, kk)))
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    choice = jnp.argmax(vals / temp + g, axis=-1)
    sampled = jnp.take_along_axis(idx, choice[:, None], axis=1)[:, 0]
    return jnp.where(temperature <= 0.0, idx[:, 0],
                     sampled).astype(jnp.int32)


def _tied(logits):
    """Every row's maximum three times over, at columns 5, 17 and 40."""
    logits[:, [40, 17, 5]] = logits.max(axis=1, keepdims=True) + 1.0
    return logits


# name -> (rows S, vocabulary V, logits dtype, temperature per row,
#          top_k per row, what to do to the logits first)
_SAMPLE_CASES = {
    "all_greedy": (6, 300, "float32", [0.0] * 6, [0, 1, 6, 100, 0, 6], None),
    "all_sampled": (6, 300, "float32", [0.7, 1.0, 0.2, 1.5, 0.9, 3.0],
                    [0, 1, 6, 100, 64, 65], None),
    "mixed_rows": (6, 300, "float32", [0.0, 0.8, 0.0, -1.0, 1.3, 0.0],
                   [0, 6, 6, 0, 1, 100], None),
    "greedy_tied_maxima": (4, 300, "float32", [0.0, 0.0, -0.5, 0.0],
                           [0, 6, 0, 1], _tied),
    "tied_maxima_beside_a_sampled_row": (4, 300, "float32",
                                         [0.0, 0.9, 0.0, 0.0], [0, 6, 0, 1],
                                         _tied),
    "one_row_greedy": (1, 300, "float32", [0.0], [0], None),
    "one_row_sampled": (1, 300, "float32", [0.8], [6], None),
    "vocabulary_below_topk_max": (5, 48, "float32",
                                  [0.0, 0.9, 0.0, 1.1, 0.6],
                                  [0, 0, 6, 100, 1], None),
    "bf16_logits_greedy": (6, 300, "bfloat16", [0.0] * 6,
                           [0, 1, 6, 100, 0, 6], None),
    "bf16_logits_mixed": (6, 300, "bfloat16",
                          [0.0, 0.8, 0.0, 0.0, 1.3, 0.0],
                          [0, 6, 6, 0, 1, 100], None),
}


@pytest.mark.parametrize("case", list(_SAMPLE_CASES))
def test_sample_is_the_top_k_then_select_reference_token_for_token(case):
    """Greedy rows take an argmax and the sort runs only in a launch that
    holds a sampled row — and no request can tell: the same tokens as
    sorting every row first, over greedy, sampled and mixed launches,
    tied maxima (the lowest index wins), one row (a prefill's shape), a
    vocabulary below ``TOPK_MAX``, bf16 logits and ``top_k`` 0 / 1 / 6 /
    beyond ``TOPK_MAX``, at three token indices."""
    import jax
    from paddle_tpu.decode.adapter import sample as _sample
    S, V, dtype, temps, topks, prepare = _SAMPLE_CASES[case]
    rng = np.random.RandomState(len(case))
    logits = (rng.randn(S, V) * 3).astype(np.float32)
    if prepare is not None:
        logits = prepare(logits)
    logits = jnp.asarray(logits).astype(dtype)
    if dtype == "bfloat16":    # rounding makes ties of its own: keep them
        assert len(np.unique(np.asarray(logits[0], np.float32))) < V
    seeds = jnp.asarray(rng.randint(0, 2 ** 31, S), jnp.uint32)
    temps = jnp.asarray(temps, jnp.float32)
    topks = jnp.asarray(topks, jnp.int32)
    new, ref = jax.jit(_sample), jax.jit(_sample_top_k_then_select)
    for index in (0, 1, 77):
        steps = jnp.full((S,), index, jnp.int32)
        got = np.asarray(new(logits, seeds, steps, temps, topks))
        want = np.asarray(ref(logits, seeds, steps, temps, topks))
        assert got.dtype == np.int32 and got.shape == (S,)
        assert (got == want).all(), (case, index, got, want)
    if prepare is _tied:
        greedy_rows = np.asarray(temps) <= 0
        assert (got[greedy_rows] == 5).all()


def test_decode_step_sorts_the_vocabulary_only_inside_the_conditional():
    """The compiled decode step holds its one sort of the vocabulary in a
    branch of ``_sample``'s conditional: nothing the entry computation
    runs in every launch sorts or takes a top-k."""
    import jax
    from hlo_text import sorts_outside_a_branch
    lm = TransformerLM(TINY)
    plist = lm.param_list(lm.init_params(seed=5))
    cache = PagedKVCache(TINY.n_layer, TINY.n_head, TINY.head_dim, 9, 4)
    S, MB = 3, 8
    i32 = jnp.int32
    feed = [jnp.zeros((S,), i32), jnp.zeros((S,), i32),
            jnp.zeros((S, MB), i32), jnp.zeros((S,), jnp.uint32),
            jnp.zeros((S,), i32), jnp.zeros((S,), jnp.float32),
            jnp.zeros((S,), i32)]

    def step(feed, state, const):
        return lm.decode_step(const, state, *feed, attn_impl="xla")

    text = jax.jit(step).lower(feed, cache.state(), plist).compile().as_text()
    outside, inside = sorts_outside_a_branch(text)
    assert not outside, f"sorted in every launch: {sorted(outside)}"
    assert len(inside) == 1, inside
    # and the reader sees a sort that IS outside one (the old epilogue)
    outside, inside = sorts_outside_a_branch(
        jax.jit(_sample_top_k_then_select).lower(
            jnp.zeros((S, TINY.vocab)), *feed[3:]).compile().as_text())
    assert outside and not inside


def test_greedy_steps_are_counted_and_a_sampled_neighbour_changes_no_token():
    """``greedy_steps`` / ``greedy_prefills`` count the launches whose
    every row was greedy — all of a greedy run's, fewer once a sampled
    request shares the batch — and a greedy stream's tokens are the same
    whether or not a sampled stream sits beside it."""
    lm, params, eng = _engine("greedy_ctr")
    try:
        prompt = np.arange(5, dtype=np.int32)
        alone = eng.generate(prompt, max_new_tokens=8)
        other = eng.generate(prompt[::-1].copy(), max_new_tokens=4)
        assert eng.drain(timeout=30)
        z = eng.decodez()
        assert z["steps"] > 0 and z["greedy_steps"] == z["steps"]
        assert z["greedy_prefills"] == z["prefills"] == 2
        sampled = eng.submit(np.arange(7, dtype=np.int32),
                             SamplingParams(max_new_tokens=12, seed=3,
                                            temperature=0.9, top_k=6))
        beside = eng.generate(prompt, max_new_tokens=8)
        sampled.result(timeout=60)
        assert eng.drain(timeout=30)
        assert beside["tokens"] == alone["tokens"] != other["tokens"]
        z2 = eng.decodez()
        shared = z2["steps"] - z["steps"]
        assert shared >= 11                # the sampled stream's own steps
        assert z2["greedy_steps"] - z["greedy_steps"] <= shared - 11
        assert z2["greedy_steps"] < z2["steps"]
        assert z2["prefills"] == 4 and z2["greedy_prefills"] == 3
        snap = obs.stats.default_registry().snapshot()
        assert snap["decode.greedy_ctr.greedy_steps"] == \
            z2["greedy_steps"]
    finally:
        eng.close()


def test_the_table_walk_is_counted_from_the_contexts_the_host_holds():
    """``step_live_blocks`` / ``step_table_blocks``: of the slots x blocks
    a slot that a step's attention is handed, a live stream's blocks up to
    its context and one of every idle slot are walked.  One stream alone
    on three slots of eight 4-token blocks: a prompt of 5 and 7 tokens
    more are six steps at contexts 6..11."""
    lm, params, eng = _engine("walk")
    try:
        assert eng.decodez()["step_table_blocks"] == 0
        eng.generate(np.arange(5, dtype=np.int32), max_new_tokens=7)
        assert eng.drain(timeout=30)
        z = eng.decodez()
        assert z["steps"] == 6 and eng.max_blocks_per_seq == 8
        assert z["step_table_blocks"] == 6 * 3 * 8
        # contexts 6, 7, 8 hold two blocks, 9, 10, 11 three; two idle slots
        assert z["step_live_blocks"] == 3 * 2 + 3 * 3 + 6 * 2
        snap = obs.stats.default_registry().snapshot()
        assert snap["decode.walk.step_live_blocks"] == z["step_live_blocks"]
        assert snap["decode.walk.step_table_blocks"] == \
            z["step_table_blocks"]
    finally:
        eng.close()


def test_the_table_walk_observer_counts_a_step_by_hand():
    """The observer alone, two live streams on four slots: a prefill adds
    nothing (these programs return token and logits only), a step the
    streams' blocks and the idle slots' one each."""
    lm = TransformerLM(TINY)
    watch = lm.observer("walk_o", lm.make_cache(9, 4), (4, 8))
    watch.prefill([], 5, 8)
    assert watch.decodez() == {"step_live_blocks": 0, "step_table_blocks": 0}
    watch.step([], np.asarray([1, 4, 5, 32]))     # a full house
    assert watch.decodez() == {"step_live_blocks": 1 + 1 + 2 + 8,
                               "step_table_blocks": 32}
    watch.step([], np.asarray([17, 3]))           # two idle slots
    assert watch.decodez() == {"step_live_blocks": 12 + 5 + 1 + 2,
                               "step_table_blocks": 64}


def _tiny_sambay():
    from paddle_tpu.decode.sambay import SambaYConfig, SambaYLM
    # 8 layers: two window layers (rings of 32 rows: two 16-row blocks), the
    # full layer and one cross layer (two readers of the pool)
    return SambaYLM(SambaYConfig(
        vocab_size=96, hidden_size=256, num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=384,
        sliding_window=32, max_seq_len=96, dtype="float32"))


def _tiny_falcon_h1():
    from paddle_tpu.decode.falcon_h1 import FalconH1Config, FalconH1LM
    return FalconH1LM(FalconH1Config(        # three layers, each a reader
        vocab_size=96, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=128, mamba_d_ssm=64, mamba_n_heads=4,
        mamba_d_head=16, mamba_n_groups=2, mamba_d_state=32, mamba_d_conv=4,
        mamba_chunk_size=8, max_seq_len=96, dtype="float32"))


# model → (pool readers, window layers, blocks a ring)
HYBRIDS = {"sambay": (_tiny_sambay, 2, 2, 2),
           "falcon_h1": (_tiny_falcon_h1, 3, 0, 0)}


@pytest.mark.parametrize("which", sorted(HYBRIDS))
def test_a_hybrid_model_s_walks_are_counted_over_every_reading_layer(which):
    """``step_live_blocks`` / ``step_table_blocks`` of the two hybrid
    models: every layer that reads the pool walks a live stream's blocks up
    to its context of the table's six, every window layer its ring's up to
    ``min(context, W)`` of the ring's two, and each one block of the idle
    slot.  One stream alone on two slots of 16-token blocks: a prompt of 13
    and 24 tokens more are 23 steps at contexts 14..36."""
    make, readers, rings, ring_blocks = HYBRIDS[which]
    lm = make()
    eng = DecodeEngine(lm, lm.init_params(1), name=f"walk_{which}",
                       max_slots=2, block_tokens=16, num_blocks=14,
                       prefill_buckets=[16], prefix_cache=False,
                       overcommit=False)
    try:
        assert eng.decodez()["step_table_blocks"] == 0
        eng.generate(np.arange(13, dtype=np.int32), max_new_tokens=24)
        assert eng.drain(timeout=120)
        z = eng.decodez()
        assert z["steps"] == 23 and eng.max_blocks_per_seq == 6
        assert z["step_table_blocks"] == 23 * 2 * (
            readers * 6 + rings * ring_blocks)
        # contexts 14..16 hold one block, 17..32 two, 33..36 three; a ring
        # one block to 16 rows and both from there on; the idle slot one
        pool = 3 * 1 + 16 * 2 + 4 * 3 + 23
        ring = 3 * 1 + 20 * 2 + 23
        assert z["step_live_blocks"] == readers * pool + rings * ring
        snap = obs.stats.default_registry().snapshot()
        for key in ("step_live_blocks", "step_table_blocks"):
            assert snap[f"decode.walk_{which}.{key}"] == z[key]
    finally:
        eng.close()


def test_cancel_frees_slot_and_blocks_mid_stream():
    lm, params, eng = _engine("cancel")
    try:
        h = eng.submit(np.arange(4, dtype=np.int32),
                       SamplingParams(max_new_tokens=25))
        assert h.next_token(timeout=30) is not None  # stream started
        h.cancel()
        out = h.result(timeout=30)
        assert out["finish"] == "cancelled"
        assert len(out["tokens"]) < 25
        eng.drain(timeout=10)
        assert eng.cache.allocator.free_blocks == eng.cache.num_blocks - 1
        z = eng.decodez()
        assert z["joins"] == z["leaves"] == 1
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# token fan-out: a step's tokens go out behind the next step's dispatch
# ---------------------------------------------------------------------------

def _submitted(eng):
    """The engine-side handle of every request submitted from here on, in
    order: how a test gets at a served stream's."""
    handles, submit = [], eng.submit

    def submitting(*a, **kw):
        handles.append(submit(*a, **kw))
        return handles[-1]
    eng.submit = submitting
    return handles


class _Stream:
    """One generation as the fan-out tests read it: ``handle`` is the
    engine's, ``read()`` gives the tokens streamed up to FIN (``error`` the
    exception that ended the stream instead, if one did)."""

    def __init__(self, handle=None):
        self.handle, self.reader = handle, None
        self.got, self.error = [], None

    @property
    def rid(self):
        return self.handle.rid

    def read(self):
        if self.reader is not None:             # read over the wire
            self.reader.join(timeout=120)
            assert not self.reader.is_alive()
        else:
            try:
                for tok in self.handle:
                    self.got.append(tok)
            except Exception as e:              # noqa: BLE001
                self.error = e
        return self.got


@pytest.fixture(params=["queued", "pushed"])
def open_streams(request):
    """``open_streams(eng)`` -> ``submit(prompt, sampling) -> _Stream``, by
    one of the two ways a token reaches a reader: ``queued`` — read in
    process from the handle's queue; ``pushed`` — through ``DecodeServer``
    and ``DecodeClient`` on the native transport, where the engine's thread
    writes the token frames itself."""
    import threading
    import time
    servers = []

    def opener(eng):
        if request.param == "queued":
            return lambda prompt, sp: _Stream(eng.submit(prompt, sp))
        srv = DecodeServer(engines={eng.name: eng}, own_engines=False)
        srv.start()
        servers.append(srv)
        cli = DecodeClient(endpoints=[srv.endpoint])
        handles = _submitted(eng)

        def served(prompt, sp):
            stream, seen = _Stream(), len(handles)

            def read():
                try:
                    for tok in cli.generate_stream(
                            eng.name, prompt, **sp.to_dict()):
                        stream.got.append(tok)
                except Exception as e:          # noqa: BLE001
                    stream.error = e
            stream.reader = threading.Thread(target=read, daemon=True)
            stream.reader.start()
            deadline = time.monotonic() + 30
            while len(handles) == seen:     # one at a time: in the order asked
                assert time.monotonic() < deadline
                time.sleep(0.001)
            stream.handle = handles[seen]
            assert stream.handle._sink is not None
            return stream
        return served

    opener.path = request.param     # for names: stats are kept by name
    yield opener
    for srv in servers:
        srv.stop()


def _recorded(eng, monkeypatch):
    """The engine thread's own order of events, as a list: every
    ``run_callable`` once it has returned (``("dispatch", kind)``), every
    step's read (``("read",)``: the observer runs right after it, inside
    the wait), and what each handle is told: ``("book", rid, k)``,
    ``("emit", rid, k, live)`` with the slots live at that moment — a
    token handed to the handle's queue or, for a pushed stream, to the one
    foreign call that writes its frame — and ``("fin", rid, reason)``."""
    from paddle_tpu.decode.engine import DecodeHandle
    log, booked, emitted = [], {}, {}
    run, observe = eng._exe.run_callable, eng._observer.step
    push = eng._push_tokens
    book, emit, fin = (DecodeHandle._book, DecodeHandle._emit,
                       DecodeHandle._finish)

    def run_callable(key, *a, **kw):
        out = run(key, *a, **kw)
        log.append(("dispatch", key.split("/")[2]))
        return out

    def step(*a, **kw):
        log.append(("read",))
        return observe(*a, **kw)

    def _book(self, token, logits):
        k = booked[self.rid] = booked.get(self.rid, -1) + 1
        log.append(("book", self.rid, k))
        book(self, token, logits)

    def _emitted(handle):
        k = emitted[handle.rid] = emitted.get(handle.rid, -1) + 1
        log.append(("emit", handle.rid, k,
                    sum(s is not None for s in eng._slots)))

    def _emit(self, token):
        _emitted(self)
        emit(self, token)

    def _push_tokens(entries):
        for handle, _, reason in entries:
            if reason is None and handle._sink is not None:
                _emitted(handle)
        return push(entries)

    def _finish(self, reason):
        log.append(("fin", self.rid, reason))
        fin(self, reason)

    monkeypatch.setattr(eng._exe, "run_callable", run_callable)
    monkeypatch.setattr(eng._observer, "step", step)
    monkeypatch.setattr(eng, "_push_tokens", _push_tokens)
    monkeypatch.setattr(DecodeHandle, "_book", _book)
    monkeypatch.setattr(DecodeHandle, "_emit", _emit)
    monkeypatch.setattr(DecodeHandle, "_finish", _finish)
    return log


def test_a_lone_streams_tokens_go_out_behind_the_next_dispatch(
        monkeypatch, open_streams):
    """The whole order of one stream of four tokens: the prefill's token
    at once; each step's token after the NEXT step's dispatch and before
    its read; the last step's token and FIN at once, with no later
    request to set them off."""
    lm, params, eng = _engine("fan_lone_" + open_streams.path)
    try:
        log = _recorded(eng, monkeypatch)
        st = open_streams(eng)(np.arange(5, dtype=np.int32),
                               SamplingParams(max_new_tokens=4))
        streamed, h = st.read(), st.handle    # ends at FIN: no hang
        assert st.error is None
        out = h.result(timeout=30)
        rid = h.rid
        assert log == [
            ("dispatch", "prefill"), ("book", rid, 0), ("emit", rid, 0, 1),
            ("dispatch", "step"), ("read",), ("book", rid, 1),
            ("dispatch", "step"), ("emit", rid, 1, 1), ("read",),
            ("book", rid, 2),
            ("dispatch", "step"), ("emit", rid, 2, 1), ("read",),
            ("book", rid, 3), ("emit", rid, 3, 0), ("fin", rid, "length")]
        assert streamed == out["tokens"] == h.tokens and len(streamed) == 4
    finally:
        eng.close()


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "seeded"])
def test_no_stream_is_woken_between_a_read_and_the_next_dispatch(
        monkeypatch, sampled, open_streams):
    """Five streams over three slots, joining and leaving: no token of a
    step is handed out between that step's read and the next step's
    dispatch unless the batch has emptied; every stream gets token ...
    token, FIN in order; and the tokens are the model's own — each the
    sampler's choice (by seed and index) from logits that equal the full
    re-forward's — so they are what the order before this one streamed."""
    from paddle_tpu.decode.adapter import sample as _sample
    lm, params, eng = _engine(
        "fan_many_" + ("s_" if sampled else "g_") + open_streams.path,
        capture_logits=True)
    try:
        log = _recorded(eng, monkeypatch)
        rng = np.random.RandomState(1)
        prompts = [rng.randint(0, TINY.vocab, n).astype(np.int32)
                   for n in (3, 7, 5, 11, 2)]
        sps = [SamplingParams(max_new_tokens=m, seed=11 + i,
                              temperature=0.8 if sampled else 0.0,
                              top_k=6 if sampled else 0)
               for i, m in enumerate((6, 3, 8, 1, 5))]
        submit = open_streams(eng)
        streams = [submit(p, sp) for p, sp in zip(prompts, sps)]
        streamed = [st.read() for st in streams]
        assert [st.error for st in streams] == [None] * 5
        handles = [st.handle for st in streams]
        results = [h.result(timeout=120) for h in handles]
        assert eng.drain(timeout=30)
        behind = 0
        for at, ev in enumerate(log):
            if ev[0] != "emit" or ev[2] == 0:
                continue                   # a prefill's token: at once
            last = [e for e in log[:at] if e[0] in ("read", "dispatch")
                    and e[-1] != "prefill"][-1]
            if last == ("dispatch", "step"):
                behind += 1                # this step is in flight
            else:
                assert ev[3] == 0, (at, ev)    # at once: no slot live
        assert behind >= 8
        plist = lm.param_list(params)
        for h, p, sp, got, r in zip(handles, prompts, sps, streamed,
                                    results):
            mine = [e for e in log if e[0] in ("book", "emit", "fin")
                    and e[1] == h.rid]
            n = sp.max_new_tokens
            assert [e[0] for e in mine if e[0] != "book"] == \
                ["emit"] * n + ["fin"]
            for k in range(n):             # booked before it is emitted
                assert mine.index(("book", h.rid, k)) < min(
                    i for i, e in enumerate(mine)
                    if e[:3] == ("emit", h.rid, k))
            assert got == r["tokens"] == h.tokens and len(got) == n
            assert r["finish"] == "length"
            toks = list(p)
            for k, row in enumerate(h.logits):
                full = lm.full_logits(
                    plist, jnp.asarray(np.asarray(toks, np.int32)[None]))
                assert np.abs(np.asarray(full[0, -1]) - row).max() < 1e-4
                want = int(np.asarray(_sample(
                    jnp.asarray(row[None]),
                    jnp.asarray([sp.seed], jnp.uint32),
                    jnp.asarray([k], jnp.int32),
                    jnp.asarray([sp.temperature], jnp.float32),
                    jnp.asarray([sp.top_k], jnp.int32)))[0])
                assert want == got[k], (h.rid, k)
                toks.append(got[k])
    finally:
        eng.close()


def test_drain_waits_for_the_last_hand_out(monkeypatch, open_streams):
    """``drain()`` is true only once the last step's tokens and FIN have
    gone out, however long the hand-out takes after the slot is free."""
    import time
    lm, params, eng = _engine("fan_drain_" + open_streams.path)
    try:
        flush = eng._flush_fanout

        def slow_flush(step_in_flight=False):
            if not step_in_flight and eng._fanout:
                time.sleep(0.3)            # slot free, tokens not yet out
            flush(step_in_flight)
        monkeypatch.setattr(eng, "_flush_fanout", slow_flush)
        st = open_streams(eng)(np.arange(4, dtype=np.int32),
                               SamplingParams(max_new_tokens=3))
        h = st.handle
        deadline = time.monotonic() + 30
        while any(s is not None for s in eng._slots) or eng._pending \
                or not h.tokens:
            assert time.monotonic() < deadline
            time.sleep(0.001)
        assert eng.drain(timeout=30)
        assert h._done.is_set()
        assert h.result(timeout=0)["tokens"] == st.read() \
            and len(h.tokens) == 3 and st.error is None
    finally:
        eng.close()


def test_close_and_an_engine_error_hand_out_what_was_computed_first(
        open_streams):
    """A token the engine has read is never dropped: ``close()`` with a
    step's tokens still pending, and a dispatch that raises, both hand
    them out before the stream is failed."""
    import time
    lm, params, eng = _engine("fan_close_" + open_streams.path)
    try:
        st = open_streams(eng)(np.arange(4, dtype=np.int32),
                               SamplingParams(max_new_tokens=25))
        h = st.handle
        deadline = time.monotonic() + 30
        while not h.tokens:                       # the stream has started
            assert time.monotonic() < deadline
            time.sleep(0.001)
        eng.close()
        got = st.read()
        assert isinstance(st.error, RuntimeError) and "closed" in str(st.error)
        assert got == h.tokens and 1 <= len(got) < 25
    finally:
        eng.close()
    lm, params, eng = _engine("fan_error_" + open_streams.path)
    try:
        run, calls = eng._exe.run_callable, []

        def run_callable(key, *a, **kw):
            calls.append(key)
            if calls.count(f"decode/{eng.name}/step") == 3:
                raise ValueError("the third step is refused")
            return run(key, *a, **kw)
        eng._exe.run_callable = run_callable
        st = open_streams(eng)(np.arange(4, dtype=np.int32),
                               SamplingParams(max_new_tokens=25))
        got, h = st.read(), st.handle
        # in process the engine's own exception; over the wire the ERR frame
        assert isinstance(st.error, (ValueError, RuntimeError)) \
            and "the third step is refused" in str(st.error)
        assert got == h.tokens and len(got) == 3   # the prefill's + two
        z = eng.decodez()
        assert z["joins"] == z["leaves"] == 1 and z["fanout_immediate"] == 1
        assert eng.cache.allocator.free_blocks == eng.cache.num_blocks - 1
        eng._exe.run_callable = run
        assert len(eng.generate(np.arange(4, dtype=np.int32),
                                max_new_tokens=3)["tokens"]) == 3
    finally:
        eng.close()


def test_a_cancel_between_a_read_and_its_hand_out_keeps_the_order(
        monkeypatch, open_streams):
    """The client goes away right after the engine has read a step and
    before that step's token is handed out: the token still goes out,
    FIN ("cancelled") after it, and the slot and its blocks are freed."""
    lm, params, eng = _engine("fan_cancel_" + open_streams.path)
    try:
        log = _recorded(eng, monkeypatch)
        book, box = eng._book_step, {}

        def book_then_cancel(*a, **kw):
            book(*a, **kw)
            (slot,) = [s for s in eng._slots if s is not None]
            if len(slot.req.handle.tokens) == 3:
                assert eng._fanout            # its token is pending
                slot.req.handle.cancel()
        monkeypatch.setattr(eng, "_book_step", book_then_cancel)
        st = open_streams(eng)(np.arange(4, dtype=np.int32),
                               SamplingParams(max_new_tokens=25))
        got, h = st.read(), st.handle
        assert st.error is None
        out = h.result(timeout=30)
        assert out["finish"] == "cancelled"
        assert got == out["tokens"] == h.tokens and len(got) == 3
        assert log[-2:] == [("emit", h.rid, 2, 0),
                            ("fin", h.rid, "cancelled")]
        assert eng.drain(timeout=10)
        assert eng.cache.allocator.free_blocks == eng.cache.num_blocks - 1
        z = eng.decodez()
        assert z["joins"] == z["leaves"] == 1
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Executor.run_callable: cache-resident donated state
# ---------------------------------------------------------------------------

def test_run_callable_donates_state_and_counts_cache():
    from paddle_tpu.core.executor import Executor

    exe = Executor(training=False)

    def build():
        def fn(feed, state, const):
            acc = state[0] + feed[0] * const[0]
            return [acc * 2], [acc]
        return fn

    d = obs.stats.default_registry().to_dict()
    miss0 = d.get("executor.cache_misses", 0)
    state = [jnp.zeros((4,), jnp.float32)]
    const = [jnp.asarray(2.0, jnp.float32)]
    (out,), state = exe.run_callable(
        "t/acc", build, [np.ones(4, np.float32)], state, const)
    assert np.allclose(np.asarray(out), 4.0)
    old = state
    (out,), state = exe.run_callable(
        "t/acc", build, [np.ones(4, np.float32)], state, const)
    assert np.allclose(np.asarray(state[0]), 4.0)  # accumulated on device
    d = obs.stats.default_registry().to_dict()
    assert d.get("executor.cache_misses", 0) == miss0 + 1  # one compile
    # a new feed SHAPE is a counted shape-recompile, like program runs
    rc0 = d.get("executor.shape_recompiles", 0)
    exe.run_callable("t/acc", build, [np.ones(8, np.float32)],
                     [jnp.zeros((8,), jnp.float32)], const)
    d = obs.stats.default_registry().to_dict()
    assert d.get("executor.shape_recompiles", 0) == rc0 + 1


# ---------------------------------------------------------------------------
# streaming server / client over real sockets
# ---------------------------------------------------------------------------

def test_streaming_server_and_client():
    lm, params, eng = _engine("wire")
    srv = DecodeServer(engines={"wire": eng})
    srv.start()
    try:
        cli = DecodeClient(endpoints=[srv.endpoint])
        gen = cli.generate_stream("wire", [1, 2, 3], max_new_tokens=5)
        toks = []
        try:
            while True:
                toks.append(next(gen))
        except StopIteration as stop:
            fin = stop.value
        assert len(toks) == 5 and fin["finish"] == "length"
        # greedy determinism: the same prompt re-decodes identically
        again = cli.generate("wire", [1, 2, 3], max_new_tokens=5,
                             chunk_tokens=2)
        assert again["tokens"] == toks
        # typed rejection crosses the wire (no failover loop)
        with pytest.raises(RequestTooLong):
            cli.generate("wire", list(range(30)), max_new_tokens=2)
        st = cli.status(srv.endpoint)
        assert st["wire"]["tokens"] >= 10
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# who writes a token's frame: the engine's thread (a pushed stream) or the
# connection's (the queue path)
# ---------------------------------------------------------------------------

LONG = LMConfig(vocab=48, d_model=32, n_head=2, d_ffn=48, n_layer=2,
                max_seq_len=512)


def _long_engine(name, **kw):
    lm = TransformerLM(LONG)
    params = lm.init_params(seed=5)
    kw.setdefault("max_slots", 4)
    kw.setdefault("block_tokens", 16)
    kw.setdefault("prefill_buckets", (8,))
    return DecodeEngine(lm, params, name=name, **kw)


def _raw_stream(endpoint, model, rcvbuf=None, **body):
    """A DECODE request sent over a plain socket that the test reads (or
    does not read) itself."""
    import json
    import socket
    from paddle_tpu.decode import server as dserver
    from paddle_tpu.distributed import transport
    host, port = endpoint.rsplit(":", 1)
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    if rcvbuf:
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    sock.connect((host, int(port)))
    sock.settimeout(60)
    req = b"".join(transport._pack_body_vec(
        dserver.DECODE, 0, model, [json.dumps(body).encode("utf-8")]))
    sock.sendall(len(req).to_bytes(4, "little") + req)
    return sock


def _read_stream_frame(sock):
    """-> ``("T", [tokens])`` | ``("F", fin dict)`` | ``("ERR", text)``."""
    import json
    from paddle_tpu.distributed import serde, transport

    def exact(n):
        got = b""
        while len(got) < n:
            chunk = sock.recv(n - len(got))
            assert chunk, "the server closed the stream"
            got += chunk
        return got
    body = exact(int.from_bytes(exact(4), "little"))
    rtype, _, _, payload = transport._unpack_body(body)
    if rtype == transport.ERR:
        return "ERR", bytes(payload).decode("utf-8")
    tag, rest = bytes(payload[:1]), payload[1:]
    if tag == b"T":
        return "T", [int(t) for t in serde.loads_batch(rest)[0][1]]
    assert tag == b"F", tag
    return "F", json.loads(bytes(rest).decode("utf-8"))


@pytest.mark.parametrize("backend", ["native", "python"])
def test_eight_concurrent_streams_read_the_same_tokens_either_way(
        backend, monkeypatch):
    """Through DecodeServer / DecodeClient eight streams at once read the
    model's own tokens and FIN.  On the native transport every token frame
    is the engine thread's (``pushed_frames`` == tokens, no fall-back, no
    ``queue.put`` for a token, the transport's ``stream_frames`` counts them
    all the same); on ``rpc_transport=python`` the connection threads drain
    the queues as before and nothing is pushed."""
    import threading
    import paddle_tpu as fluid
    from paddle_tpu.decode.engine import DecodeHandle
    fluid.set_flags({"rpc_transport": backend})
    name = "eight_" + backend
    lm, params, eng = _engine(name, max_slots=8)
    srv = DecodeServer(engines={name: eng})
    srv.start()
    try:
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, TINY.vocab, 2 + i).astype(np.int32)
                   for i in range(8)]
        budgets = [12, 3, 9, 1, 14, 7, 5, 10]
        want = [eng.generate(p, max_new_tokens=m)["tokens"]
                for p, m in zip(prompts, budgets)]
        queued, emit = [], DecodeHandle._emit
        monkeypatch.setattr(
            DecodeHandle, "_emit",
            lambda self, tok: (queued.append(self.rid), emit(self, tok)))
        frames = "rpc.server.stream_frames"
        z0 = eng.decodez()
        f0 = obs.stats.default_registry().to_dict().get(frames, 0)
        cli = DecodeClient(endpoints=[srv.endpoint])
        outs = [None] * 8

        def one(i):
            outs[i] = cli.generate(name, prompts[i],
                                   max_new_tokens=budgets[i])
        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert [o["tokens"] for o in outs] == want
        assert [(o["finish"], o["n_tokens"]) for o in outs] == \
            [("length", m) for m in budgets]
        z = eng.decodez()
        tokens = z["tokens"] - z0["tokens"]
        assert tokens == sum(budgets) and z["push_fallbacks"] == 0
        f1 = obs.stats.default_registry().to_dict().get(frames, 0)
        assert f1 - f0 == tokens + 8                 # every T-frame and FIN
        if backend == "native":
            assert z["pushed_frames"] - z0["pushed_frames"] == tokens
            assert queued == []
        else:
            assert z["pushed_frames"] == 0 and len(queued) == tokens
    finally:
        srv.stop()
        fluid.set_flags({"rpc_transport": "native"})


def test_a_chunked_stream_is_drained_from_the_queue_as_before():
    """``chunk_tokens`` > 1 is the caller's to ask for and the connection
    thread's to serve: chunks of four, the rest, FIN; nothing pushed."""
    lm, params, eng = _engine("chunked")
    srv = DecodeServer(engines={"chunked": eng})
    srv.start()
    try:
        want = eng.generate([1, 2, 3], max_new_tokens=10)["tokens"]
        sock = _raw_stream(srv.endpoint, "chunked", prompt=[1, 2, 3],
                           max_new_tokens=10, chunk_tokens=4)
        got = [_read_stream_frame(sock) for _ in range(4)]
        sock.close()
        assert [kind for kind, _ in got] == ["T", "T", "T", "F"]
        assert [len(toks) for _, toks in got[:3]] == [4, 4, 2]
        assert sum((toks for _, toks in got[:3]), []) == want
        assert got[3][1] == {"n_tokens": 10, "finish": "length"}
        assert eng.decodez()["pushed_frames"] == 0
    finally:
        srv.stop()


def test_a_reader_that_stops_reading_falls_back_once_and_holds_nobody():
    """A caller with a small receive buffer that stops reading: its socket
    fills, its stream is moved to the queue path — once, for good — and only
    its own connection thread waits for it.  The engine's thread is never
    held: the other streams run to their FIN meanwhile and the stalled
    stream's own generation finishes too.  When the caller reads again it
    gets every token in order, whole frames only, FIN last.  (A frame
    carries the model's name; a name of 60,000 characters makes a frame
    large enough that a few dozen fill a socket.)"""
    import threading
    import time
    model = "m" * 60000
    eng = _long_engine("stalled")
    srv = DecodeServer(engines={model: eng})
    srv.start()
    try:
        n = 300
        want = eng.generate([5, 6, 7], max_new_tokens=n)["tokens"]
        z0 = eng.decodez()
        handles = _submitted(eng)
        slow = _raw_stream(srv.endpoint, model, rcvbuf=4096,
                           prompt=[5, 6, 7], max_new_tokens=n)
        deadline = time.monotonic() + 30
        while not handles:
            assert time.monotonic() < deadline
            time.sleep(0.001)
        stalled = handles[0]
        cli = DecodeClient(endpoints=[srv.endpoint])
        outs = [None] * 2

        def one(i):
            outs[i] = cli.generate(model, [5, 6, 7], max_new_tokens=n)
        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        # nobody has read a byte of the stalled stream so far
        assert [o["tokens"] for o in outs] == [want, want]
        assert stalled.result(timeout=60)["tokens"] == want
        assert eng.drain(timeout=30)
        z = eng.decodez()
        assert z["push_fallbacks"] - z0["push_fallbacks"] == 1
        assert 0 < stalled._n_pushed < n and stalled._sink is None
        assert z["pushed_frames"] - z0["pushed_frames"] == \
            2 * n + stalled._n_pushed
        got = [_read_stream_frame(slow) for _ in range(n + 1)]
        slow.close()
        assert [kind for kind, _ in got] == ["T"] * n + ["F"]
        assert [toks for _, toks in got[:n]] == [[t] for t in want]
        assert got[n][1] == {"n_tokens": n, "finish": "length"}
        assert eng.decodez()["push_fallbacks"] - z0["push_fallbacks"] == 1
    finally:
        srv.stop()


def test_a_reader_that_closes_mid_stream_frees_its_slot_and_blocks():
    """A pushed stream's caller goes away: the push that finds the peer
    dead cancels the handle, so the slot and its blocks are freed long
    before the budget is spent."""
    import time
    eng = _long_engine("hangup")
    srv = DecodeServer(engines={"hangup": eng})
    srv.start()
    try:
        handles = _submitted(eng)
        sock = _raw_stream(srv.endpoint, "hangup", prompt=[1, 2, 3],
                           max_new_tokens=450)
        assert _read_stream_frame(sock)[0] == "T"
        assert _read_stream_frame(sock)[0] == "T"
        sock.close()
        out = handles[0].result(timeout=60)
        assert out["finish"] == "cancelled" and out["n_tokens"] < 450
        assert eng.drain(timeout=30)
        deadline = time.monotonic() + 30
        while eng.cache.allocator.free_blocks != eng.cache.num_blocks - 1:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        z = eng.decodez()
        assert z["joins"] == z["leaves"] == 1
        assert z["push_fallbacks"] == 0 and z["pushed_frames"] >= 2
    finally:
        srv.stop()


@pytest.mark.parametrize("backend", ["native", "python"])
def test_a_wedged_engine_still_yields_the_error_frame_within_the_deadline(
        backend):
    """The engine stops producing: the connection thread — asleep while the
    engine pushes, or waiting on the queue — wakes once a
    ``FLAGS_rpc_deadline``, sees no token since, and answers with the typed
    ERR frame; the request is cancelled."""
    import threading
    import time
    import paddle_tpu as fluid
    fluid.set_flags({"rpc_transport": backend, "rpc_deadline": 1.0})
    name = "wedged_" + backend
    eng = _long_engine(name)
    srv = DecodeServer(engines={name: eng})
    srv.start()
    gate = threading.Event()
    try:
        eng.generate([1, 2, 3], max_new_tokens=3)   # compiled: no wait is it
        run, calls = eng._exe.run_callable, []

        def run_callable(key, *a, **kw):
            calls.append(key)
            if calls.count(f"decode/{name}/step") == 4:
                gate.wait(timeout=60)           # the wedge
            return run(key, *a, **kw)
        eng._exe.run_callable = run_callable
        handles = _submitted(eng)
        sock = _raw_stream(srv.endpoint, name, prompt=[1, 2, 3],
                           max_new_tokens=100)
        t0 = time.monotonic()
        got = []
        while not got or got[-1][0] == "T":
            got.append(_read_stream_frame(sock))
        waited = time.monotonic() - t0
        # the prefill's token and two steps': the third step's waits behind
        # the fourth dispatch, which never returns
        assert [kind for kind, _ in got] == ["T"] * 3 + ["ERR"]
        assert "no token within 1.0s" in got[-1][1]
        assert 1.0 <= waited < 10.0
        assert handles[0].cancelled
        assert handles[0]._n_pushed == (3 if backend == "native" else 0)
        gate.set()
        assert handles[0].result(timeout=60)["finish"] == "cancelled"
        sock.close()
    finally:
        gate.set()
        srv.stop()
        fluid.set_flags({"rpc_transport": "native", "rpc_deadline": 120.0})


def test_save_load_lm_and_served_roundtrip(tmp_path):
    lm = TransformerLM(TINY)
    params = lm.init_params(seed=9)
    save_lm(str(tmp_path / "lm"), TINY, params)
    lm2, params2 = load_lm(str(tmp_path / "lm"))
    assert lm2.config == TINY
    assert sorted(params2) == sorted(params)
    eng = DecodeEngine(lm2, params2, name="loaded", max_slots=2,
                       block_tokens=4, prefill_buckets=(8, 16))
    srv = DecodeServer(engines={"loaded": eng})
    srv.start()
    try:
        out = DecodeClient(endpoints=[srv.endpoint]).generate(
            "loaded", [3, 1, 4], max_new_tokens=4)
        ref = TransformerLM(TINY)
        plist = ref.param_list(params)
        toks = [3, 1, 4]
        for t in out["tokens"]:
            lg = ref.full_logits(
                plist, jnp.asarray(np.asarray(toks, np.int32)[None]))
            assert t == int(np.asarray(lg[0, -1]).argmax())
            toks.append(t)
    finally:
        srv.stop()


def test_load_lm_missing_params(tmp_path):
    lm = TransformerLM(TINY)
    params = lm.init_params(seed=9)
    params.pop("out_proj")
    save_lm(str(tmp_path / "lm"), TINY, params)
    with pytest.raises(ValueError, match="missing params"):
        load_lm(str(tmp_path / "lm"))


def test_serve_cli_decode_parser():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "serve_cli", os.path.join(os.path.dirname(__file__), "..",
                                  "tools", "serve.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    args = mod.build_parser().parse_args(
        ["/m/lm", "--decode", "--decode-slots", "4",
         "--decode-block-tokens", "8",
         "--decode-prefill-buckets", "8,16", "--max-seq-len", "64"])
    assert args.decode and args.decode_slots == 4
    assert args.decode_block_tokens == 8
    assert args.max_seq_len == 64


# ---------------------------------------------------------------------------
# satellite: serving-batcher max_seq_len typed rejection
# ---------------------------------------------------------------------------

class _StubPredictor:
    feed_names = ["ids"]
    fetch_names = ["out"]

    def run(self, feed):
        return [np.asarray(feed["ids"], np.float32)]


def test_batcher_max_seq_len_rejects_before_latching():
    from paddle_tpu.serving import DynamicBatcher

    b = DynamicBatcher(_StubPredictor(), name="cap", buckets=(1, 2, 4),
                       max_delay_ms=1.0, max_seq_len=8)
    try:
        # the FIRST request being over-length must reject alone — not
        # latch an off-ladder sample shape into the feed contract
        with pytest.raises(RequestTooLong) as ei:
            b.submit({"ids": np.zeros((1, 9), np.int64)})
        assert ei.value.limit == 8 and ei.value.length == 9
        d = ei.value.to_dict()
        assert RequestTooLong.from_dict(d).limit == 8
        out = b.infer({"ids": np.zeros((1, 8), np.int64)}, timeout=30)
        assert out[0].shape == (1, 8)
        # contract latched at 8: a later over-length request still sheds
        with pytest.raises(RequestTooLong):
            b.submit({"ids": np.zeros((1, 12), np.int64)})
        assert b.stats.shed == 2
    finally:
        b.close()


class _TwoFeedPredictor:
    feed_names = ["ids", "features"]
    fetch_names = ["out"]

    def run(self, feed):
        return [np.asarray(feed["ids"], np.float32)]


def test_batcher_max_seq_len_dict_scopes_to_named_feeds():
    from paddle_tpu.serving import DynamicBatcher

    # dict form: only 'ids' is a sequence; a wide fixed 'features'
    # feed must never be measured against the sequence bound
    b = DynamicBatcher(_TwoFeedPredictor(), name="scoped",
                       buckets=(1, 2), max_delay_ms=1.0,
                       max_seq_len={"ids": 8})
    try:
        out = b.infer({"ids": np.zeros((1, 8), np.int64),
                       "features": np.zeros((1, 256), np.float32)},
                      timeout=30)
        assert out[0].shape == (1, 8)
        with pytest.raises(RequestTooLong, match="'ids'"):
            b.submit({"ids": np.zeros((1, 9), np.int64),
                      "features": np.zeros((1, 256), np.float32)})
    finally:
        b.close()


def test_decode_drain_finishes_streams_and_rejects_stragglers():
    """ISSUE 14 satellite: DecodeServer graceful drain — the lease
    deregisters FIRST, an in-flight stream generates all the way to
    its FIN inside the drain bound (zero dropped tokens), a straggler
    submit racing the drain gets a typed Draining reply, and SIGTERM
    is wired as the drain trigger."""
    import os
    import signal as _signal
    import threading
    import time
    from paddle_tpu.decode import Draining
    from paddle_tpu.distributed import registry as reg_mod
    from paddle_tpu.distributed import transport
    from paddle_tpu.distributed.registry import RegistryServer

    reg = RegistryServer("127.0.0.1:0")
    reg.start()
    reg_ep = f"127.0.0.1:{reg.port}"
    lm, params, eng = _engine("drainy")
    srv = DecodeServer(engines={"drainy": eng}, registry_ep=reg_ep,
                       replica_id="r0", lease_ttl=1.0)
    srv.start()
    done = {}
    try:
        cli = DecodeClient(endpoints=[srv.endpoint])
        # reference decode of the same prompt on an undisturbed run
        want = cli.generate("drainy", [1, 2, 3], max_new_tokens=12)

        def long_stream():
            done["fin"] = cli.generate("drainy", [1, 2, 3],
                                       max_new_tokens=12)
        t = threading.Thread(target=long_stream)
        t.start()
        time.sleep(0.05)                 # stream admitted + running
        # SIGTERM = the drain trigger (supervisor shrink / rolling
        # restart); handler chains and runs stop(drain=True) async
        prev = _signal.getsignal(_signal.SIGTERM)
        chained = []
        _signal.signal(_signal.SIGTERM,
                       lambda s, f: chained.append(s))
        srv.install_sigterm_drain(drain_timeout=30.0)
        os.kill(os.getpid(), _signal.SIGTERM)
        try:
            deadline = time.monotonic() + 10
            while not srv.service.draining \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            assert srv.service.draining
            # (the previous disposition only fires AFTER the drain —
            # asserted below once the stream is known complete; the
            # tiny model can finish its whole stream inside the poll
            # granularity, so no mid-drain emptiness check here)
            # lease deregistered FIRST: discovery routes away while the
            # stream still generates
            snap = reg_mod.fetch_snapshot(transport.RPCClient(0), reg_ep)
            assert "decode/drainy/r0" not in snap["leases"]
            # straggler racing the drain: typed rejection, not a hang
            if eng.drain(timeout=0.0):
                pass   # stream already finished: nothing to race
            else:
                with pytest.raises(Draining) as ei:
                    DecodeClient(endpoints=[srv.endpoint]).generate(
                        "drainy", [4, 5], max_new_tokens=2)
                assert ei.value.model == "drainy"
            t.join(timeout=30)
            assert done["fin"]["tokens"] == want["tokens"]
            assert done["fin"]["finish"] == "length"
            # AFTER the drain completes, SIGTERM is re-delivered under
            # the previous disposition (here: the benign test handler —
            # in production: the flight recorder's dump-then-die)
            deadline = time.monotonic() + 15
            while not chained and time.monotonic() < deadline:
                time.sleep(0.05)
            assert chained == [_signal.SIGTERM]
        finally:
            _signal.signal(_signal.SIGTERM, prev)
        # the drain thread closes the server; wait for it
        deadline = time.monotonic() + 15
        while srv._started and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        reg.stop()
