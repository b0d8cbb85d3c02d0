"""``Executor.run_callable``'s launch path: a launch's host feeds go to the
device in one packed buffer and come apart inside the compiled program bit
for bit; the signature is the logical entries', so whoever sends the same
arrays under the same key hits the same executable; and the constants'
part of the signature is reused while every constant is the array the
key's last launch brought."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.core import executor as executor_mod
from paddle_tpu.core.executor import Executor
from paddle_tpu.observability import step_stats
from paddle_tpu.decode import (DecodeEngine, LMConfig, SamplingParams,
                               TransformerLM)


TINY = LMConfig(vocab=48, d_model=32, n_head=2, d_ffn=48, n_layer=2,
                max_seq_len=32)


def _counters():
    d = obs.stats.default_registry().to_dict()
    return {k.split(".", 1)[1]: v for k, v in d.items()
            if k.startswith("executor.") and not isinstance(v, dict)}


def _delta(before):
    after = _counters()
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


def _echo():
    """A callable that hands its feed straight back."""
    return lambda feed, state, const: (list(feed), list(state))


def _unpacked(feed):
    """What the entry-by-entry conversion made of a feed."""
    return [np.asarray(v if isinstance(v, jax.Array) else jnp.asarray(v))
            for v in feed]


def _bits(a):
    a = np.asarray(a)
    return a.dtype, a.shape, a.tobytes()


NAN_BITS = np.array([0x7FC00001, 0xFFC12345, 0x7F800001], np.uint32)

# every case: the feed entries, the transfers a launch makes for them
FEEDS = {
    "engine_step": (lambda: [
        np.arange(5, dtype=np.int32) - 2,
        np.array([0, 2**31, 2**32 - 1, 0x9E3779B9, 7], np.uint32),
        np.array([-0.7, 0.0, -0.0, 1e-42, np.inf], np.float32),
        np.arange(12, dtype=np.int32).reshape(3, 4)], 1),
    "nan_bits": (lambda: [NAN_BITS.view(np.float32), NAN_BITS.copy()], 1),
    "scalars": (lambda: [np.int32(-9), np.uint32(2**31 + 5),
                         np.float32(-1.5), np.zeros((), np.float32)], 1),
    "strided_and_empty": (lambda: [
        np.arange(24, dtype=np.int32).reshape(4, 6)[::2, 1::2],
        np.asfortranarray(np.arange(6, dtype=np.float32).reshape(2, 3)),
        np.zeros((0, 3), np.int32), np.uint32(3)], 1),
    # a device entry is no transfer; a one-byte entry is its own
    "passes_through": (lambda: [
        np.arange(4, dtype=np.int32), jnp.arange(3, dtype=jnp.float32) - 1,
        np.array([True, False, True]), np.array([1, -2], np.int8),
        np.float32(2.5)], 3),
    "nothing_packed": (lambda: [jnp.ones((2, 2), jnp.float32),
                                np.array([3, 4], np.int16)], 1),
}


@pytest.mark.parametrize("case", sorted(FEEDS))
def test_a_packed_feed_reaches_the_callable_bit_for_bit(case):
    make, transfers = FEEDS[case]
    exe, feed = Executor(training=False), make()
    want = _unpacked(feed)
    c0 = _counters()
    outs, _ = exe.run_callable(f"t/echo/{case}", _echo, feed)
    d = _delta(c0)
    assert d["steps"] == 1 and d["feed_transfers"] == transfers
    assert [_bits(o) for o in outs] == [_bits(w) for w in want]
    # the logical entries' bytes, as before
    assert step_stats.last_n(1)[0].feed_bytes == \
        sum(w.nbytes for w in want)


def test_the_signature_is_the_logical_entries_own():
    """Shapes and canonical dtypes, entry by entry, under the names and in
    the form the unpacked path signed: what a replay's hit rests on."""
    exe = Executor(training=False)
    feed = [np.zeros((1, 8), np.int32), np.int32(5), np.zeros(4, np.int32),
            np.uint32(0), np.float32(0.0), np.int32(0)]
    state, const = [jnp.zeros((2,), jnp.float32)], [jnp.ones((3, 3))]
    exe.run_callable("t/sig", _echo, feed, state, const)
    (key,) = [k for k in exe._cache if k[0] == "callable"]
    arrays = [jnp.asarray(v) for v in feed]
    assert key == ("callable", "t/sig",
                   Executor._feed_sig([str(i) for i in range(6)], arrays)
                   + Executor._feed_sig(["s0"], [jnp.zeros((2,), jnp.float32)])
                   + Executor._feed_sig(["c0"], const), False,
                   (True,) * 6)       # and which entries were packed
    assert exe._cache[key].jitted.__name__ == "fn_t_sig"


def test_a_wider_host_entry_is_packed_under_its_canonical_dtype():
    """With x64 off an int64 / float64 host array IS an int32 / float32
    feed (``jnp.asarray`` makes it one): it is packed as one.  With x64
    on it is eight bytes wide and goes alone."""
    exe = Executor(training=False)
    feed = [np.array([1, -2, 3], np.int64), np.array([0.5, -1.25]),
            np.arange(3, dtype=np.int32)]
    want = _unpacked(feed)
    c0 = _counters()
    outs, _ = exe.run_callable("t/wide", _echo, feed)
    assert [_bits(o) for o in outs] == [_bits(w) for w in want]
    assert _delta(c0)["feed_transfers"] == \
        (3 if jax.config.jax_enable_x64 else 1)
    with jax.enable_x64(False):
        want = _unpacked(feed)
        assert [w.dtype for w in want] == [np.int32, np.float32, np.int32]
        c0 = _counters()
        outs, _ = exe.run_callable("t/wide", _echo, feed)
        assert [_bits(o) for o in outs] == [_bits(w) for w in want]
        assert _delta(c0)["feed_transfers"] == 1


def test_other_values_hit_and_another_shape_recompiles():
    exe = Executor(training=False)

    def build():
        return lambda feed, state, const: (
            [feed[0].sum() + feed[1] * const[0]], [state[0] + feed[2]])

    def launch(n, scale, state):
        return exe.run_callable(
            "t/hit", build,
            [np.full((n,), 3, np.int32), np.float32(scale),
             np.ones((2,), np.float32)], state, [jnp.float32(2.0)])

    c0 = _counters()
    (out,), state = launch(4, 1.5, [jnp.zeros((2,), jnp.float32)])
    d = _delta(c0)
    assert float(out) == 15.0 and d.pop("build_ms") > 0
    assert d == {"cache_misses": 1, "steps": 1, "feed_transfers": 1}
    c0 = _counters()
    (out,), state = launch(4, -0.5, state)
    assert float(out) == 11.0 and np.allclose(np.asarray(state[0]), 2.0)
    assert _delta(c0) == {"cache_hits": 1, "steps": 1, "feed_transfers": 1}
    c0 = _counters()
    (out,), _ = launch(6, 1.0, state)
    d = _delta(c0)
    assert float(out) == 20.0
    assert d["cache_misses"] == 1 and d["shape_recompiles"] == 1


def test_a_replay_in_the_drivers_manner_hits_the_engines_executables():
    """The benchmark's ``replay``: the idle engine's executor, the engine's
    own keys and shapes, a ``build_fn`` that raises on a miss."""
    lm = TransformerLM(TINY)
    eng = DecodeEngine(lm, lm.init_params(seed=5), name="replayed",
                       max_slots=3, block_tokens=4, prefill_buckets=(8, 16))
    try:
        prompt = np.array([4, 9, 2, 30, 7], np.int32)
        got = eng.submit(prompt, SamplingParams(max_new_tokens=3)).result(
            timeout=120)["tokens"]
        exe, cache = eng._exe, eng.cache
        S, MB = eng.max_slots, eng.max_blocks_per_seq

        def missed():
            raise RuntimeError("replay missed the engine's executable cache")

        def dispatch(key, feed):
            outs, new_state = exe.run_callable(
                key, missed, feed, state=cache.state(), const=eng._plist)
            cache.update(new_state)
            return outs

        c0 = _counters()
        blocks = cache.allocator.alloc(2)
        tables = np.zeros((S, MB), np.int32)
        tables[0, :2] = blocks
        tokens = np.zeros((1, 8), np.int32)
        tokens[0, :5] = prompt
        tok, *_ = dispatch(
            "decode/replayed/prefill/8",
            [tokens, np.int32(5), tables[0].copy(), np.uint32(0),
             np.float32(0.0), np.int32(0)])
        toks = [int(np.asarray(tok))]
        zi, zu = np.zeros((S,), np.int32), np.zeros((S,), np.uint32)
        for n in range(2):
            last, pos = zi.copy(), zi.copy()
            last[0], pos[0] = toks[-1], 5 + n
            out, *_ = dispatch(
                "decode/replayed/step",
                [last, pos, tables, zu, zi, np.zeros((S,), np.float32), zi])
            toks.append(int(np.asarray(out)[0]))
        cache.allocator.release(blocks)
        d = _delta(c0)
        assert toks == [int(t) for t in got]
        assert d["cache_hits"] == 3 and d["feed_transfers"] == 3
        assert d["const_sig_reuses"] == 3 and "cache_misses" not in d
        with pytest.raises(RuntimeError, match="replay missed"):
            dispatch("decode/replayed/prefill/8",     # another rung's shape
                     [np.zeros((1, 12), np.int32), np.int32(5),
                      tables[0].copy(), np.uint32(0), np.float32(0.0),
                      np.int32(0)])
    finally:
        eng.close()


def test_replaced_constants_hit_under_a_signature_made_anew():
    exe = Executor(training=False)

    def build():
        return lambda feed, state, const: ([feed[0] * const[0] + const[1]],
                                           [])

    def launch(const):
        c0 = _counters()
        (out,), _ = exe.run_callable("t/const", build,
                                     [np.ones((2,), np.float32)], [], const)
        return np.asarray(out), _delta(c0)

    w = [jnp.full((2,), 3.0, jnp.float32), jnp.float32(1.0)]
    out, d = launch(w)
    assert out.tolist() == [4.0, 4.0] and d["cache_misses"] == 1
    assert "const_sig_reuses" not in d
    out, d = launch(list(w))                 # another list, the same arrays
    assert d["cache_hits"] == 1 and d["const_sig_reuses"] == 1
    # a control's weights: other arrays of the same shapes hit, walked anew
    control = [w[0] * 2, w[1]]
    out, d = launch(control)
    assert out.tolist() == [7.0, 7.0]
    assert d["cache_hits"] == 1 and "const_sig_reuses" not in d
    out, d = launch(control)
    assert d["const_sig_reuses"] == 1
    out, d = launch(w)                       # and back: walked again, a hit
    assert out.tolist() == [4.0, 4.0]
    assert d["cache_hits"] == 1 and "const_sig_reuses" not in d
    # another shape, or one constant more, is another program
    out, d = launch([jnp.full((1,), 3.0, jnp.float32), w[1]])
    assert d["cache_misses"] == 1 and d["shape_recompiles"] == 1
    assert "const_sig_reuses" not in d
    out, d = launch(w + [jnp.float32(0.0)])
    assert d["cache_misses"] == 1 and "const_sig_reuses" not in d


def test_the_remembered_constants_are_not_kept_alive():
    """A control swaps a weight in for one replay and drops it: the memo
    must neither hold it on the device nor match a stranger at its id."""
    import gc
    import weakref
    exe = Executor(training=False)
    build = lambda: (lambda feed, state, const: ([feed[0] + const[0]], []))
    swapped = jnp.full((2,), 5.0, jnp.float32)
    watch = weakref.ref(swapped)
    exe.run_callable("t/weak", build, [np.zeros((2,), np.float32)], [],
                     [swapped])
    del swapped
    gc.collect()
    assert watch() is None
    c0 = _counters()
    (out,), _ = exe.run_callable("t/weak", build,
                                 [np.zeros((2,), np.float32)], [],
                                 [jnp.full((2,), 6.0, jnp.float32)])
    assert np.asarray(out).tolist() == [6.0, 6.0]
    assert "const_sig_reuses" not in _delta(c0)
    # a constant no weakref can watch is signed every launch, as before
    for _ in range(2):
        c0 = _counters()
        exe.run_callable("t/weak_py", build, [np.zeros((2,), np.float32)],
                         [], [np.float32(1.0)])
        assert "const_sig_reuses" not in _delta(c0)


def test_a_feed_that_arrives_otherwise_is_a_counted_miss():
    """The same logical feed, one entry arriving as a ``jax.Array`` or as
    a Python scalar: the program is built round one layout, so this is
    another entry — a miss that is counted and built in the open, never a
    compile under a ``cache_hit`` — and a replay that fed so raises."""
    exe = Executor(training=False)
    build = lambda: (lambda feed, state, const: ([feed[0] * feed[1]], []))
    a, b = np.arange(3, dtype=np.float32), np.float32(-2.0)
    c0 = _counters()
    (x,), _ = exe.run_callable("t/mixed", build, [a, b])
    (y,), _ = exe.run_callable("t/mixed", build, [jnp.asarray(a), b])
    d = _delta(c0)
    assert np.asarray(x).tolist() == np.asarray(y).tolist() == [-0.0, -2.0,
                                                                -4.0]
    assert d["cache_misses"] == 2 and d["shape_recompiles"] == 1
    assert "cache_hits" not in d and d["feed_transfers"] == 2
    assert len([k for k in exe._cache if k[0] == "callable"]) == 2
    # each form hits its own entry from then on, and compiles nothing
    c0 = _counters()
    exe.run_callable("t/mixed", build, [a + 1, b])
    exe.run_callable("t/mixed", build, [jnp.asarray(a) + 1, b])
    assert _delta(c0) == {"cache_hits": 2, "steps": 2, "feed_transfers": 2}

    def missed():
        raise RuntimeError("replay missed")

    exe.run_callable("t/mixed", missed, [a, b])
    with pytest.raises(RuntimeError, match="replay missed"):
        exe.run_callable("t/mixed", missed, [a, -2.0])


def test_signature_names_are_built_once_a_length():
    assert executor_mod._sig_names("c", 3) == ("c0", "c1", "c2")
    assert executor_mod._sig_names("", 2) == ("0", "1")
    assert executor_mod._sig_names("c", 300) is \
        executor_mod._sig_names("c", 300)


# ---------------------------------------------------------------------------
# a toy engine's tokens are the parent's
# ---------------------------------------------------------------------------

# the tokens commit 8e4cfb6 (seven ``jnp.asarray`` a launch) produced for
# these requests: one greedy, three sampled — a seed above 2**31, the full
# vocabulary, the largest seed
PINNED = [
    (SamplingParams(max_new_tokens=8),
     [11, 33, 10, 35, 16, 46, 16, 11]),
    (SamplingParams(max_new_tokens=10, temperature=0.9, top_k=8,
                    seed=0x9E3779B9),
     [16, 46, 34, 19, 11, 4, 16, 46, 46, 4]),
    (SamplingParams(max_new_tokens=6, temperature=1.3, seed=11),
     [17, 8, 46, 45, 40, 10]),
    (SamplingParams(max_new_tokens=9, temperature=0.7, top_k=3,
                    seed=2**32 - 1),
     [16, 27, 27, 27, 27, 27, 11, 27, 11]),
]


def test_a_toy_engines_greedy_and_sampled_tokens_are_the_parents():
    lm = TransformerLM(TINY)
    eng = DecodeEngine(lm, lm.init_params(seed=5), name="pinned",
                       max_slots=3, block_tokens=4, prefill_buckets=(8, 16))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 48, n).astype("int32") for n in (3, 6, 11, 5)]
    c0 = _counters()
    try:
        handles = [eng.submit(p, sp) for p, (sp, _) in zip(prompts, PINNED)]
        got = [[int(t) for t in h.result(timeout=120)["tokens"]]
               for h in handles]
    finally:
        eng.close()
    assert got == [want for _, want in PINNED]
    d = _delta(c0)
    # one transfer a launch, prefill or step, and the weights walked
    # once: every later launch, whatever its key, reuses their signature
    assert d["feed_transfers"] == d["steps"] and d["cache_misses"] == 3
    assert d["const_sig_reuses"] == d["steps"] - 1
