"""The paged decode walk of ``kernels/diffattn.py`` (one grid step a slot, a
slot's live blocks only, the next fetch always in flight) under its three
uses — differential attention over the shared pool, over a window ring, and
grouped-query attention (``kernels/gqa.py``) — in interpret mode: the walks of
``tests/paged_walks.py`` over every layer of a 3-layer pool, against the XLA
fallback and a dense per-slot reference, and what a walk may not read."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import diffattn as da
from paddle_tpu.kernels import gqa
from paddle_tpu.observability import stats
from paged_walks import WALKS, walk_case

NKV, DH, BS, CHUNK = 2, 128, 8, 4
KW = NKV * DH
# blocks a slot: a multiple of the chunk, not a multiple of it (a ragged last
# chunk), fewer than one chunk (the chunk is clipped to the table)
TABLES = {"a_multiple_of_the_chunk": 8, "not_a_multiple": 6,
          "shorter_than_a_chunk": 3}
# use → (query heads, the call, its XLA lowering, its fallback counter)
USES = {
    "diff_pool": (4, lambda *a: da.decode_attention(*a, NKV),
                  lambda *a: da.decode_attention_xla(*a, NKV),
                  "attn.diff_decode_fallbacks"),
    "diff_ring": (4, lambda *a: da.decode_attention(
        *a, NKV, name="diff_ring_decode_attn"),
        lambda *a: da.decode_attention_xla(*a, NKV),
        "attn.diff_decode_fallbacks"),
    "gqa": (10, lambda *a: gqa.decode_attention(*a, NKV),
            lambda *a: gqa.decode_attention_xla(*a, NKV),
            "attn.gqa_decode_fallbacks"),
}


@pytest.fixture(autouse=True)
def small_walk(monkeypatch):
    """Chunks of four blocks whose copies start two a trip: whole trips and
    a remainder both occur at these sizes, as at the served ones (32, 8)."""
    monkeypatch.setattr(da, "_CHUNK_BLOCKS", CHUNK)
    monkeypatch.setattr(da, "_COPY_UNROLL", 2)


def _dense(use, q, rows):
    """The definition, one slot: q [nh, 128], rows [n, 2·kw] (the slot's live
    rows) → [nh, 128], or both components' [nh, 2, 128], float64."""
    q, rows = np.asarray(q, np.float64), np.asarray(rows, np.float64)
    n, nh = rows.shape[0], q.shape[0]
    k, v = rows[:, :KW].reshape(n, NKV, DH), rows[:, KW:].reshape(n, NKV, DH)
    parts = 1 if use == "gqa" else 2
    d = DH // parts
    out = np.zeros((nh, parts, DH))
    for h in range(nh):
        g = h // (nh // NKV)
        for c in range(parts):
            s = q[h, c * d:(c + 1) * d] @ k[:, g, c * d:(c + 1) * d].T \
                / np.sqrt(d)
            p = np.exp(s - s.max())
            out[h, c] = (p / p.sum()) @ v[:, g]
    return out[:, 0] if use == "gqa" else out


def _case(use, contexts, MB, seed, poison=False, nan_slots=()):
    """q, pool [3, N, bs, 2·kw], tables, lengths.  A ring's table is the
    slot's own blocks in order (no trash block: an idle slot's ring is its
    own); with ``poison`` whatever a table names past the slot's context is
    NaN; the blocks of ``nan_slots`` are NaN whole."""
    rng = np.random.RandomState(seed)
    _, kc, vc, bt, cl = walk_case(rng, contexts, MB, bs=BS, H=NKV, D=DH, L=3,
                                  poison=poison and use != "diff_ring")
    pool = np.concatenate([np.asarray(kc), np.asarray(vc)], axis=-1)
    bt = np.asarray(bt).copy()
    S = len(contexts)
    if use == "diff_ring":
        bt = (2 + np.arange(S * MB, dtype=np.int32)).reshape(S, MB)
        if poison:
            for s, n in enumerate(contexts):
                pool[:, bt[s, -(-n // BS):]] = np.nan
    for s in nan_slots:
        pool[:, bt[s]] = np.nan
    q = rng.randn(S, USES[use][0], DH).astype("float32")
    return jnp.asarray(q), jnp.asarray(pool), jnp.asarray(bt), cl


def _check(use, got, q, pool, bt, cl, layer, skip=()):
    pool, bt = np.asarray(pool), np.asarray(bt)
    for s in range(q.shape[0]):
        if s in skip:
            continue
        rows = pool[layer][bt[s]].reshape(-1, 2 * KW)[:int(cl[s])]
        np.testing.assert_allclose(got[s], _dense(use, q[s], rows),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("table", sorted(TABLES))
@pytest.mark.parametrize("walk", sorted(WALKS))
@pytest.mark.parametrize("use", sorted(USES))
def test_the_walk_reads_a_slot_s_live_rows_of_every_layer(use, walk, table):
    MB = TABLES[table]
    contexts = WALKS[walk](min(CHUNK, MB) * BS, MB * BS)
    q, pool, bt, cl = _case(use, contexts, MB, seed=3)
    _, call, xla, counter = USES[use]
    before = stats.to_dict().get(counter, 0)
    step = jax.jit(call)
    for layer in range(3):
        got = np.asarray(step(q, pool, bt, cl, jnp.int32(layer)))
        _check(use, got, q, pool, bt, cl, layer)
        np.testing.assert_allclose(
            got, xla(q, pool, bt, cl, layer), rtol=1e-5, atol=1e-5)
        # the same launch again: nothing it left behind reaches a result
        np.testing.assert_array_equal(
            got, step(q, pool, bt, cl, jnp.int32(layer)))
    assert stats.to_dict().get(counter, 0) == before


@pytest.mark.parametrize("table", sorted(TABLES))
@pytest.mark.parametrize("use", sorted(USES))
def test_nothing_past_a_context_is_read_or_multiplied(use, table):
    """What a table names past a slot's context is NaN (a block that is
    copied or computed though dead shows in the output; the XLA lowering
    reads them all — ``0 x NaN`` — and is no reference here), and so is what
    the slots before it left in BOTH halves of the buffer: two adjacent
    slots whose live rows are NaN whole, twice, so that every later slot's
    rows past its frontier are theirs."""
    MB = TABLES[table]
    full = MB * BS
    contexts = WALKS["all_of_these_in_adjacent_slots"](
        min(CHUNK, MB) * BS, full)
    contexts = [full, full] + contexts[:6] + [full, full] + contexts[6:]
    nan_slots = (0, 1, 8, 9)
    q, pool, bt, cl = _case(use, contexts, MB, seed=4, poison=True,
                            nan_slots=nan_slots)
    got = np.asarray(jax.jit(USES[use][1])(q, pool, bt, cl, jnp.int32(1)))
    for s in nan_slots:         # the poison did pass through the buffer
        assert np.isnan(got[s]).all()
    rest = [s for s in range(len(contexts)) if s not in nan_slots]
    assert np.isfinite(got[rest]).all()
    _check(use, got, q, pool, bt, cl, 1, skip=nan_slots)
