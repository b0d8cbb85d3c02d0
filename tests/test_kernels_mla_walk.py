"""The latent decode walk of ``kernels/mla.py`` (``mla_paged_decode_attn``:
one grid step a slot, a slot's live blocks only, the next fetch always in
flight, the softmax state in the chunk loop's carry — the schedule it shares
with ``kernels/diffattn.py``) in interpret mode: the walks of
``tests/paged_walks.py`` over every layer of a 3-layer pool at 16 and 32
heads, the layer an int and a traced scalar, against the XLA lowering and a
float64 per-slot definition, and what a walk may not read."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import diffattn as da
from paddle_tpu.kernels import mla as MK
from paddle_tpu.observability import stats
from paged_walks import WALKS

RANK, ROPE, BS, CHUNK, SCALE = 96, 16, 8, 4, 0.3
W = MK.row_width(RANK, ROPE)
# blocks a slot: a multiple of the chunk, not a multiple of it (a ragged last
# chunk), fewer than one chunk (the chunk is clipped to the table)
TABLES = {"a_multiple_of_the_chunk": 8, "not_a_multiple": 6,
          "shorter_than_a_chunk": 3}
COUNTER = "mla.decode_attn_fallbacks"


@pytest.fixture(autouse=True)
def small_walk(monkeypatch):
    """Chunks of four blocks whose copies start two a trip: whole trips and
    a remainder both occur at these sizes, as at the served ones (32, 8)."""
    monkeypatch.setattr(MK, "_CHUNK_BLOCKS", CHUNK)
    monkeypatch.setattr(da, "_COPY_UNROLL", 2)


def _dense(q, rows):
    """The definition, one slot: q [H, W], rows [n, W] (the slot's live
    rows) → [H, rank], float64."""
    q, rows = np.asarray(q, np.float64), np.asarray(rows, np.float64)
    s = q @ rows.T * SCALE
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ rows[:, :RANK]


def _case(heads, contexts, MB, seed, poison=False, nan_slots=()):
    """q [S, H, W], pool [3, N, bs, W] (lanes past rank + rope zero, as the
    engine writes them), tables, lengths.  A one-token slot's table is all
    trash block 0 (an idle decode slot); every other slot's table is full of
    blocks of its own, also past its context — with ``poison`` those dead
    entries name block 1, which is NaN; the blocks of ``nan_slots`` are NaN
    whole."""
    rng = np.random.RandomState(seed)
    S = len(contexts)
    pool = np.zeros((3, 2 + S * MB, BS, W), np.float32)
    pool[..., :RANK + ROPE] = rng.randn(*pool.shape[:-1], RANK + ROPE)
    bt = (2 + rng.permutation(S * MB)).reshape(S, MB).astype(np.int32)
    for s, n in enumerate(contexts):
        if n == 1:
            bt[s, :] = 0
        elif poison:
            bt[s, -(-n // BS):] = 1
    if poison:
        pool[:, 1] = np.nan
    for s in nan_slots:
        pool[:, bt[s]] = np.nan
    q = np.zeros((S, heads, W), np.float32)
    q[..., :RANK + ROPE] = rng.randn(S, heads, RANK + ROPE)
    return (jnp.asarray(q), jnp.asarray(pool), jnp.asarray(bt),
            jnp.asarray(np.asarray(contexts, np.int32)))


def _check(got, q, pool, bt, cl, layer, skip=()):
    pool, bt = np.asarray(pool), np.asarray(bt)
    for s in range(q.shape[0]):
        if s in skip:
            continue
        rows = pool[layer][bt[s]].reshape(-1, W)[:int(cl[s])]
        np.testing.assert_allclose(got[s], _dense(q[s], rows),
                                   rtol=1e-5, atol=1e-5)


def _walk(q, pool, bt, cl, layer):
    return MK.decode_attention(q, pool, bt, cl, layer, RANK, SCALE)


@pytest.mark.parametrize("layer_as", ["an_int", "a_traced_scalar"])
@pytest.mark.parametrize("heads", [16, 32])
@pytest.mark.parametrize("table", sorted(TABLES))
@pytest.mark.parametrize("walk", sorted(WALKS))
def test_the_walk_reads_a_slot_s_live_rows_of_every_layer(walk, table, heads,
                                                          layer_as):
    MB = TABLES[table]
    contexts = WALKS[walk](min(CHUNK, MB) * BS, MB * BS)
    q, pool, bt, cl = _case(heads, contexts, MB, seed=5)
    before = stats.to_dict().get(COUNTER, 0)
    traced = jax.jit(_walk)
    for layer in range(3):
        if layer_as == "an_int":
            got = np.asarray(_walk(q, pool, bt, cl, layer))
        else:
            got = np.asarray(traced(q, pool, bt, cl, jnp.int32(layer)))
        assert got.shape == (len(contexts), heads, RANK)
        assert got.dtype == np.float32
        _check(got, q, pool, bt, cl, layer)
        np.testing.assert_allclose(
            got, MK.decode_attention_xla(q, pool, bt, cl, layer, RANK, SCALE),
            rtol=1e-5, atol=1e-5)
        # the same launch again: nothing it left behind reaches a result
        np.testing.assert_array_equal(
            got, traced(q, pool, bt, cl, jnp.int32(layer)))
    assert stats.to_dict().get(COUNTER, 0) == before


@pytest.mark.parametrize("heads", [16, 32])
@pytest.mark.parametrize("table", sorted(TABLES))
def test_nothing_past_a_context_is_read_or_multiplied(table, heads):
    """What a table names past a slot's context is NaN (a block that is
    copied or computed though dead shows in the output: here the value IS
    the row, so a dead row that reached the value product would be ``0 x
    NaN``; the XLA lowering reads them all and is no reference here), and so
    is what the slots before it left in BOTH halves of the buffer: two
    adjacent slots whose live rows are NaN whole, twice, so that every later
    slot's rows past its frontier are theirs — idle one-token slots on the
    trash block between full tables among them."""
    MB = TABLES[table]
    full = MB * BS
    contexts = WALKS["all_of_these_in_adjacent_slots"](
        min(CHUNK, MB) * BS, full)
    contexts = [full, full] + contexts[:6] + [full, full] + contexts[6:]
    nan_slots = (0, 1, 8, 9)
    q, pool, bt, cl = _case(heads, contexts, MB, seed=6, poison=True,
                            nan_slots=nan_slots)
    got = np.asarray(jax.jit(_walk)(q, pool, bt, cl, jnp.int32(1)))
    for s in nan_slots:         # the poison did pass through the buffer
        assert np.isnan(got[s]).all()
    rest = [s for s in range(len(contexts)) if s not in nan_slots]
    assert np.isfinite(got[rest]).all()
    _check(got, q, pool, bt, cl, 1, skip=nan_slots)


def test_the_call_is_one_kernel_on_a_grid_of_slots_and_pads_no_table():
    """Grid ``(S,)``, the tables as they are handed over (no ``pad`` in the
    program though the table is not a multiple of the chunk), a double
    buffer, two byte-counting semaphores and the parity in SMEM; the scanned
    layers and an unscanned one beside them trace the kernel once."""
    from paged_walks import eqns_under
    q, pool, bt, cl = _case(16, [5, 48, 1], 6, seed=7)

    def program(q, pool, bt, cl):
        first = _walk(q, pool, bt, cl, 0)
        _, rest = jax.lax.scan(
            lambda c, layer: (c, _walk(q, pool, bt, cl, layer)), 0,
            jnp.arange(1, 3, dtype=jnp.int32))
        return first, rest

    eqns = list(eqns_under(jax.make_jaxpr(program)(q, pool, bt, cl).jaxpr))
    assert "pad" not in {e.primitive.name for e in eqns}
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 2 and len({id(e.params["jaxpr"]) for e in calls}) == 1
    call = calls[0]
    assert call.params["name"] == "mla_paged_decode_attn"
    gm = call.params["grid_mapping"]
    assert tuple(gm.grid) == (3,) and gm.num_index_operands == 3
    inside = [e.primitive.name for e in eqns_under(call.params["jaxpr"])]
    assert "dma_start" in inside and "dma_wait" in inside
    first, rest = jax.jit(program)(q, pool, bt, cl)
    for layer, got in enumerate([first, rest[0], rest[1]]):
        _check(np.asarray(got), q, pool, bt, cl, layer)
