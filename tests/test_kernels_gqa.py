"""Grouped-query attention's kernels (``kernels/gqa.py``) in interpret mode
against dense attention with rotary positions: the paged decode kernel over a
pool of several layers (the walks of ``tests/paged_walks.py``) and the causal
flash prefill."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.decode.adapter import rotary
from paddle_tpu.kernels import diffattn as da
from paddle_tpu.kernels import gqa
from paddle_tpu.observability import stats
from paged_walks import WALKS, walk_case

NH, NKV, DH = 10, 2, 128        # a K/V-head group of 5, as published
KW = NKV * DH
THETA = 1e11


def _dense(q, k, v, keep):
    """The definition: q [T, nh, dh], k, v [J, nkv, dh], keep [T, J] → [T,
    nh, dh], float64."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    out = np.zeros(q.shape)
    for h in range(NH):
        g = h // (NH // NKV)
        s = np.where(keep, q[:, h] @ k[:, g].T / np.sqrt(DH), -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = (p / p.sum(-1, keepdims=True)) @ v[:, g]
    return out


def _rows(k, v):
    return jnp.concatenate([k.reshape(k.shape[0], KW),
                            v.reshape(v.shape[0], KW)], axis=-1)


def _tiles(monkeypatch, rows, columns):
    """The group flash forward's plan at a test's size: query tiles of
    ``rows``, key tiles of up to ``columns``."""
    monkeypatch.setattr(gqa, "_Q_TILE", rows)
    monkeypatch.setattr(gqa, "_K_TILE", columns)


@pytest.mark.parametrize("walk", sorted(WALKS))
def test_paged_decode_kernel_walks_only_a_slot_s_live_rows(monkeypatch, walk):
    """Contexts by where they end against a chunk of the walk, idle slots
    (one token, an all-trash table) among them, in adjacent slots."""
    chunk, bs, MB = 2, 8, 6
    monkeypatch.setattr(da, "_CHUNK_BLOCKS", chunk)
    rng = np.random.RandomState(3)
    contexts = WALKS[walk](chunk * bs, MB * bs)
    _, kc, vc, bt, cl = walk_case(rng, contexts, MB, bs=bs, H=NKV, D=DH, L=3)
    pool = jnp.concatenate([kc, vc], axis=-1)
    S = len(contexts)
    q = jnp.asarray(rng.randn(S, NH, DH).astype("float32"))
    before = stats.to_dict().get("attn.gqa_decode_fallbacks", 0)
    got = jax.jit(lambda *a: gqa.decode_attention(*a, NKV))(
        q, pool, bt, cl, jnp.int32(2))
    assert stats.to_dict().get("attn.gqa_decode_fallbacks", 0) == before
    xla = gqa.decode_attention_xla(q, pool, bt, cl, 2, NKV)
    for s in range(S):
        n = int(cl[s])
        rows = np.asarray(pool)[2][np.asarray(bt)[s]].reshape(-1, 2 * KW)[:n]
        want = _dense(np.asarray(q)[s:s + 1], rows[:, :KW].reshape(n, NKV, DH),
                      rows[:, KW:].reshape(n, NKV, DH), np.ones((1, n), bool))
        np.testing.assert_allclose(got[s], want[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(xla[s], want[0], rtol=1e-5, atol=1e-5)


def test_decode_over_rotated_keys_equals_dense_attention_at_those_positions():
    """Keys are cached after their rotation and the query rotated at its own
    position: the paged result is dense attention over rotated q and k."""
    rng = np.random.default_rng(1)
    bs, MB, n = 8, 4, 27
    k = jnp.asarray(rng.standard_normal((n, NKV, DH)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((n, NKV, DH)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((1, NH, DH)), jnp.float32)
    kr = rotary(k, jnp.arange(n), THETA)
    qr = rotary(q, jnp.asarray([n - 1]), THETA)
    pool = jnp.zeros((1, 1 + MB, bs, 2 * KW), jnp.float32)
    rows = jnp.pad(_rows(kr, v), ((0, MB * bs - n), (0, 0)))
    pool = pool.at[0, 1:].set(rows.reshape(MB, bs, 2 * KW))
    bt = jnp.arange(1, 1 + MB, dtype=jnp.int32)[None]
    got = gqa.decode_attention(qr, pool, bt, jnp.asarray([n], jnp.int32), 0,
                               NKV)
    want = _dense(qr, kr, v, np.ones((1, n), bool))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # and it is NOT attention without positions
    plain = _dense(q, k, v, np.ones((1, n), bool))
    assert np.abs(np.asarray(got) - plain).max() > 1e-2


@pytest.mark.parametrize("T,tile,wide,length", [
    (32, 8, 8, None), (16, 16, 16, None), (24, 8, 8, None),
    (64, 8, 32, None), (64, 8, 32, 27), (48, 8, 32, 40)],
    ids=["four_tiles", "one_tile", "three_tiles", "wide_key_tiles",
         "length_inside_a_tile", "length_on_a_tile_s_edge"])
def test_flash_prefill_matches_dense_causal_attention(monkeypatch, T, tile,
                                                      wide, length):
    """``prefill_attention`` is the group flash forward with no window under
    the name ``gqa_flash_fwd``: a group of five over rotated q and k."""
    _tiles(monkeypatch, tile, wide)
    rng = np.random.default_rng(T)
    pos = jnp.arange(T)
    q = rotary(jnp.asarray(rng.standard_normal((T, NH, DH)), jnp.float32),
               pos, THETA)
    k = rotary(jnp.asarray(rng.standard_normal((T, NKV, DH)), jnp.float32),
               pos, THETA)
    v = jnp.asarray(rng.standard_normal((T, NKV, DH)), jnp.float32)
    before = stats.to_dict().get("attn.gqa_prefill_fallbacks", 0)
    fn = jax.jit(lambda q, r: gqa.prefill_attention(q, r, NKV, length))
    got = fn(q, _rows(k, v))
    assert stats.to_dict().get("attn.gqa_prefill_fallbacks", 0) == before
    from paged_walks import eqns_under
    assert [e.params["name"] for e in eqns_under(
        jax.make_jaxpr(fn)(q, _rows(k, v)).jaxpr)
        if e.primitive.name == "pallas_call"] == ["gqa_flash_fwd"]
    keep = np.tril(np.ones((T, T), bool))
    want = _dense(q, k, v, keep)
    real = T if length is None else length
    np.testing.assert_allclose(got[:real], want[:real], rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[-(-real // tile) * tile:]).any()
    np.testing.assert_allclose(gqa.prefill_attention_xla(q, _rows(k, v), NKV),
                               want, rtol=1e-5, atol=1e-5)


def test_another_head_size_falls_back_and_counts():
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((8, 4, 16)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((8, 2 * 2 * 16)), jnp.float32)
    before = stats.to_dict().get("attn.gqa_prefill_fallbacks", 0)
    got = gqa.prefill_attention(q, rows, 2)
    assert stats.to_dict()["attn.gqa_prefill_fallbacks"] == before + 1
    np.testing.assert_array_equal(got, gqa.prefill_attention_xla(q, rows, 2))


# -- a group of seven (28 query heads over 4 K/V heads, cut to 14 over 2):
# the window flash forward, the group as the rows of one product, the ring --
GNH = 14


def _dense_g(q, k, v, keep):
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    out = np.zeros(q.shape)
    for h in range(q.shape[1]):
        g = h // (q.shape[1] // k.shape[1])
        s = np.where(keep, q[:, h] @ k[:, g].T / np.sqrt(DH), -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = (p / p.sum(-1, keepdims=True)) @ v[:, g]
    return out


def _window_keep(T, window):
    t = np.arange(T)
    keep = t[:, None] >= t[None, :]
    if window is not None:
        keep &= t[:, None] - t[None, :] < window
    return keep


# (T, query rows, widest key tile, window, length): square tiles, then key
# tiles wider than a query tile, then prompts with padding behind them
FLASH_CASES = {
    "no_window": (64, 8, 8, None, None),
    "window_mid_tile": (64, 8, 8, 20, None),
    "window_one_tile": (64, 8, 8, 8, None),
    "window_at_T": (64, 8, 8, 64, None),
    "window_past_T": (64, 8, 8, 200, None),
    "three_tiles_deep": (48, 16, 16, 17, None),
    "one_tile": (16, 16, 16, 5, None),
    "wide_no_window": (64, 8, 32, None, None),
    "wide_window_inside_a_key_tile": (96, 8, 32, 43, None),
    "wide_window_on_a_key_tile_s_edge": (96, 8, 32, 33, None),
    "wide_window_of_one_key_tile": (96, 8, 32, 32, None),
    "wide_prompt_shorter_than_a_key_tile": (16, 8, 32, None, None),
    "wide_T_not_a_multiple_of_the_widest": (48, 8, 32, 20, None),
    "length_inside_a_tile": (64, 8, 32, None, 27),
    "length_on_a_tile_s_edge": (64, 8, 32, 20, 40),
    "length_on_a_key_tile_s_edge": (96, 8, 32, 43, 64),
    "length_of_zero": (64, 8, 32, 20, 0),
    "length_of_T": (64, 8, 32, None, 64),
    "length_of_one": (64, 8, 16, 9, 1),
    "square_tiles_with_a_length": (64, 8, 8, 20, 35),
}


def _check_flash(q, rows, n_kv, window, length, want, q_tile):
    """The group flash forward against the dense result ``want``: every row
    with no ``length``; with one, the real rows, and exact zeros in the query
    tiles that hold only padding."""
    T = q.shape[0]
    before = stats.to_dict().get("attn.gqa_window_prefill_fallbacks", 0)
    got = jax.jit(lambda q, r, n: gqa.group_prefill_attention(
        q, r, n_kv, window, n))(
            q, rows, None if length is None else jnp.int32(length))
    assert stats.to_dict().get(
        "attn.gqa_window_prefill_fallbacks", 0) == before
    real = T if length is None else length
    np.testing.assert_allclose(got[:real], want[:real], rtol=1e-5, atol=1e-5)
    skipped = -(-real // q_tile) * q_tile
    assert not np.asarray(got[skipped:]).any()
    # a tile the prompt ends in is computed whole, pad rows and all
    np.testing.assert_allclose(got[real:skipped], want[real:skipped],
                               rtol=1e-5, atol=1e-5)
    if length is not None:
        # with no length the same real rows, to the bit
        np.testing.assert_array_equal(
            got[:real], gqa.group_prefill_attention(q, rows, n_kv, window
                                                    )[:real])


@pytest.mark.parametrize("case", list(FLASH_CASES))
def test_group_flash_matches_dense_windowed_attention(monkeypatch, case):
    """Prompts shorter than, as long as and several times the window; a
    window that ends inside a tile and on a tile's edge; key tiles as wide as
    a query tile and four times as wide; real lengths inside a tile, on a
    tile's edge, of nothing and of the whole rung."""
    T, q_tile, k_tile, window, length = FLASH_CASES[case]
    _tiles(monkeypatch, q_tile, k_tile)
    rng = np.random.default_rng(T + (window or 0))
    q = jnp.asarray(rng.standard_normal((T, GNH, DH)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((T, NKV, DH)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((T, NKV, DH)), jnp.float32)
    want = _dense_g(q, k, v, _window_keep(T, window))
    _check_flash(q, _rows(k, v), NKV, window, length, want, min(T, q_tile))
    np.testing.assert_allclose(
        gqa.prefill_attention_xla(q, _rows(k, v), NKV, window), want,
        rtol=1e-5, atol=1e-5)
    if window is not None and window < T:
        # and it is NOT causal attention without a window
        assert np.abs(want - _dense_g(q, k, v, _window_keep(T, None))
                      ).max() > 1e-2


def test_the_dense_reference_takes_a_block_of_queries_at_a_time():
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.standard_normal((32, GNH, DH)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((32, 2 * KW)), jnp.float32)
    whole = gqa.prefill_attention_xla(q, rows, NKV, 11)
    np.testing.assert_allclose(
        gqa.prefill_attention_xla(q[8:24], rows[:24], NKV, 11, start=8),
        whole[8:24], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("T,q_tile,k_tile,window,plan", [
    (12288, 256, 1024, None, (256, 1024, 12)),
    (12288, 256, 1024, 4096, (256, 1024, 5)),
    (12288, 256, 512, 4096, (256, 512, 9)),
    (2560, 256, 1024, None, (256, 512, 5)),     # the widest that divides T
    (1536, 256, 1024, None, (256, 768, 2)),
    (512, 256, 1024, 4096, (256, 512, 1)),
    (64, 256, 1024, None, (64, 64, 1)),
    (48, 8, 32, 20, (8, 24, 2)),
    (300, 256, 1024, None, (256, 256, 1))],      # no tile divides T: XLA's
    ids=["full_12288", "window_12288", "window_12288_of_512", "T_2560",
         "T_1536", "one_key_tile", "below_a_query_tile", "small",
         "no_tile_divides_T"])
def test_flash_plan_takes_the_widest_key_tile_that_divides_the_prompt(
        monkeypatch, T, q_tile, k_tile, window, plan):
    _tiles(monkeypatch, q_tile, k_tile)
    assert gqa.flash_plan(T, window) == plan


@pytest.mark.parametrize("window,length", [(None, 64), (20, 64), (None, 27),
                                           (20, 40), (43, 1), (20, 0)])
def test_the_walk_fetches_a_visible_tile_once_and_nothing_for_padding(
        window, length):
    """The K/V index map over a K/V head's grid steps, in the order the grid
    walks them: a change of index is a fetch."""
    T, bq, bk = 96, 8, 32
    first, last = gqa._key_span(np.arange(T // bq), bq, bk, window, np)
    n_kw = int((last - first).max()) + 1
    walk = [int(gqa._key_tile(jnp.int32(i), jnp.int32(j), jnp.int32(length),
                              bq, bk, window))
            for i in range(T // bq) for j in range(n_kw)]
    fetched = [t for at, t in enumerate(walk) if at == 0 or t != walk[at - 1]]
    keep = _window_keep(T, window)
    want = []       # every real query tile's visible key tiles, in order
    for i in range(-(-length // bq)):
        cols = np.flatnonzero(keep[i * bq:(i + 1) * bq].any(0))
        want += sorted(set(cols // bk))
    want = [t for at, t in enumerate(want) if at == 0 or t != want[at - 1]]
    assert fetched == (want or fetched[:1])


def test_group_flash_names_follow_the_window_and_fetch_a_tile_once_a_group(
        monkeypatch):
    _tiles(monkeypatch, 8, 32)
    from paged_walks import eqns_under
    q = jnp.zeros((96, GNH, DH), jnp.bfloat16)
    rows = jnp.zeros((96, 2 * KW), jnp.bfloat16)
    for window, name, tiles in ((None, "gqa_group_flash_fwd", 3),
                                (20, "gqa_window_flash_fwd", 2)):
        calls = [e for e in eqns_under(jax.make_jaxpr(
            lambda q, r, n: gqa.group_prefill_attention(q, r, NKV, window, n)
        )(q, rows, jnp.int32(50)).jaxpr) if e.primitive.name == "pallas_call"]
        assert [e.params["name"] for e in calls] == [name]
        # one grid step a K/V head, not a query head, a (query tile of 8,
        # key tile of 32) pair: a K/V tile is fetched once a group
        grid = calls[0].params["grid_mapping"]
        assert tuple(grid.grid) == (NKV, 12, tiles)
        # the prompt's length rides the call as a prefetched scalar
        assert grid.num_index_operands == 1


def test_group_flash_at_another_head_size_falls_back_and_counts():
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((8, 4, 16)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((8, 2 * 2 * 16)), jnp.float32)
    before = stats.to_dict().get("attn.gqa_window_prefill_fallbacks", 0)
    got = gqa.group_prefill_attention(q, rows, 2, 3)
    assert stats.to_dict()["attn.gqa_window_prefill_fallbacks"] == before + 1
    np.testing.assert_array_equal(
        got, gqa.prefill_attention_xla(q, rows, 2, 3))


@pytest.mark.parametrize("contexts", [
    [5, 1, 31], [32, 32, 1], [33, 100, 64, 47]],
    ids=["below_the_window", "at_the_window", "above_the_window"])
def test_ring_walk_reads_a_slot_s_live_ring_rows_under_its_own_name(
        monkeypatch, contexts):
    """A ring of W = 32 rows in blocks of 8 a slot: a stream's rows lie at
    ``position mod W``; the walk reads ``min(context, W)`` of them, which are
    the window's keys whatever their order.  Three window layers; the walk is
    of layer 1."""
    W, rb, layers = 32, 8, 3
    monkeypatch.setattr(da, "_CHUNK_BLOCKS", 2)
    rng = np.random.default_rng(len(contexts))
    S, nrb = len(contexts), W // rb
    longest = max(contexts)
    k = rng.standard_normal((S, longest, NKV, DH)).astype("float32")
    v = rng.standard_normal((S, longest, NKV, DH)).astype("float32")
    rings = rng.standard_normal((layers, S * nrb, rb, 2 * KW)
                                ).astype("float32")
    for s, n in enumerate(contexts):
        for t in range(n):          # later positions overwrite earlier ones
            rings[1, s * nrb + (t % W) // rb, t % rb] = np.concatenate(
                [k[s, t].reshape(-1), v[s, t].reshape(-1)])
    q = jnp.asarray(rng.standard_normal((S, GNH, DH)), jnp.float32)
    tables = (np.arange(S)[:, None] * nrb + np.arange(nrb)).astype("int32")
    live = np.minimum(contexts, W).astype("int32")
    before = stats.to_dict().get("attn.gqa_ring_decode_fallbacks", 0)
    fn = jax.jit(lambda q, r, t, n, l: gqa.ring_decode_attention(
        q, r, t, n, l, NKV))
    got = fn(q, jnp.asarray(rings), jnp.asarray(tables), jnp.asarray(live),
             jnp.int32(1))
    assert stats.to_dict().get("attn.gqa_ring_decode_fallbacks", 0) == before
    from paged_walks import eqns_under
    names = [e.params["name"] for e in eqns_under(jax.make_jaxpr(fn)(
        q, jnp.asarray(rings), jnp.asarray(tables), jnp.asarray(live),
        jnp.int32(1)).jaxpr) if e.primitive.name == "pallas_call"]
    assert names == ["gqa_ring_decode_attn"]
    xla = gqa.decode_attention_xla(q, jnp.asarray(rings), jnp.asarray(tables),
                                   jnp.asarray(live), 1, NKV)
    for s, n in enumerate(contexts):
        lo = max(0, n - W)
        want = _dense_g(np.asarray(q)[s:s + 1], k[s, lo:n], v[s, lo:n],
                        np.ones((1, n - lo), bool))
        np.testing.assert_allclose(got[s], want[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(xla[s], want[0], rtol=1e-5, atol=1e-5)


def test_ring_walk_at_another_head_size_falls_back_and_counts():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((2, 4, 16)), jnp.float32)
    rings = jnp.asarray(rng.standard_normal((1, 4, 8, 2 * 2 * 16)),
                        jnp.float32)
    tables = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    live = jnp.asarray([3, 16], jnp.int32)
    before = stats.to_dict().get("attn.gqa_ring_decode_fallbacks", 0)
    got = gqa.ring_decode_attention(q, rings, tables, live, 0, 2)
    assert stats.to_dict()["attn.gqa_ring_decode_fallbacks"] == before + 1
    np.testing.assert_array_equal(
        got, gqa.decode_attention_xla(q, rings, tables, live, 0, 2))


# -- 64-wide heads (32 query heads over 8 K/V heads, cut to 16 over 4): a
# lane tile of a row is a PAIR of K/V heads, a pair's 2 x 4 query heads the
# rows that share it --
H64, NH64, NKV64 = 64, 16, 4
KW64 = NKV64 * H64


def _dense64(q, k, v, keep):
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    out = np.zeros(q.shape)
    for h in range(q.shape[1]):
        g = h // (q.shape[1] // k.shape[1])
        s = np.where(keep, q[:, h] @ k[:, g].T / np.sqrt(H64), -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = (p / p.sum(-1, keepdims=True)) @ v[:, g]
    return out


def _rows64(k, v):
    return jnp.concatenate([k.reshape(k.shape[0], KW64),
                            v.reshape(v.shape[0], KW64)], axis=-1)


@pytest.mark.parametrize("walk", sorted(WALKS))
def test_paged_walk_at_heads_of_64_pairs_the_kv_heads_of_a_tile(monkeypatch,
                                                                walk):
    chunk, bs, MB = 2, 8, 6
    monkeypatch.setattr(da, "_CHUNK_BLOCKS", chunk)
    rng = np.random.RandomState(11)
    contexts = WALKS[walk](chunk * bs, MB * bs)
    _, kc, vc, bt, cl = walk_case(rng, contexts, MB, bs=bs, H=NKV64, D=H64,
                                  L=2)
    pool = jnp.concatenate([kc, vc], axis=-1)
    S = len(contexts)
    q = jnp.asarray(rng.randn(S, NH64, H64).astype("float32"))
    before = stats.to_dict().get("attn.gqa_decode_fallbacks", 0)
    got = jax.jit(lambda *a: gqa.decode_attention(*a, NKV64))(
        q, pool, bt, cl, jnp.int32(1))
    assert stats.to_dict().get("attn.gqa_decode_fallbacks", 0) == before
    for s in range(S):
        n = int(cl[s])
        rows = np.asarray(pool)[1][np.asarray(bt)[s]].reshape(-1, 2 * KW64)[:n]
        want = _dense64(np.asarray(q)[s:s + 1],
                        rows[:, :KW64].reshape(n, NKV64, H64),
                        rows[:, KW64:].reshape(n, NKV64, H64),
                        np.ones((1, n), bool))
        np.testing.assert_allclose(got[s:s + 1], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        got, gqa.decode_attention_xla(q, pool, bt, cl, 1, NKV64), rtol=1e-5,
        atol=1e-5)


@pytest.mark.parametrize("case", [
    "no_window", "three_tiles_deep", "one_tile", "wide_no_window",
    "wide_window_inside_a_key_tile", "wide_T_not_a_multiple_of_the_widest",
    "length_inside_a_tile", "length_on_a_tile_s_edge", "length_of_zero",
    "length_of_T"])
def test_group_flash_at_heads_of_64_matches_dense_attention(monkeypatch,
                                                            case):
    T, q_tile, k_tile, window, length = FLASH_CASES[case]
    _tiles(monkeypatch, q_tile, k_tile)
    rng = np.random.default_rng(T + (window or 0))
    q = jnp.asarray(rng.standard_normal((T, NH64, H64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((T, NKV64, H64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((T, NKV64, H64)), jnp.float32)
    want = _dense64(q, k, v, _window_keep(T, window))
    _check_flash(q, _rows64(k, v), NKV64, window, length, want,
                 min(T, q_tile))
    np.testing.assert_allclose(
        gqa.prefill_attention_xla(q, _rows64(k, v), NKV64, window), want,
        rtol=1e-5, atol=1e-5)


def test_kernels_at_heads_of_64_have_names_of_their_own_and_a_tile_a_pair(
        monkeypatch):
    _tiles(monkeypatch, 8, 8)
    from paged_walks import eqns_under

    def calls(fn, *args):
        return [e for e in eqns_under(jax.make_jaxpr(fn)(*args).jaxpr)
                if e.primitive.name == "pallas_call"]

    q = jnp.zeros((64, NH64, H64), jnp.bfloat16)
    rows = jnp.zeros((64, 2 * KW64), jnp.bfloat16)
    for window, name in ((None, "gqa64_group_flash_fwd"),
                         (20, "gqa64_window_flash_fwd")):
        got = calls(lambda q, r: gqa.group_prefill_attention(
            q, r, NKV64, window), q, rows)
        assert [e.params["name"] for e in got] == [name]
        # one grid step a PAIR of K/V heads
        assert tuple(got[0].params["grid_mapping"].grid)[:2] == (NKV64 // 2, 8)
    pool = jnp.zeros((1, 8, 8, 2 * KW64), jnp.bfloat16)
    bt = jnp.zeros((2, 4), jnp.int32)
    cl = jnp.ones((2,), jnp.int32)
    qd = jnp.zeros((2, NH64, H64), jnp.bfloat16)
    for fn, name in ((gqa.decode_attention, "gqa64_paged_decode_attn"),
                     (gqa.ring_decode_attention, "gqa64_ring_decode_attn")):
        got = calls(lambda q, p: fn(q, p, bt, cl, 0, NKV64), qd, pool)
        assert [e.params["name"] for e in got] == [name]


def test_an_odd_count_of_kv_heads_of_64_falls_back():
    assert gqa.tiled(64, 8) and gqa.tiled(128, 3) and not gqa.tiled(64, 3)
    assert not gqa.tiled(32, 4)
