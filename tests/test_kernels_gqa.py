"""Grouped-query attention's kernels (``kernels/gqa.py``) in interpret mode
against dense attention with rotary positions: the paged decode kernel over a
pool of several layers (the walks of ``tests/paged_walks.py``) and the causal
flash prefill."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.decode.falcon_h1 import rotary
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


@pytest.mark.parametrize("T,tile", [(32, 8), (16, 16), (24, 8)],
                         ids=["four_tiles", "one_tile", "three_tiles"])
def test_flash_prefill_matches_dense_causal_attention(monkeypatch, T, tile):
    monkeypatch.setattr(gqa, "_FLASH_BLOCK", tile)
    rng = np.random.default_rng(T)
    pos = jnp.arange(T)
    q = rotary(jnp.asarray(rng.standard_normal((T, NH, DH)), jnp.float32),
               pos, THETA)
    k = rotary(jnp.asarray(rng.standard_normal((T, NKV, DH)), jnp.float32),
               pos, THETA)
    v = jnp.asarray(rng.standard_normal((T, NKV, DH)), jnp.float32)
    before = stats.to_dict().get("attn.gqa_prefill_fallbacks", 0)
    got = jax.jit(lambda q, r: gqa.prefill_attention(q, r, NKV))(
        q, _rows(k, v))
    assert stats.to_dict().get("attn.gqa_prefill_fallbacks", 0) == before
    keep = np.tril(np.ones((T, T), bool))
    want = _dense(q, k, v, keep)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
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


@pytest.mark.parametrize("T,tile,window", [
    (64, 8, None), (64, 8, 20), (64, 8, 8), (64, 8, 64), (64, 8, 200),
    (48, 16, 17), (16, 16, 5)],
    ids=["no_window", "window_mid_tile", "window_one_tile", "window_at_T",
         "window_past_T", "three_tiles_deep", "one_tile"])
def test_group_flash_matches_dense_windowed_attention(monkeypatch, T, tile,
                                                      window):
    """Prompts shorter than, as long as and several times the window; a
    window that ends inside a tile and on a tile's edge."""
    monkeypatch.setattr(da, "_FLASH_BLOCK", tile)
    rng = np.random.default_rng(T + (window or 0))
    q = jnp.asarray(rng.standard_normal((T, GNH, DH)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((T, NKV, DH)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((T, NKV, DH)), jnp.float32)
    before = stats.to_dict().get("attn.gqa_window_prefill_fallbacks", 0)
    got = jax.jit(lambda q, r: gqa.group_prefill_attention(q, r, NKV, window)
                  )(q, _rows(k, v))
    assert stats.to_dict().get(
        "attn.gqa_window_prefill_fallbacks", 0) == before
    want = _dense_g(q, k, v, _window_keep(T, window))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        gqa.prefill_attention_xla(q, _rows(k, v), NKV, window), want,
        rtol=1e-5, atol=1e-5)
    if window is not None and window < T:
        # and it is NOT causal attention without a window
        assert np.abs(want - _dense_g(q, k, v, _window_keep(T, None))
                      ).max() > 1e-2


def test_group_flash_names_follow_the_window_and_fetch_a_tile_once_a_group(
        monkeypatch):
    monkeypatch.setattr(da, "_FLASH_BLOCK", 8)
    from paged_walks import eqns_under
    q = jnp.zeros((64, GNH, DH), jnp.bfloat16)
    rows = jnp.zeros((64, 2 * KW), jnp.bfloat16)
    for window, name, tiles in ((None, "gqa_group_flash_fwd", 8),
                                (20, "gqa_window_flash_fwd", 4)):
        calls = [e for e in eqns_under(jax.make_jaxpr(
            lambda q, r: gqa.group_prefill_attention(q, r, NKV, window)
        )(q, rows).jaxpr) if e.primitive.name == "pallas_call"]
        assert [e.params["name"] for e in calls] == [name]
        # one grid step a K/V head, not a query head, a (query, key) tile
        assert tuple(calls[0].params["grid_mapping"].grid) == (NKV, 8, tiles)


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


@pytest.mark.parametrize("T,tile,window", [(64, 8, None), (48, 16, 17),
                                           (16, 16, None)],
                         ids=["no_window", "window", "one_tile"])
def test_group_flash_at_heads_of_64_matches_dense_attention(monkeypatch, T,
                                                            tile, window):
    monkeypatch.setattr(da, "_FLASH_BLOCK", tile)
    rng = np.random.default_rng(T + (window or 0))
    q = jnp.asarray(rng.standard_normal((T, NH64, H64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((T, NKV64, H64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((T, NKV64, H64)), jnp.float32)
    before = stats.to_dict().get("attn.gqa_window_prefill_fallbacks", 0)
    got = jax.jit(lambda q, r: gqa.group_prefill_attention(
        q, r, NKV64, window))(q, _rows64(k, v))
    assert stats.to_dict().get(
        "attn.gqa_window_prefill_fallbacks", 0) == before
    want = _dense64(q, k, v, _window_keep(T, window))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        gqa.prefill_attention_xla(q, _rows64(k, v), NKV64, window), want,
        rtol=1e-5, atol=1e-5)


def test_kernels_at_heads_of_64_have_names_of_their_own_and_a_tile_a_pair(
        monkeypatch):
    monkeypatch.setattr(da, "_FLASH_BLOCK", 8)
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
