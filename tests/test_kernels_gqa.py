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
