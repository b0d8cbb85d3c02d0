"""Differential attention's kernels (``kernels/diffattn.py``) in interpret
mode against dense masked attention: the paged decode kernel over a pool and
over a window ring, and the flash prefill with and without a window."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import diffattn as da
from paddle_tpu.observability import stats

NH, NKV, DH = 4, 2, 64
KW = NKV * 2 * DH


def _dense(q, k, v, keep):
    """The definition: q [T, nh, 2, dh], k [J, nkv, 2, dh], v [J, nkv, 2dh],
    keep [T, J] → [T, nh, 2, 2dh]."""
    out = np.zeros((q.shape[0], NH, 2, 2 * DH), np.float64)
    for h in range(NH):
        g = h // (NH // NKV)
        for c in range(2):
            s = q[:, h, c] @ k[:, g, c].T / np.sqrt(DH)
            s = np.where(keep, s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            out[:, h, c] = (p / p.sum(-1, keepdims=True)) @ v[:, g]
    return out


def _split(rows):
    rows = np.asarray(rows, np.float64)
    return (rows[:, :KW].reshape(-1, NKV, 2, DH),
            rows[:, KW:].reshape(-1, NKV, 2 * DH))


@pytest.mark.parametrize("chunk", [4, 32])
def test_paged_decode_kernel_matches_dense_attention(monkeypatch, chunk):
    monkeypatch.setattr(da, "_CHUNK_BLOCKS", chunk)
    rng = np.random.default_rng(0)
    S, bs, NB, MB = 3, 8, 40, 12
    pool = jnp.asarray(rng.normal(size=(2, NB, bs, 2 * KW)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(S, NH, 2 * DH)), jnp.float32)
    bt = jnp.asarray(rng.integers(1, NB, size=(S, MB)), jnp.int32)
    cl = jnp.asarray([1, 37, 96], jnp.int32)
    before = stats.to_dict().get("attn.diff_decode_fallbacks", 0)
    got = jax.jit(lambda *a: da.decode_attention(*a, NKV))(
        q, pool, bt, cl, jnp.int32(1))
    assert stats.to_dict().get("attn.diff_decode_fallbacks", 0) == before
    xla = da.decode_attention(q, pool, bt, cl, 1, NKV, impl="xla")
    assert stats.to_dict()["attn.diff_decode_fallbacks"] == before + 1
    for s in range(S):
        rows = np.asarray(pool)[1][np.asarray(bt)[s]].reshape(-1, 2 * KW)
        k, v = _split(rows[:int(cl[s])])
        want = _dense(np.asarray(q, np.float64)[s:s + 1].reshape(
            1, NH, 2, DH), k, v, np.ones((1, int(cl[s])), bool))[0]
        np.testing.assert_allclose(got[s], want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(xla[s], want, rtol=1e-5, atol=1e-5)


def test_a_window_ring_is_the_same_call_with_the_slot_s_own_blocks():
    """Rows at ``position mod W``; the order they lie in does not matter."""
    rng = np.random.default_rng(1)
    S, W, rb, ctx = 2, 16, 8, 41
    nrb = W // rb
    rows = rng.normal(size=(S, ctx, 2 * KW)).astype(np.float32)
    rings = np.zeros((3, S * nrb, rb, 2 * KW), np.float32)
    for s in range(S):
        for t in range(ctx):
            at = t % W
            rings[2, s * nrb + at // rb, at % rb] = rows[s, t]
    q = jnp.asarray(rng.normal(size=(S, NH, 2 * DH)), jnp.float32)
    tables = jnp.arange(S * nrb, dtype=jnp.int32).reshape(S, nrb)
    got = jax.jit(lambda *a: da.decode_attention(
        *a, NKV, name="diff_ring_decode_attn"))(
        q, jnp.asarray(rings), tables, jnp.full((S,), W, jnp.int32),
        jnp.int32(2))
    for s in range(S):
        k, v = _split(rows[s, ctx - W:])
        want = _dense(np.asarray(q, np.float64)[s:s + 1].reshape(
            1, NH, 2, DH), k, v, np.ones((1, W), bool))[0]
        np.testing.assert_allclose(got[s], want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T,window,block", [(32, 8, 8), (32, None, 8),
                                            (64, 24, 16), (16, 8, 256),
                                            (48, 40, 16)])
def test_window_flash_matches_dense_masked_attention(monkeypatch, T, window,
                                                     block):
    monkeypatch.setattr(da, "_FLASH_BLOCK", block)
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(T, NH, 2 * DH)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(T, 2 * KW)), jnp.float32)
    before = stats.to_dict().get("attn.diff_prefill_fallbacks", 0)
    got = jax.jit(lambda q, r: da.prefill_attention(
        q, r, NKV, window))(q, rows)
    assert stats.to_dict().get("attn.diff_prefill_fallbacks", 0) == before
    # a prompt that is no whole number of tiles takes the XLA lowering,
    # counted
    ragged = da.prefill_attention(q[:T - 3], rows[:T - 3], NKV, window)
    assert stats.to_dict()["attn.diff_prefill_fallbacks"] == before + 1
    xla = da.prefill_attention_xla(q, rows, NKV, window)
    np.testing.assert_array_equal(ragged, da.prefill_attention_xla(
        q[:T - 3], rows[:T - 3], NKV, window))
    k, v = _split(rows)
    want = _dense(np.asarray(q, np.float64).reshape(T, NH, 2, DH), k, v,
                  np.asarray(da.visible(T, window)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(xla, want, rtol=1e-5, atol=1e-5)
    last = np.asarray(da.row_attention(q[T - 1], rows,
                                       jnp.arange(T) < T, NKV))
    if window is None:
        np.testing.assert_allclose(last, want[T - 1], rtol=1e-5, atol=1e-5)


def test_the_window_s_grid_skips_the_tiles_left_of_it():
    assert da.flash_tiles(3072, 512) == (256, 3)     # of 12 key tiles
    assert da.flash_tiles(3072, None) == (256, 12)
    assert da.flash_tiles(16, 8) == (16, 1)
    with pytest.raises(ValueError, match="unknown differential attention"):
        da.decode_attention(
            jnp.zeros((1, NH, 128)), jnp.zeros((1, 2, 8, 2 * KW)),
            jnp.zeros((1, 1), jnp.int32), jnp.ones((1,), jnp.int32), 0, NKV,
            impl="cuda")
