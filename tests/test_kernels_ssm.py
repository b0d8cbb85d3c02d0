"""The selective scan of ``kernels/ssm.py``: the chunked Pallas kernel in
interpret mode against the sequential ``lax.scan``, the one-token update
against one step of it, and the convolution for a prompt against the same one
token at a time."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import ssm
from paddle_tpu.observability import stats


def _inputs(T, Di, N=16, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(T, Di)), jnp.float32)
    d = jnp.asarray(np.abs(rng.normal(size=(T, Di))) * 0.1, jnp.float32)
    A = -jnp.exp(jnp.asarray(rng.normal(size=(N, Di)), jnp.float32))
    B = jnp.asarray(rng.normal(size=(T, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(T, N)), jnp.float32)
    return x, d, A, B, C


@pytest.mark.parametrize("T,Di,chunk", [(64, 512, 16), (48, 1024, 16),
                                        (32, 2048, 32), (24, 256, 256)])
def test_chunked_scan_matches_the_sequential_one(monkeypatch, T, Di, chunk):
    monkeypatch.setattr(ssm, "_CHUNK", chunk)
    args = _inputs(T, Di)
    y0, h0 = ssm.selective_scan_xla(*args)
    before = stats.to_dict().get("ssm.scan_fallbacks", 0)
    y1, h1 = jax.jit(ssm.selective_scan)(*args)
    assert stats.to_dict().get("ssm.scan_fallbacks", 0) == before
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h1, h0, rtol=1e-5, atol=1e-5)


def test_positions_with_a_zero_step_leave_the_state_as_it_is(monkeypatch):
    monkeypatch.setattr(ssm, "_CHUNK", 16)
    x, d, A, B, C = _inputs(64, 512, seed=1)
    d = d.at[41:].set(0.0)              # a bucket of 64, a prompt of 41
    _, h = jax.jit(ssm.selective_scan)(x, d, A, B, C)
    _, want = ssm.selective_scan_xla(x[:41], d[:41], A, B[:41], C[:41])
    np.testing.assert_allclose(h, want, rtol=1e-6, atol=1e-6)


def test_unsupported_shapes_fall_back_and_count():
    x, d, A, B, C = _inputs(8, 192)     # 192 channels: no whole lane tile
    assert not ssm.scan_supported(8, 192)
    before = stats.to_dict().get("ssm.scan_fallbacks", 0)
    y, h = ssm.selective_scan(x, d, A, B, C)
    assert stats.to_dict()["ssm.scan_fallbacks"] == before + 1
    ssm.selective_scan(*_inputs(8, 256))    # whole lane tiles: the kernel
    assert stats.to_dict()["ssm.scan_fallbacks"] == before + 1
    y0, h0 = ssm.selective_scan_xla(x, d, A, B, C)
    np.testing.assert_array_equal(y, y0)


def test_one_token_update_and_convolution_step_continue_a_prompt():
    T, Di, K = 12, 256, 4
    x, d, A, B, C = _inputs(T, Di, seed=2)
    y_all, h_all = ssm.selective_scan_xla(x, d, A, B, C)
    _, h = ssm.selective_scan_xla(x[:T - 1], d[:T - 1], A, B[:T - 1],
                                  C[:T - 1])
    y, h = ssm.selective_step(h[None], x[-1:], d[-1:], A, B[-1:], C[-1:])
    np.testing.assert_allclose(y[0], y_all[-1], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(h[0], h_all, rtol=1e-5, atol=1e-6)
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(K, Di)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(Di,)), jnp.float32)
    conv = ssm.causal_conv(x, w, b)
    tail = jnp.zeros((1, K - 1, Di), jnp.float32)
    for t in range(T):
        got, tail = ssm.conv_step(tail, x[t:t + 1], w, b)
        np.testing.assert_allclose(got[0], conv[t], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(tail[0], x[T - K + 1:])


def test_a_filter_without_a_bias_is_the_filter_with_a_bias_of_zeros():
    rng = np.random.default_rng(8)
    a = jnp.asarray(rng.standard_normal((9, 6)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 6)), jnp.float32)
    zeros = jnp.zeros((6,), jnp.float32)
    whole = ssm.causal_conv(a, w)
    np.testing.assert_array_equal(whole, ssm.causal_conv(a, w, zeros))
    # a prompt's last two inputs are the tail the next token continues from
    tail = a[None, 5:7]
    got, new_tail = ssm.conv_step(tail, a[None, 7], w)
    np.testing.assert_allclose(got[0], whole[7], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(new_tail[0], a[6:8])
    np.testing.assert_array_equal(
        got, ssm.conv_step(tail, a[None, 7], w, zeros)[0])
