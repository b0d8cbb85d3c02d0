"""The residual-mixing kernels of ``kernels/mhc.py`` at the published shape
(four streams of 3,584, ``[T, 4, 3584]`` held as rows of 14,336 lanes): the
XLA forms and the Pallas kernels (interpreted off the chip) against a plain
``jax.numpy`` statement of the equations, for row counts that are no multiple
of the kernels' tile."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import mhc
from paddle_tpu.observability import stats

N, D = 4, 3584
ARGS = dict(eps=1e-6, iters=20, clamp=30.0, hc_eps=1e-6)


def _draw(T, seed, res_std=0.3, dtype=jnp.bfloat16):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (T, N * D), jnp.float32).astype(dtype)
    phi = jax.random.normal(k[1], (24, N * D), jnp.float32) * (N * D) ** -0.5
    # the ill-conditioning rides b, not the product: an error of the product
    # would be amplified by exp and no tolerance on the maps would mean much
    b = jnp.concatenate([jax.random.normal(k[2], (8,)),
                         res_std * jax.random.normal(k[3], (16,))])
    alpha = jnp.asarray([0.7, 1.3, 0.5], jnp.float32)
    y = jax.random.normal(k[4], (T, D), jnp.float32).astype(dtype)
    return x, phi, b, alpha, y


def _plain(x, phi, b, alpha, y, iters=20, clip=True):
    """The equations, one line each, in float64 numpy."""
    X = np.asarray(x, np.float64).reshape(x.shape[0], N, D)
    u = X.reshape(len(X), -1)
    rho = (np.mean(u * u, -1, keepdims=True) + 1e-6) ** -0.5
    m = rho * (u @ np.asarray(phi, np.float64).T)
    b, a = np.asarray(b, np.float64), np.asarray(alpha, np.float64)
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))  # noqa: E731
    h_pre = sig(a[0] * m[:, :4] + b[:4])
    h_post = 2.0 * sig(a[1] * m[:, 4:8] + b[4:8])
    A = (a[2] * m[:, 8:] + b[8:]).reshape(-1, 4, 4)
    M = np.exp(np.clip(A, -30.0, 30.0) if clip else A)
    for _ in range(iters):
        M = M / (M.sum(-1, keepdims=True) + 1e-6)
        M = M / (M.sum(-2, keepdims=True) + 1e-6)
    h = np.einsum("tj,tjd->td", h_pre, X)
    out = np.einsum("tij,tjd->tid", M, X) \
        + h_post[:, :, None] * np.asarray(y, np.float64)[:, None]
    return h, h_pre, h_post, M, out.reshape(len(X), -1)


def _run(impl, x, phi, b, alpha, y):
    pre = jax.jit(lambda *a: mhc.mhc_pre(*a, **ARGS, impl=impl))
    h, h_pre, h_post, h_res = pre(x, phi, b, alpha)
    out = jax.jit(lambda *a: mhc.mhc_post(*a, impl=impl))(x, y, h_post, h_res)
    return h, h_pre, h_post, h_res, out


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("T", [1, 77, 200])
def test_both_forms_match_the_equations_at_the_published_shape(impl, T):
    """1e-5 of a value on the maps (float32 sums of 14,336 terms against
    float64; the three bf16 pieces of Phi hold all 24 bits); half a bf16 step
    of the largest value on ``h`` and on the streams (both are rounded to
    bf16 once, from float32 sums)."""
    x, phi, b, alpha, y = _draw(T, seed=T)
    want = _plain(x, phi, b, alpha, y)
    got = _run(impl, x, phi, b, alpha, y)
    assert got[0].dtype == got[4].dtype == jnp.bfloat16
    assert got[4].shape == x.shape and got[3].shape == (T, 4, 4)
    for g, w in zip(got[1:4], want[1:4]):
        assert g.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(g), w, atol=1e-5)
    for g, w in ((got[0], want[0]), (got[4], want[4])):
        step = np.abs(w).max() * 2.0 ** -8
        assert np.abs(np.asarray(g, np.float64) - w).max() <= step
    # rows and columns of H_res sum to 1
    res = np.asarray(got[3])
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-3)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-3)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_ill_conditioned_maps_need_all_twenty_rounds_and_the_clip(impl):
    """Entries of ``A_res`` at N(0, 8^2), some past the clip: ten rounds and
    twenty differ by far more than the tolerance, the unclipped matrix is
    another matrix, and the kernel holds the clipped twenty to 1e-5."""
    x, phi, b, alpha, y = _draw(77, seed=5, res_std=8.0)
    b = b.at[8].set(41.0).at[9].set(34.0)       # both past the clip, one row
    want = _plain(x, phi, b, alpha, y)
    got = _run(impl, x, phi, b, alpha, y)
    np.testing.assert_allclose(np.asarray(got[3]), want[3], atol=1e-5)
    ten = _plain(x, phi, b, alpha, y, iters=10)[3]
    assert np.abs(ten - want[3]).max() > 1e-3
    unclipped = _plain(x, phi, b, alpha, y, clip=False)[3]
    assert np.abs(unclipped - want[3]).max() > 1e-2


def test_float32_streams_take_the_product_whole():
    """The tests' models hold float32 streams: the kernel then takes ``Phi``
    as it is, at the highest precision."""
    x, phi, b, alpha, y = _draw(40, seed=9, dtype=jnp.float32)
    want = _plain(x, phi, b, alpha, y)
    got = _run("pallas", x, phi, b, alpha, y)
    for g, w in zip(got[1:4], want[1:4]):
        np.testing.assert_allclose(np.asarray(g), w, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[4]), want[4], atol=2e-5)


def test_the_fallbacks_are_counted_and_the_kernels_are_not():
    x, phi, b, alpha, y = _draw(8, seed=1)
    sc = stats.scope("mhc")
    before = (sc.counter("pre_fallbacks").value,
              sc.counter("post_fallbacks").value)
    got = _run("pallas", x, phi, b, alpha, y)
    assert (sc.counter("pre_fallbacks").value,
            sc.counter("post_fallbacks").value) == before
    _run("xla", x, phi, b, alpha, y)
    # streams that are no whole lane tiles cannot take the kernel
    mhc.mhc_pre(x[:, :4 * 96], phi[:, :4 * 96], b, alpha, **ARGS)
    assert sc.counter("pre_fallbacks").value == before[0] + 2
    assert sc.counter("post_fallbacks").value == before[1] + 1
    with pytest.raises(ValueError, match="unknown mhc impl"):
        mhc.mhc_post(x, y, got[2], got[3], impl="mosaic")
    with pytest.raises(ValueError, match="no \\[n"):
        mhc.streams(phi[:23])
