"""``kernels/kda.py`` on the CPU (the Pallas kernels interpret themselves):
the chunked prefill against the recurrence one position at a time at both
ends of the decay's range, pads that leave the state as it is, the halving
that cuts the triangle, and the one-token update in place over every layer's
rows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddle_tpu.kernels import kda
from paddle_tpu.observability import stats


def _decays(rng, shape, strongest, weakest=1e-3):
    """Log-decays log-uniform in [weakest, strongest] nats."""
    return -np.exp(rng.uniform(np.log(weakest), np.log(strongest),
                               shape)).astype("float32")


def draw(rng, T, H, K, V, strongest, weakest=1e-3, real=None):
    """q, k, v, a, b as a layer hands them over: unit keys that share a
    direction (SiLU's outputs do), log-decays log-uniform in [weakest,
    strongest] nats a position a channel, step sizes in (0, 1)."""
    q = rng.standard_normal((T, H, K)).astype("float32")
    k = rng.standard_normal((T, H, K)).astype("float32") + 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * K ** -0.5
    v = rng.standard_normal((T, H, V)).astype("float32")
    a = _decays(rng, (T, H, K), strongest, weakest)
    b = (1 / (1 + np.exp(-rng.standard_normal((T, H))))).astype("float32")
    if real is not None:
        a[real:], b[real:] = 0.0, 0.0
    return tuple(jnp.asarray(x) for x in (q, k, v, a, b))


# the family's initialisation gives softplus(dt_bias) in [1e-3, 1e-1] times A
# in [1, 16]: 1.6 nats a position at the strongest, and a low-rank term on top
# of it; 40 nats a position is 2,560 a chunk — exp(+2,560) is not a float32,
# so a form that divides by a running decay fails here
@pytest.mark.parametrize("strongest", [1e-2, 1.6, 40.0],
                         ids=["weakest", "the_draw_s_strongest",
                              "past_float32"])
@pytest.mark.parametrize("T,real", [(64, 64), (192, 150), (128, 65)],
                         ids=["one_chunk", "ends_inside_a_chunk",
                              "one_past_an_edge"])
# the output's floor: 2e-6 at the published head (K = V = 128), where the
# kernel's products, two bf16 pieces an operand, read 1.1e-6; 1e-5 at the toy
# head, whose outputs are larger (to 0.62, sixteen channels under the same
# scale) and read to 7.8e-6 — the served output is bf16, a step of 2e-3 of a
# value.  The state's tolerance is one
@pytest.mark.parametrize("K,atol", [(16, 1e-5), (128, 2e-6)],
                         ids=["toy_head", "published_head"])
def test_the_chunked_form_is_the_recurrence(strongest, T, real, K, atol):
    args = draw(np.random.default_rng(T + real), T, 2, K, K, strongest,
                real=real)
    before = stats.to_dict().get("kda.chunk_fallbacks", 0)
    o, S = kda.kda_scan(*args)
    assert stats.to_dict().get("kda.chunk_fallbacks", 0) == before
    want_o, want_S = kda.kda_scan_xla(*args)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(
        np.asarray(S)).all()
    np.testing.assert_allclose(o[:real], want_o[:real], rtol=2e-5, atol=atol)
    np.testing.assert_allclose(S, want_S, rtol=2e-5, atol=2e-5)
    # the pads left the state as the last real position made it
    short = tuple(x[:real] for x in args)
    np.testing.assert_allclose(S, kda.kda_scan_xla(*short)[1], rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("strongest", [1e-2, 1.6, 40.0],
                         ids=["weakest", "the_draw_s_strongest",
                              "past_float32"])
@pytest.mark.parametrize("seed", [0, 1])
def test_three_pieces_hold_a_float32_and_the_sums_of_64_lose_nothing(
        strongest, seed):
    a = jnp.asarray(_decays(np.random.default_rng(seed), (64, 128),
                            strongest))
    a1, a2, a3 = (x.astype(jnp.float32) for x in kda.pieces(a, 3))
    np.testing.assert_array_equal((a3 + a2) + a1, a)      # bit for bit
    w = jnp.asarray(kda.halving(64)[0])                   # rows sum to 64
    np.testing.assert_array_equal(w.astype(jnp.bfloat16), w)
    got = np.asarray(jax.jit(kda.dot_01)(w.astype(jnp.bfloat16), a))
    whole = np.asarray(jnp.dot(w, a, precision=lax.Precision.HIGHEST))
    exact = np.asarray(w, np.float64) @ np.asarray(a, np.float64)
    # the float32 product at its highest precision rounds every sum it
    # takes; three passes over pieces of eight bits round fewer: as near the
    # product as that one at the worst, and within two of its last places
    assert np.abs(got - exact).max() <= np.abs(whole - exact).max()
    assert (np.abs(got - exact)
            <= 2 * np.spacing(np.abs(exact).astype("float32"))).all()


# the kernel's operand shapes: the inverse's and X·rhs / B·u, a level's
# product, the state's two reads (one product of both since the split), the
# state's write
@pytest.mark.parametrize("x,y,dims", [
    ((64, 64), (64, 64), kda.NN), ((64, 64), (64, 128), kda.NN),
    ((128, 128), (64, 128), kda.NT), ((64, 128), (128, 128), kda.NT),
    ((128, 128), (128, 128), kda.NT), ((128, 64), (64, 128), kda.NN)],
    ids=["inverse", "X_rhs_and_B_u", "level", "state_read",
         "both_state_reads", "state_write"])
@pytest.mark.parametrize("decayed", [False, True],
                         ids=["as_drawn", "under_a_decay"])
def test_two_pieces_an_operand_keep_a_product_to_sixteen_bits(x, y, dims,
                                                              decayed):
    rng = np.random.default_rng(x[0] + y[1])
    xs = rng.standard_normal(x).astype("float32")
    ys = rng.standard_normal(y).astype("float32")
    if decayed:             # a level's operands: exp of sums to 64 x 40 nats
        xs *= np.exp(_decays(rng, x, 40.0) * rng.integers(0, 64, x))
        ys *= np.exp(_decays(rng, y, 40.0) * rng.integers(0, 64, y))
    got = jax.jit(lambda a, b: kda.dot_split(
        kda.pieces(a), kda.pieces(b), dims))(xs, ys)
    whole = lax.dot_general(xs, ys, dims, precision=lax.Precision.HIGHEST)
    bound = lax.dot_general(np.abs(xs), np.abs(ys), dims,
                            precision=lax.Precision.HIGHEST)
    assert (np.abs(np.asarray(got) - np.asarray(whole))
            <= 2.0 ** -15 * np.asarray(bound)).all()
    # ... and one piece an operand is another result: it does not
    one = lax.dot_general(xs.astype(jnp.bfloat16), ys.astype(jnp.bfloat16),
                          dims, preferred_element_type=jnp.float32)
    assert (np.abs(np.asarray(one) - np.asarray(whole))
            > 2.0 ** -15 * np.asarray(bound)).mean() > 0.5


def test_the_recurrence_is_the_equations_in_numpy():
    q, k, v, a, b = (np.asarray(x) for x in draw(
        np.random.default_rng(3), 9, 1, 4, 4, 2.0))
    S = np.zeros((4, 4))                    # [key, value]
    want = []
    for t in range(9):
        S = np.exp(a[t, 0])[:, None] * S
        S = S + b[t, 0] * np.outer(k[t, 0], v[t, 0] - S.T @ k[t, 0])
        want.append(S.T @ q[t, 0])
    o, ST = kda.kda_scan_xla(*(jnp.asarray(x) for x in (q, k, v, a, b)))
    np.testing.assert_allclose(o[:, 0], np.stack(want), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(ST[0], S.T, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("C", [8, 64])
def test_the_halving_cuts_the_strict_triangle_once(C):
    sums, M = kda.halving(C)
    levels = M.shape[0]
    assert 2 ** levels == C and sums.shape == ((levels + 1) * C, C)
    W, tri = sums[:levels * C].reshape(levels, C, C), sums[levels * C:]
    idx = np.arange(C)
    np.testing.assert_array_equal(M.sum(0), idx[:, None] > idx[None, :])
    np.testing.assert_array_equal(tri, idx[:, None] >= idx[None, :])
    # a pair's two exponents add up to the a's strictly after r up to s
    a = np.random.default_rng(C).standard_normal(C)
    for lv in range(levels):
        e = W[lv] @ a
        for s, r in zip(*np.nonzero(M[lv])):
            np.testing.assert_allclose(e[s] + e[r], a[r + 1:s + 1].sum(),
                                       rtol=1e-5, atol=1e-5)
            assert (W[lv][s] >= 0).all() and (W[lv][r] >= 0).all()


def test_a_length_that_is_not_whole_chunks_falls_back_and_counts():
    args = draw(np.random.default_rng(0), 40, 1, 8, 8, 1.0)
    before = stats.to_dict().get("kda.chunk_fallbacks", 0)
    o, _ = kda.kda_scan(*args)
    assert stats.to_dict()["kda.chunk_fallbacks"] == before + 1
    np.testing.assert_array_equal(o, kda.kda_scan_xla(*args)[0])
    assert kda.scan_supported(128, 16, 16) and not kda.scan_supported(
        96, 16, 16, chunk=48)


@pytest.mark.parametrize("layer", [0, 2])
def test_the_one_token_update_is_in_place_over_every_layer_s_rows(layer):
    rng = np.random.default_rng(layer)
    L, S, H, K = 3, 4, 4, 16
    states = jnp.asarray(rng.standard_normal((L, S, H, K, K)), jnp.float32)
    q, k, v, a, b = (x[:S] for x in draw(rng, 8, H, K, K, 5.0))
    want_o, want = kda.kda_step_xla(states[layer], q, k, v, a, b)
    step = jax.jit(kda.kda_state_step, donate_argnums=(0,))
    kept = np.asarray(states)
    o, new = step(states, jnp.int32(layer), q, k, v, a, b)
    np.testing.assert_allclose(o, want_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(new[layer], want, rtol=1e-5, atol=1e-6)
    others = [i for i in range(L) if i != layer]
    np.testing.assert_array_equal(np.asarray(new)[others], kept[others])
    # ... and a step after a prompt's scan is the scan one position on
    seq = draw(rng, 65, H, K, K, 1.6)
    _, S64 = kda.kda_scan(*(x[:64] for x in seq))
    o65, S65 = kda.kda_scan_xla(*seq)
    o1, rows = kda.kda_state_step(S64[None, None], 0,
                                  *(x[64:65] for x in seq))
    np.testing.assert_allclose(o1[0], o65[64], rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(rows[0, 0], S65, rtol=2e-5, atol=2e-5)
