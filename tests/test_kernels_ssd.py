"""The chunked state-space-duality scan and the one-token update against the
recurrence written out one position at a time (``kernels/ssd.py``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import ssd as K
from paddle_tpu.observability import stats


def _inputs(T, H=4, P=16, G=2, N=32, seed=0, length=None):
    r = np.random.default_rng(seed)
    x = r.standard_normal((T, H, P)).astype(np.float32)
    dt = np.exp(r.uniform(np.log(1e-3), np.log(0.3), (T, H))
                ).astype(np.float32)
    if length is not None:
        dt[length:] = 0.0
    A = -np.exp(r.uniform(0.0, 2.5, (H,))).astype(np.float32)
    B = r.standard_normal((T, G, N)).astype(np.float32)
    C = r.standard_normal((T, G, N)).astype(np.float32)
    return [jnp.asarray(a) for a in (x, dt, A, B, C)]


def _numpy_scan(x, dt, A, B, C):
    """The recurrence in float64 numpy, a loop a position and a head."""
    x, dt, A, B, C = (np.asarray(a, np.float64) for a in (x, dt, A, B, C))
    T, H, P = x.shape
    G, N = B.shape[1:]
    S = np.zeros((H, N, P))
    y = np.zeros((T, H, P))
    for t in range(T):
        for h in range(H):
            g = h // (H // G)
            S[h] = np.exp(dt[t, h] * A[h]) * S[h] \
                + np.outer(B[t, g], dt[t, h] * x[t, h])
            y[t, h] = C[t, g] @ S[h]
    return y, S


def test_the_sequential_form_is_the_recurrence():
    args = _inputs(12)
    y, S = K.ssd_scan_xla(*args)
    y64, S64 = _numpy_scan(*args)
    np.testing.assert_allclose(y, y64, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(S, S64, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("T,length", [
    (32, None),         # whole chunks
    (32, 16),           # the prompt ends on a chunk boundary
    (32, 17),           # one past it
    (16, 5),            # shorter than a chunk
    (8, 8),             # one chunk
    (48, 33)], ids=["whole", "boundary", "one_past", "short", "one_chunk",
                    "three_chunks"])
def test_chunked_scan_equals_the_recurrence_and_passes_over_pads(T, length):
    args = _inputs(T, seed=T + (length or 0), length=length)
    before = stats.snapshot().get("ssm.ssd_fallbacks", 0)
    y, S = jax.jit(lambda *a: K.ssd_scan(*a, chunk=8))(*args)
    assert stats.snapshot().get("ssm.ssd_fallbacks", 0) == before
    y0, S0 = K.ssd_scan_xla(*args)
    n = T if length is None else length
    np.testing.assert_allclose(y[:n], y0[:n], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(S, S0, rtol=1e-4, atol=1e-4)
    if length is not None:
        # the state is the one at the last real position
        _, S_cut = K.ssd_scan_xla(*[a[:length] if a.shape[0] == T else a
                                    for a in args])
        np.testing.assert_allclose(S, S_cut, rtol=1e-4, atol=1e-4)


def test_groups_are_told_apart():
    """Heads of group 1 read B and C of group 1: with group 1's B zeroed
    its heads' states stay zero and group 0's do not."""
    x, dt, A, B, C = _inputs(16)
    B = B.at[:, 1].set(0.0)
    _, S = K.ssd_scan(x, dt, A, B, C, chunk=8)
    assert float(jnp.abs(S[:2]).max()) > 0.1
    assert float(jnp.abs(S[2:]).max()) == 0.0


def test_a_length_off_the_chunk_falls_back_and_counts():
    args = _inputs(12)
    before = stats.snapshot().get("ssm.ssd_fallbacks", 0)
    y, S = K.ssd_scan(*args, chunk=8)
    assert stats.snapshot().get("ssm.ssd_fallbacks", 0) == before + 1
    y0, S0 = K.ssd_scan_xla(*args)
    np.testing.assert_array_equal(y, y0)


def test_state_step_in_place_on_one_layer_and_handed_from_a_prompt():
    """A prompt's state handed to the one-token update continues the
    recurrence: prefix by the chunked scan, then steps, equals the whole
    sequence; the other layers' rows are not touched."""
    T, P0, slots, L = 24, 16, 3, 2
    x, dt, A, B, C = _inputs(T, seed=7)
    y_all, S_all = K.ssd_scan_xla(x, dt, A, B, C)
    _, S_p = K.ssd_scan(x[:P0], dt[:P0], A, B[:P0], C[:P0], chunk=8)
    marker = 3.0
    states = jnp.full((L, slots) + S_p.shape, marker, jnp.float32)
    states = states.at[1, 2].set(S_p)
    step = jax.jit(K.ssd_state_step)
    for t in range(P0, T):
        def rows(a):
            return jnp.broadcast_to(a[t][None], (slots,) + a.shape[1:])
        y, states = step(states, jnp.int32(1), rows(x), rows(dt), A, rows(B),
                         rows(C))
        np.testing.assert_allclose(y[2], y_all[t], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(states[1, 2], S_all, rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(states[0] - marker).max()) == 0.0


def test_state_step_kernel_equals_the_xla_form():
    r = np.random.default_rng(3)
    slots, H, P, G, N = 5, 4, 16, 2, 32
    S = jnp.asarray(r.standard_normal((2, slots, H, N, P)), jnp.float32)
    x = jnp.asarray(r.standard_normal((slots, H, P)), jnp.float32)
    dt = jnp.asarray(r.uniform(1e-3, 0.2, (slots, H)), jnp.float32)
    A = -jnp.asarray(r.uniform(1, 8, (H,)), jnp.float32)
    B = jnp.asarray(r.standard_normal((slots, G, N)), jnp.float32)
    C = jnp.asarray(r.standard_normal((slots, G, N)), jnp.float32)
    y, new = K.ssd_state_step(S, 0, x, dt, A, B, C)
    y0, new0 = K.ssd_step_xla(S[0], x, dt, A, B, C)
    np.testing.assert_allclose(y, y0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new[0], new0, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(new[1], S[1])
