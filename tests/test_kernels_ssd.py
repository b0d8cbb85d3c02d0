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


# -- 64-wide heads: two of one group's heads a lane tile ---------------------
# (H, P, G, N): a small pair layout, and NVIDIA-Nemotron-3-Nano's published
# heads (64 of 64 channels, 8 groups of 8, a state of 128)
PAIRED = {"small": (8, 64, 2, 16), "nemotron_h": (64, 64, 8, 128)}


def test_the_kept_layout_pairs_64_wide_heads_and_keeps_the_others():
    assert K.state_layout(64, 128, 64) == (32, 128, 128)
    assert K.state_layout(32, 256, 128) == (32, 256, 128)
    assert K.state_layout(4, 32, 16) == (4, 32, 16)
    S = jnp.arange(2 * 4 * 3 * 64, dtype=jnp.float32).reshape(2, 4, 3, 64)
    kept = K.pack_state(S)
    assert kept.shape == (2, 2, 3, 128)
    # pair j holds head 2j on lanes 0-63 and head 2j + 1 on lanes 64-127
    np.testing.assert_array_equal(kept[1, 1, :, :64], S[1, 2])
    np.testing.assert_array_equal(kept[1, 1, :, 64:], S[1, 3])
    np.testing.assert_array_equal(K.unpack_state(kept, 64), S)
    wide = jnp.ones((4, 32, 128))
    assert K.pack_state(wide) is wide and K.unpack_state(wide, 128) is wide


@pytest.mark.parametrize("shape,T,length", [
    ("small", 32, None), ("small", 32, 17), ("small", 16, 5),
    ("nemotron_h", 256, 200)],
    ids=["small_whole", "small_one_past", "small_short", "published"])
def test_the_paired_scan_equals_the_recurrence(shape, T, length):
    H, P, G, N = PAIRED[shape]
    chunk = 128 if shape == "nemotron_h" else 8
    args = _inputs(T, H, P, G, N, seed=T + H, length=length)
    before = stats.snapshot().get("ssm.ssd_fallbacks", 0)
    fn = lambda *a: K.ssd_scan(*a, chunk=chunk)     # noqa: E731
    y, kept = jax.jit(fn)(*args)
    assert stats.snapshot().get("ssm.ssd_fallbacks", 0) == before
    assert kept.shape == (H // 2, N, 2 * P)
    names = [e.params["name"] for e in jax.make_jaxpr(fn)(*args).eqns
             if e.primitive.name == "pallas_call"]
    assert names == ["ssd64_chunk_scan"]
    y0, S0 = K.ssd_scan_xla(*args)
    n = T if length is None else length
    scale = float(jnp.abs(y0[:n]).max())
    np.testing.assert_allclose(y[:n], y0[:n], rtol=1e-4, atol=1e-4 * scale)
    np.testing.assert_allclose(K.unpack_state(kept, P), S0, rtol=1e-4,
                               atol=1e-4 * float(jnp.abs(S0).max()))


def test_a_pair_never_straddles_two_groups():
    """Three heads a group cannot be paired inside it: the scan and the step
    fall back (and keep the paired layout all the same)."""
    H, P, G, N = 6, 64, 2, 16
    args = _inputs(16, H, P, G, N, seed=5)
    before = stats.snapshot().get("ssm.ssd_fallbacks", 0)
    y, kept = K.ssd_scan(*args, chunk=8)
    assert stats.snapshot().get("ssm.ssd_fallbacks", 0) == before + 1
    y0, S0 = K.ssd_scan_xla(*args)
    np.testing.assert_array_equal(y, y0)
    np.testing.assert_array_equal(K.unpack_state(kept, P), S0)


@pytest.mark.parametrize("shape", sorted(PAIRED))
def test_the_paired_step_equals_the_xla_form_in_place(shape):
    H, P, G, N = PAIRED[shape]
    r = np.random.default_rng(11)
    slots = 3
    S = jnp.asarray(r.standard_normal((2, slots, H, N, P)), jnp.float32)
    x = jnp.asarray(r.standard_normal((slots, H, P)), jnp.float32)
    dt = jnp.asarray(r.uniform(1e-3, 0.2, (slots, H)), jnp.float32)
    A = -jnp.asarray(r.uniform(1, 8, (H,)), jnp.float32)
    B = jnp.asarray(r.standard_normal((slots, G, N)), jnp.float32)
    C = jnp.asarray(r.standard_normal((slots, G, N)), jnp.float32)
    kept = K.pack_state(S)
    fn = lambda *a: K.ssd_state_step(a[0], 1, *a[1:])   # noqa: E731
    before = stats.snapshot().get("ssm.ssd_fallbacks", 0)
    y, new = jax.jit(fn)(kept, x, dt, A, B, C)
    assert stats.snapshot().get("ssm.ssd_fallbacks", 0) == before
    names = [e.params["name"] for e in jax.make_jaxpr(fn)(
        kept, x, dt, A, B, C).eqns if e.primitive.name == "pallas_call"]
    assert names == ["ssd64_state_step"]
    y0, new0 = K.ssd_step_xla(S[1], x, dt, A, B, C)
    np.testing.assert_allclose(y, y0, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(K.unpack_state(new[1], P), new0, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(new[0], kept[0])


def test_a_prompt_s_paired_rows_are_handed_to_the_step():
    H, P, G, N = PAIRED["small"]
    T, P0 = 24, 16
    x, dt, A, B, C = _inputs(T, H, P, G, N, seed=9)
    y_all, S_all = K.ssd_scan_xla(x, dt, A, B, C)
    _, kept = K.ssd_scan(x[:P0], dt[:P0], A, B[:P0], C[:P0], chunk=8)
    states = jnp.zeros((1, 2) + kept.shape, jnp.float32).at[0, 1].set(kept)
    for t in range(P0, T):
        def rows(a):
            return jnp.broadcast_to(a[t][None], (2,) + a.shape[1:])
        y, states = K.ssd_state_step(states, 0, rows(x), rows(dt), A,
                                     rows(B), rows(C))
        np.testing.assert_allclose(y[1], y_all[t], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(K.unpack_state(states[0, 1], P), S_all,
                               rtol=1e-4, atol=1e-4)
