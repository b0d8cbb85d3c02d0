"""Fused Pallas LSTM kernel vs the XLA scan lowering (interpret mode on
CPU; the same kernel compiles on TPU).  Covers fwd parity, gradient
parity through jax.grad, length masking, reverse, and the program-level
lstm op with use_pallas_kernel forced."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import rnn as R

rng = np.random.RandomState(3)


def ref_lstm(xproj, w, h0, c0, mask):
    """jnp scan reference — same math as ops/nn_ops.py _lstm."""
    B, T, H4 = xproj.shape
    H = H4 // 4
    xs = jnp.swapaxes(xproj, 0, 1)
    ms = jnp.swapaxes(mask, 0, 1)[..., None]

    def step(carry, inp):
        h, c = carry
        x_t, m_t = inp
        gates = x_t + jnp.matmul(h, w)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        c_new = m_t * c_new + (1 - m_t) * c
        h_new = m_t * h_new + (1 - m_t) * h
        return (h_new, c_new), (h_new, c_new)

    _, (hs, cs) = jax.lax.scan(step, (h0, c0), (xs, ms))
    return jnp.swapaxes(hs, 0, 1), jnp.swapaxes(cs, 0, 1)


def data(B=4, T=6, H=16, masked=True):
    xproj = rng.randn(B, T, 4 * H).astype("float32") * 0.5
    w = rng.randn(H, 4 * H).astype("float32") * 0.3
    h0 = rng.randn(B, H).astype("float32") * 0.1
    c0 = rng.randn(B, H).astype("float32") * 0.1
    if masked:
        lens = rng.randint(1, T + 1, (B,))
        mask = (np.arange(T)[None, :] < lens[:, None]).astype("float32")
    else:
        mask = np.ones((B, T), "float32")
    return (jnp.asarray(xproj), jnp.asarray(w), jnp.asarray(h0),
            jnp.asarray(c0), jnp.asarray(mask))


@pytest.mark.parametrize("masked", [False, True])
def test_fused_lstm_forward_matches_scan(masked):
    xproj, w, h0, c0, mask = data(masked=masked)
    hs1, cs1 = R.lstm_fused(xproj, w, h0, c0, mask, True)
    hs2, cs2 = ref_lstm(xproj, w, h0, c0, mask)
    np.testing.assert_allclose(np.asarray(hs1), np.asarray(hs2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cs1), np.asarray(cs2),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("masked", [False, True])
def test_fused_lstm_grads_match_scan(masked):
    """lstm_fused_grad (bwd kernel, gates recomputed in-kernel) vs
    jax.grad of the jnp scan reference, for loss = |hs|^2 + 0.5|cs|^2."""
    xproj, w, h0, c0, mask = data(masked=masked)

    def loss_ref(xproj, w, h0, c0):
        hs, cs = ref_lstm(xproj, w, h0, c0, mask)
        return jnp.sum(hs ** 2) + 0.5 * jnp.sum(cs ** 2)

    hs, cs = R.lstm_fused(xproj, w, h0, c0, mask, True)
    g1 = R.lstm_fused_grad(xproj, w, h0, c0, mask, hs, cs,
                           2.0 * hs, 1.0 * cs, True)
    g2 = jax.grad(loss_ref, (0, 1, 2, 3))(xproj, w, h0, c0)
    for a, b, name in zip(g1, g2, ["dx", "dw", "dh0", "dc0"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_fused_kernels_reverse_is_the_flipped_scan():
    """``reverse=True`` walks time backward through the kernels' own
    index maps and must equal flip -> forward kernel -> flip, forward
    and backward, masked — with no flipped copy made (on the v5e XLA
    returned wrong values for the flip+transpose the ops used to feed
    the kernels with, PERF.md Bring-up)."""
    xproj, w, h0, c0, mask = data(masked=True)
    f = lambda a: jnp.flip(a, 1)  # noqa: E731
    hs, cs = R.lstm_fused(xproj, w, h0, c0, mask, True, reverse=True)
    hs_f, cs_f = R.lstm_fused(f(xproj), w, h0, c0, f(mask), True)
    np.testing.assert_array_equal(np.asarray(hs), np.asarray(f(hs_f)))
    np.testing.assert_array_equal(np.asarray(cs), np.asarray(f(cs_f)))
    g = R.lstm_fused_grad(xproj, w, h0, c0, mask, hs, cs, 2.0 * hs, cs,
                          True, reverse=True)
    g_f = R.lstm_fused_grad(f(xproj), w, h0, c0, f(mask), hs_f, cs_f,
                            2.0 * hs_f, cs_f, True)
    for a, b in zip(g, (f(g_f[0]),) + tuple(g_f[1:])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)

    xg = xproj[:, :, :3 * w.shape[0]]
    wg = w[:, :3 * w.shape[0]]
    hg = R.gru_fused(xg, wg, h0, mask, True, reverse=True)
    hg_f = R.gru_fused(f(xg), wg, h0, f(mask), True)
    np.testing.assert_array_equal(np.asarray(hg), np.asarray(f(hg_f)))
    g = R.gru_fused_grad(xg, wg, h0, mask, hg, 2.0 * hg, True, reverse=True)
    g_f = R.gru_fused_grad(f(xg), wg, h0, f(mask), hg_f, 2.0 * hg_f, True)
    for a, b in zip(g, (f(g_f[0]),) + tuple(g_f[1:])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("reverse", [False, True])
def test_lstm_op_pallas_parity_in_program(reverse):
    """The lstm op with use_pallas_kernel=True (interpret) reproduces the
    XLA lowering inside a full program, including the backward pass —
    both directions (is_reverse exercises the scan-domain flips and the
    LastH/LastC cotangent folding in the explicit Pallas grad)."""
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.executor import Executor, Scope, scope_guard
    from paddle_tpu.core.program import Program, program_guard

    B, T, H = 4, 5, 8
    x = rng.randn(B, T, 4 * H).astype("float32") * 0.3
    lens = np.array([5, 3, 1, 4], "int64")

    def run(use_pallas):
        prog, startup = Program(), Program()
        prog.random_seed = 7
        with program_guard(prog, startup), unique_name.guard():
            d = fluid.layers.data("x", [T, 4 * H], lod_level=1)
            from paddle_tpu.layer_helper import LayerHelper
            helper = LayerHelper("lstm")
            w = helper.create_parameter("w", (H, 4 * H), "float32")
            hidden = helper.create_variable_for_type_inference(
                "float32", shape=(B, T, H))
            cell = helper.create_variable_for_type_inference(
                "float32", shape=(B, T, H))
            lh = helper.create_variable_for_type_inference(
                "float32", shape=(B, H))
            lc = helper.create_variable_for_type_inference(
                "float32", shape=(B, H))
            attrs = {"is_reverse": reverse}
            if use_pallas is not None:
                attrs["use_pallas_kernel"] = use_pallas
            from paddle_tpu.layers.nn import seq_len_var
            helper.append_op(
                "lstm",
                {"Input": [d], "Weight": [w], "SeqLen": [seq_len_var(d)]},
                {"Hidden": [hidden], "Cell": [cell],
                 "LastH": [lh], "LastC": [lc]}, attrs)
            loss = fluid.layers.elementwise_add(
                fluid.layers.mean(hidden),
                fluid.layers.mean(lh))
            pairs = fluid.append_backward(loss)
            grad_w = dict((p.name, g) for p, g in pairs)[w.name]
        scope, exe = Scope(), Executor()
        with scope_guard(scope):
            exe.run(startup)
            outs = exe.run(prog, feed={"x": x, "x@LEN": lens},
                           fetch_list=[hidden.name, grad_w.name])
        return outs

    h_x, gw_x = run(None)       # default: XLA scan on CPU
    h_p, gw_p = run(True)       # forced pallas interpret
    np.testing.assert_allclose(h_p, h_x, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gw_p, gw_x, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# fused GRU cell
# ---------------------------------------------------------------------------

def ref_gru(xproj, w, h0, mask):
    """jnp scan reference — same math as ops/nn_ops.py _gru."""
    B, T, H3 = xproj.shape
    H = H3 // 3
    w_uz, w_c = w[:, :2 * H], w[:, 2 * H:]
    xs = jnp.swapaxes(xproj, 0, 1)
    ms = jnp.swapaxes(mask, 0, 1)[..., None]

    def step(h, inp):
        x_t, m_t = inp
        uz = jax.nn.sigmoid(x_t[:, :2 * H] + jnp.matmul(h, w_uz))
        u, r = uz[:, :H], uz[:, H:]
        c = jnp.tanh(x_t[:, 2 * H:] + jnp.matmul(r * h, w_c))
        h_new = u * h + (1 - u) * c
        h_new = m_t * h_new + (1 - m_t) * h
        return h_new, h_new

    _, hs = jax.lax.scan(step, h0, (xs, ms))
    return jnp.swapaxes(hs, 0, 1)


@pytest.mark.parametrize("masked", [False, True])
def test_fused_gru_forward_and_grads_match_scan(masked):
    B, T, H = 4, 6, 16
    xproj = jnp.asarray(rng.randn(B, T, 3 * H).astype("float32") * 0.5)
    w = jnp.asarray(rng.randn(H, 3 * H).astype("float32") * 0.3)
    h0 = jnp.asarray(rng.randn(B, H).astype("float32") * 0.1)
    if masked:
        lens = rng.randint(1, T + 1, (B,))
        mask = jnp.asarray(
            (np.arange(T)[None, :] < lens[:, None]).astype("float32"))
    else:
        mask = jnp.ones((B, T), "float32")

    hs1 = R.gru_fused(xproj, w, h0, mask, True)
    hs2 = ref_gru(xproj, w, h0, mask)
    np.testing.assert_allclose(np.asarray(hs1), np.asarray(hs2),
                               rtol=1e-5, atol=1e-5)

    def loss_ref(xproj, w, h0):
        return jnp.sum(ref_gru(xproj, w, h0, mask) ** 2)

    g1 = R.gru_fused_grad(xproj, w, h0, mask, hs1, 2.0 * hs1, True)
    g2 = jax.grad(loss_ref, (0, 1, 2))(xproj, w, h0)
    for a, b, name in zip(g1, g2, ["dx", "dw", "dh0"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("reverse", [False, True])
def test_gru_op_pallas_parity_in_program(reverse):
    """gru op with use_pallas_kernel=True vs the XLA scan, fwd + grads,
    both directions."""
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.executor import Executor, Scope, scope_guard
    from paddle_tpu.core.program import Program, program_guard

    B, T, H = 4, 5, 8
    x = rng.randn(B, T, 3 * H).astype("float32") * 0.3
    lens = np.array([5, 3, 1, 4], "int64")

    def run(use_pallas):
        prog, startup = Program(), Program()
        prog.random_seed = 7
        with program_guard(prog, startup), unique_name.guard():
            d = fluid.layers.data("x", [T, 3 * H], lod_level=1)
            from paddle_tpu.layer_helper import LayerHelper
            helper = LayerHelper("gru")
            w = helper.create_parameter("w", (H, 3 * H), "float32")
            hidden = helper.create_variable_for_type_inference(
                "float32", shape=(B, T, H))
            lh = helper.create_variable_for_type_inference(
                "float32", shape=(B, H))
            attrs = {"is_reverse": reverse}
            if use_pallas is not None:
                attrs["use_pallas_kernel"] = use_pallas
            from paddle_tpu.layers.nn import seq_len_var
            helper.append_op(
                "gru",
                {"Input": [d], "Weight": [w], "SeqLen": [seq_len_var(d)]},
                {"Hidden": [hidden], "LastH": [lh]}, attrs)
            loss = fluid.layers.elementwise_add(
                fluid.layers.mean(hidden), fluid.layers.mean(lh))
            pairs = fluid.append_backward(loss)
            grad_w = dict((p.name, g) for p, g in pairs)[w.name]
        scope, exe = Scope(), Executor()
        with scope_guard(scope):
            exe.run(startup)
            return exe.run(prog, feed={"x": x, "x@LEN": lens},
                           fetch_list=[hidden.name, grad_w.name])

    h_x, gw_x = run(None)
    h_p, gw_p = run(True)
    np.testing.assert_allclose(h_p, h_x, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gw_p, gw_x, rtol=2e-4, atol=2e-4)
