"""Fused LSTM cell — Pallas TPU kernels.

Reference precedent: the hand-fused CPU JIT RNN kernels
(``paddle/fluid/operators/math/jit_kernel_rnn.cc``, ``lstm_compute.h``) —
the reference fuses the cell's elementwise tail into the gate GEMM because
a naive per-step op chain is bandwidth-bound.  Same argument on TPU, so
the whole time loop IS the kernel here:

- grid = (T,): one sequential grid step per time step; the recurrent
  weights ride VMEM for the entire scan (constant index map — copied in
  once), h/c state lives in f32 VMEM scratch, never round-tripping HBM.
- forward stores ONLY hs/cs (the op's outputs); the backward kernel
  recomputes the gates from hs[t-1]/xproj[t] — one extra [B,4H] GEMM per
  step in exchange for not writing four [T,B,H] gate tensors in forward
  (the FlashAttention trade applied to the RNN cell).
- backward: reversed-time grid; dh/dc carries and the full dW
  accumulator live in VMEM scratch; emits per-step dX-projection and the
  initial-state grads.

Gradients are wired at the PROGRAM level (ops/nn_ops.py registers an
explicit ``lstm`` grad that calls :func:`lstm_fused_grad`), not via
``jax.custom_vjp``: the explicit grad op is the framework's native
mechanism.

Length masking matches the XLA lowering (ops/nn_ops.py _lstm): finished
rows pass h/c through unchanged, so grads flow straight through masked
steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import pallas_interpret

# w + dW output + dW scratch are ~4 MB each at H=512 — past the 16 MB
# default scoped-vmem limit with double-buffered blocks; v5e has 128 MB
# physical VMEM, so raise the cap for these kernels.
_VMEM_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=100 * 1024 * 1024)


def _gates(x_t, h, w):
    """[B,4H] pre-activations -> post-activation (i, f, g, o)."""
    H = h.shape[-1]
    pre = x_t.astype(jnp.float32) + jnp.dot(
        h.astype(w.dtype), w[:], preferred_element_type=jnp.float32)
    i = jax.nn.sigmoid(pre[:, :H])
    f = jax.nn.sigmoid(pre[:, H:2 * H])
    g = jnp.tanh(pre[:, 2 * H:3 * H])
    o = jax.nn.sigmoid(pre[:, 3 * H:])
    return i, f, g, o


def _lstm_fwd_kernel(xs_ref, w_ref, m_ref, h0_ref, c0_ref,
                     hs_ref, cs_ref, h_scr, c_scr, *, T: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = h0_ref[:].astype(jnp.float32)
        c_scr[:] = c0_ref[:].astype(jnp.float32)

    h, c = h_scr[:], c_scr[:]
    i, f, g, o = _gates(xs_ref[0], h, w_ref)
    c_new = f * c + i * g
    h_new = o * jnp.tanh(c_new)
    m = m_ref[0, 0][:, None].astype(jnp.float32)      # [B, 1]
    c_out = m * c_new + (1.0 - m) * c
    h_out = m * h_new + (1.0 - m) * h
    h_scr[:] = h_out
    c_scr[:] = c_out
    hs_ref[0] = h_out.astype(hs_ref.dtype)
    cs_ref[0] = c_out.astype(cs_ref.dtype)


def _lstm_bwd_kernel(xs_ref, w_ref, m_ref, h0_ref, c0_ref,
                     hsm1_ref, csm1_ref, cs_ref, dhs_ref, dcs_ref,
                     dxs_ref, dw_ref, dh0_ref, dc0_ref,
                     dh_scr, dc_scr, dw_scr, *, T: int):
    idx = pl.program_id(0)          # 0..T-1, walking time BACKWARD
    t = T - 1 - idx

    @pl.when(idx == 0)
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dc_scr[:] = jnp.zeros_like(dc_scr)
        dw_scr[:] = jnp.zeros_like(dw_scr)

    is_first = t == 0
    c_prev = jnp.where(is_first, c0_ref[:].astype(jnp.float32),
                       csm1_ref[0].astype(jnp.float32))
    h_prev = jnp.where(is_first, h0_ref[:].astype(jnp.float32),
                       hsm1_ref[0].astype(jnp.float32))
    i, f, g, o = _gates(xs_ref[0], h_prev, w_ref)     # recompute
    c_t = cs_ref[0].astype(jnp.float32)
    m = m_ref[0, 0][:, None].astype(jnp.float32)

    dh_total = dhs_ref[0].astype(jnp.float32) + dh_scr[:]
    dc_total = dcs_ref[0].astype(jnp.float32) + dc_scr[:]
    dh_new = m * dh_total
    dc_new = m * dc_total
    tc = jnp.tanh(c_t)
    do = dh_new * tc
    dc_new = dc_new + dh_new * o * (1.0 - tc * tc)
    di = dc_new * g
    df = dc_new * c_prev
    dg = dc_new * i
    dc_prev = dc_new * f + (1.0 - m) * dc_total
    dgates = jnp.concatenate(
        [di * i * (1.0 - i), df * f * (1.0 - f),
         dg * (1.0 - g * g), do * o * (1.0 - o)], axis=-1)  # [B, 4H]
    dxs_ref[0] = dgates.astype(dxs_ref.dtype)
    wd = w_ref[:]
    dh_prev = jnp.dot(dgates.astype(wd.dtype), wd.T,
                      preferred_element_type=jnp.float32) \
        + (1.0 - m) * dh_total
    dw_scr[:] += jnp.dot(h_prev.astype(wd.dtype).T, dgates.astype(wd.dtype),
                         preferred_element_type=jnp.float32)
    dh_scr[:] = dh_prev
    dc_scr[:] = dc_prev

    @pl.when(idx == T - 1)
    def _finish():
        dw_ref[:] = dw_scr[:].astype(dw_ref.dtype)
        dh0_ref[:] = dh_scr[:].astype(dh0_ref.dtype)
        dc0_ref[:] = dc_scr[:].astype(dc0_ref.dtype)


def _tm(x):
    """[B,T,...] -> time-major [T,B,...]."""
    return jnp.swapaxes(x, 0, 1)


def _time_maps(T: int, reverse: bool):
    """Block index maps over the time-major axis for a scan that walks
    time forward, or backward when ``reverse`` (an ``is_reverse`` layer
    reads and writes every time-indexed array through these maps — no
    flipped copy of any operand or result is ever made).

    Returns ``(at, bwd, bwd_prev)``: ``at(g)`` is the time block scan
    step g touches (the forward kernels' map); the backward kernels'
    grid step g undoes scan step T-1-g, whose own block is ``bwd(g)``
    and whose predecessor's is ``bwd_prev(g)`` (clamped; the kernel
    selects the initial state at scan step 0)."""
    def at(step):
        return (T - 1 - step if reverse else step, 0, 0)

    return (at, lambda g: at(T - 1 - g),
            lambda g: at(jnp.maximum(T - 2 - g, 0)))


def lstm_fused(xproj, w, h0, c0, mask, interpret=None, reverse=False):
    """Fused LSTM scan (forward only — grads via :func:`lstm_fused_grad`).

    xproj [B,T,4H] (x·Wx+b), w [H,4H], h0/c0 [B,H], mask [B,T] (1.0 =
    live step).  Returns (hs [B,T,H], cs [B,T,H]).  Gate order i,f,c,o
    matches ops/nn_ops.py _lstm.  ``reverse`` walks time backward:
    hs[:, t] is the state after consuming steps T-1..t, in place (see
    :func:`_time_maps`)."""
    if interpret is None:
        interpret = pallas_interpret()
    B, T, H4 = xproj.shape
    H = H4 // 4
    xs, ms = _tm(xproj), _tm(mask)[:, None, :]   # [T,1,B]: TPU-tileable
    at, _, _ = _time_maps(T, reverse)
    kernel = functools.partial(_lstm_fwd_kernel, T=T)
    hs, cs = pl.pallas_call(
        kernel,
        name="lstm_cell",
        out_shape=[jax.ShapeDtypeStruct((T, B, H), xproj.dtype),
                   jax.ShapeDtypeStruct((T, B, H), xproj.dtype)],
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H4), at),                    # xs
            pl.BlockSpec((H, H4), lambda t: (0, 0)),         # w (resident)
            pl.BlockSpec((1, 1, B), at),                     # mask
            pl.BlockSpec((B, H), lambda t: (0, 0)),          # h0
            pl.BlockSpec((B, H), lambda t: (0, 0)),          # c0
        ],
        out_specs=[pl.BlockSpec((1, B, H), at)] * 2,
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32),
                        pltpu.VMEM((B, H), jnp.float32)],
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
    )(xs, w, ms, h0, c0)
    return _tm(hs), _tm(cs)


def lstm_fused_grad(xproj, w, h0, c0, mask, hs, cs, dhs, dcs,
                    interpret=None, reverse=False):
    """Backward of :func:`lstm_fused` — all batch-major [B,T,...] in/out,
    in the same (unflipped) time order for either ``reverse``.
    Returns (dxproj, dw, dh0, dc0)."""
    if interpret is None:
        interpret = pallas_interpret()
    B, T, H4 = xproj.shape
    H = H4 // 4
    xs, ms = _tm(xproj), _tm(mask)[:, None, :]   # [T,1,B]
    hs_tm, cs_tm = _tm(hs), _tm(cs)
    dhs_tm = _tm(dhs).astype(xproj.dtype)
    dcs_tm = _tm(dcs).astype(xproj.dtype)
    kernel = functools.partial(_lstm_bwd_kernel, T=T)
    _, rev, revm1 = _time_maps(T, reverse)

    dxs, dw, dh0, dc0 = pl.pallas_call(
        kernel,
        name="lstm_cell_bwd",
        out_shape=[jax.ShapeDtypeStruct((T, B, H4), xproj.dtype),
                   jax.ShapeDtypeStruct((H, H4), w.dtype),
                   jax.ShapeDtypeStruct((B, H), xproj.dtype),
                   jax.ShapeDtypeStruct((B, H), xproj.dtype)],
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H4), rev),                  # xs
            pl.BlockSpec((H, H4), lambda t: (0, 0)),        # w
            pl.BlockSpec((1, 1, B), rev),                   # mask
            pl.BlockSpec((B, H), lambda t: (0, 0)),         # h0
            pl.BlockSpec((B, H), lambda t: (0, 0)),         # c0
            pl.BlockSpec((1, B, H), revm1),                 # hs[t-1]
            pl.BlockSpec((1, B, H), revm1),                 # cs[t-1]
            pl.BlockSpec((1, B, H), rev),                   # cs[t]
            pl.BlockSpec((1, B, H), rev),                   # dhs
            pl.BlockSpec((1, B, H), rev),                   # dcs
        ],
        out_specs=[
            pl.BlockSpec((1, B, H4), rev),                  # dxs
            pl.BlockSpec((H, H4), lambda t: (0, 0)),        # dw
            pl.BlockSpec((B, H), lambda t: (0, 0)),         # dh0
            pl.BlockSpec((B, H), lambda t: (0, 0)),         # dc0
        ],
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32),
                        pltpu.VMEM((B, H), jnp.float32),
                        pltpu.VMEM((H, H4), jnp.float32)],
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
    )(xs, w, ms, h0, c0, hs_tm, cs_tm, cs_tm, dhs_tm, dcs_tm)
    return _tm(dxs), dw, dh0, dc0


def lstm_supported(B, T, H, dtype) -> bool:
    """Pallas path gate: MXU-friendly shapes whose VMEM-resident weight
    footprint fits (w + dW output block + dW f32 scratch ≈ 3·H·4H·4 B
    must stay well under the 100 MB cap); anything else takes the XLA
    scan lowering."""
    if 3 * H * 4 * H * 4 > 80 * 1024 * 1024:   # H > ~1290
        return False
    return H % 128 == 0 and B % 8 == 0 and T >= 1


# ---------------------------------------------------------------------------
# Fused GRU cell — same design as the LSTM above (jit_kernel_rnn.cc GRU
# precedent): grid=(T,), weights VMEM-resident, backward recomputes gates.
# Gate layout matches ops/nn_ops.py _gru: w = [update | reset | candidate].
# ---------------------------------------------------------------------------

def _gru_gates(x_t, h, w):
    """Returns (u, r, c) post-activation for one step."""
    H = h.shape[-1]
    w_uz, w_c = w[:, :2 * H], w[:, 2 * H:]
    a = x_t[:, :2 * H].astype(jnp.float32) + jnp.dot(
        h.astype(w.dtype), w_uz, preferred_element_type=jnp.float32)
    u = jax.nn.sigmoid(a[:, :H])
    r = jax.nn.sigmoid(a[:, H:])
    b = x_t[:, 2 * H:].astype(jnp.float32) + jnp.dot(
        (r * h).astype(w.dtype), w_c, preferred_element_type=jnp.float32)
    return u, r, jnp.tanh(b)


def _gru_fwd_kernel(xs_ref, w_ref, m_ref, h0_ref, hs_ref, h_scr, *, T: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = h0_ref[:].astype(jnp.float32)

    h = h_scr[:]
    u, r, c = _gru_gates(xs_ref[0], h, w_ref)
    h_new = u * h + (1.0 - u) * c
    m = m_ref[0, 0][:, None].astype(jnp.float32)
    h_out = m * h_new + (1.0 - m) * h
    h_scr[:] = h_out
    hs_ref[0] = h_out.astype(hs_ref.dtype)


def _gru_bwd_kernel(xs_ref, w_ref, m_ref, h0_ref, hsm1_ref, dhs_ref,
                    dxs_ref, dw_ref, dh0_ref, dh_scr, dw_scr, *, T: int):
    idx = pl.program_id(0)
    t = T - 1 - idx

    @pl.when(idx == 0)
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dw_scr[:] = jnp.zeros_like(dw_scr)

    H = dh_scr.shape[-1]
    h_prev = jnp.where(t == 0, h0_ref[:].astype(jnp.float32),
                       hsm1_ref[0].astype(jnp.float32))
    u, r, c = _gru_gates(xs_ref[0], h_prev, w_ref)
    m = m_ref[0, 0][:, None].astype(jnp.float32)
    wd = w_ref[:]
    w_uz, w_c = wd[:, :2 * H], wd[:, 2 * H:]

    dh_total = dhs_ref[0].astype(jnp.float32) + dh_scr[:]
    din = m * dh_total
    du = din * (h_prev - c)
    dh_prev = din * u + (1.0 - m) * dh_total
    dc = din * (1.0 - u)
    db = dc * (1.0 - c * c)                          # [B,H]
    drh = jnp.dot(db.astype(wd.dtype), w_c.T,
                  preferred_element_type=jnp.float32)
    dr = drh * h_prev
    dh_prev = dh_prev + drh * r
    da = jnp.concatenate([du * u * (1.0 - u), dr * r * (1.0 - r)], axis=-1)
    dh_prev = dh_prev + jnp.dot(da.astype(wd.dtype), w_uz.T,
                                preferred_element_type=jnp.float32)
    dxs_ref[0] = jnp.concatenate([da, db], axis=-1).astype(dxs_ref.dtype)
    dw_scr[:, :2 * H] += jnp.dot(h_prev.astype(wd.dtype).T,
                                 da.astype(wd.dtype),
                                 preferred_element_type=jnp.float32)
    dw_scr[:, 2 * H:] += jnp.dot((r * h_prev).astype(wd.dtype).T,
                                 db.astype(wd.dtype),
                                 preferred_element_type=jnp.float32)
    dh_scr[:] = dh_prev

    @pl.when(idx == T - 1)
    def _finish():
        dw_ref[:] = dw_scr[:].astype(dw_ref.dtype)
        dh0_ref[:] = dh_scr[:].astype(dh0_ref.dtype)


def gru_fused(xproj, w, h0, mask, interpret=None, reverse=False):
    """Fused GRU scan (forward; grads via :func:`gru_fused_grad`).
    xproj [B,T,3H], w [H,3H], h0 [B,H], mask [B,T] -> hs [B,T,H];
    ``reverse`` as in :func:`lstm_fused`."""
    if interpret is None:
        interpret = pallas_interpret()
    B, T, H3 = xproj.shape
    H = H3 // 3
    xs, ms = _tm(xproj), _tm(mask)[:, None, :]
    at, _, _ = _time_maps(T, reverse)
    kernel = functools.partial(_gru_fwd_kernel, T=T)
    hs = pl.pallas_call(
        kernel,
        name="gru_cell",
        out_shape=jax.ShapeDtypeStruct((T, B, H), xproj.dtype),
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H3), at),
            pl.BlockSpec((H, H3), lambda t: (0, 0)),
            pl.BlockSpec((1, 1, B), at),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, B, H), at),
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32)],
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
    )(xs, w, ms, h0)
    return _tm(hs)


def gru_fused_grad(xproj, w, h0, mask, hs, dhs, interpret=None,
                   reverse=False):
    """Backward of :func:`gru_fused`; returns (dxproj, dw, dh0)."""
    if interpret is None:
        interpret = pallas_interpret()
    B, T, H3 = xproj.shape
    H = H3 // 3
    xs, ms = _tm(xproj), _tm(mask)[:, None, :]
    hs_tm = _tm(hs)
    dhs_tm = _tm(dhs).astype(xproj.dtype)
    kernel = functools.partial(_gru_bwd_kernel, T=T)
    _, rev, revm1 = _time_maps(T, reverse)

    dxs, dw, dh0 = pl.pallas_call(
        kernel,
        name="gru_cell_bwd",
        out_shape=[jax.ShapeDtypeStruct((T, B, H3), xproj.dtype),
                   jax.ShapeDtypeStruct((H, H3), w.dtype),
                   jax.ShapeDtypeStruct((B, H), xproj.dtype)],
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H3), rev),                  # xs
            pl.BlockSpec((H, H3), lambda t: (0, 0)),        # w
            pl.BlockSpec((1, 1, B), rev),                   # mask
            pl.BlockSpec((B, H), lambda t: (0, 0)),         # h0
            pl.BlockSpec((1, B, H), revm1),                 # hs[t-1]
            pl.BlockSpec((1, B, H), rev),                   # dhs
        ],
        out_specs=[
            pl.BlockSpec((1, B, H3), rev),
            pl.BlockSpec((H, H3), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32),
                        pltpu.VMEM((H, H3), jnp.float32)],
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
    )(xs, w, ms, h0, hs_tm, dhs_tm)
    return _tm(dxs), dw, dh0


def gru_supported(B, T, H, dtype) -> bool:
    if 3 * H * 3 * H * 4 > 80 * 1024 * 1024:
        return False
    return H % 128 == 0 and B % 8 == 0 and T >= 1
