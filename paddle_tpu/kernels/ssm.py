"""Selective state-space scan (Mamba-1's recurrence) for the decode plane.

    h_t = exp(Δ_t ⊗ A) ⊙ h_{t−1} + (Δ_t ⊙ x_t) ⊗ B_t ,   y_t = h_t C_t

with ``x, Δ`` [T, Di], ``A`` [N, Di] (negative), ``B, C`` [T, N] and the state
``h`` [N, Di] float32 — the state index on the major axis, the channels on
the lanes, which is also how the cache keeps a stream's ``h``.

- :func:`selective_scan` — a prompt's scan from a zero state, chunked over
  time (``kernels/rnn.py`` is the precedent: the time loop IS the kernel).
  The Pallas kernel (``ssm_selective_scan``) gives each grid step ``tc``
  positions of a block of 8 x 128 channels; the block's state, ``N`` vregs,
  is the loop carry of the chunk and rests in VMEM scratch between chunks, so
  ``h`` never visits HBM until the last chunk writes it.  Channels are viewed
  ``[Di/128, 128]`` so that one position's block is whole (8, 128) tiles
  reached by an index on a major axis; ``B`` and ``C`` arrive lane-broadcast
  (``[T, N, 128]``) so that a state's coefficient is a sublane broadcast.  A
  position with ``Δ = 0`` leaves the state as it is (``exp(0) = 1``, no
  input), which is how the pad positions of a prefill bucket are passed over:
  the state returned is the one at the last real position.  The XLA fallback
  (:func:`selective_scan_xla`, a sequential ``lax.scan``) counts into
  ``ssm.scan_fallbacks``.
- :func:`selective_step` — one token a stream, batched over the slots:
  elementwise over ``[S, N, Di]``, which XLA fuses into one pass over the
  state (read once, written once); there is nothing for a kernel to add.
- :func:`causal_conv` / :func:`conv_step` — the depthwise causal convolution
  before the scan, over a prompt and for one token given the tail.  Mamba-2's
  mixer (``decode/falcon_h1.py``) convolves with these too; its recurrence —
  a scalar decay a head, so a chunk regroups into matrix products — is
  ``kernels/ssd.py``'s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import stats as _obs_stats
from ..platform import pallas_interpret

LANE = 128
_ROWS = 8               # channel rows a block: 8 x 128 = one float32 vreg
_CHUNK = 256            # positions a grid step


def selective_scan_xla(x, delta, A, B, C):
    """The recurrence one position at a time: (y [T, Di], h_T [N, Di]),
    float32."""
    x32, A32 = x.astype(jnp.float32), A.astype(jnp.float32)

    def step(h, row):
        xt, dt, bt, ct = row
        h = jnp.exp(dt[None, :] * A32) * h + (dt * xt)[None, :] * bt[:, None]
        return h, jnp.sum(h * ct[:, None], axis=0)

    h0 = jnp.zeros(A.shape, jnp.float32)
    h, y = lax.scan(step, h0, (x32, delta.astype(jnp.float32),
                               B.astype(jnp.float32), C.astype(jnp.float32)))
    return y, h


def _scan_kernel(d_ref, x_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_scr,
                 *, tc: int, n_state: int, n_chunks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        h_scr[:] = jnp.zeros_like(h_scr)

    rows = h_scr.shape[1]

    def body(t, hs):
        d = d_ref[t]                                    # [rows, 128]
        dx = d * x_ref[t]
        bt, ct = b_ref[t], c_ref[t]                     # [N, 128]
        out, y = [], None
        for n in range(n_state):
            h = jnp.exp(d * a_ref[n]) * hs[n] + dx * jnp.broadcast_to(
                bt[n:n + 1, :], (rows, LANE))
            out.append(h)
            term = h * jnp.broadcast_to(ct[n:n + 1, :], (rows, LANE))
            y = term if y is None else y + term
        y_ref[t] = y
        return tuple(out)

    hs = lax.fori_loop(0, tc, body,
                       tuple(h_scr[n] for n in range(n_state)))
    for n in range(n_state):
        h_scr[n] = hs[n]

    @pl.when(j == n_chunks - 1)
    def _finish():
        hout_ref[:] = h_scr[:]


def scan_supported(T: int, Di: int) -> bool:
    """Whole lane tiles of channels, and either whole blocks of eight rows
    or few enough rows for one block."""
    if Di % LANE:
        return False
    rows = Di // LANE
    return (rows % _ROWS == 0 or rows <= 2 * _ROWS) and \
        T % min(T, _CHUNK) == 0


def _scan_pallas(x, delta, A, B, C):
    T, Di = x.shape
    N = A.shape[0]
    R = Di // LANE
    rb = _ROWS if R % _ROWS == 0 else R
    tc = min(T, _CHUNK)
    n_chunks = T // tc
    f32 = jnp.float32

    def rows(a):                    # [T, Di] → [T, R, 128]
        return a.astype(f32).reshape(T, R, LANE)

    def lanes(a):                   # [T, N] → [T, N, 128]
        return jnp.broadcast_to(a.astype(f32)[:, :, None], (T, N, LANE))

    seq = pl.BlockSpec((tc, rb, LANE), lambda i, j: (j, i, 0))
    coef = pl.BlockSpec((tc, N, LANE), lambda i, j: (j, 0, 0))
    state = pl.BlockSpec((N, rb, LANE), lambda i, j: (0, i, 0))
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, tc=tc, n_state=N, n_chunks=n_chunks),
        name="ssm_selective_scan",
        grid=(R // rb, n_chunks),
        in_specs=[seq, seq, state, coef, coef],
        out_specs=[seq, state],
        out_shape=[jax.ShapeDtypeStruct((T, R, LANE), f32),
                   jax.ShapeDtypeStruct((N, R, LANE), f32)],
        scratch_shapes=[pltpu.VMEM((N, rb, LANE), f32)],
        interpret=pallas_interpret(),
    )(rows(delta), rows(x), A.astype(f32).reshape(N, R, LANE), lanes(B),
      lanes(C))
    return y.reshape(T, Di), h.reshape(N, Di)


def selective_scan(x, delta, A, B, C):
    """x, delta [T, Di], A [N, Di], B, C [T, N] → (y [T, Di], h_T [N, Di]),
    float32, from a zero state."""
    if not scan_supported(*x.shape):
        _obs_stats.scope("ssm").counter("scan_fallbacks").inc()
        return selective_scan_xla(x, delta, A, B, C)
    return _scan_pallas(x, delta, A, B, C)


def selective_step(h, x, delta, A, B, C):
    """One token a row: h [S, N, Di] float32, x, delta [S, Di], A [N, Di],
    B, C [S, N] → (y [S, Di] float32, h')."""
    x32, d32 = x.astype(jnp.float32), delta.astype(jnp.float32)
    h = jnp.exp(d32[:, None, :] * A.astype(jnp.float32)[None]) * h \
        + (d32 * x32)[:, None, :] * B.astype(jnp.float32)[:, :, None]
    return jnp.sum(h * C.astype(jnp.float32)[:, :, None], axis=1), h


def causal_conv(a, w, b=None):
    """Depthwise causal convolution of a prompt: a [T, Di], w [K, Di] (w[K-1]
    weighs the current position), b [Di] or None (a filter without a bias) →
    [T, Di] float32, before the activation; positions before the prompt are
    zeros."""
    K, T = w.shape[0], a.shape[0]
    a32 = a.astype(jnp.float32)
    padded = jnp.concatenate([jnp.zeros((K - 1, a.shape[1]), jnp.float32),
                              a32])
    out = 0.0 if b is None else b.astype(jnp.float32)[None, :]
    for k in range(K):
        out = out + w[k].astype(jnp.float32)[None, :] * padded[k:k + T]
    return out


def conv_step(tail, a, w, b=None):
    """One token a row: tail [S, K-1, Di] (the last K-1 inputs, oldest
    first), a [S, Di] → (conv [S, Di] float32, tail')."""
    window = jnp.concatenate([tail, a[:, None, :].astype(tail.dtype)], axis=1)
    def taps():
        return jnp.einsum("skd,kd->sd", window.astype(jnp.float32),
                          w.astype(jnp.float32))

    out = taps() if b is None else b.astype(jnp.float32)[None, :] + taps()
    return out, window[:, 1:]


__all__ = ["selective_scan", "selective_scan_xla", "selective_step",
           "scan_supported", "causal_conv", "conv_step"]
