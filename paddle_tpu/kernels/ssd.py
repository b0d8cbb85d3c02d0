"""State-space duality (Mamba-2's recurrence) for the decode plane.

A head ``h`` of group ``g = h // (H / G)`` keeps a state ``S_h`` [N, P]
float32 — the state index on the major axis, the head's channels on the
lanes, which is also how the cache keeps a stream's rows — and, with a
scalar decay ``A_h < 0`` a head and a step size ``Δ_{t,h}`` a position,

    S_t = exp(Δ_t A) S_{t−1} + B_t ⊗ (Δ_t x_t) ,   y_t = C_tᵀ S_t

with ``x`` [T, H, P], ``Δ`` [T, H], ``B, C`` [T, G, N] (a group's heads share
them).  (``kernels/ssm.py`` is Mamba-1's: a decay a channel and a state
index, an elementwise scan.  Here the decay is a scalar a head, so a chunk
of positions regroups into matrix products.)

- :func:`ssd_scan` — a prompt's scan from a zero state.  The Pallas kernel
  (``ssd_chunk_scan``) takes a chunk of ``Q`` positions and a block of heads
  of one group a grid step.  With ``c_t`` the running sum of ``Δ A`` inside
  the chunk: ``Y = ((L ∘ C Bᵀ) · diag(Δ)) X + diag(exp c) C S_prev`` with
  ``L[t, s] = exp(c_t − c_s)`` for ``s ≤ t``, and ``S_next = exp(c_Q) S_prev
  + Bᵀ diag(exp(c_Q − c) Δ) X`` — the same recurrence regrouped, every
  product on the MXU in float32.  ``C Bᵀ`` is formed once for the block's
  heads; a head's state is the output block itself, resident in VMEM over
  the chunk axis, so it visits HBM once, at the end.  A position with ``Δ =
  0`` leaves the state as it is, which is how the pads of a prefill bucket
  are passed over.  The per-head coefficients are [T, H] numbers, made
  outside in both orientations (along the lanes for a chunk's columns,
  along the sublanes for its rows, a chunk's whole decay on a head's
  lanes) so that nothing is transposed inside but ``B``.  The XLA fallback
  (:func:`ssd_scan_xla`, the recurrence one position at a time, also the
  parity anchor) counts into ``ssm.ssd_fallbacks``.
- :func:`ssd_state_step` — one token a slot over the rows of ALL layers
  ``[L, S, H, N, P]``, the layer a prefetched scalar: the kernel
  (``ssd_state_step``) reads a slot's block of heads once and writes it
  once, onto itself (``input_output_aliases``), so the rows of the other
  layers and the array's layout stay as they lie.
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
NEG_INF = -1e30
_SCAN_HEADS = 8         # heads a grid step of the chunk scan
_STEP_HEADS = 16        # heads a grid step of the one-token update
_HIGHEST = lax.Precision.HIGHEST


def _fallback() -> None:
    _obs_stats.scope("ssm").counter("ssd_fallbacks").inc()


def _head_block(H: int, G: int, most: int) -> int:
    """Heads a grid step: the largest divisor of a group's heads up to
    ``most`` (a block never straddles two groups)."""
    hpg = H // G
    return max(d for d in range(1, min(most, hpg) + 1) if hpg % d == 0)


def _tiles_ok(P: int, N: int) -> bool:
    """Off the TPU the kernels interpret themselves at any size; Mosaic
    wants a head's channels and the state index in whole lane tiles."""
    return pallas_interpret() or (P % LANE == 0 and N % LANE == 0)


# -- a prompt ---------------------------------------------------------------
def ssd_scan_xla(x, dt, A, B, C):
    """The recurrence one position at a time: x [T, H, P], dt [T, H], A [H],
    B, C [T, G, N] → (y [T, H, P], S_T [H, N, P]), float32."""
    f32 = jnp.float32
    H, P = x.shape[1:]
    G, N = B.shape[1:]
    A32 = A.astype(f32)

    def heads(a):                       # [T, G, N] → [T, H, N]
        return jnp.repeat(a.astype(f32), H // G, axis=1)

    def step(S, row):
        xt, dtt, bt, ct = row
        S = jnp.exp(dtt * A32)[:, None, None] * S \
            + bt[:, :, None] * (dtt[:, None] * xt)[:, None, :]
        return S, jnp.sum(S * ct[:, :, None], axis=1)

    S, y = lax.scan(step, jnp.zeros((H, N, P), f32),
                    (x.astype(f32), dt.astype(f32), heads(B), heads(C)))
    return y, S


def _chunk_kernel(rows_ref, cols_ref, whole_ref, x_ref, b_ref, c_ref, y_ref,
                  s_ref, *, hb: int, P: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[:] = jnp.zeros_like(s_ref)

    f32 = jnp.float32
    Bm, Cm = b_ref[:].astype(f32), c_ref[:].astype(f32)         # [Q, N]
    G = lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())), precision=_HIGHEST,
                        preferred_element_type=f32)             # [Q, Q]
    Bt = Bm.T                                                   # [N, Q]
    causal = lax.broadcasted_iota(jnp.int32, G.shape, 0) \
        >= lax.broadcasted_iota(jnp.int32, G.shape, 1)

    def mm(a, b):
        return jnp.dot(a, b, precision=_HIGHEST, preferred_element_type=f32)

    for i in range(hb):
        c_row = rows_ref[0, 0, 0, i:i + 1, :]                   # [1, Q]
        dt_row = rows_ref[0, 0, 1, i:i + 1, :]
        whole = whole_ref[0, 0, i:i + 1, :]             # exp(c_Q), [1, P]
        c_col = cols_ref[0, 0, 0, :, i:i + 1]                   # [Q, 1]
        grown = cols_ref[0, 0, 1, :, i:i + 1]                   # exp(c_t)
        left = cols_ref[0, 0, 2, :, i:i + 1]            # exp(c_Q - c_s) Δ_s
        L = jnp.exp(jnp.where(causal, c_col - c_row, NEG_INF))
        xh = x_ref[:, i * P:(i + 1) * P].astype(f32)            # [Q, P]
        prev = s_ref[i]                                         # [N, P]
        y_ref[:, i * P:(i + 1) * P] = mm(L * G * dt_row, xh) \
            + grown * mm(Cm, prev)
        s_ref[i] = whole * prev + mm(Bt, left * xh)


def scan_supported(T: int, H: int, P: int, G: int, N: int, chunk: int
                   ) -> bool:
    return T % chunk == 0 and H % G == 0 and _tiles_ok(P, N) \
        and (pallas_interpret() or chunk % LANE == 0)


def _scan_pallas(x, dt, A, B, C, chunk: int):
    T, H, P = x.shape
    G, N = B.shape[1:]
    Q, nC = chunk, T // chunk
    hb = _head_block(H, G, _SCAN_HEADS)
    nb = H // hb
    f32 = jnp.float32
    dt32 = dt.astype(f32).reshape(nC, Q, H)
    c = jnp.cumsum(dt32 * A.astype(f32), axis=1)                # [nC, Q, H]
    last = c[:, -1:, :]
    cols = jnp.stack([c, jnp.exp(c), jnp.exp(last - c) * dt32], axis=1)
    rows = jnp.stack([c, dt32], axis=1)
    # [nC, ·, Q, H] → a block of heads a grid step, in both orientations
    cols = cols.reshape(nC, 3, Q, nb, hb).transpose(0, 3, 1, 2, 4)
    rows = rows.reshape(nC, 2, Q, nb, hb).transpose(0, 3, 1, 4, 2)
    # a chunk's whole decay a head, on the head's lanes
    whole = jnp.broadcast_to(jnp.exp(last).reshape(nC, nb, hb, 1),
                             (nC, nb, hb, P))

    def group(i):
        return i * hb // (H // G)

    y, S = pl.pallas_call(
        functools.partial(_chunk_kernel, hb=hb, P=P),
        name="ssd_chunk_scan",
        grid=(nb, nC),
        in_specs=[
            pl.BlockSpec((1, 1, 2, hb, Q), lambda i, j: (j, i, 0, 0, 0)),
            pl.BlockSpec((1, 1, 3, Q, hb), lambda i, j: (j, i, 0, 0, 0)),
            pl.BlockSpec((1, 1, hb, P), lambda i, j: (j, i, 0, 0)),
            pl.BlockSpec((Q, hb * P), lambda i, j: (j, i)),
            pl.BlockSpec((Q, N), lambda i, j: (j, group(i))),
            pl.BlockSpec((Q, N), lambda i, j: (j, group(i)))],
        out_specs=[pl.BlockSpec((Q, hb * P), lambda i, j: (j, i)),
                   pl.BlockSpec((hb, N, P), lambda i, j: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, H * P), f32),
                   jax.ShapeDtypeStruct((H, N, P), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=pallas_interpret(),
    )(rows, cols, whole, x.reshape(T, H * P), B.reshape(T, G * N),
      C.reshape(T, G * N))
    return y.reshape(T, H, P), S


def ssd_scan(x, dt, A, B, C, chunk: int = LANE):
    """x [T, H, P], dt [T, H] (0 at a pad), A [H], B, C [T, G, N] → (y [T, H,
    P], S_T [H, N, P]), float32, from a zero state."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    if not scan_supported(T, H, P, G, N, chunk):
        _fallback()
        return ssd_scan_xla(x, dt, A, B, C)
    return _scan_pallas(x, dt, A, B, C, chunk)


# -- one token a slot -------------------------------------------------------
def ssd_step_xla(S, x, dt, A, B, C):
    """S [S, H, N, P] float32, x [S, H, P], dt [S, H], A [H], B, C [S, G, N]
    → (y [S, H, P] float32, S')."""
    f32 = jnp.float32
    H, G = x.shape[1], B.shape[1]
    dt32 = dt.astype(f32)
    Bh = jnp.repeat(B.astype(f32), H // G, axis=1)              # [S, H, N]
    Ch = jnp.repeat(C.astype(f32), H // G, axis=1)
    S = jnp.exp(dt32 * A.astype(f32))[:, :, None, None] * S \
        + Bh[:, :, :, None] * (dt32[:, :, None] * x.astype(f32))[:, :, None, :]
    return jnp.sum(S * Ch[:, :, :, None], axis=2), S


def _step_kernel(ly_ref, decay_ref, dx_ref, b_ref, c_ref, s_ref, y_ref,
                 out_ref, *, hb: int, P: int):
    del ly_ref
    Bb, Cb = b_ref[0, 0], c_ref[0, 0]                           # [N, P]
    for i in range(hb):
        lanes = slice(i * P, (i + 1) * P)
        S = decay_ref[0, :, lanes] * s_ref[0, 0, i] \
            + Bb * dx_ref[0, :, lanes]
        out_ref[0, 0, i] = S
        y_ref[0, :, lanes] = jnp.sum(S * Cb, axis=0, keepdims=True)


def _step_pallas(states, layer, x, dt, A, B, C):
    _, S, H, N, P = states.shape
    G = B.shape[1]
    hb = _head_block(H, G, _STEP_HEADS)
    f32 = jnp.float32
    dt32 = dt.astype(f32)

    def lanes(a):                       # [S, H] → [S, 1, H·P], a head's
        return jnp.repeat(a, P, axis=1)[:, None, :]     # value on its lanes

    def spread(a):                      # [S, G, N] → [S, G, N, P]
        return jnp.broadcast_to(a.astype(f32)[..., None], (S, G, N, P))

    row = pl.BlockSpec((1, 1, hb * P), lambda s, j, ly: (s, 0, j))
    coef = pl.BlockSpec((1, 1, N, P),
                        lambda s, j, ly: (s, j * hb // (H // G), 0, 0))
    state = pl.BlockSpec((1, 1, hb, N, P),
                         lambda s, j, ly: (ly[0], s, j, 0, 0))
    y, states = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb, P=P),
        name="ssd_state_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, H // hb),
            in_specs=[row, row, coef, coef, state],
            out_specs=[row, state]),
        out_shape=[jax.ShapeDtypeStruct((S, 1, H * P), f32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=pallas_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      lanes(jnp.exp(dt32 * A.astype(f32))),
      (dt32[:, :, None] * x.astype(f32)).reshape(S, 1, H * P),
      spread(B), spread(C), states)
    return y.reshape(S, H, P), states


def ssd_state_step(states, layer, x, dt, A, B, C):
    """One token a slot, in place: states [L, S, H, N, P] float32 (every
    layer's rows, as they lie), layer an int or a traced scalar, x [S, H, P],
    dt [S, H], A [H], B, C [S, G, N] → (y [S, H, P] float32, states')."""
    H, N, P = states.shape[2:]
    if H % B.shape[1] or not _tiles_ok(P, N):
        _fallback()
        y, new = ssd_step_xla(
            lax.dynamic_index_in_dim(states, layer, keepdims=False), x, dt,
            A, B, C)
        return y, lax.dynamic_update_index_in_dim(states, new, layer, 0)
    return _step_pallas(states, layer, x, dt, A, B, C)


__all__ = ["ssd_scan", "ssd_scan_xla", "ssd_state_step", "ssd_step_xla",
           "scan_supported", "LANE"]
