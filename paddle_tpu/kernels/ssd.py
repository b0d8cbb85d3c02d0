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
- **Heads of 64 channels** are half a lane tile, so they lie TWO to a tile,
  a pair never straddling a group: a stream's rows are kept ``[H / 2, N,
  128]`` (:func:`state_layout`; ``[H, N, 64]`` float32 is padded to twice the
  bytes on a TPU), heads ``2j`` and ``2j + 1`` on the two halves of pair
  ``j``'s lanes, and both kernels have a form of their own under names of
  their own (``ssd64_chunk_scan``, ``ssd64_state_step``) that treats a pair
  as one 128-lane head whose halves carry their own coefficients.  The
  128-wide forms are untouched by it.
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
HALF = LANE // 2
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


def paired(P: int) -> bool:
    """Whether heads ``P`` channels wide lie two to a lane tile: a 64-wide
    head is half a tile, and its rows are kept (``state_layout``) and walked
    with its neighbour's beside them."""
    return P == HALF


def _tiles_ok(H: int, G: int, P: int, N: int) -> bool:
    """Off the TPU the kernels interpret themselves at any size; Mosaic
    wants a head's channels — or a pair of 64-wide heads of ONE group's — and
    the state index in whole lane tiles."""
    if paired(P) and (H // G) % 2:
        return False
    return pallas_interpret() or ((P % LANE == 0 or paired(P))
                                  and N % LANE == 0)


def state_layout(H: int, N: int, P: int) -> tuple:
    """The shape a stream's recurrent rows of one layer are KEPT in: ``[H, N,
    P]``, or where heads are 64 wide ``[H / 2, N, 128]`` — heads ``2j`` and
    ``2j + 1`` side by side on the lanes of pair ``j`` (as ``[H, N, 64]``
    float32 a TPU pads every row of 64 to a lane tile: twice the bytes)."""
    return (H // 2, N, 2 * P) if paired(P) else (H, N, P)


def pack_state(S):
    """S [..., H, N, P] → its kept layout (:func:`state_layout`)."""
    *lead, H, N, P = S.shape
    if not paired(P):
        return S
    S = S.reshape(*lead, H // 2, 2, N, P)
    return jnp.moveaxis(S, -3, -2).reshape(*lead, H // 2, N, 2 * P)


def unpack_state(S, P: int):
    """The kept layout [..., ·, N, ·] of heads ``P`` wide → [..., H, N, P]."""
    if not paired(P):
        return S
    *lead, Hp, N, _ = S.shape
    S = S.reshape(*lead, Hp, N, 2, P)
    return jnp.moveaxis(S, -2, -3).reshape(*lead, 2 * Hp, N, P)


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


def _pair_chunk_kernel(rows_ref, cols_ref, whole_ref, x_ref, b_ref, c_ref,
                       y_ref, s_ref, *, hb: int):
    """:func:`_chunk_kernel` for 64-wide heads, a PAIR of one group's heads a
    lane tile: the pair's states are one ``[N, 128]`` block and the products
    with ``C`` and ``Bᵀ`` serve both heads at once, each head's coefficients
    on its own half of the lanes; the one product that differs a head — the
    chunk's own positions through ``L_h`` — takes each head's half of ``x``
    with the other half zero, so its rows land on the head's own lanes."""
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
    Q = G.shape[0]
    first = lax.broadcasted_iota(jnp.int32, (Q, LANE), 1) < HALF
    first_row = first[:1]

    def mm(a, b):
        return jnp.dot(a, b, precision=_HIGHEST, preferred_element_type=f32)

    def own(h):
        """Head ``h``'s [Q, Q] map of the chunk's own positions."""
        c_row = rows_ref[0, 0, 0, h:h + 1, :]                   # [1, Q]
        dt_row = rows_ref[0, 0, 1, h:h + 1, :]
        c_col = cols_ref[0, 0, 0, :, h:h + 1]                   # [Q, 1]
        return jnp.exp(jnp.where(causal, c_col - c_row, NEG_INF)) * G * dt_row

    def halves(k, a, b):
        """Column ``k`` of ``cols`` of heads a and b, each on its lanes."""
        return jnp.where(first, cols_ref[0, 0, k, :, a:a + 1],
                         cols_ref[0, 0, k, :, b:b + 1])

    for i in range(hb // 2):
        a, b = 2 * i, 2 * i + 1
        xh = x_ref[:, i * LANE:(i + 1) * LANE].astype(f32)      # [Q, 128]
        prev = s_ref[i]                                         # [N, 128]
        y_ref[:, i * LANE:(i + 1) * LANE] = \
            mm(own(a), jnp.where(first, xh, 0.0)) \
            + mm(own(b), jnp.where(first, 0.0, xh)) \
            + halves(1, a, b) * mm(Cm, prev)
        whole = jnp.where(first_row, whole_ref[0, 0, a:a + 1, :],
                          whole_ref[0, 0, b:b + 1, :])          # [1, 128]
        s_ref[i] = whole * prev + mm(Bt, halves(2, a, b) * xh)


def scan_supported(T: int, H: int, P: int, G: int, N: int, chunk: int
                   ) -> bool:
    return T % chunk == 0 and H % G == 0 and _tiles_ok(H, G, P, N) \
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
    # a chunk's whole decay a head, on the head's lanes (of a pair's tile:
    # on the whole tile, the kernel takes each head's half)
    Pw = LANE if paired(P) else P
    whole = jnp.broadcast_to(jnp.exp(last).reshape(nC, nb, hb, 1),
                             (nC, nb, hb, Pw))

    def group(i):
        return i * hb // (H // G)

    kept = state_layout(H, N, P)
    y, S = pl.pallas_call(
        functools.partial(_pair_chunk_kernel, hb=hb) if paired(P)
        else functools.partial(_chunk_kernel, hb=hb, P=P),
        name="ssd64_chunk_scan" if paired(P) else "ssd_chunk_scan",
        grid=(nb, nC),
        in_specs=[
            pl.BlockSpec((1, 1, 2, hb, Q), lambda i, j: (j, i, 0, 0, 0)),
            pl.BlockSpec((1, 1, 3, Q, hb), lambda i, j: (j, i, 0, 0, 0)),
            pl.BlockSpec((1, 1, hb, Pw), lambda i, j: (j, i, 0, 0)),
            pl.BlockSpec((Q, hb * P), lambda i, j: (j, i)),
            pl.BlockSpec((Q, N), lambda i, j: (j, group(i))),
            pl.BlockSpec((Q, N), lambda i, j: (j, group(i)))],
        out_specs=[pl.BlockSpec((Q, hb * P), lambda i, j: (j, i)),
                   pl.BlockSpec((kept[0] // nb,) + kept[1:],
                                lambda i, j: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, H * P), f32),
                   jax.ShapeDtypeStruct(kept, f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=pallas_interpret(),
    )(rows, cols, whole, x.reshape(T, H * P), B.reshape(T, G * N),
      C.reshape(T, G * N))
    return y.reshape(T, H, P), S


def ssd_scan(x, dt, A, B, C, chunk: int = LANE):
    """x [T, H, P], dt [T, H] (0 at a pad), A [H], B, C [T, G, N] → (y [T, H,
    P], S_T in its kept layout (:func:`state_layout`: [H, N, P], 64-wide
    heads two to a tile)), float32, from a zero state."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    if not scan_supported(T, H, P, G, N, chunk):
        _fallback()
        y, S = ssd_scan_xla(x, dt, A, B, C)
        return y, pack_state(S)
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


def _pair_step_kernel(ly_ref, decay_ref, dx_ref, b_ref, c_ref, s_ref, y_ref,
                      out_ref, *, pairs: int, per_group: int):
    """:func:`_step_kernel` for 64-wide heads kept two to a lane tile, ALL of
    a slot's pairs a grid step: a pair's tile is a head of 128 lanes whose
    halves carry their own decay and input, and ``B`` and ``C`` come as they
    are, ``[G, N]`` a slot — turned ONCE a slot so that a group's
    coefficients lie along the sublanes and meet every lane of its pairs'
    tiles — where the 128-wide form is handed them spread over a head's
    lanes (a quarter as many bytes again as the rows themselves at eight
    groups of a 128-deep state)."""
    del ly_ref
    G, N = b_ref.shape[1:]
    fill = jnp.zeros((LANE - G, N), jnp.float32)

    def turned(ref):            # [G, N] → [N, 128]: group g is column g
        return jnp.concatenate([ref[0], fill], axis=0).T

    Bt, Ct = turned(b_ref), turned(c_ref)
    for i in range(pairs):
        g = i // per_group
        lanes = slice(i * LANE, (i + 1) * LANE)
        S = decay_ref[0, :, lanes] * s_ref[0, 0, i] \
            + Bt[:, g:g + 1] * dx_ref[0, :, lanes]
        out_ref[0, 0, i] = S
        y_ref[0, :, lanes] = jnp.sum(S * Ct[:, g:g + 1], axis=0,
                                     keepdims=True)


def _pair_step_pallas(states, layer, x, dt, A, B, C):
    _, S, Hp, N, _ = states.shape
    H, P = x.shape[1:]
    G = B.shape[1]
    f32 = jnp.float32
    dt32 = dt.astype(f32)

    def lanes(a):                       # [S, H] → [S, 1, H·P], a head's
        return jnp.repeat(a, P, axis=1)[:, None, :]     # value on its lanes

    row = pl.BlockSpec((1, 1, H * P), lambda s, ly: (s, 0, 0))
    coef = pl.BlockSpec((1, G, N), lambda s, ly: (s, 0, 0))
    state = pl.BlockSpec((1, 1, Hp, N, LANE),
                         lambda s, ly: (ly[0], s, 0, 0, 0))
    y, states = pl.pallas_call(
        functools.partial(_pair_step_kernel, pairs=Hp, per_group=Hp // G),
        name="ssd64_state_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S,),
            in_specs=[row, row, coef, coef, state],
            out_specs=[row, state]),
        out_shape=[jax.ShapeDtypeStruct((S, 1, H * P), f32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=pallas_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      lanes(jnp.exp(dt32 * A.astype(f32))),
      (dt32[:, :, None] * x.astype(f32)).reshape(S, 1, H * P),
      B.astype(f32), C.astype(f32), states)
    return y.reshape(x.shape), states


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
    """One token a slot, in place: states [L, S, *kept] float32 (every
    layer's rows, as they lie: :func:`state_layout`, [H, N, P] but for
    64-wide heads), layer an int or a traced scalar, x [S, H, P], dt [S, H],
    A [H], B, C [S, G, N] → (y [S, H, P] float32, states')."""
    (H, P), N = x.shape[1:], states.shape[3]
    if H % B.shape[1] or not _tiles_ok(H, B.shape[1], P, N):
        _fallback()
        y, new = ssd_step_xla(
            unpack_state(lax.dynamic_index_in_dim(states, layer,
                                                  keepdims=False), P),
            x, dt, A, B, C)
        return y, lax.dynamic_update_index_in_dim(states, pack_state(new),
                                                  layer, 0)
    if paired(P):
        return _pair_step_pallas(states, layer, x, dt, A, B, C)
    return _step_pallas(states, layer, x, dt, A, B, C)


__all__ = ["ssd_scan", "ssd_scan_xla", "ssd_state_step", "ssd_step_xla",
           "scan_supported", "state_layout", "pack_state", "unpack_state",
           "paired", "LANE"]
