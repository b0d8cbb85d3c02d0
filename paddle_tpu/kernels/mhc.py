"""Manifold-constrained hyper-connections (mHC): the residual mixing of a
model that keeps ``n`` residual streams a token (arXiv:2512.24880 over
arXiv:2409.19606), for the decode plane.

A token's residual is ``X`` [n, D], held as one row of ``n·D`` lanes (stream
``j`` the lanes ``jD … (j+1)D``): ``[N, n, D]`` with a second-minor axis of 4
would be tiled apart on the TPU and every reshape of it a copy.  A sub-layer
``F`` (attention or a feed-forward) with its own ``Φ`` [n² + 2n, n·D], ``b``
[n² + 2n], ``α`` [3] (all float32) computes

    u = vec(X) in float32            ρ = (mean(u²) + eps)^(-1/2)
    m = ρ · (Φ u)                    # n² + 2n numbers
    H_pre  = σ(α₀ m[0:n] + b[0:n])                       ∈ (0, 1)^n
    H_post = 2 σ(α₁ m[n:2n] + b[n:2n])                   ∈ (0, 2)^n
    M = exp(clip(α₂ mat(m[2n:]) + mat(b[2n:]), ±clamp))
    iters times:  M ← M / (rowsum(M) + ε);  M ← M / (colsum(M) + ε)
    H_res = M                        # ≈ doubly stochastic, n × n
    h  = Σ_j H_pre[j] X[j]           # what F's norm reads
    X'[i] = Σ_j H_res[i, j] X[j] + H_post[i] · F(norm(h))

— bandwidth work: a sub-layer reads the widest tensor of the program twice
and writes it once.  Two entry points, each with an XLA form (the fallback,
counted in ``mhc.pre_fallbacks`` / ``mhc.post_fallbacks``) and a Pallas
kernel:

- :func:`mhc_pre` (kernel ``mhc_pre``) — one pass over ``X``: ``Φ u`` and
  ``Σ u²`` of a tile of rows together, the maps, the Sinkhorn rounds and
  ``h``.  ``Φ`` is stored as it is contracted, [n² + 2n, n·D]: 24 rows of
  lanes (1.4 MB at n = 4, D = 3,584) where its transpose would be padded to
  128 lanes a row in HBM and in VMEM alike (7.3 MB).  The streams are bf16, so
  ``u`` is whole in ONE bf16 piece; ``Φ``'s float32 is THREE bf16 pieces
  (``hi + mid + lo``, all 24 bits), which the kernel cuts once, at its first
  grid step, into a VMEM scratch of 128 rows — ``hi`` at row 0, ``mid`` at 32,
  ``lo`` at 64 — so that ONE pass of the MXU (``X Φ₃ᵀ``, 72 of its 128
  columns used) is the float32 product; two lane rotations bring the three
  partial sums together, the smallest first.  The Sinkhorn rounds run with the
  TOKENS along the lanes (the maps' tile transposed, an entry of ``M`` a row):
  64 vector operations a round for 128 tokens, where the row layout would
  spend 128 lanes on an entry.
- :func:`mhc_post` (kernel ``mhc_post``) — one read of ``X`` and ``y``, one
  write, in place (``X`` is donated to ``X'``).

Both tile the rows; a last tile past the rows computes on what lies there and
writes nothing of it.  Rows are independent, so padding never reaches a real
row.  No knob, flag or environment variable chooses a form: ``impl`` is the
caller's argument, as in ``kernels/mla.py``.

Measured on the v5e at the published shape (``[T, 4·3584]`` bf16; my chip
runs, PR 54; N calls in ONE program — a ``lax.fori_loop`` of 44 and of 4, the
difference over 40 — because a dispatch costs 0.6 ms on the chip's host;
``mhc_pre`` is the pair's time less ``mhc_post``'s, so ± 20 µs on it; µs a
call, beside them the bytes' time at 819 GB/s):

    rows a grid step x lanes a chunk    T = 64        T = 2,048     T = 8,192
    mhc_pre   128 x 512 (kept)          11   [4.5]    88   [92]     451  [361]
              256 x 512                 13            65            443
              128 x 256                 —             66            455
               64 x 512                 20            97            476
              XLA form                  29            306           1,203
    mhc_post  128 x 512 (kept)          15   [5.0]    196  [162]    805  [646]
              256 x 512                 20            209           804
              128 x 256                 22            208           805
               64 x 512                 13            203           805
              XLA form                  13            456           2,401

The tile does not matter (both kernels sit at 77-80% of their bytes' time at
8,192 rows, whatever it is); the fusion does: the pair takes 1.25 ms where XLA
takes 3.60.  The 24-wide product runs on the MXU: ONE pass over the three
pieces, 72 of 128 columns, is 28 cycles a token on four units, under the 66 a
token's 36 KB take from HBM; three passes (a piece a pass, or ``Φ`` widened to
its transpose's 128 lanes) would be 84 and bound the kernel, and the VPU's 24
multiply-adds an element are further off still.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import stats as _obs_stats
from ..platform import pallas_interpret

LANE = 128
ROW_TILE = 128          # rows a grid step (the table above)
# lanes of one stream a pass of the weighted sums takes at a time
_CHUNK = 512
_PIECE_ROWS = 32        # Φ's three bf16 pieces lie 32 rows apart
_VMEM = pltpu.CompilerParams(vmem_limit_bytes=100 * 1024 * 1024,
                             dimension_semantics=("arbitrary",))


def n_maps(n: int) -> int:
    """Numbers a sub-layer's maps hold: ``H_pre`` n, ``H_post`` n, ``H_res``
    n²."""
    return n * n + 2 * n


def streams(phi) -> int:
    """``n`` of a ``Φ`` [n² + 2n, n·D]."""
    n = math.isqrt(phi.shape[0] + 1) - 1
    if n_maps(n) != phi.shape[0] or phi.shape[1] % n:
        raise ValueError(f"phi {phi.shape} is no [n² + 2n, n·D]")
    return n


# ---------------------------------------------------------------------------
# the XLA forms
# ---------------------------------------------------------------------------

def sinkhorn(a_res, iters: int, clamp: float, hc_eps: float):
    """a_res [..., n, n] float32 → ``M``: the exponential of the clipped
    entries, then ``iters`` rounds of rows then columns, ε in both
    denominators."""
    m = jnp.exp(jnp.clip(a_res, -clamp, clamp))

    def one(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + hc_eps)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + hc_eps)
    return lax.fori_loop(0, iters, one, m)


def maps_xla(x, phi, b, alpha, eps: float, iters: int, clamp: float,
             hc_eps: float):
    """x [N, n·D] → (H_pre [N, n], H_post [N, n], H_res [N, n, n]), all
    float32, the product at the highest precision."""
    N, n = x.shape[0], streams(phi)
    u = x.astype(jnp.float32)
    rho = lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)
    m = jnp.einsum("nk,mk->nm", u, phi.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST) * rho
    b = b.astype(jnp.float32)
    h_pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + b[n:2 * n])
    a_res = alpha[2] * m[:, 2 * n:] + b[2 * n:]
    return h_pre, h_post, sinkhorn(a_res.reshape(N, n, n), iters, clamp,
                                   hc_eps)


def mhc_pre_xla(x, phi, b, alpha, eps: float, iters: int, clamp: float,
                hc_eps: float):
    h_pre, h_post, h_res = maps_xla(x, phi, b, alpha, eps, iters, clamp,
                                    hc_eps)
    n = h_pre.shape[1]
    h = jnp.einsum("nj,njd->nd", h_pre,
                   x.reshape(x.shape[0], n, -1).astype(jnp.float32))
    return h.astype(x.dtype), h_pre, h_post, h_res


def mhc_post_xla(x, y, h_post, h_res):
    N, n = h_post.shape
    x32 = x.reshape(N, n, -1).astype(jnp.float32)
    out = jnp.einsum("nij,njd->nid", h_res, x32) \
        + h_post[:, :, None] * y.astype(jnp.float32)[:, None, :]
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _chunks(D: int):
    """Static (start, width) lane chunks of one stream."""
    return [(c, min(_CHUNK, D - c)) for c in range(0, D, _CHUNK)]


def _pre_kernel(x_ref, phi_ref, ab_ref, h_ref, pre_ref, post_ref, res_ref,
                pieces, rows_t, *, n, D, eps, iters, clamp, hc_eps):
    K = n * D
    M = n_maps(n)

    split = x_ref.dtype == jnp.bfloat16

    @pl.when(pl.program_id(0) == 0)
    def _cut():
        pieces[...] = jnp.zeros(pieces.shape, pieces.dtype)
        rest = phi_ref[...]
        if not split:       # float32 streams (the tests'): Φ as it is
            pieces[0:M, :] = rest
            return
        # Φ's float32 as three bf16 pieces, hi / mid / lo, 32 rows apart
        for p in range(3):
            piece = rest.astype(jnp.bfloat16)
            pieces[p * _PIECE_ROWS:p * _PIECE_ROWS + M, :] = piece
            rest = rest - piece.astype(jnp.float32)

    x = x_ref[...]                                          # [TM, K]
    tm = x.shape[0]
    prod = lax.dot_general(
        x, pieces[...], (((1,), (1,)), ((), ())),
        precision=None if split else lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                 # [TM, 128]
    # lane k ← lo[k] + mid[k] + hi[k], the smallest first
    m = (pltpu.roll(prod, LANE - 2 * _PIECE_ROWS, 1)
         + pltpu.roll(prod, LANE - _PIECE_ROWS, 1)) + prod if split else prod
    ss = jnp.zeros((tm, LANE), jnp.float32)
    for c in range(0, K, LANE):
        v = x_ref[:, c:c + LANE].astype(jnp.float32)
        ss = ss + v * v
    rho = lax.rsqrt(jnp.sum(ss, axis=-1, keepdims=True) * (1.0 / K) + eps)
    a = m * rho * ab_ref[0:1, :] + ab_ref[1:2, :]           # [TM, 128]
    sig = jax.nn.sigmoid(a)
    pre_ref[...] = sig[:, 0:n]
    post_ref[...] = 2.0 * sig[:, n:2 * n]
    # the Sinkhorn rounds, tokens along the lanes: an entry of M a row
    a_t = a.T                                               # [128, TM]
    mm = tuple(jnp.exp(jnp.clip(a_t[2 * n + k:2 * n + k + 1, :], -clamp,
                                clamp)) for k in range(n * n))

    def one(_, mm):
        mm = list(mm)
        for i in range(n):
            row = mm[i * n:(i + 1) * n]
            s = row[0]
            for r in row[1:]:
                s = s + r
            inv = 1.0 / (s + hc_eps)
            mm[i * n:(i + 1) * n] = [r * inv for r in row]
        for j in range(n):
            col = mm[j::n]
            s = col[0]
            for r in col[1:]:
                s = s + r
            inv = 1.0 / (s + hc_eps)
            mm[j::n] = [r * inv for r in col]
        return tuple(mm)
    mm = lax.fori_loop(0, iters, one, mm)
    for k in range(n * n):
        rows_t[k:k + 1, :] = mm[k]
    res_ref[...] = rows_t[...].T[:, 0:n * n]
    # h = Σ_j H_pre[j] X[j]
    for c, w in _chunks(D):
        acc = jnp.zeros((tm, w), jnp.float32)
        for j in range(n):
            acc = acc + sig[:, j:j + 1] * x_ref[
                :, j * D + c:j * D + c + w].astype(jnp.float32)
        h_ref[:, c:c + w] = acc.astype(h_ref.dtype)


def _post_kernel(x_ref, y_ref, post_ref, res_ref, o_ref, *, n, D):
    post, res = post_ref[...], res_ref[...]
    for c, w in _chunks(D):
        y = y_ref[:, c:c + w].astype(jnp.float32)
        xs = [x_ref[:, j * D + c:j * D + c + w].astype(jnp.float32)
              for j in range(n)]
        for i in range(n):
            acc = post[:, i:i + 1] * y
            for j in range(n):
                k = i * n + j
                acc = acc + res[:, k:k + 1] * xs[j]
            o_ref[:, i * D + c:i * D + c + w] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "iters", "clamp",
                                             "hc_eps", "interpret"))
def _pre_pallas(x, phi, b, alpha, *, eps, iters, clamp, hc_eps, interpret):
    (N, K), n = x.shape, streams(phi)
    D, M = K // n, n_maps(n)
    # α and b along the maps' lanes: row 0 the factor, row 1 the offset
    lanes = jnp.arange(LANE)
    factor = jnp.where(lanes < n, alpha[0], jnp.where(
        lanes < 2 * n, alpha[1], jnp.where(lanes < M, alpha[2], 0.0)))
    ab = jnp.zeros((8, LANE), jnp.float32).at[0].set(factor).at[1, :M].set(
        b.astype(jnp.float32))
    tm = ROW_TILE
    kernel = functools.partial(_pre_kernel, n=n, D=D, eps=eps, iters=iters,
                               clamp=clamp, hc_eps=hc_eps)
    row = lambda i: (i, 0)      # noqa: E731
    whole = lambda i: (0, 0)    # noqa: E731
    h, pre, post, res = pl.pallas_call(
        kernel,
        name="mhc_pre",
        grid=(pl.cdiv(N, tm),),
        in_specs=[pl.BlockSpec((tm, K), row),
                  pl.BlockSpec((M, K), whole),
                  pl.BlockSpec((8, LANE), whole)],
        out_specs=[pl.BlockSpec((tm, D), row),
                   pl.BlockSpec((tm, n), row),
                   pl.BlockSpec((tm, n), row),
                   pl.BlockSpec((tm, n * n), row)],
        out_shape=[jax.ShapeDtypeStruct((N, D), x.dtype),
                   jax.ShapeDtypeStruct((N, n), jnp.float32),
                   jax.ShapeDtypeStruct((N, n), jnp.float32),
                   jax.ShapeDtypeStruct((N, n * n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((LANE, K), jnp.bfloat16 if x.dtype
                                   == jnp.bfloat16 else jnp.float32),
                        pltpu.VMEM((LANE, tm), jnp.float32)],
        compiler_params=_VMEM,
        interpret=interpret,
    )(x, phi.astype(jnp.float32), ab)
    return h, pre, post, res.reshape(N, n, n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _post_pallas(x, y, h_post, h_res, *, interpret):
    (N, K), n = x.shape, h_post.shape[1]
    D = K // n
    tm = ROW_TILE
    row = lambda i: (i, 0)      # noqa: E731
    return pl.pallas_call(
        functools.partial(_post_kernel, n=n, D=D),
        name="mhc_post",
        grid=(pl.cdiv(N, tm),),
        in_specs=[pl.BlockSpec((tm, K), row), pl.BlockSpec((tm, D), row),
                  pl.BlockSpec((tm, n), row), pl.BlockSpec((tm, n * n), row)],
        out_specs=pl.BlockSpec((tm, K), row),
        out_shape=jax.ShapeDtypeStruct((N, K), x.dtype),
        input_output_aliases={0: 0},
        compiler_params=_VMEM,
        interpret=interpret,
    )(x, y, h_post.astype(jnp.float32),
      h_res.reshape(N, n * n).astype(jnp.float32))


def _kernel_fits(x, n: int) -> bool:
    """The kernels' layout: streams of whole lane tiles, at most 32 maps."""
    return (x.shape[1] // n) % LANE == 0 and n_maps(n) <= _PIECE_ROWS


def mhc_pre(x, phi, b, alpha, eps: float, iters: int, clamp: float,
            hc_eps: float, impl=None, interpret=None):
    """x [N, n·D] (a token's n streams side by side along the lanes — ``[N,
    n, D]`` row-major, bf16 as served), phi [n² + 2n, n·D], b [n² + 2n],
    alpha [3] (float32) → (h [N, D] in x's dtype, H_pre [N, n], H_post [N,
    n], H_res [N, n, n], the maps float32)."""
    if impl not in (None, "pallas", "xla"):
        raise ValueError(f"unknown mhc impl {impl!r}")
    if impl == "xla" or not _kernel_fits(x, streams(phi)):
        _obs_stats.scope("mhc").counter("pre_fallbacks").inc()
        return mhc_pre_xla(x, phi, b, alpha, eps, iters, clamp, hc_eps)
    if interpret is None:
        interpret = pallas_interpret()
    return _pre_pallas(x, phi, b, alpha, eps=float(eps), iters=int(iters),
                       clamp=float(clamp), hc_eps=float(hc_eps),
                       interpret=bool(interpret))


def mhc_post(x, y, h_post, h_res, impl=None, interpret=None):
    """x [N, n·D], y [N, D] (the sub-layer's output), H_post [N, n], H_res
    [N, n, n] → ``X'`` [N, n·D] in x's dtype, both weighted sums float32."""
    if impl not in (None, "pallas", "xla"):
        raise ValueError(f"unknown mhc impl {impl!r}")
    if impl == "xla" or not _kernel_fits(x, h_post.shape[1]):
        _obs_stats.scope("mhc").counter("post_fallbacks").inc()
        return mhc_post_xla(x, y, h_post, h_res)
    if interpret is None:
        interpret = pallas_interpret()
    return _post_pallas(x, y, h_post, h_res, interpret=bool(interpret))


__all__ = ["mhc_pre", "mhc_post", "mhc_pre_xla", "mhc_post_xla", "maps_xla",
           "sinkhorn", "n_maps", "ROW_TILE"]
