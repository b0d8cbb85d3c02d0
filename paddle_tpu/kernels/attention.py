"""Fused / flash / ring attention — the framework's hot-op kernel story.

Reference precedent: the CPU JIT kernel library
(``paddle/fluid/operators/math/jit_kernel*`` — hand-tuned kernels behind a
dispatch layer) and the cuDNN library_type kernels.  Here the hot op is
attention; three implementations sit behind one function:

- ``xla``:    plain jnp einsum/softmax chain (XLA fuses; always available)
- ``pallas``: tiled online-softmax flash-attention kernel (MXU-sized tiles,
              VMEM accumulators; interpret mode off-TPU), with optional
              in-kernel attention-probability dropout (TPU PRNG seeded per
              (batch·head, q-block, k-block) tile — regenerated bit-exactly
              by the backward kernels, so no mask is ever materialized)
- ``ring``:   sequence-parallel attention over a mesh axis — K/V shards
              rotate around the ring via ``lax.ppermute``; every shard
              pair runs the SAME Pallas flash kernel and partials merge
              by log2 softmax mass, so a device never materializes more
              than a [block_q, block_k] tile: O(block) compute memory at
              any sequence length.  This is the long-context scaling
              mechanism (SURVEY.md §5: absent in the 2018 reference,
              required here as first-class).

Gradients: ``jax.custom_vjp``.  The Pallas path saves only (out, LSE) and
runs tiled backward kernels (dq accumulation over k-blocks; dk/dv
accumulation over q-blocks) — O(block) memory for training at any sequence
length, the FlashAttention-2 backward scheme.  Ring attention has its own
vjp that lifts the same decomposition to shard granularity: per-pair
``_pallas_bwd`` with the GLOBAL merged lse, dk/dv accumulators riding the
ring home (see ``ring_attention``).
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import pallas_interpret

NEG_INF = -1e30
LOG2E = 1.4426950408889634  # kernels run softmax in exp2 units (see below)


# ---------------------------------------------------------------------------
# plain XLA implementation (also the custom_vjp backward math)
# ---------------------------------------------------------------------------

def mha_xla(q, k, v, kv_mask=None, causal=False, sm_scale=None,
            q_offset=0, kv_offset=0, dropout_rate=0.0, dropout_seed=None):
    """q,k,v: [B,H,Tq|Tk,D]; kv_mask: [B,Tk] 1/0; returns [B,H,Tq,D].

    q_offset/kv_offset give global positions for causal masking when the
    sequence is sharded (ring attention).  ``dropout_rate`` applies
    attention-prob dropout keyed by ``dropout_seed`` (deterministic per
    seed, so a re-lowered backward sees the same mask; the bits differ
    from the pallas kernel's tile hash — same distribution, either path
    is self-consistent)."""
    if sm_scale is None:
        sm_scale = float(1.0 / np.sqrt(q.shape[-1]))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :] > 0, s, NEG_INF)
    if causal:
        qi = jnp.arange(q.shape[2])[:, None] + q_offset
        ki = jnp.arange(k.shape[2])[None, :] + kv_offset
        s = jnp.where(qi >= ki, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate and dropout_rate > 0.0:
        seed = (jnp.zeros((), jnp.int32) if dropout_seed is None
                else jnp.asarray(dropout_seed, jnp.int32).reshape(()))
        p = p * _hash_dropout(seed, q_offset * 131071 + kv_offset, p.shape,
                              dropout_rate)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _hash_dropout(seed, salt, shape, rate):
    """Counter-hash dropout multiplier for the XLA attention path — the
    jnp twin of the Pallas kernels' ``_tile_dropout``: ~10 integer VPU ops
    per element instead of a threefry invocation (jax.random.bernoulli
    cost a measured ~36% of the seq-256 Transformer step), and cheap
    enough for XLA to REMATERIALIZE in the backward rather than storing a
    [B,H,Tq,Tk] mask.  Deterministic per (seed, salt, element coords)."""
    b = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    h = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    q = jax.lax.broadcasted_iota(jnp.uint32, shape, 2)
    k = jax.lax.broadcasted_iota(jnp.uint32, shape, 3)
    x = (q * jnp.uint32(0x9E3779B1)) ^ (k * jnp.uint32(0x85EBCA77))
    x = x ^ (b * jnp.uint32(0xC2B2AE3D) + h * jnp.uint32(0x27D4EB2F))
    x = x ^ (seed.astype(jnp.uint32)
             + jnp.asarray(salt, jnp.uint32) * jnp.uint32(0x165667B1))
    return _finalize_dropout(x, rate)


# ---------------------------------------------------------------------------
# Pallas flash-attention forward kernel
# ---------------------------------------------------------------------------

def _scaled_q(q_ref, sm_scale):
    """Fold ``sm_scale * log2(e)`` into the q tile so the kernels never
    touch the [block_q, block_k] scores with a scale multiply AND run
    softmax in exp2 units (exp(x) lowers to exp2(x*log2e) on the VPU —
    pre-folding the multiplier saves one more op per score element).
    The [block_q, D] multiply is ~block_k/1 times cheaper than scaling s."""
    return (q_ref[:].astype(jnp.float32) * (sm_scale * LOG2E)
            ).astype(q_ref.dtype)


def _lane_pack_ok(D, dropout_rate):
    """Eligibility gate for the forward ones-lane denominator: V must
    leave output lanes idle (D < 128) and dropout must be off (l must
    accumulate UNdropped probability mass).  NOTE(perf A/B, r4): bf16
    score tiles were tried and REGRESSED (52.9->49.5 fwd TF, maxdiff
    2x) — Mosaic requires f32 matmul accumulators, so the downcast is
    an extra f32-width op; scores stay f32."""
    return D < 128 and not (dropout_rate and dropout_rate > 0.0)


def _sds(shape, dtype, like):
    """ShapeDtypeStruct for a pallas_call out_shape that works under
    shard_map's varying-mesh-axes (vma) checking: outputs vary over the
    same mesh axes as the operand ``like`` (ring attention calls the
    kernels per shard inside shard_map)."""
    vma = getattr(jax.typeof(like), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _append_ones_lane(x):
    """Append a ones lane to the minor dim (the fwd kernel's softmax
    denominator rides it — see _flash_fwd_kernel)."""
    return jnp.concatenate(
        [x, jnp.ones(x.shape[:-1] + (1,), x.dtype)], axis=-1)


def _tile_scores(q, k_ref, mask_ref, qi, kb, *, causal,
                 block_q, block_k, has_mask=True):
    """Masked scores (in exp2 units — q pre-scaled by ``_scaled_q``) for
    one (q-block, k-block) tile.

    The dot runs in the INPUT dtype (bf16 on TPU) with an f32
    accumulator — upcasting q/k first would push the MXU into f32 mode
    at ~1/8 the bf16 rate."""
    s = jnp.dot(q, k_ref[:].T, preferred_element_type=jnp.float32)
    if has_mask:
        mask = mask_ref[0, :]
        s = jnp.where(mask[None, :] > 0, s, NEG_INF)
    if causal:
        # unconditional masking measured FASTER than branching per tile
        # (lax.cond on the diagonal predicate cost ~15% at T=8192 — the
        # branch breaks Mosaic's straight-line VPU pipelining).  With
        # square tiles the diagonal pattern is a CONSTANT triangular mask
        # (hoisted out of the grid loop by Mosaic) OR'd with the scalar
        # below-diagonal predicate — no per-tile iota arithmetic.
        if block_q == block_k:
            tri = (jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                   >= jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
            below = qi * block_q > kb * block_k  # strictly past the diagonal
            above = qi * block_q < kb * block_k  # fully masked (reachable
            # only as the degenerate clamped tile when Tk > Tq)
            keep = jnp.logical_and(jnp.logical_or(below, tri),
                                   jnp.logical_not(above))
            s = jnp.where(keep, s, NEG_INF)
        else:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    return s


def _last_kb(qi, *, causal, block_q, block_k, num_kb):
    """Last k-block index intersecting the causal frontier of q-block qi
    (the whole k range when not causal)."""
    if not causal:
        return num_kb - 1
    return jnp.minimum(((qi + 1) * block_q - 1) // block_k, num_kb - 1)


def _first_qb(kb, *, causal, block_q, block_k, num_qb):
    """First q-block index at/below the causal frontier of k-block kb,
    clamped into range: a k-block entirely above the frontier (possible
    when Tk > Tq) degenerates to the last q-block, whose fully-masked
    tile contributes exact zeros — so dk/dv come out zero, not stale."""
    if not causal:
        return 0
    return jnp.minimum((kb * block_k) // block_q, num_qb - 1)


def _finalize_dropout(x, rate):
    """Shared murmur-finalizer tail of both dropout hashes (Pallas tile
    and XLA paths): mix -> top-24-bit uniform [0,1) -> keep/scale.  Kept
    in ONE place so the mask semantics of the two paths cannot diverge
    (test_dropout_engages_in_lowered_hlo anchors on the 0x7FEB352D
    constant).  The bitcast detour exists because mosaic lacks a direct
    uint32->f32 convert (values < 2^24 are sign-safe)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    u = (jax.lax.bitcast_convert_type(x >> 8, jnp.int32)
         .astype(jnp.float32) * jnp.float32(1.0 / (1 << 24)))
    keep = u >= jnp.float32(rate)
    return jnp.where(keep, 1.0 / (1.0 - rate), 0.0).astype(jnp.float32)


def _tile_dropout(seed_ref, bh, qi, kb, shape, rate: float):
    """Regenerable dropout multiplier for one tile: a counter-based hash of
    (base seed, tile coords, element coords) in plain vector ops — the same
    bits in compiled and interpret mode, so forward and both backward
    kernels reproduce the identical mask with nothing stored (reference
    dropout_op.cc's saved Mask, made unnecessary).  Murmur3-style finalizer
    over distinct odd multipliers per coordinate."""
    rows = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    x = rows * jnp.uint32(0x9E3779B1) ^ cols * jnp.uint32(0x85EBCA77)
    x = x ^ (seed_ref[0].astype(jnp.uint32)
             + jnp.uint32(bh).astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)
             + jnp.uint32(qi).astype(jnp.uint32) * jnp.uint32(0x27D4EB2F)
             + jnp.uint32(kb).astype(jnp.uint32) * jnp.uint32(0x165667B1))
    return _finalize_dropout(x, rate)


def _flash_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr, *,
                      sm_scale: float, causal: bool, dropout_rate: float,
                      block_q: int, block_k: int, num_kb: int,
                      has_mask: bool, ones_lane: bool, head_dim: int):
    """Grid (B*H, nq, nk); K/V stream through VMEM one block_k tile at a
    time (nk is the sequential minor grid axis on TPU, so the online-softmax
    state lives in VMEM scratch across k iterations — O(block) memory at any
    sequence length).  Emits the per-row logsumexp (base-2 units) for the
    backward pass.

    Causal tiles entirely above the diagonal are SKIPPED: no compute, and
    the K/V index maps clamp to the causal frontier so the pipeline issues
    no copies for them either — ~2x on long causal sequences.

    ``ones_lane`` (head_dim < 128, no dropout): V carries an appended ones
    column, so the PV dot accumulates the softmax denominator in an
    otherwise-idle MXU lane and the per-element VPU sum-reduce disappears
    (l rides acc_scr[:, head_dim]).  The kernel is VPU-bound (PERF.md §1);
    with the exp2/q-prescale folding this drops the per-score-element op
    count from ~8 to ~5."""
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    last = _last_kb(qi, causal=causal, block_q=block_q, block_k=block_k,
                    num_kb=num_kb)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(kb <= last)
    def _compute():
        qs = _scaled_q(q_ref, sm_scale)
        s = _tile_scores(qs, k_ref, mask_ref, qi, kb,
                         causal=causal, block_q=block_q, block_k=block_k,
                         has_mask=has_mask)
        v_blk = v_ref[:]

        m, acc = m_scr[:], acc_scr[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        m_scr[:] = m_new
        if not ones_lane:
            l_scr[:] = (l_scr[:] * alpha.astype(jnp.float32)
                        + jnp.sum(p.astype(jnp.float32), axis=-1,
                                  keepdims=True))
        if dropout_rate > 0.0:
            # dropout applies to normalized probs; l accumulates undropped
            p = p * _tile_dropout(seed_ref, bh, qi, kb, p.shape,
                                  dropout_rate).astype(p.dtype)
        acc_scr[:] = acc * alpha.astype(jnp.float32) + jnp.dot(
            p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32)

    @pl.when(kb == last)
    def _finish():
        if ones_lane:
            l_fin = acc_scr[:, head_dim:head_dim + 1]
            out = acc_scr[:, :head_dim]
        else:
            l_fin = l_scr[:]
            out = acc_scr[:]
        o_ref[:] = (out / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)
        # rows with no unmasked keys (query padding): +inf LSE → p == 0
        # everywhere in the backward kernels, never NaN.  LSE rides a
        # whole-row [1, Tq] block (TPU tiling forbids 1D per-q-block
        # outputs); each q-block writes its slice.
        lse = jnp.where(l_fin > 0.0,
                        m_scr[:].astype(jnp.float32)
                        + jnp.log2(jnp.maximum(l_fin, 1e-30)),
                        jnp.float32(1e30))
        lse_ref[0, pl.dslice(qi * block_q, block_q)] = lse[:, 0].astype(lse_ref.dtype)


# NOTE(perf A/B, r3): CompilerParams(dimension_semantics=("parallel",
# "parallel", "arbitrary")) measured ~20% SLOWER at T=8192 than the
# default on this chip, as did per-tile lax.cond causal-mask branching —
# both left out deliberately.


def _pad_to(x, multiple, axis):
    rem = x.shape[axis] % multiple
    if rem == 0:
        return x, 0
    pad = multiple - rem
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def _resolve_blocks(block_q, block_k, Tq, Tk):
    """Measured-best tile sizes on v5e (r3 K-sweep at T=8192 causal
    fwd+bwd: 512x1024 -> 46 ms, 1024x1024 -> 23 ms): big q-blocks cut
    K/V restreaming (streamed bytes scale with Tq/block_q), big k-blocks
    amortize VMEM pipelining; 2048-wide blocks fail to compile."""
    if block_q is None:
        block_q = 1024 if Tq >= 1024 else (512 if Tq >= 512 else 128)
    if block_k is None:
        block_k = 1024 if Tk >= 1024 else (512 if Tk >= 512 else 128)
    return block_q, block_k


def _prep_padded(q, k, v, kv_mask, block_q, block_k):
    """Pad to block multiples and flatten (B,H).  When ``kv_mask`` is None
    and no length padding was added, no mask array is materialized at all
    (``has_mask=False`` compiles the mask load + where out of the kernels)."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    q4, _ = _pad_to(q, block_q, 2)
    k4, pad_k = _pad_to(k, block_k, 2)
    v4, _ = _pad_to(v, block_k, 2)
    Tq_p, Tk_p = q4.shape[2], k4.shape[2]
    qf = q4.reshape(B * H, Tq_p, D)
    kf = k4.reshape(B * H, Tk_p, D)
    vf = v4.reshape(B * H, Tk_p, v.shape[-1])
    if kv_mask is None and pad_k == 0:
        # never read (has_mask=False); one block wide — the mask index
        # map pins block (b, 0, 0), so no larger buffer is ever touched
        maskf = jnp.zeros((B * H, 1, block_k), jnp.float32)
        return qf, kf, vf, maskf, Tq_p, Tk_p, False
    if kv_mask is None:
        kv_mask = jnp.ones((B, Tk), jnp.float32)
    mask2, _ = _pad_to(kv_mask.astype(jnp.float32), block_k, 1)
    maskf = jnp.repeat(mask2[:, None, :], H, axis=1).reshape(B * H, 1, Tk_p)
    return qf, kf, vf, maskf, Tq_p, Tk_p, True


def _seed_arr(dropout_seed):
    if dropout_seed is None:
        return jnp.zeros((1,), jnp.int32)
    return jnp.asarray(dropout_seed, jnp.int32).reshape((1,))


def _fwd_maps(causal, has_mask, block_q, block_k, num_kb):
    """Index maps for K/V/mask blocks in q-major grids (fwd, dq): clamp
    skipped causal tiles to the frontier block (_last_kb), so the pipeline
    re-references the previous block and issues no copy for them."""
    def kv_map(b, i, j):
        j = _last_kb_clamp(j, i, causal, block_q, block_k)
        return (b, j, 0)

    def mask_map(b, i, j):
        if not has_mask:
            return (b, 0, 0)
        return (b, 0, _last_kb_clamp(j, i, causal, block_q, block_k))
    return kv_map, mask_map


def _last_kb_clamp(j, i, causal, block_q, block_k):
    if causal:
        j = jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)
    return j


def _pallas_fwd(q, k, v, kv_mask, causal, sm_scale, dropout_rate=0.0,
                dropout_seed=None, block_q=None, block_k=None,
                interpret=None):
    block_q, block_k = _resolve_blocks(block_q, block_k,
                                       q.shape[2], k.shape[2])
    """Returns (out [B,H,Tq,D], lse [B*H, Tq_padded])."""
    if sm_scale is None:
        sm_scale = float(1.0 / np.sqrt(q.shape[-1]))
    if interpret is None:
        interpret = pallas_interpret()
    B, H, Tq, D = q.shape
    qf, kf, vf, maskf, Tq_p, Tk_p, has_mask = _prep_padded(
        q, k, v, kv_mask, block_q, block_k)
    num_kb = Tk_p // block_k
    # v may be narrower than q and k (latent attention's prefill: 192-wide
    # scores, 128-wide values); the output takes v's width
    Dv = v.shape[-1]
    # ones-lane denominator (measured +28% on the D=64 seq-8192 fwd)
    ones_lane = _lane_pack_ok(Dv, dropout_rate)
    D_v = Dv + 1 if ones_lane else Dv
    if ones_lane:
        vf = _append_ones_lane(vf)

    kv_map, mask_map = _fwd_maps(causal, has_mask, block_q, block_k, num_kb)
    kernel = functools.partial(
        _flash_fwd_kernel, block_k=block_k, sm_scale=sm_scale,
        causal=causal, dropout_rate=float(dropout_rate),
        block_q=block_q, num_kb=num_kb, has_mask=has_mask,
        ones_lane=ones_lane, head_dim=Dv)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        out_shape=[
            _sds((B * H, Tq_p, Dv), q.dtype, qf),
            _sds((B * H, 1, Tq_p), jnp.float32, qf),
        ],
        grid=(B * H, Tq_p // block_q, num_kb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # seed
            pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), kv_map),
            pl.BlockSpec((None, block_k, D_v), kv_map),
            pl.BlockSpec((None, 1, block_k), mask_map),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, Tq_p), lambda b, i, j: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D_v), jnp.float32),
        ],
        interpret=interpret,
    )(_seed_arr(dropout_seed), qf, kf, vf, maskf)
    return out.reshape(B, H, Tq_p, Dv)[:, :, :Tq, :], lse


def mha_pallas(q, k, v, kv_mask=None, causal=False, sm_scale=None,
               block_q=None, block_k=None, interpret=None,
               dropout_rate=0.0, dropout_seed=None):
    """Flash-attention forward via pallas_call; grid (B*H, Tq/block_q)."""
    out, _ = _pallas_fwd(q, k, v, kv_mask, causal, sm_scale, dropout_rate,
                         dropout_seed, block_q, block_k, interpret)
    return out


# ---------------------------------------------------------------------------
# Pallas flash-attention backward kernels (FlashAttention-2 scheme)
# ---------------------------------------------------------------------------

def _flash_bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref, do_ref,
                         lse_ref, delta_ref, dq_ref, dq_scr, *,
                         sm_scale, causal, dropout_rate,
                         block_q, block_k, num_kb, has_mask):
    """Grid (B*H, nq, nk): dq accumulates across k-blocks in VMEM.
    Causal tiles above the diagonal skipped (no compute, no copies).

    NOTE(perf A/B, r4): packing a ``-delta`` column into do against a
    ones column in V (so do@v.T emits dp-delta via an idle MXU lane)
    was tried and REVERTED: it forces delta through the activation
    dtype, inflating bf16 dq/dk error 5x (rel maxdiff 0.037 vs 0.0075
    against the XLA chain), for no measured full-step gain — the D<128
    backward is MXU-half-fill bound, not VPU bound (PERF.md par.1)."""
    bh, qi, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    last = _last_kb(qi, causal=causal, block_q=block_q, block_k=block_k,
                    num_kb=num_kb)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(kb <= last)
    def _compute():
        s = _tile_scores(_scaled_q(q_ref, sm_scale), k_ref, mask_ref, qi, kb,
                         causal=causal, block_q=block_q, block_k=block_k,
                         has_mask=has_mask)
        lse = lse_ref[0, pl.dslice(qi * block_q, block_q)]
        delta = delta_ref[0, pl.dslice(qi * block_q, block_q)]
        p = jnp.exp2(s - lse[:, None])                      # [bq, bk]
        do = do_ref[:]
        v_blk = v_ref[:]
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            dp = dp * _tile_dropout(seed_ref, bh, qi, kb, dp.shape,
                                    dropout_rate)
        # d/dq of s2 = (q*scale*log2e)@k.T with p = exp2(s2-lse2):
        # dL/ds2 = p*(dp-delta)*ln2; chain through the log2e fold and the
        # ln2/log2e product cancels — ds/dq math is IDENTICAL to natural
        # units, so plain sm_scale scales dq (and dk below)
        ds = (p * (dp - delta[:, None])).astype(k_ref.dtype)
        dq_scr[:] += jnp.dot(ds, k_ref[:],
                             preferred_element_type=jnp.float32) * sm_scale

    @pl.when(kb == last)
    def _finish():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref, do_ref,
                          lse_ref, delta_ref, dk_ref, dv_ref,
                          dk_scr, dv_scr, *,
                          sm_scale, causal, dropout_rate,
                          block_q, block_k, num_qb, has_mask):
    """Grid (B*H, nk, nq): dk/dv accumulate across q-blocks in VMEM.
    Causal q-blocks entirely above this k-block's diagonal are skipped."""
    bh, kb, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    first = _first_qb(kb, causal=causal, block_q=block_q, block_k=block_k,
                      num_qb=num_qb)

    @pl.when(qi == first)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(qi >= first)
    def _compute():
        s = _tile_scores(_scaled_q(q_ref, sm_scale), k_ref, mask_ref, qi, kb,
                         causal=causal, block_q=block_q, block_k=block_k,
                         has_mask=has_mask)
        lse = lse_ref[0, pl.dslice(qi * block_q, block_q)]
        delta = delta_ref[0, pl.dslice(qi * block_q, block_q)]
        p = jnp.exp2(s - lse[:, None])                      # [bq, bk]
        do = do_ref[:]
        v_blk = v_ref[:]
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            # same (bh, qi, kb) seeding as forward/dq → identical bits
            drop = _tile_dropout(seed_ref, bh, qi, kb, p.shape, dropout_rate)
            dv_scr[:] += jnp.dot((p * drop).astype(do.dtype).T, do,
                                 preferred_element_type=jnp.float32)
            dp = dp * drop
        else:
            dv_scr[:] += jnp.dot(p.astype(do.dtype).T, do,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None])).astype(q_ref.dtype)
        dk_scr[:] += jnp.dot(ds.T, q_ref[:],
                             preferred_element_type=jnp.float32) * sm_scale

    @pl.when(qi == num_qb - 1)
    def _finish():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _pallas_bwd(q, k, v, kv_mask, out, lse, g, causal, sm_scale,
                dropout_rate=0.0, dropout_seed=None,
                block_q=None, block_k=None, interpret=None, delta=None):
    block_q, block_k = _resolve_blocks(block_q, block_k,
                                       q.shape[2], k.shape[2])
    if sm_scale is None:
        sm_scale = float(1.0 / np.sqrt(q.shape[-1]))
    if interpret is None:
        interpret = pallas_interpret()
    B, H, Tq, D = q.shape
    qf, kf, vf, maskf, Tq_p, Tk_p, has_mask = _prep_padded(
        q, k, v, kv_mask, block_q, block_k)
    gof, _ = _pad_to(g.reshape(B * H, Tq, D), block_q, 1)
    if delta is None:  # [BH, 1, Tq_p]; ring bwd hoists it across pairs
        outf, _ = _pad_to(out.reshape(B * H, Tq, D), block_q, 1)
        delta = jnp.sum(gof.astype(jnp.float32) * outf.astype(jnp.float32),
                        axis=-1)[:, None, :]
    num_qb, num_kb = Tq_p // block_q, Tk_p // block_k
    seed = _seed_arr(dropout_seed)

    kv_map, mask_map = _fwd_maps(causal, has_mask, block_q, block_k, num_kb)
    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
        dropout_rate=float(dropout_rate), block_q=block_q, block_k=block_k,
        num_kb=num_kb, has_mask=has_mask)
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_bwd_dq",
        out_shape=_sds((B * H, Tq_p, D), q.dtype, qf),
        grid=(B * H, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), kv_map),
            pl.BlockSpec((None, block_k, D), kv_map),
            pl.BlockSpec((None, 1, block_k), mask_map),
            pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, Tq_p), lambda b, i, j: (b, 0, 0)),
            pl.BlockSpec((None, 1, Tq_p), lambda b, i, j: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(seed, qf, kf, vf, maskf, gof, lse, delta)

    def q_map(b, j, i):
        # clamp skipped above-diagonal q-blocks to this k-block's frontier
        # (same clamp as _first_qb, incl. the num_qb bound for Tk > Tq)
        if causal:
            i = jnp.maximum(i, _first_qb(j, causal=causal, block_q=block_q,
                                         block_k=block_k, num_qb=num_qb))
        return (b, i, 0)

    def qmask_map(b, j, i):
        return (b, 0, 0) if not has_mask else (b, 0, j)

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
        dropout_rate=float(dropout_rate), block_q=block_q, block_k=block_k,
        num_qb=num_qb, has_mask=has_mask)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_bwd_dkv",
        out_shape=[
            _sds((B * H, Tk_p, D), k.dtype, kf),
            _sds((B * H, Tk_p, D), v.dtype, kf),
        ],
        grid=(B * H, num_kb, num_qb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, block_q, D), q_map),
            pl.BlockSpec((None, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, 1, block_k), qmask_map),
            pl.BlockSpec((None, block_q, D), q_map),
            pl.BlockSpec((None, 1, Tq_p), lambda b, j, i: (b, 0, 0)),
            pl.BlockSpec((None, 1, Tq_p), lambda b, j, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, j, i: (b, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
    )(seed, qf, kf, vf, maskf, gof, lse, delta)

    Tk = k.shape[2]
    dq = dq.reshape(B, H, Tq_p, D)[:, :, :Tq, :]
    dk = dk.reshape(B, H, Tk_p, D)[:, :, :Tk, :]
    dv = dv.reshape(B, H, Tk_p, D)[:, :, :Tk, :]
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrapper: pallas forward AND pallas backward (O(block) memory)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention(q, k, v, kv_mask, causal=False, sm_scale=None,
                    dropout_rate=0.0, dropout_seed=None):
    """Flash attention with optional in-kernel attention-prob dropout.
    ``dropout_seed``: int32 scalar/array; required when dropout_rate > 0
    (vary it per training step for fresh masks)."""
    return mha_pallas(q, k, v, kv_mask, causal, sm_scale,
                      dropout_rate=dropout_rate, dropout_seed=dropout_seed)


def _fa_fwd(q, k, v, kv_mask, causal, sm_scale, dropout_rate, dropout_seed):
    out, lse = _pallas_fwd(q, k, v, kv_mask, causal, sm_scale,
                           dropout_rate, dropout_seed)
    return out, (q, k, v, kv_mask, dropout_seed, out, lse)


def _fa_bwd(causal, sm_scale, dropout_rate, res, g):
    q, k, v, kv_mask, dropout_seed, out, lse = res
    dq, dk, dv = _pallas_bwd(q, k, v, kv_mask, out, lse, g, causal, sm_scale,
                             dropout_rate, dropout_seed)
    return dq, dk, dv, None, None


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# Ring attention: sequence-parallel over a mesh axis
# ---------------------------------------------------------------------------

RING_PAIR_SALT = (1000003, 7919)  # distinct odd primes per (q, kv) shard


def _pair_seed(seed0, q_idx, kv_idx):
    """Per-ordered-shard-pair dropout seed: the pallas kernels key masks on
    LOCAL tile coords, so without a distinct seed every (q-shard, kv-shard)
    pair would repeat the same dropout bits.  int32 wraparound is fine
    (the hash finalizer mixes)."""
    a, b = RING_PAIR_SALT
    return (seed0 + q_idx.astype(jnp.int32) * a
            + kv_idx.astype(jnp.int32) * b)


def _pvary(x, axis_name):
    """Mark a freshly-created (replicated) array as varying over the ring
    axis so it can enter ppermute/scan carries under shard_map's vma
    checking."""
    return jax.lax.pcast(x, axis_name, to="varying")


def _mass_lse(lse):
    """Kernel empty-row sentinel (+1e30, makes backward p==0) -> merge
    identity (-1e30 == log2 of zero probability mass)."""
    return jnp.where(lse > 1e29, jnp.float32(-1e30), lse)


def _kernel_lse(lse):
    """Inverse of _mass_lse for feeding the merged LSE back to the
    backward kernels."""
    return jnp.where(lse < -1e29, jnp.float32(1e30), lse)


def _merge_partial(o_a, lse_a, o_p, lse_p):
    """Online merge of two normalized partial attentions via their log2
    probability masses (the flash-decoding combine): out = sum_i o_i *
    2^(lse_i - lse_tot)."""
    lse_t = jnp.logaddexp2(lse_a, lse_p)
    o_t = (o_a * jnp.exp2(lse_a - lse_t) + o_p * jnp.exp2(lse_p - lse_t))
    return o_t, lse_t


def _ring_pair_fwd(q, k_blk, v_blk, m_blk, causal, sm_scale, rate, seed):
    """One (q-shard, kv-shard) partial via the Pallas flash kernel:
    returns (normalized f32 out, [B,H,S,1] log2-mass lse)."""
    out, lse = _pallas_fwd(q, k_blk, v_blk, m_blk, causal, sm_scale,
                           rate, seed)
    B, H, S, D = q.shape
    lse = lse[:, 0, :S].reshape(B, H, S, 1)
    return out.astype(jnp.float32), _mass_lse(lse)


def ring_attention(q, k, v, kv_mask, axis_name: str, causal=False,
                   sm_scale=None, dropout_rate=0.0, dropout_seed=None):
    """Blockwise ring attention (called under shard_map with the sequence
    dimension of q/k/v sharded over ``axis_name``): the flash-kernel ring,
    see ``_ring_flash`` for the design."""
    return _ring_flash(q, k, v, kv_mask, axis_name, causal, sm_scale,
                       dropout_rate, dropout_seed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ring_flash(q, k, v, kv_mask, axis_name: str, causal=False,
                sm_scale=None, dropout_rate=0.0, dropout_seed=None):
    """Flash-kernel ring attention.

    Each device holds local shards [B,H,S/sp,D].  The local (diagonal)
    pair runs the Pallas flash kernel with in-kernel causal tile skip;
    K/V then rotate around the ring via ``lax.ppermute`` and every
    off-diagonal pair runs the SAME flash kernel (causal pairs entirely
    above the diagonal are skipped via ``lax.cond`` — no compute, no
    kernel launch).  Partials merge by their log2 softmax masses
    (``_merge_partial``), so no device ever materializes more than the
    kernel's [block_q, block_k] score tile — O(block) memory inside each
    shard, O(S/sp) activations per device.  Dropout uses the kernels'
    counter-hash with a per-shard-pair seed (no threefry, nothing
    stored).

    Backward (``_ring_bwd``): the same decomposition the flash backward
    uses over k-blocks, lifted to shard granularity — each pair calls
    the tiled ``_pallas_bwd`` with the GLOBAL merged lse/out/do (so
    per-pair probabilities are exact global softmax values), dq
    accumulates locally, and dk/dv accumulators rotate around the ring
    WITH their k/v shards, arriving home after a final ppermute.
    Reference role: long-context sequence parallelism, absent in the
    2018 reference (SURVEY.md par.5, par.7) — here first-class.
    """
    out, _ = _ring_fwd(q, k, v, kv_mask, axis_name, causal, sm_scale,
                       dropout_rate, dropout_seed)
    return out


def _ring_fwd(q, k, v, kv_mask, axis_name, causal, sm_scale,
              dropout_rate, dropout_seed):
    if sm_scale is None:
        sm_scale = float(1.0 / np.sqrt(q.shape[-1]))
    if kv_mask is None:
        # fresh arrays are replicated; the ppermute'd scan carry needs the
        # mask varying over the ring axis (shard_map vma check)
        kv_mask = _pvary(jnp.ones((q.shape[0], k.shape[2]), jnp.float32),
                         axis_name)
    sp = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    seed0 = (jnp.zeros((), jnp.int32) if dropout_seed is None
             else jnp.asarray(dropout_seed, jnp.int32).reshape(()))

    o, lse = _ring_pair_fwd(q, k, v, kv_mask, causal, sm_scale,
                            dropout_rate, _pair_seed(seed0, idx, idx))
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(carry, t):
        k_c, v_c, m_c, o_a, lse_a = carry
        k_c = lax.ppermute(k_c, axis_name, perm)
        v_c = lax.ppermute(v_c, axis_name, perm)
        m_c = lax.ppermute(m_c, axis_name, perm)
        kv_i = (idx - t) % sp

        def compute(_):
            return _ring_pair_fwd(q, k_c, v_c, m_c, False, sm_scale,
                                  dropout_rate,
                                  _pair_seed(seed0, idx, kv_i))

        def skip(_):
            return (jnp.zeros_like(o_a), jnp.full_like(lse_a, -1e30))

        if causal:
            o_p, lse_p = lax.cond(kv_i < idx, compute, skip, None)
        else:
            o_p, lse_p = compute(None)
        o_a, lse_a = _merge_partial(o_a, lse_a, o_p, lse_p)
        return (k_c, v_c, m_c, o_a, lse_a), None

    (k_c, v_c, m_c, o, lse), _ = lax.scan(
        step, (k, v, kv_mask, o, lse), jnp.arange(1, sp))
    return o.astype(q.dtype), lse


def _ring_vjp_fwd(q, k, v, kv_mask, axis_name, causal, sm_scale,
                  dropout_rate, dropout_seed):
    out, lse = _ring_fwd(q, k, v, kv_mask, axis_name, causal, sm_scale,
                         dropout_rate, dropout_seed)
    return out, (q, k, v, kv_mask, dropout_seed, out, lse)


def _ring_vjp_bwd(axis_name, causal, sm_scale, dropout_rate, res, g):
    q, k, v, kv_mask, dropout_seed, out, lse = res
    if sm_scale is None:
        sm_scale = float(1.0 / np.sqrt(q.shape[-1]))
    if kv_mask is None:
        # fresh arrays are replicated; the ppermute'd scan carry needs the
        # mask varying over the ring axis (shard_map vma check)
        kv_mask = _pvary(jnp.ones((q.shape[0], k.shape[2]), jnp.float32),
                         axis_name)
    sp = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    seed0 = (jnp.zeros((), jnp.int32) if dropout_seed is None
             else jnp.asarray(dropout_seed, jnp.int32).reshape(()))
    B, H, S, D = q.shape
    # merged lse back to kernel form, padded the way _pallas_bwd blocks it
    # (padded q rows: sentinel 1e30 -> p == 0 -> zero contributions)
    block_q, _ = _resolve_blocks(None, None, S, k.shape[2])
    Tq_p = S + (-S) % block_q
    lse_k = jnp.full((B * H, 1, Tq_p), 1e30, jnp.float32)
    lse_k = lse_k.at[:, :, :S].set(
        _kernel_lse(lse).reshape(B * H, 1, S))
    # delta depends only on (g, out) — identical across all sp pairs
    delta = jnp.zeros((B * H, 1, Tq_p), jnp.float32)
    delta = delta.at[:, :, :S].set(
        jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1).reshape(B * H, 1, S))

    dq, dk, dv = _pallas_bwd(q, k, v, kv_mask, out, lse_k, g, causal,
                             sm_scale, dropout_rate,
                             _pair_seed(seed0, idx, idx), delta=delta)
    dq = dq.astype(jnp.float32)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(carry, t):
        k_c, v_c, m_c, dk_a, dv_a, dq_a = carry
        k_c = lax.ppermute(k_c, axis_name, perm)
        v_c = lax.ppermute(v_c, axis_name, perm)
        m_c = lax.ppermute(m_c, axis_name, perm)
        dk_a = lax.ppermute(dk_a, axis_name, perm)
        dv_a = lax.ppermute(dv_a, axis_name, perm)
        kv_i = (idx - t) % sp

        def compute(_):
            dq_p, dk_p, dv_p = _pallas_bwd(
                q, k_c, v_c, m_c, out, lse_k, g, False, sm_scale,
                dropout_rate, _pair_seed(seed0, idx, kv_i), delta=delta)
            return (dq_p.astype(jnp.float32), dk_p.astype(jnp.float32),
                    dv_p.astype(jnp.float32))

        def skip(_):
            return (jnp.zeros_like(dq_a), jnp.zeros_like(dk_a),
                    jnp.zeros_like(dv_a))

        if causal:
            dq_p, dk_p, dv_p = lax.cond(kv_i < idx, compute, skip, None)
        else:
            dq_p, dk_p, dv_p = compute(None)
        return (k_c, v_c, m_c, dk_a + dk_p, dv_a + dv_p, dq_a + dq_p), None

    carry = (k, v, kv_mask, dk.astype(jnp.float32), dv.astype(jnp.float32),
             dq)
    (k_c, v_c, m_c, dk_a, dv_a, dq_a), _ = lax.scan(
        step, carry, jnp.arange(1, sp))
    # one more hop brings each shard's dk/dv accumulator home
    dk = lax.ppermute(dk_a, axis_name, perm).astype(k.dtype)
    dv = lax.ppermute(dv_a, axis_name, perm).astype(v.dtype)
    return dq_a.astype(q.dtype), dk, dv, None, None


_ring_flash.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


# ---------------------------------------------------------------------------
# Paged decode attention: one query token per request against a paged KV
# cache (the decode plane's hot op — paddle_tpu/decode)
# ---------------------------------------------------------------------------

# int8 KV dequant factor: quantized cache blocks store
# round(x / s * 127) codes (the kernels/quant.py scale convention),
# so x ≈ code * s / 127
_INV_QMAX = 1.0 / 127.0


def _head_sums(x, head_dim: int):
    """x [bs, H*D], heads merged into the lane axis → [bs, H*D], every
    lane holding the sum over ITS head's D lanes.

    Mosaic has no shape cast from [bs, H*D] to [bs, H, D] ("unsupported
    shape cast"), so heads are told apart by lane masks inside aligned
    lane chunks: 128 lanes (128 // D heads each) when D divides 128 and
    H*D is a multiple of 128 — GPT-1's 12 x 64 is six chunks of two
    heads — one head per chunk when D is a multiple of 128, else one
    chunk holding every head (off-TPU sizes).  A chunk slice is whole
    vregs, so the only cross-lane work is one lane reduce per head."""
    bs, hd = x.shape
    if 128 % head_dim == 0 and hd % 128 == 0:
        width = 128
    elif head_dim % 128 == 0:
        width = head_dim
    else:
        width = hd
    lane = jax.lax.broadcasted_iota(jnp.int32, (bs, width), 1)
    masks = [jnp.logical_and(lane >= g * head_dim, lane < (g + 1) * head_dim)
             for g in range(width // head_dim)]
    chunks = []
    for c in range(hd // width):
        chunk = x[:, c * width:(c + 1) * width]
        out = jnp.zeros_like(chunk)
        for mask in masks:
            out = jnp.where(mask, jnp.sum(jnp.where(mask, chunk, 0.0),
                                          axis=-1, keepdims=True), out)
        chunks.append(out)
    return jnp.concatenate(chunks, axis=-1)


# blocks a chunk: 8 x 16 tokens x 768 f32 lanes = 384 KB each of K and V,
# 1.5 MB double-buffered
_DECODE_CHUNK_BLOCKS = 8


def _decode_attn_kernel(bt_ref, cl_ref, ly_ref, q_ref, k_hbm, v_hbm, *rest,
                        block_tokens: int, chunk: int,
                        max_blocks: int, head_dim: int, sm_scale: float,
                        quantized: bool = False):
    """Grid (S,): one grid step a slot.  The K and V pools stay in HBM,
    whole (``memory_space=pl.ANY``); a slot's LIVE blocks —
    ``ceil(context_len / bs)`` of its table, one for an idle slot — are
    fetched by explicit async copies in chunks of ``chunk`` blocks into
    a double buffer, block ``b`` of a chunk into ``buf[half, b]``, the
    layer (``ly_ref``, prefetched with the tables) in the copy's source
    index.  A table position past the frontier is neither copied nor
    computed, and none past the table is read.

    The next fetch is always in flight: before the kernel waits for a
    chunk it starts the slot's next one into the other half, and on a
    slot's LAST chunk the next slot's first (scratch and semaphores
    persist over the sequential grid).  ``half_scr`` carries which half
    that was from one grid step to the next, so every start is waited
    for exactly once, by the slot that computes it.

    ``quantized``: the cache blocks are int8 codes and two extra
    [1, MB, H*D] scale refs follow the v ref (the slot's
    per-block-per-head abs-max rows, a head's scale repeated over its D
    lanes, one row a table position) — a block is dequantized IN VMEM
    after its copy landed (``code * s/127``), so HBM traffic per block
    is a quarter of f32's while scores still run in f32.

    One query row per slot leaves the MXU nothing to do, so everything
    is VPU work on a block in its stored lane-dense [bs, H*D] layout:
    q, the running max / sum and the PV accumulator are [1, H*D] rows
    with a head's value repeated over its D lanes (the loops' carry),
    scores are a per-head lane sum of ``k * q`` (:func:`_head_sums`),
    and max / sum / PV reduce over the block's tokens (the sublane
    axis).  (Mosaic refuses the batched ``dot_general`` over the MIDDLE
    axis the first version used: "failed to parse
    TPU_DotDimensionNumbersAttr parameter 'lhs_non_contracting_dims'",
    PERF.md Bring-up.)  Scores run in f32 natural units."""
    if quantized:
        ks_ref, vs_ref, o_ref, kbuf, vbuf, sem, half_scr = rest
    else:
        o_ref, kbuf, vbuf, sem, half_scr = rest
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    layer = ly_ref[0]
    bs = block_tokens

    def live_blocks(slot):
        return jnp.clip((cl_ref[slot] + bs - 1) // bs, 1, max_blocks)

    def fetch(slot, c, half, wait: bool):
        """Start (or wait for) the copies of chunk ``c`` of ``slot``: its
        live blocks, each into its own place of ``half``."""
        first = c * chunk

        def one(b, carry):
            blk = bt_ref[slot, first + b]
            for i, (pool, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                cp = pltpu.make_async_copy(
                    pool.at[layer, blk], buf.at[half, b], sem.at[i, half, b])
                cp.wait() if wait else cp.start()
            return carry

        jax.lax.fori_loop(
            0, jnp.minimum(chunk, live_blocks(slot) - first), one, 0)

    @pl.when(s == 0)
    def _first():
        half_scr[0] = 0
        fetch(0, 0, 0, wait=False)

    first_half = half_scr[0]
    cl = cl_ref[s]
    n_live = live_blocks(s)
    n_chunks = (n_live + chunk - 1) // chunk
    q = q_ref[0].astype(jnp.float32) * sm_scale            # [1, H*D]

    def chunk_step(c, carry):
        half = (first_half + c) % 2
        # what is computed next: this slot's next chunk, or after its
        # last the next slot's first (none after the last slot's last)
        last = c + 1 == n_chunks

        @pl.when(jnp.logical_or(jnp.logical_not(last), s + 1 < n_slots))
        def _ahead():
            fetch(jnp.where(last, jnp.minimum(s + 1, n_slots - 1), s),
                  jnp.where(last, 0, c + 1), 1 - half, wait=False)

        fetch(s, c, half, wait=True)

        def block_step(b, carry):
            m, l, acc = carry
            j = c * chunk + b
            k_blk = kbuf[half, b].astype(jnp.float32)      # [bs, H*D]
            v_blk = vbuf[half, b].astype(jnp.float32)
            if quantized:
                k_blk = k_blk * (ks_ref[0, pl.ds(j, 1), :] * _INV_QMAX)
                v_blk = v_blk * (vs_ref[0, pl.ds(j, 1), :] * _INV_QMAX)
            scores = _head_sums(k_blk * q, head_dim)       # [bs, H*D]
            pos = j * bs + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 0)
            scores = jnp.where(pos < cl, scores, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(scores, axis=0, keepdims=True))
            p = jnp.exp(scores - m_new)                    # [bs, H*D]
            alpha = jnp.exp(m - m_new)
            return (m_new, l * alpha + jnp.sum(p, axis=0, keepdims=True),
                    acc * alpha + jnp.sum(p * v_blk, axis=0, keepdims=True))

        return jax.lax.fori_loop(
            0, jnp.minimum(chunk, n_live - c * chunk), block_step, carry)

    row = (1, q.shape[-1])
    _, l, acc = jax.lax.fori_loop(
        0, n_chunks, chunk_step,
        (jnp.full(row, NEG_INF, jnp.float32), jnp.zeros(row, jnp.float32),
         jnp.zeros(row, jnp.float32)))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    half_scr[0] = (first_half + n_chunks) % 2


def paged_attention_xla(q, k_cache, v_cache, block_tables, context_lens,
                        layer, sm_scale=None, k_scale=None, v_scale=None):
    """XLA gather fallback for :func:`decode_attention` (always
    available; also the parity reference the kernel is pinned to).

    q: [S, H, D]; k_cache/v_cache: the WHOLE pool [L, N_blocks, bs,
    H*D]; block_tables: [S, MB] int32; context_lens: [S] int32; layer:
    static int → [S, H, D].  The gather indexes ``[layer,
    block_tables]`` in one step, so no layer slice of the pool exists.
    With ``k_scale``/``v_scale`` ([L, N_blocks, H] f32, the int8
    cache's parallel scale pools) the gathered codes are dequantized
    before the softmax — same math as the kernel's VMEM dequant.
    """
    if sm_scale is None:
        sm_scale = float(1.0 / np.sqrt(q.shape[-1]))
    S, H, D = q.shape
    bs = k_cache.shape[2]
    MB = block_tables.shape[1]
    k = k_cache[layer, block_tables]             # [S, MB, bs, H*D]
    v = v_cache[layer, block_tables]
    if k_scale is not None:
        ks = k_scale[layer, block_tables]        # [S, MB, H]
        vs = v_scale[layer, block_tables]
        k = (k.reshape(S, MB, bs, H, D).astype(jnp.float32)
             * (ks[:, :, None, :, None] * _INV_QMAX))
        v = (v.reshape(S, MB, bs, H, D).astype(jnp.float32)
             * (vs[:, :, None, :, None] * _INV_QMAX))
    k = k.reshape(S, MB * bs, H, D)
    v = v.reshape(S, MB * bs, H, D)
    s = jnp.einsum("shd,sthd->sht", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    pos = jnp.arange(MB * bs, dtype=jnp.int32)
    s = jnp.where(pos[None, None, :] < context_lens[:, None, None],
                  s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("sht,sthd->shd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _paged_attn_pallas(q, k_cache, v_cache, block_tables, context_lens,
                       layer, sm_scale, interpret, k_scale=None,
                       v_scale=None):
    """``layer`` is an int32 scalar here, prefetched with the tables: the
    layers of a model then share ONE trace and ONE lowering of the kernel
    (a static layer made each its own — twelve a decode step, most of a
    warm start's build of that program)."""
    S, H, D = q.shape
    bs, HD = k_cache.shape[2:]
    MB = block_tables.shape[1]
    chunk = min(_DECODE_CHUNK_BLOCKS, MB)
    bt = block_tables.astype(jnp.int32)
    cl = context_lens.astype(jnp.int32)
    quantized = k_scale is not None

    def row_map(s, bt, cl, ly):
        return (s, 0, 0)

    # q and the output as [S, 1, H*D] rows: a (1, 1, H*D) block's minor
    # dims equal the array's (a (1, H*D) block of [S, H*D] breaks the
    # TPU (8, 128) rule)
    in_specs = [
        pl.BlockSpec((1, 1, HD), row_map),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [bt, cl, layer.reshape(1), q.reshape(S, 1, HD), k_cache,
                v_cache]
    if quantized:
        # the scale pools' minor dim is H, too narrow for a copy of its
        # own, so the slots' scale rows are gathered here ([S, MB, H],
        # small) and spread over each head's D lanes; a slot's rows come
        # in as one pipelined block, row = table position
        in_specs += [pl.BlockSpec((1, MB, HD), row_map)] * 2
        operands += [jnp.repeat(sc[layer, bt], D, axis=-1)
                     for sc in (k_scale, v_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, HD), row_map),
        scratch_shapes=[pltpu.VMEM((2, chunk, bs, HD), k_cache.dtype),
                        pltpu.VMEM((2, chunk, bs, HD), v_cache.dtype),
                        pltpu.SemaphoreType.DMA((2, 2, chunk)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    kernel = functools.partial(_decode_attn_kernel,
                               block_tokens=bs, chunk=chunk, max_blocks=MB,
                               head_dim=D, sm_scale=sm_scale,
                               quantized=quantized)
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, 1, HD), q.dtype),
        interpret=interpret,
    )(*operands)
    return out.reshape(S, H, D)


def decode_attention(q, k_cache, v_cache, block_tables, context_lens,
                     layer, sm_scale=None, interpret=None, impl=None,
                     k_scale=None, v_scale=None):
    """Paged decode attention: one query token per request against the
    live blocks of its block list (scalar-prefetched tables and lengths;
    the kernel fetches a slot's blocks itself — ``_decode_attn_kernel``).

    q: [S, H, D] (S decode slots); k_cache/v_cache: the WHOLE pool
    [L, N_blocks, block_tokens, H*D], every layer of it, handed over as
    it lies in HBM; ``layer``: int, which layer's blocks to read (it
    goes, as a prefetched scalar, into the source index of the kernel's
    copies — slicing ``k_cache[layer]`` first would copy the layer, and
    a layer baked into the kernel would trace and lower one kernel a
    layer); block_tables:
    [S, MB] int32 cache-block ids per slot; context_lens: [S] int32
    valid tokens per slot, at least 1 (positions ≥ context_len are
    masked, table entries past ``ceil(context_len / block_tokens)`` never
    read).  Returns [S, H, D].

    ``k_scale``/``v_scale``: [L, N_blocks, H] f32 per-block-per-head
    abs-max pools when the cache stores int8 codes
    (``DecodeEngine(cache_dtype="int8")``); both paths dequantize with
    ``code * s/127`` — the kernel in VMEM after the block copy lands,
    the XLA path after the gather.

    ``impl``: None / "pallas" (the kernel; interpret mode off-TPU like
    the flash kernels) or "xla" (the gather path, also the parity
    reference).  There is no fallback between them: a kernel the
    compiler refuses fails the dispatch that contains it (Mosaic's
    refusals arrive when jit lowers or compiles the step, long after
    this function returned — a trace-time ``try`` here never saw one)."""
    if sm_scale is None:
        sm_scale = float(1.0 / np.sqrt(q.shape[-1]))
    layer = int(layer)
    if impl == "xla":
        return paged_attention_xla(q, k_cache, v_cache, block_tables,
                                   context_lens, layer, sm_scale,
                                   k_scale=k_scale, v_scale=v_scale)
    if impl not in (None, "pallas"):
        raise ValueError(f"unknown decode attention impl {impl!r}")
    if interpret is None:
        interpret = pallas_interpret()
    return _paged_attn_pallas(q, k_cache, v_cache, block_tables,
                              context_lens, jnp.int32(layer), sm_scale,
                              interpret, k_scale=k_scale, v_scale=v_scale)
