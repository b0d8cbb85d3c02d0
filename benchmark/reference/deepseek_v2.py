"""Plain reference of DeepSeek-V2's decoder block (``model_type`` deepseek_v2):
one full causal forward of one sequence in float32 ``jax.numpy`` at the
highest matmul precision — the *expanded* attention formula only (no absorbed
projections, no cache), every expert applied to every token and weighted by
the router (zero where it was not chosen), YaRN as its paper and the
published modelling code state it.  Nothing is imported from the program.

``cfg`` is the published ``config.json`` as a dict (``hidden_size``,
``num_attention_heads``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``kv_lora_rank``, ``num_hidden_layers``,
``first_k_dense_replace``, ``n_routed_experts``, ``num_experts_per_tok``,
``norm_topk_prob``, ``routed_scaling_factor``, ``rms_norm_eps``,
``rope_theta``, ``rope_scaling``).  Weights are a name → array dict, any
float dtype, widened to float32 one matrix (one expert) at a time:

``emb`` [V, D], ``final_norm`` [D], ``head`` [D, V], and a layer ``l<i>.``:
``attn_norm``, ``ffn_norm`` [D], ``kv_norm`` [rank], ``wq`` [D, H*(nope+rope)]
(a head ``[q_nope | q_pe]``), ``wkva`` [D, rank+rope] (``[c | k_pe]``),
``wkvb`` [rank, H*(nope+v)] (a head ``[k_nope | v]``), ``wo`` [H*v, D]; dense
layers ``w_gate``, ``w_up`` [D, F], ``w_down`` [F, D]; expert layers
``router`` [D, E], ``e_gate``, ``e_up`` [E, D, Fe], ``e_down`` [E, Fe, D] and
the shared experts as one SwiGLU ``s_gate``, ``s_up`` [D, Fs], ``s_down``
[Fs, D].  The rotary slices are in the half-split order (the published code
de-interleaves pairs first: on random weights a permutation of columns).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 1024      # queries a block: the [H, Q_BLOCK, T] scores must fit


def f32(a):
    return jnp.asarray(a, jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(w)


def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1.0 else 0.1 * mscale * math.log(scale) + 1.0


def yarn(cfg):
    """(inv_freq [rope/2], factor on cos and sin, softmax scale)."""
    d, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    rs = cfg.get("rope_scaling") or {}
    factor = float(rs.get("factor", 1.0))
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    width = int(cfg["qk_nope_head_dim"]) + d
    if factor <= 1.0:
        return freq.astype(np.float32), 1.0, width ** -0.5
    orig = float(rs["original_max_position_embeddings"])

    def correction(beta):
        return d * math.log(orig / (beta * 2 * math.pi)) / (2 * math.log(theta))
    lo = max(math.floor(correction(float(rs["beta_fast"]))), 0)
    hi = min(math.ceil(correction(float(rs["beta_slow"]))), d - 1)
    ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    inv = freq / factor * ramp + freq * (1.0 - ramp)
    m_all = yarn_mscale(factor, float(rs.get("mscale_all_dim", 0.0)))
    m = yarn_mscale(factor, float(rs.get("mscale", 1.0)))
    return inv.astype(np.float32), m / m_all, width ** -0.5 * m_all * m_all


def rope(x, pos, inv_freq, factor):
    """x [T, ..., rope], pos [T]: half-split rotation."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ f32(wg)) * (x @ f32(wu))) @ f32(wd)


def attention(p, L, cfg, x, pos, length):
    H = int(cfg["num_attention_heads"])
    dn, dr = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    dv, r = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
    inv_freq, factor, scale = yarn(cfg)
    T = x.shape[0]
    q = (x @ f32(p[L + "wq"])).reshape(T, H, dn + dr)
    kva = x @ f32(p[L + "wkva"])
    c = rms_norm(kva[:, :r], p[L + "kv_norm"], float(cfg["rms_norm_eps"]))
    k_pe = rope(kva[:, r:], pos, inv_freq, factor)                  # [T, dr]
    q_pe = rope(q[..., dn:], pos, inv_freq, factor)                 # [T, H, dr]
    kv = (c @ f32(p[L + "wkvb"])).reshape(T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    blk = min(Q_BLOCK, T)
    assert T % blk == 0, (T, blk)

    def block(i):
        qs = i * blk + jnp.arange(blk)
        s = (jnp.einsum("qhd,khd->hqk", q[qs, :, :dn], k_nope)
             + jnp.einsum("qhd,kd->hqk", q_pe[qs], k_pe)) * scale
        keep = (pos[None, :] <= qs[:, None]) & (pos[None, :] < length)
        w = jax.nn.softmax(jnp.where(keep[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v).reshape(blk, H * dv)
    ctx = jax.lax.map(block, jnp.arange(T // blk)).reshape(T, H * dv)
    return ctx @ f32(p[L + "wo"])


def moe(p, L, cfg, x, forced_ids=None):
    """→ (output [T, D], the router's own choice ids [T, K]).  With
    ``forced_ids`` [T, K] those experts are used instead, at the weights this
    router gives them."""
    E, K = int(cfg["n_routed_experts"]), int(cfg["num_experts_per_tok"])
    s = jax.nn.softmax(x @ f32(p[L + "router"]), axis=-1)           # [T, E]
    _, own = jax.lax.top_k(s, K)
    ids = own if forced_ids is None else forced_ids
    chosen = jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=1)
    w = s * chosen
    if cfg.get("norm_topk_prob"):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * float(cfg.get("routed_scaling_factor", 1.0))

    def one(acc, e):
        wg, wu, wd, we = e
        return acc + we[:, None] * swiglu(x, wg, wu, wd), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (p[L + "e_gate"], p[L + "e_up"], p[L + "e_down"],
                         w.T))
    return y + swiglu(x, p[L + "s_gate"], p[L + "s_up"], p[L + "s_down"]), \
        own.astype(jnp.int32)


def forward(params, cfg, tokens, length, out_positions, forced_ids=None):
    """tokens [T] int32 (one sequence, padded), length [] int32,
    out_positions [P] int32 → (logits [P, V] float32 at those positions, the
    routers' own choices [n_moe_layers, T, K] int32).  ``forced_ids`` of that
    shape makes every expert layer use the given experts."""
    with jax.default_matmul_precision("highest"):
        p = params
        eps = float(cfg["rms_norm_eps"])
        T = tokens.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        x = f32(p["emb"][tokens])
        own, m = [], 0
        for i in range(int(cfg["num_hidden_layers"])):
            L = f"l{i}."
            x = x + attention(p, L, cfg, rms_norm(x, p[L + "attn_norm"], eps),
                              pos, length)
            h = rms_norm(x, p[L + "ffn_norm"], eps)
            if i < int(cfg["first_k_dense_replace"]):
                x = x + swiglu(h, p[L + "w_gate"], p[L + "w_up"],
                               p[L + "w_down"])
            else:
                f, ids = moe(p, L, cfg, h,
                             None if forced_ids is None else forced_ids[m])
                x = x + f
                own.append(ids)
                m += 1
        x = rms_norm(x[out_positions], p["final_norm"], eps)
        K = int(cfg["num_experts_per_tok"])
        return x @ f32(p["head"]), (jnp.stack(own) if own else
                                    jnp.zeros((0, T, K), jnp.int32))
