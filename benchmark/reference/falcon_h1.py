"""Plain reference of the Falcon-H1 stack (``model_type`` falcon_h1): one full
causal forward of one sequence in float32 ``jax.numpy`` at the highest matmul
precision — every position through every layer, the state-space recurrence
one position at a time (a sequential ``lax.scan``, no chunking), dense masked
attention with rotate-half rotary positions, no cache, no batching, no
kernel.  Nothing is imported from the program.

``cfg`` is the published ``config.json`` as a dict.  Every layer holds BOTH
mixers on one normed input ``u = RMSNorm(x)``:

- state space: ``p = ((u · ssm_in_multiplier) W_in) ⊙ µ``, ``µ`` repeating
  ``ssm_multipliers[0..4]`` over the segments ``[z | x | B | C | dt]``; ``[x |
  B | C] ← silu(conv(·) + b)``; ``Δ = softplus(dt + dt_bias)`` a head; ``S_t =
  exp(Δ_t A_h) S_{t−1} + B_{t,g} ⊗ (Δ_t x_{t,h})``, ``A = −exp(A_log)``, ``y =
  C_{t,g}ᵀ S_t + D_h x``; ``y ← RMSNorm_groups(y ⊙ silu(z)) ⊙ w``; ``(y W_out)
  · ssm_out_multiplier``;
- attention: ``q, k, v`` from ``u · attention_in_multiplier``, ``k ·
  key_multiplier``, rotary on ``q`` and ``k``, causal softmax over
  ``num_attention_heads`` query heads on ``num_key_value_heads`` K/V heads,
  ``(o W_o) · attention_out_multiplier``;

then ``x ← x + both``, and ``x ← x + (silu(v W_gate · m₀) ⊙ v W_up) W_down ·
m₁`` with ``v = RMSNorm'(x)``.  ``x₀ = E[token] · embedding_multiplier``; a
final RMSNorm; ``logits = (x W_headᵀ) · lm_head_multiplier``.

Weights are the program's name → array dict, any float dtype: ``emb``, ``head``
[V, D], ``final_norm`` [D], and the layers stacked as ``lay.*`` [L, …]:
``ln1``, ``ln2`` [D], ``in_proj`` [D, 2·Ds + 2·G·N + H] (``[z | x |
B | C | dt]``), ``conv_w`` [K, Ds + 2·G·N] (row K−1 weighs the current
position), ``conv_b``, ``dt_bias``, ``a_log``, ``d_skip`` [H], ``ssm_norm``
[Ds], ``out_proj`` [Ds, D], ``wqkv`` [D, (nh + 2·nkv)·dh] (``[q | k | v]``),
``wo`` [nh·dh, D], ``mlp_gate``, ``mlp_up`` [D, F], ``mlp_down`` [F, D].  They
are widened to float32 ONE MATRIX AT A TIME — a layer is several jitted calls,
the MLP in blocks of its intermediate columns, the head in blocks of
vocabulary rows at the judged positions only — because the check runs beside
a live engine that holds most of the chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 512           # queries a block of the dense attention
MLP_BLOCKS = 4          # blocks of the MLP's intermediate columns
HEAD_ROWS = 16384       # vocabulary rows a block of the head
TRAINED_STEP_SIZE = (1e-3, 1e-1)    # where Mamba's initialisation puts Δ
# a layer's own readings, in this order (``forward``'s third result)
STATS = ("ssm_rms", "attn_rms", "mlp_rms", "step_size_min", "step_size_max",
         "step_size_in_range_share", "attn_logit_std")


def f32(a):
    return jnp.asarray(a, jnp.float32)


def sizes(cfg: dict) -> dict:
    G, N = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    Ds, H = int(cfg["mamba_d_ssm"]), int(cfg["mamba_n_heads"])
    return {"D": int(cfg["hidden_size"]), "L": int(cfg["num_hidden_layers"]),
            "F": int(cfg["intermediate_size"]),
            "nh": int(cfg["num_attention_heads"]),
            "nkv": int(cfg["num_key_value_heads"]), "dh": int(cfg["head_dim"]),
            "Ds": Ds, "H": H, "P": Ds // H, "G": G, "N": N,
            "K": int(cfg["mamba_d_conv"]), "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "m_emb": float(cfg["embedding_multiplier"]),
            "m_head": float(cfg["lm_head_multiplier"]),
            "m_attn_in": float(cfg["attention_in_multiplier"]),
            "m_attn_out": float(cfg["attention_out_multiplier"]),
            "m_key": float(cfg["key_multiplier"]),
            "m_ssm_in": float(cfg["ssm_in_multiplier"]),
            "m_ssm_out": float(cfg["ssm_out_multiplier"]),
            "m_ssm": tuple(float(m) for m in cfg["ssm_multipliers"]),
            "m_mlp": tuple(float(m) for m in cfg["mlp_multipliers"])}


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(g)


def _rms(a, real):
    """Root mean square of a [T, ·] over the real positions."""
    n = jnp.sum(real) * a.shape[1]
    return jnp.sqrt(jnp.sum(jnp.where(real[:, None], a * a, 0.0)) / n)


def rotate(x, theta):
    """x [T, heads, dh], position t at row t: rotate-half rotary."""
    T, _, dh = x.shape
    half = dh // 2
    inv = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * (-math.log(theta) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def state_space(w, u, length, sz):
    """u [T, D] → (the branch's output [T, D], S after position ``length −
    1`` [H, N, P], the step sizes of the real positions [smallest, largest,
    share inside :data:`TRAINED_STEP_SIZE`]); positions from ``length`` on do
    not move S."""
    Ds, H, P, G, N, K = (sz[k] for k in ("Ds", "H", "P", "G", "N", "K"))
    T = u.shape[0]
    bw = G * N
    mu = jnp.concatenate([jnp.full((n,), m, jnp.float32) for n, m in zip(
        (Ds, Ds, bw, bw, H), sz["m_ssm"])])
    p = ((u * sz["m_ssm_in"]) @ f32(w["in_proj"])) * mu
    z, a, dt = p[:, :Ds], p[:, Ds:2 * Ds + 2 * bw], p[:, 2 * Ds + 2 * bw:]
    padded = jnp.concatenate([jnp.zeros((K - 1, a.shape[1]), jnp.float32), a])
    conv = f32(w["conv_b"])[None, :]
    for k in range(K):
        conv = conv + f32(w["conv_w"][k])[None, :] * padded[k:k + T]
    c = jax.nn.silu(conv)
    x = c[:, :Ds].reshape(T, H, P)
    B = jnp.repeat(c[:, Ds:Ds + bw].reshape(T, G, N), H // G, axis=1)
    C = jnp.repeat(c[:, Ds + bw:].reshape(T, G, N), H // G, axis=1)
    delta = jax.nn.softplus(dt + f32(w["dt_bias"]))             # [T, H]
    real = (jnp.arange(T) < length)[:, None]
    lo, hi = TRAINED_STEP_SIZE
    inside = jnp.logical_and(delta >= lo, delta <= hi)
    steps = jnp.stack([
        jnp.min(jnp.where(real, delta, jnp.inf)),
        jnp.max(jnp.where(real, delta, 0.0)),
        jnp.sum(jnp.where(real, inside, False)) / (length * H)])
    delta = jnp.where(real, delta, 0.0)
    A = -jnp.exp(f32(w["a_log"]))                               # [H]

    def step(S, row):
        xt, dt_t, bt, ct = row          # [H, P], [H], [H, N], [H, N]
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + bt[:, :, None] * (dt_t[:, None] * xt)[:, None, :]
        return S, jnp.sum(S * ct[:, :, None], axis=1)

    S, y = jax.lax.scan(step, jnp.zeros((H, N, P), jnp.float32),
                        (x, delta, B, C))
    y = y + f32(w["d_skip"])[None, :, None] * x
    y = y.reshape(T, G, -1) * jax.nn.silu(z).reshape(T, G, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + sz["eps"])
    y = y.reshape(T, Ds) * f32(w["ssm_norm"])
    return (y @ f32(w["out_proj"])) * sz["m_ssm_out"], S, steps


def attention(w, u, length, sz):
    """u [T, D] → (the branch's output [T, D], the standard deviation of the
    visible scores of the real queries)."""
    nh, nkv, dh = sz["nh"], sz["nkv"], sz["dh"]
    T = u.shape[0]
    qkv = (u * sz["m_attn_in"]) @ f32(w["wqkv"])
    q = rotate(qkv[:, :nh * dh].reshape(T, nh, dh), sz["theta"])
    k = rotate((qkv[:, nh * dh:(nh + nkv) * dh] * sz["m_key"]
                ).reshape(T, nkv, dh), sz["theta"])
    v = qkv[:, (nh + nkv) * dh:].reshape(T, nkv, dh)
    kh = jnp.repeat(k, nh // nkv, axis=1)
    vh = jnp.repeat(v, nh // nkv, axis=1)
    keys = jnp.arange(T)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK, axis=0)
        t = start + jnp.arange(Q_BLOCK)
        keep = keys[None, :] <= t[:, None]
        s = jnp.einsum("thd,jhd->htj", qb, kh) / math.sqrt(dh)
        seen = jnp.logical_and(keep, (t < length)[:, None])
        moments = jnp.stack([jnp.sum(seen) * nh,
                             jnp.sum(jnp.where(seen, s, 0.0)),
                             jnp.sum(jnp.where(seen, s * s, 0.0))])
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("htj,jhd->thd", p, vh), moments

    pad = -T % Q_BLOCK
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    o, moments = jax.lax.map(block, jnp.arange(0, T + pad, Q_BLOCK))
    n, s1, s2 = moments.sum(0)
    std = jnp.sqrt(jnp.maximum(s2 / n - (s1 / n) ** 2, 0.0))
    o = o.reshape(T + pad, nh * dh)[:T]
    return (o @ f32(w["wo"])) * sz["m_attn_out"], std


@functools.lru_cache(maxsize=None)
def _fns(frozen: tuple):
    sz = dict(frozen)
    eps = sz["eps"]

    @jax.jit
    def ssm(w, x, length):
        return state_space(w, rms_norm(x, w["ln1"], eps), length, sz)

    @jax.jit
    def attn(w, x, length):
        return attention(w, rms_norm(x, w["ln1"], eps), length, sz)

    @jax.jit
    def mlp_block(gate, up, down, v, b):
        """Columns ``b`` of the intermediate: v [T, D] → its share of the
        MLP's output [T, D] (before the down multiplier)."""
        n = gate.shape[1] // MLP_BLOCKS
        g = v @ f32(jax.lax.dynamic_slice_in_dim(gate, b * n, n, axis=1))
        h = v @ f32(jax.lax.dynamic_slice_in_dim(up, b * n, n, axis=1))
        return (jax.nn.silu(g * sz["m_mlp"][0]) * h) @ f32(
            jax.lax.dynamic_slice_in_dim(down, b * n, n, axis=0))

    @jax.jit
    def join(x, out_ssm, out_attn, ln2, length):
        real = jnp.arange(x.shape[0]) < length
        base = _rms(x, real)
        x = x + out_ssm + out_attn
        return (x, rms_norm(x, ln2, eps), _rms(out_ssm, real) / base,
                _rms(out_attn, real) / base)

    @jax.jit
    def close(x, out_mlp, length):
        real = jnp.arange(x.shape[0]) < length
        out_mlp = out_mlp * sz["m_mlp"][1]
        return x + out_mlp, _rms(out_mlp, real) / _rms(x, real)

    @jax.jit
    def embed(emb, tokens):
        return f32(emb[tokens]) * sz["m_emb"]

    @jax.jit
    def head_block(rows, g, x, at):
        return (rms_norm(x[at], g, eps) @ f32(rows).T) * sz["m_head"]

    return ssm, attn, mlp_block, join, close, embed, head_block


def forward(params: dict, cfg: dict, tokens, length, out_positions):
    """tokens [T] int32 (positions from ``length`` on are padding),
    out_positions [n] int32 (each below ``length``) → (logits [n, V] float32,
    every layer's state after position ``length − 1`` [L, H, N, P], every
    layer's own readings [L, len(STATS)] in the order of :data:`STATS`: the
    root mean square of the state-space branch's, the attention branch's and
    the MLP's output over the residual's they are added to, the step sizes of
    the real positions — smallest, largest, share inside
    :data:`TRAINED_STEP_SIZE` — and the standard deviation of the visible
    attention scores)."""
    sz = sizes(cfg)
    ssm, attn, mlp_block, join, close, embed, head_block = _fns(
        tuple(sorted(sz.items())))
    length = jnp.int32(length)
    states, stats = [], []
    with jax.default_matmul_precision("highest"):
        x = embed(params["emb"], jnp.asarray(tokens))
        for i in range(sz["L"]):
            w = {k[4:]: v[i] for k, v in params.items()
                 if k.startswith("lay.")}
            out_ssm, S, steps = ssm(w, x, length)
            out_attn, std = attn(w, x, length)
            x, v, r_ssm, r_attn = join(x, out_ssm, out_attn, w["ln2"], length)
            out_mlp = sum(mlp_block(w["mlp_gate"], w["mlp_up"], w["mlp_down"],
                                    v, jnp.int32(b))
                          for b in range(MLP_BLOCKS))
            x, r_mlp = close(x, out_mlp, length)
            states.append(S)
            stats.append(jnp.stack([r_ssm, r_attn, r_mlp, *steps, std]))
        at = jnp.asarray(out_positions)
        logits = jnp.concatenate(
            [head_block(params["head"][r:r + HEAD_ROWS], params["final_norm"],
                        x, at)
             for r in range(0, params["head"].shape[0], HEAD_ROWS)], axis=1)
    return logits, jnp.stack(states), jnp.stack(stats)
