"""Plain reference of the SambaY stack (``model_type`` phi4flash; arXiv
2507.06607): one full causal forward of one sequence in float32
``jax.numpy`` at the highest matmul precision — every position through every
layer, a sequential ``lax.scan`` for the recurrence, dense masked attention
with both softmaxes of a differential head written out, no cache, no
batching, no kernel.  Nothing is imported from the program.

``cfg`` is the published ``config.json`` as a dict (``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``sliding_window``, ``layer_norm_eps``) plus the state-space sizes the
configuration file assumes (``d_state``, ``d_conv``, ``expand``,
``dt_rank``).  With L layers: state-space mixers at 0, 2, …, L/2, window
attention at 1, 3, …, L/2 − 1, full attention at L/2 + 1, then gated memory
units (even) and cross attention over the full layer's keys and values (odd).

Weights are the program's name → array dict, any float dtype, widened to
float32 ONE LAYER AT A TIME (each layer is a jitted call of its own, so the
float32 copy of a layer lives only while that layer runs): ``emb`` [V, D]
(also the head), ``final_g``, ``final_b`` [D]; the (state-space, window) pairs
stacked as ``sp.s.*`` / ``sp.w.*`` [L/4, …], layers L/2 and L/2 + 1 as
``ms.*`` / ``mf.*``, the (memory unit, cross attention) pairs as ``cp.g.*`` /
``cp.c.*`` [L/4 − 1, …].  Every layer: ``ln1_g``, ``ln1_b``, ``ln2_g``,
``ln2_b`` [D], ``mlp_gate``, ``mlp_up`` [D, F], ``mlp_down`` [F, D].
State-space: ``in_proj`` [D, 2·Di] (``[a | z]``), ``conv_w`` [K, Di] (row K−1
weighs the current position), ``conv_b``, ``x_proj`` [Di, R + 2N] (``[δ | B |
C]``), ``dt_w`` [R, Di], ``dt_b``, ``a_log`` [N, Di], ``skip`` [Di],
``out_proj`` [Di, D].  Attention: ``wqkv`` [D, D + 2·kw] (``[q | k | v]``, a
head's query ``[q¹ | q²]``, a K/V head's key ``[k¹ | k²]`` and value 2·dh
wide), ``bqkv``; cross attention ``wq`` [D, D], ``bq``; both ``wo`` [D, D],
``bo``, ``subln`` [2·dh], ``lam_q1``, ``lam_k1``, ``lam_q2``, ``lam_k2`` [dh].
Memory unit: ``w1`` [D, Di], ``w2`` [Di, D].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 512       # queries a block: the [heads, 2, Q_BLOCK, T] scores must fit
TRAINED_STEP_SIZE = (1e-3, 1e-1)    # where Mamba's initialisation puts Δ
SUBLN_EPS = 1e-5


def f32(a):
    return jnp.asarray(a, jnp.float32)


def sizes(cfg: dict) -> dict:
    D = int(cfg["hidden_size"])
    H, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    L = int(cfg["num_hidden_layers"])
    return {"D": D, "L": L, "dh": D // H, "nh": H // 2, "nkv": Hkv // 2,
            "kw": Hkv * (D // H), "Di": int(cfg["expand"]) * D,
            "N": int(cfg["d_state"]), "K": int(cfg["d_conv"]),
            "R": int(cfg.get("dt_rank") or -(-D // 16)),
            "W": int(cfg["sliding_window"]),
            "eps": float(cfg["layer_norm_eps"])}


def layer_kind(cfg: dict, i: int) -> str:
    half = int(cfg["num_hidden_layers"]) // 2
    if i <= half:
        return "ssm" if i % 2 == 0 else "swa"
    if i == half + 1:
        return "full"
    return "gmu" if i % 2 == 0 else "cross"


def layer_weights(params: dict, cfg: dict, i: int) -> dict:
    """Layer ``i``'s own weights, in the dtype they are stored in."""
    half = int(cfg["num_hidden_layers"]) // 2
    if i < half:
        prefix, at = ("sp.s." if i % 2 == 0 else "sp.w."), i // 2
    elif i <= half + 1:
        prefix, at = ("ms." if i == half else "mf."), None
    else:
        j = i - half - 2
        prefix, at = ("cp.g." if j % 2 == 0 else "cp.c."), j // 2
    n = len(prefix)
    return {k[n:]: (v if at is None else v[at])
            for k, v in params.items() if k.startswith(prefix)}


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * f32(g) + f32(b)


def mlp(w, x, eps):
    u = layer_norm(x, w["ln2_g"], w["ln2_b"], eps)
    return x + (jax.nn.silu(u @ f32(w["mlp_gate"])) * (u @ f32(w["mlp_up"]))
                ) @ f32(w["mlp_down"])


def state_space(w, u, length, sz):
    """u [T, D] → (output [T, D], the memory y [T, Di], h after position
    ``length − 1`` [N, Di], the step sizes Δ of the real positions as
    [smallest, largest, share inside :data:`TRAINED_STEP_SIZE`]); positions
    from ``length`` on do not move h."""
    Di, N, K, R = sz["Di"], sz["N"], sz["K"], sz["R"]
    T = u.shape[0]
    az = u @ f32(w["in_proj"])
    a, z = az[:, :Di], az[:, Di:]
    padded = jnp.concatenate([jnp.zeros((K - 1, Di), jnp.float32), a])
    conv = f32(w["conv_b"])[None, :]
    for k in range(K):
        conv = conv + f32(w["conv_w"][k])[None, :] * padded[k:k + T]
    c = jax.nn.silu(conv)
    dbc = c @ f32(w["x_proj"])
    delta = jax.nn.softplus(dbc[:, :R] @ f32(w["dt_w"]) + f32(w["dt_b"]))
    real = (jnp.arange(T) < length)[:, None]
    lo, hi = TRAINED_STEP_SIZE
    inside = jnp.logical_and(delta >= lo, delta <= hi)
    steps = jnp.stack([
        jnp.min(jnp.where(real, delta, jnp.inf)),
        jnp.max(jnp.where(real, delta, 0.0)),
        jnp.sum(jnp.where(real, inside, False)) / (length * Di)])
    delta = jnp.where(real, delta, 0.0)
    A = -jnp.exp(f32(w["a_log"]))

    def step(h, row):
        ct, dt, bt, kt = row
        h = jnp.exp(dt[None, :] * A) * h + (dt * ct)[None, :] * bt[:, None]
        return h, jnp.sum(h * kt[:, None], axis=0)

    h, y = jax.lax.scan(step, jnp.zeros((N, Di), jnp.float32),
                        (c, delta, dbc[:, R:R + N], dbc[:, R + N:]))
    y = y + f32(w["skip"]) * c
    return (y * jax.nn.silu(z)) @ f32(w["out_proj"]), y, h, steps


def diff_attention(w, q, k, v, window, lam0, sz):
    """q [T, nh, 2, dh], k [T, nkv, 2, dh], v [T, nkv, 2·dh] → [T, D]: both
    softmaxes over the visible keys (j ≤ t, and t − j < window if given),
    ``o¹ − λ o²``, the sub-layer RMSNorm, ``(1 − λ_init)``, ``W_o``."""
    T, nh, nkv, dh = q.shape[0], sz["nh"], sz["nkv"], sz["dh"]
    group = nh // nkv
    lam = jnp.exp(jnp.sum(f32(w["lam_q1"]) * f32(w["lam_k1"]))) \
        - jnp.exp(jnp.sum(f32(w["lam_q2"]) * f32(w["lam_k2"]))) + lam0
    kh = jnp.repeat(k, group, axis=1)           # [T, nh, 2, dh]
    vh = jnp.repeat(v, group, axis=1)           # [T, nh, 2·dh]
    keys = jnp.arange(T)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK, axis=0)
        t = start + jnp.arange(Q_BLOCK)
        keep = keys[None, :] <= t[:, None]
        if window is not None:
            keep = jnp.logical_and(keep, t[:, None] - keys[None, :] < window)
        s = jnp.einsum("thcd,jhcd->hctj", qb, kh) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hctj,jhv->thcv", p, vh)
        return o[:, :, 0] - lam * o[:, :, 1]    # [Q_BLOCK, nh, 2·dh]

    pad = -T % Q_BLOCK
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    o = jax.lax.map(block, jnp.arange(0, T + pad, Q_BLOCK))
    o = o.reshape(T + pad, nh, 2 * dh)[:T]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + SUBLN_EPS) \
        * f32(w["subln"])
    return ((1.0 - lam0) * o).reshape(T, -1) @ f32(w["wo"]) + f32(w["bo"])


def split_qkv(w, u, sz):
    D, kw, nh, nkv, dh = sz["D"], sz["kw"], sz["nh"], sz["nkv"], sz["dh"]
    T = u.shape[0]
    qkv = u @ f32(w["wqkv"]) + f32(w["bqkv"])
    return (qkv[:, :D].reshape(T, nh, 2, dh),
            qkv[:, D:D + kw].reshape(T, nkv, 2, dh),
            qkv[:, D + kw:].reshape(T, nkv, 2 * dh))


@functools.lru_cache(maxsize=None)
def _layer_fn(kind: str, memory: bool, frozen: tuple):
    """The jitted forward of one kind of layer: (weights, x [T, D], length,
    the layer's λ_init, shared) → (x', shared'); ``shared`` carries the
    memory (from the state-space layer with ``memory`` set) and the full
    layer's keys and values upward."""
    sz = dict(frozen)
    eps = sz["eps"]

    def fn(w, x, length, lam0, shared):
        u = layer_norm(x, w["ln1_g"], w["ln1_b"], eps)
        shared = dict(shared)
        if kind == "ssm":
            out, y, shared["h"], shared["steps"] = state_space(
                w, u, length, sz)
            if memory:
                shared["memory"] = y
        elif kind in ("swa", "full"):
            q, k, v = split_qkv(w, u, sz)
            if kind == "full":
                shared["k"], shared["v"] = k, v
            out = diff_attention(w, q, k, v,
                                 sz["W"] if kind == "swa" else None, lam0, sz)
        elif kind == "gmu":
            out = (jax.nn.silu(u @ f32(w["w1"])) * shared["memory"]) \
                @ f32(w["w2"])
        else:
            q = (u @ f32(w["wq"]) + f32(w["bq"])).reshape(
                u.shape[0], sz["nh"], 2, sz["dh"])
            out = diff_attention(w, q, shared["k"], shared["v"], None, lam0,
                                 sz)
        return mlp(w, x + out, eps), shared

    return jax.jit(fn)


@jax.jit
def _embed(emb, tokens):
    return f32(emb[tokens])


@functools.partial(jax.jit, static_argnums=(5,))
def _head(emb, g, b, x, at, eps):
    return layer_norm(x[at], g, b, eps) @ f32(emb).T


def forward(params: dict, cfg: dict, tokens, length, out_positions):
    """tokens [T] int32 (positions from ``length`` on are padding),
    out_positions [n] int32 (each below ``length``) → (logits [n, V] float32,
    the state-space layers' states after position ``length − 1`` [L/4 + 1, N,
    Di], their step sizes over the real positions [L/4 + 1, 3]: smallest,
    largest, share inside :data:`TRAINED_STEP_SIZE`)."""
    sz = sizes(cfg)
    frozen = tuple(sorted(sz.items()))
    states, steps, shared = [], [], {}
    with jax.default_matmul_precision("highest"):
        x = _embed(params["emb"], jnp.asarray(tokens))
        for i in range(sz["L"]):
            kind = layer_kind(cfg, i)
            x, shared = _layer_fn(kind, i == sz["L"] // 2, frozen)(
                layer_weights(params, cfg, i), x, jnp.int32(length),
                jnp.float32(lambda_init(i)), shared)
            if kind == "ssm":
                states.append(shared.pop("h"))
                steps.append(shared.pop("steps"))
        logits = _head(params["emb"], params["final_g"], params["final_b"],
                       x, jnp.asarray(out_positions), sz["eps"])
    return logits, jnp.stack(states), jnp.stack(steps)
