"""Plain reference of the Nemotron-H stack (``model_type`` nemotron_h): one
full causal forward of one sequence in float32 ``jax.numpy`` at the highest
matmul precision — every position through every layer, the state-space
recurrence one position at a time (a sequential ``lax.scan``, no chunking),
dense masked attention with NO rotation, every held expert over every
position weighed by what the router gave it, no cache, no batching, no
kernel.  Nothing is imported from the program.

``cfg`` is the published ``config.json`` as a dict (with ``router_experts``
and ``first_expert`` where the chip holds a share).  Layer ``l`` is ONE mixer
behind one RMSNorm, ``x ← x + mixer_l(RMSNorm_l(x))`` at
``layer_norm_epsilon``, and ``hybrid_override_pattern[l]`` says which:

- ``M``: ``[z | xBC | dt] = u W_in`` of widths ``d_inner | d_inner + 2·G·N |
  H``, ``d_inner = mamba_num_heads · mamba_head_dim``; ``xBC ← silu(conv(xBC)
  + b)`` depthwise causal; ``Δ = softplus(dt + dt_bias)``; ``S_t = exp(Δ_t
  A_h) S_{t−1} + B_{t,g} ⊗ (Δ_t x_{t,h})``, ``A = −exp(A_log)``, ``y = C_{t,
  g}ᵀ S_t + D_h x``; ``y ← RMSNorm_groups(y ⊙ silu(z)) ⊙ w`` (gate before the
  norm, statistics a group of ``d_inner / n_groups`` channels); ``y W_out``.
- ``E``: ``s = sigmoid(u W_r)``, the ``num_experts_per_tok`` largest of ``s +
  b`` chosen, weights ``s_e / (Σ chosen s + 1e-20) · routed_scaling_factor``;
  expert ``e``: ``relu(u W_up,eᵀ)² W_down,e``; one shared expert of the same
  form; the HELD experts' part (``first_expert …``) plus the shared one.
- ``*``: ``q, k, v`` from ``u``, no bias, no norm, no rotation, causal
  ``softmax(q kᵀ / √head_dim) v`` with ``num_attention_heads`` query heads on
  ``num_key_value_heads`` K/V heads, ``o W_o``.

``x₀ = E[token]``; a final RMSNorm; ``logits = x W_headᵀ``, untied.

Weights are the program's name → array dict, any float dtype: ``emb``,
``head`` [V, D], ``final_norm`` [D], and each kind's layers stacked: ``m.*``
(``ln`` [D], ``in_proj`` [D, ·], ``conv_w`` [K, ·] — row K−1 weighs the
current position —, ``conv_b``, ``dt_bias``, ``a_log``, ``d_skip`` [H],
``ssm_norm`` [d_inner], ``out_proj`` [d_inner, D]), ``e.*`` (``ln``,
``router`` [D, Er], ``router_bias`` [Er], ``e_up``, ``e_down`` [E, F, D] —
BOTH as ``[F, D]``, the first as a checkpoint keeps it —, ``s_up`` [D, Fs],
``s_down`` [Fs, D]) and ``a.*`` (``ln``, ``wqkv`` [D, (nh + 2·nkv)·dh], ``wo``
[nh·dh, D]).  They are widened to float32 ONE MATRIX AT A TIME — a layer is
several jitted calls, an expert a loop step, the head in blocks of vocabulary
rows at the judged positions only — because the check runs beside a live
engine that holds most of the chip.

``forced`` ([Le, T, K] int32) gives the experts every position USES (the
program's own choices: a near tie turned by bf16 activations is then not an
error of everything downstream); the reference's OWN choices are returned
either way.  ``faults`` plants a mechanism that the model does NOT have, for
the controls (:data:`FAULTS`; ``bf16_step`` is no other mechanism but the
step size and the decay in the nearest precision below the stated one).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256           # queries a block of the dense attention
SHARED_BLOCKS = 2       # blocks of the shared expert's intermediate columns
HEAD_ROWS = 16384       # vocabulary rows a block of the head
POOL_ROWS = 64          # cache rows [k | v] kept of each attention layer
KINDS = {"M": "m.", "E": "e.", "*": "a."}
# each layer's own readings of its kind (``forward``'s third result)
STATS = ("mamba_rms", "experts_rms", "attn_rms", "attn_logit_std",
         "top1_weight", "bias_turns_share", "held_choice_share",
         "step_size_min", "step_size_max", "decay_weakest")
# mechanisms this model does not have, planted by name (the controls)
FAULTS = ("silu_unit", "rotate_attention", "norm_before_gate",
          "one_norm_group", "no_skip", "bf16_step")


def f32(a):
    return jnp.asarray(a, jnp.float32)


def sizes(cfg: dict) -> dict:
    L = int(cfg["num_hidden_layers"])
    H, P = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    held = int(cfg["n_routed_experts"])
    return {"D": int(cfg["hidden_size"]), "L": L,
            "pattern": str(cfg["hybrid_override_pattern"])[:L],
            "nh": int(cfg["num_attention_heads"]),
            "nkv": int(cfg["num_key_value_heads"]), "dh": int(cfg["head_dim"]),
            "H": H, "P": P, "Di": H * P, "G": int(cfg["n_groups"]),
            "N": int(cfg["ssm_state_size"]), "K": int(cfg["conv_kernel"]),
            "E": held, "Er": int(cfg.get("router_experts") or held),
            "first": int(cfg.get("first_expert", 0)),
            "k": int(cfg["num_experts_per_tok"]),
            "scale": float(cfg["routed_scaling_factor"]),
            "eps": float(cfg["layer_norm_epsilon"]),
            "theta": float(cfg.get("rope_theta", 10000.0))}


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(g)


def _rms(a, real):
    """Root mean square of a [T, ·] over the real positions."""
    n = jnp.sum(real) * a.shape[1]
    return jnp.sqrt(jnp.sum(jnp.where(real[:, None], a * a, 0.0)) / n)


def relu2(a):
    return jnp.square(jnp.maximum(a, 0.0))


def rotate(x, theta):
    """x [T, heads, dh], position t at row t: rotate-half rotary (a planted
    fault: this model rotates nothing)."""
    T, _, dh = x.shape
    half = dh // 2
    inv = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * (-math.log(theta) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def mamba(w, u, length, sz, faults=()):
    """u [T, D] → (the mixer's output [T, D], S after position ``length − 1``
    [H, N, P], the last K − 1 real inputs of the convolution [K − 1, ·], the
    real positions' [smallest step, largest step, weakest decay exp(Δ A)])."""
    Di, H, P, G, N, K = (sz[k] for k in ("Di", "H", "P", "G", "N", "K"))
    T = u.shape[0]
    bw = G * N
    p = u @ f32(w["in_proj"])
    z, a, dt = p[:, :Di], p[:, Di:2 * Di + 2 * bw], p[:, 2 * Di + 2 * bw:]
    padded = jnp.concatenate([jnp.zeros((K - 1, a.shape[1]), jnp.float32), a])
    tail = jax.lax.dynamic_slice_in_dim(padded, length, K - 1, axis=0)
    conv = f32(w["conv_b"])[None, :]
    for k in range(K):
        conv = conv + f32(w["conv_w"][k])[None, :] * padded[k:k + T]
    c = jax.nn.silu(conv)
    x = c[:, :Di].reshape(T, G, H // G, P)
    B = c[:, Di:Di + bw].reshape(T, G, N)
    C = c[:, Di + bw:].reshape(T, G, N)
    delta = jax.nn.softplus(dt + f32(w["dt_bias"]))             # [T, H]
    A = -jnp.exp(f32(w["a_log"]))                               # [H]
    real = (jnp.arange(T) < length)[:, None]
    steps = jnp.stack([jnp.min(jnp.where(real, delta, jnp.inf)),
                       jnp.max(jnp.where(real, delta, 0.0)),
                       jnp.max(jnp.where(real, jnp.exp(delta * A), 0.0))])
    delta = jnp.where(real, delta, 0.0).reshape(T, G, H // G)
    Ag = A.reshape(G, H // G)
    # the nearest precision below the stated float32: the step size and the
    # decay carried with bfloat16's 8 bits (a lower-precision control)
    low = (lambda a: jax.lax.reduce_precision(a, 8, 7)) \
        if "bf16_step" in faults else (lambda a: a)
    delta = low(delta)

    def step(S, row):       # S [G, H/G, N, P]; a group's heads share B and C
        xt, dt_t, bt, ct = row
        S = low(jnp.exp(dt_t * Ag))[:, :, None, None] * S \
            + bt[:, None, :, None] * (dt_t[:, :, None] * xt)[:, :, None, :]
        return S, jnp.sum(S * ct[:, None, :, None], axis=2)

    S, y = jax.lax.scan(step, jnp.zeros((G, H // G, N, P), jnp.float32),
                        (x, delta, B, C))
    if "no_skip" not in faults:
        y = y + f32(w["d_skip"]).reshape(1, G, H // G, 1) * x
    groups = 1 if "one_norm_group" in faults else G
    y, gate = y.reshape(T, groups, -1), jax.nn.silu(z).reshape(T, groups, -1)

    def norm(v):
        return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                                 + sz["eps"])

    if "norm_before_gate" in faults:
        y = (norm(y).reshape(T, Di) * f32(w["ssm_norm"])) * gate.reshape(T, Di)
    else:
        y = norm(y * gate).reshape(T, Di) * f32(w["ssm_norm"])
    return y @ f32(w["out_proj"]), S.reshape(H, N, P), tail, steps


def attention(w, u, length, sz, faults=()):
    """u [T, D] → (the mixer's output [T, D], the standard deviation of the
    visible scores of the real queries, the cache rows ``[k | v]`` of the last
    :data:`POOL_ROWS` real positions — what the pool holds of them; a shorter
    sequence's first rows are repeated)."""
    nh, nkv, dh = sz["nh"], sz["nkv"], sz["dh"]
    T = u.shape[0]
    qkv = u @ f32(w["wqkv"])
    q = qkv[:, :nh * dh].reshape(T, nh, dh)
    k = qkv[:, nh * dh:(nh + nkv) * dh].reshape(T, nkv, dh)
    v = qkv[:, (nh + nkv) * dh:].reshape(T, nkv, dh)
    if "rotate_attention" in faults:
        q, k = rotate(q, sz["theta"]), rotate(k, sz["theta"])
    kept = jnp.concatenate([k.reshape(T, -1), v.reshape(T, -1)], axis=-1)[
        jnp.maximum(length - POOL_ROWS + jnp.arange(POOL_ROWS), 0)]
    keys = jnp.arange(T)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK, axis=0)
        qb = qb.reshape(Q_BLOCK, nkv, nh // nkv, dh)
        t = start + jnp.arange(Q_BLOCK)
        keep = keys[None, :] <= t[:, None]
        s = jnp.einsum("tgrd,jgd->grtj", qb, k) / math.sqrt(dh)
        seen = jnp.logical_and(keep, (t < length)[:, None])
        moments = jnp.stack([jnp.sum(seen) * nh,
                             jnp.sum(jnp.where(seen, s, 0.0)),
                             jnp.sum(jnp.where(seen, s * s, 0.0))])
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("grtj,jgd->tgrd", p, v), moments

    pad = -T % Q_BLOCK
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    o, moments = jax.lax.map(block, jnp.arange(0, T + pad, Q_BLOCK))
    n, s1, s2 = moments.sum(0)
    std = jnp.sqrt(jnp.maximum(s2 / n - (s1 / n) ** 2, 0.0))
    o = o.reshape(T + pad, nh * dh)[:T]
    return o @ f32(w["wo"]), std, kept


def route(r, bias, forced, sz):
    """Router logits r [T, Er] → (the experts used [T, K], their weights [T,
    K], the reference's OWN choice [T, K], whether the bias turned it [T])."""
    s = jax.nn.sigmoid(r)
    _, own = jax.lax.top_k(s + f32(bias), sz["k"])
    _, plain = jax.lax.top_k(s, sz["k"])
    turned = jnp.any(jnp.sort(own, -1) != jnp.sort(plain, -1), axis=-1)
    used = own if forced is None else forced
    chosen = jnp.take_along_axis(s, used, axis=-1)
    weights = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) \
        * sz["scale"]
    return used, weights, own, turned


def unit_act(faults=()):
    """An expert's activation: ``relu²`` — or, planted, ``silu(a) ⊙ a``."""
    return (lambda a: jax.nn.silu(a) * a) if "silu_unit" in faults else relu2


def held_experts(up, down, at, h, used, weights, first: int, faults=()):
    """``Σ w_k · expert_k(h)`` [T, D] over the HELD experts: every one of them
    over every position, weighed by zero where it was not chosen; a choice of
    an expert that is not held adds nothing.  ``up`` / ``down`` are the stacks
    as they lie ([Le, E, F, D]; expert ``e`` of them is the router's ``first +
    e``), ``at`` the layer's index into them."""
    E = up.shape[1]
    act = unit_act(faults)

    def one(stack, e):
        return f32(jax.lax.dynamic_slice(
            stack, (jnp.int32(at), jnp.int32(e), jnp.int32(0), jnp.int32(0)),
            (1, 1) + stack.shape[-2:]).reshape(stack.shape[-2:]))

    def body(e, acc):
        share = jnp.sum(jnp.where(used == first + e, weights, 0.0), -1,
                        keepdims=True)
        return acc + share * (act(h @ one(up, e).T) @ one(down, e))

    return jax.lax.fori_loop(0, E, body, jnp.zeros_like(h))


@functools.lru_cache(maxsize=None)
def _fns(frozen: tuple, faults: frozenset):
    sz = dict(frozen)
    eps = sz["eps"]
    act = unit_act(faults)

    @jax.jit
    def mamba_layer(w, x, length):
        real = jnp.arange(x.shape[0]) < length
        out, S, tail, steps = mamba(w, rms_norm(x, w["ln"], eps), length, sz,
                                    faults)
        return x + out, S, tail, steps, _rms(out, real) / _rms(x, real)

    @jax.jit
    def attn_layer(w, x, length):
        real = jnp.arange(x.shape[0]) < length
        out, std, kept = attention(w, rms_norm(x, w["ln"], eps), length, sz,
                                   faults)
        return x + out, std, _rms(out, real) / _rms(x, real), kept

    @jax.jit
    def routed(w, up, down, x, length, forced, at):
        """The router and the held experts of expert layer ``at``."""
        real = jnp.arange(x.shape[0]) < length
        u = rms_norm(x, w["ln"], eps)
        used, weights, own, turned = route(u @ f32(w["router"]),
                                           w["router_bias"], forced, sz)
        out = held_experts(up, down, at, u, used, weights, sz["first"],
                           faults)
        n = jnp.sum(real)
        held = (own >= sz["first"]) & (own < sz["first"] + sz["E"])
        readings = jnp.stack([
            jnp.sum(jnp.where(real, weights.max(-1), 0.0)) / n / sz["scale"],
            jnp.sum(jnp.where(real, turned, False)) / n,
            jnp.sum(jnp.where(real[:, None], held, False)) / (n * sz["k"])])
        return u, out, own, readings

    @jax.jit
    def shared_block(s_up, s_down, u, b):
        n = s_up.shape[1] // SHARED_BLOCKS
        h = u @ f32(jax.lax.dynamic_slice_in_dim(s_up, b * n, n, axis=1))
        return act(h) @ f32(jax.lax.dynamic_slice_in_dim(s_down, b * n, n,
                                                         axis=0))

    @jax.jit
    def close(x, out, length):
        real = jnp.arange(x.shape[0]) < length
        return x + out, _rms(out, real) / _rms(x, real)

    @jax.jit
    def embed(emb, tokens):
        return f32(emb[tokens])

    @jax.jit
    def head_block(rows, g, x, at):
        return rms_norm(x[at], g, eps) @ f32(rows).T

    return mamba_layer, attn_layer, routed, shared_block, close, embed, \
        head_block


def layer_weights(params: dict, kind: str, at: int, but=()) -> dict:
    """Layer ``at`` of a kind's stacks (``M`` / ``E`` / ``*``), less the
    leaves in ``but``."""
    prefix = KINDS[kind]
    return {k[len(prefix):]: v[at] for k, v in params.items()
            if k.startswith(prefix) and k[len(prefix):] not in but}


def forward(params: dict, cfg: dict, tokens, length, out_positions,
            forced=None, faults=()):
    """tokens [T] int32 (positions from ``length`` on are padding),
    out_positions [n] int32 (each below ``length``) → (logits [n, V] float32,
    the reference's own chosen experts [Le, T, K], a dict: ``states`` [Lm, H,
    N, P] and ``tails`` [Lm, K − 1, ·] after position ``length − 1``,
    ``expert_out`` [Le, n, D] (each expert layer's output at the judged
    positions) and under :data:`STATS`' names each layer's own readings of
    its kind — a mixer's output over the residual's it is added to (root mean
    square over the real positions), the visible attention scores' standard
    deviation, the mean largest routing weight over the scaling factor, the
    share of positions whose choice the bias turns, the share of choices that
    are held, the real positions' smallest and largest step size and the
    weakest decay ``exp(Δ A)``)."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown planted faults {sorted(unknown)}")
    sz = sizes(cfg)
    mamba_layer, attn_layer, routed, shared_block, close, embed, head_block \
        = _fns(tuple(sorted(sz.items())), frozenset(faults))
    length = jnp.int32(length)
    at_rows = jnp.asarray(out_positions)
    got = {k: [] for k in STATS + ("states", "tails", "expert_out",
                                   "pool_rows")}
    own_ids = []
    seen = dict.fromkeys(KINDS, 0)
    with jax.default_matmul_precision("highest"):
        x = embed(params["emb"], jnp.asarray(tokens))
        for kind in sz["pattern"]:
            at = seen[kind]
            seen[kind] += 1
            if kind == "M":
                x, S, tail, steps, r = mamba_layer(
                    layer_weights(params, kind, at), x, length)
                got["states"].append(S)
                got["tails"].append(tail)
                got["mamba_rms"].append(r)
                for name, v in zip(("step_size_min", "step_size_max",
                                    "decay_weakest"), steps):
                    got[name].append(v)
            elif kind == "*":
                x, std, r, kept = attn_layer(layer_weights(params, kind, at),
                                             x, length)
                got["pool_rows"].append(kept)
                got["attn_rms"].append(r)
                got["attn_logit_std"].append(std)
            else:
                w = layer_weights(params, kind, at, but=("e_up", "e_down"))
                u, out, own, readings = routed(
                    w, params["e.e_up"], params["e.e_down"], x, length,
                    None if forced is None else jnp.asarray(forced[at]),
                    jnp.int32(at))
                out = out + sum(shared_block(w["s_up"], w["s_down"], u,
                                             jnp.int32(b))
                                for b in range(SHARED_BLOCKS))
                x, r = close(x, out, length)
                own_ids.append(own)
                got["expert_out"].append(out[at_rows])
                got["experts_rms"].append(r)
                for name, v in zip(("top1_weight", "bias_turns_share",
                                    "held_choice_share"), readings):
                    got[name].append(v)
        logits = jnp.concatenate(
            [head_block(params["head"][r:r + HEAD_ROWS], params["final_norm"],
                        x, at_rows)
             for r in range(0, params["head"].shape[0], HEAD_ROWS)], axis=1)
    return logits, jnp.stack(own_ids), {k: jnp.stack(v)
                                        for k, v in got.items()}


def router_scores(router, u):
    """The router's logits of the rows u [n, D] (any float dtype, widened
    here) at the highest precision: what the program's own product must
    equal."""
    with jax.default_matmul_precision("highest"):
        return f32(u) @ f32(router)


def route_weights(cfg: dict, r, bias, used):
    """The equations' routing weights [n, K] of the experts ``used`` from
    router logits r [n, Er]."""
    return route(f32(r), bias, jnp.asarray(used), sizes(cfg))[1]
