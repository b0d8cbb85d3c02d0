"""Plain reference of the LFM2 mixture-of-experts stack (``model_type``
lfm2_moe; LiquidAI/LFM2-24B-A2B's ``config.json``): one full causal forward of
one sequence in float32 ``jax.numpy`` at the highest matmul precision — every
position through every layer, dense masked attention a block of queries at a
time, the experts one after another over every position, no cache, no
batching, no kernel.  Nothing is imported from the program.

``cfg`` is the published ``config.json`` as a dict.  ``RMS(x) = x ·
rsqrt(mean(x²) + norm_eps) · g``.  For layer ``l`` and a token's residual
``h``: ``h ← h + Op_l(RMS_op(h))``, then ``h ← h + FF_l(RMS_ffn(h))``, where

- ``Op_l`` is the *gated short convolution* where ``layer_types[l]`` is
  ``conv``: ``[B | C | x] = u W_in`` (split in that order), ``z = B ⊙ x``,
  ``c_t = Σ_{k < K} w_k ⊙ z_{t−(K−1)+k}`` (``K = conv_L_cache``; depthwise,
  causal, zeros before the sequence, no bias, no activation), ``Op(u) = (C ⊙
  c) W_out``;
- ``Op_l`` is *attention* where it is ``full_attention``: ``q = u W_q``, ``k =
  u W_k``, ``v = u W_v``; ``q`` and ``k`` each through an RMS norm over the
  head's lanes (one gain vector each a layer), THEN rotate-half rotary
  positions over the whole head at ``rope_parameters.rope_theta``; scores ``q
  kᵀ / √head_dim``, one causal softmax a head; ``o W_o``;
- ``FF_l`` is the dense SwiGLU ``(silu(u W_1) ⊙ u W_3) W_2`` for ``l <
  num_dense_layers``, and after that the expert block: ``s = sigmoid(u W_r)``;
  the ``num_experts_per_tok`` experts with the largest ``s + b`` (``b`` the
  layer's selection bias, where ``use_expert_bias``), weighed by ``s`` ITSELF:
  ``w_i = s_i / (Σ_chosen s_j + 1e-6)`` where ``norm_topk_prob``, times
  ``routed_scaling_factor``; ``Σ_i w_i (silu(u Wg_i) ⊙ u Wu_i) Wd_i``;

a final RMS norm, and the head is the embedding transposed.

Departures from the published code, each at its line below: the experts are
looped over ALL positions with a weight of zero where an expert was not
chosen (the published code gathers an expert's tokens; the sum is the same);
the dense unit is computed a slice of its width at a time and the head a block
of vocabulary rows at a time (the same sums); ``forced`` hands the layer the
experts to use (the program's own choices, so that a near tie turned by bf16
activations does not count as an error of everything downstream; the
reference's OWN choices are returned beside, for the comparison that judges
the routing); ``faults`` plants a mechanism that the model does NOT have, for
the controls that must fail.

Weights are the program's name → array dict, any float dtype: ``emb`` [V, D],
``final_norm`` [D]; the dense layers stacked as ``d.*`` [nd, …], the attention
layers as ``pa.*`` [P, …] and the periods' convolution layers as ``pc.*`` [P,
period − 1, …] (layer ``l ≥ nd`` is ``pa[(l − nd) // period]`` where ``(l −
nd) mod period`` is 0, else ``pc[(l − nd) // period, (l − nd) mod period −
1]``): ``ln1``, ``ln2`` [D]; ``conv_in`` [D, 3D], ``conv_w`` [K, D] (``w[K −
1]`` weighs the current position), ``conv_out`` [D, D]; ``wqkv`` [D, (nh +
2·nkv)·dh] (``[q | k | v]``), ``q_norm``, ``k_norm`` [dh], ``wo`` [nh·dh, D];
``w1``, ``w3`` [D, F], ``w2`` [F, D]; ``router`` [D, E], ``router_bias`` [E],
``e_gate``, ``e_up`` [E, D, Fe], ``e_down`` [E, Fe, D].  They are widened to
float32 ONE MATRIX AT A TIME — a layer is several jitted calls, the experts
one expert a trip of a loop — because the check runs beside a live engine
that holds most of the chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 128           # queries a block of the dense attention
HEAD_ROWS = 16384       # vocabulary rows a block of the head
FF_SLICE = 2048         # columns of the dense unit a trip, at most
ROUTE_EPS = 1e-6
# the reference's own readings, by name (``forward``'s third result): each a
# list over the layers it is taken in
STATS = ("conv_rms", "attn_rms", "ffn_rms", "attn_logit_std", "top1_weight",
         "bias_turns_share")
# mechanisms the model does NOT have (the controls)
FAULTS = ("bias_in_weights", "no_qk_norm")


def f32(a):
    return jnp.asarray(a, jnp.float32)


def sizes(cfg: dict) -> dict:
    L, nd = int(cfg["num_hidden_layers"]), int(cfg["num_dense_layers"])
    types = tuple(str(t) for t in cfg["layer_types"][:L])
    rest = types[nd:]
    period = rest.index("full_attention", 1) \
        if "full_attention" in rest[1:] else len(rest)
    nh = int(cfg["num_attention_heads"])
    return {"D": int(cfg["hidden_size"]), "L": L, "nd": nd, "types": types,
            "period": period, "E": int(cfg["num_experts"]),
            "K": int(cfg["num_experts_per_tok"]), "nh": nh,
            "nkv": int(cfg["num_key_value_heads"]),
            "dh": int(cfg.get("head_dim") or int(cfg["hidden_size"]) // nh),
            "eps": float(cfg["norm_eps"]),
            "theta": float(cfg["rope_parameters"]["rope_theta"]),
            "taps": int(cfg["conv_L_cache"]),
            "renorm": bool(cfg["norm_topk_prob"]),
            "bias": bool(cfg["use_expert_bias"]),
            "scale": float(cfg["routed_scaling_factor"])}


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(g)


def _rms(a, real):
    n = jnp.sum(real) * a.shape[-1]
    return jnp.sqrt(jnp.sum(jnp.where(real[:, None], a * a, 0.0)) / n)


def rotate(x, theta):
    """x [T, heads, dh] at positions 0 .. T − 1: rotate-half pairing (lane i
    with lane i + dh/2), frequencies theta^(−2i/dh)."""
    T, half = x.shape[0], x.shape[-1] // 2
    inv = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * (-math.log(theta) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def short_conv(w, u, sz):
    """u [T, D] → (C ⊙ conv(B ⊙ x)) W_out [T, D]."""
    D, K, T = sz["D"], sz["taps"], u.shape[0]
    bcx = u @ f32(w["conv_in"])
    gate_b, gate_c, x = bcx[:, :D], bcx[:, D:2 * D], bcx[:, 2 * D:]
    z = jnp.concatenate([jnp.zeros((K - 1, D), jnp.float32), gate_b * x])
    taps = f32(w["conv_w"])
    c = sum(taps[k][None, :] * z[k:k + T] for k in range(K))
    return (gate_c * c) @ f32(w["conv_out"])


def attention(w, u, length, sz, qk_norm: bool):
    """u [T, D] → (o W_o [T, D], the standard deviation of the visible
    scores of the real queries).  ``T`` is a multiple of :data:`Q_BLOCK` or
    below it."""
    T = u.shape[0]
    nh, nkv, dh = sz["nh"], sz["nkv"], sz["dh"]
    qkv = u @ f32(w["wqkv"])
    q = qkv[:, :nh * dh].reshape(T, nh, dh)
    k = qkv[:, nh * dh:(nh + nkv) * dh].reshape(T, nkv, dh)
    v = qkv[:, (nh + nkv) * dh:].reshape(T, nkv, dh)
    if qk_norm:     # over a head's lanes, BEFORE the rotation
        q = rms_norm(q, w["q_norm"], sz["eps"])
        k = rms_norm(k, w["k_norm"], sz["eps"])
    q, k = rotate(q, sz["theta"]), rotate(k, sz["theta"])
    qb = min(T, Q_BLOCK)
    q = q.reshape(T // qb, qb, nkv, nh // nkv, dh)
    keys = jnp.arange(T)

    def block(args):
        qs, first = args
        t = first + jnp.arange(qb)
        s = jnp.einsum("qgrd,jgd->grqj", qs, k) / math.sqrt(dh)
        keep = keys[None, :] <= t[:, None]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        seen = keep & (t[:, None] < length)
        n = jnp.sum(seen) * nh
        tot = jnp.sum(jnp.where(seen, s, 0.0))
        sq = jnp.sum(jnp.where(seen, s * s, 0.0))
        return jnp.einsum("grqj,jgd->qgrd", p, v), jnp.stack([n, tot, sq])

    o, acc = jax.lax.map(block, (q, jnp.arange(T // qb) * qb))
    n, tot, sq = jnp.sum(acc, 0)
    std = jnp.sqrt(jnp.maximum(sq / n - (tot / n) ** 2, 0.0))
    return o.reshape(T, nh * dh) @ f32(w["wo"]), std


def dense_ffn(w, u):
    """``(silu(u W_1) ⊙ u W_3) W_2``, a slice of the width at a time (a
    departure in the order of the sums only)."""
    F = w["w1"].shape[-1]
    step = max(d for d in range(1, min(F, FF_SLICE) + 1) if F % d == 0)

    def body(i, acc):
        def cols(m):
            return f32(jax.lax.dynamic_slice_in_dim(m, i * step, step, 1))
        a = jax.nn.silu(u @ cols(w["w1"])) * (u @ cols(w["w3"]))
        return acc + a @ f32(jax.lax.dynamic_slice_in_dim(
            w["w2"], i * step, step, 0))

    return jax.lax.fori_loop(0, F // step, body, jnp.zeros_like(u))


def route(r, bias, forced, sz, bias_in_weights: bool):
    """Router logits r [T, E] → (the reference's own K experts [T, K], the
    experts used [T, K] — ``forced`` where given —, their weights [T, K],
    whether the bias turned the token's choice [T])."""
    K = sz["K"]
    s = jax.nn.sigmoid(r)
    pick = s + f32(bias) if sz["bias"] else s
    _, own = jax.lax.top_k(pick, K)         # the lower index on a tie
    _, plain = jax.lax.top_k(s, K)
    turned = (jnp.sort(own, -1) != jnp.sort(plain, -1)).any(-1)
    used = own if forced is None else forced
    # the bias chooses and does not weigh (the planted fault: it weighs too)
    chosen = jnp.take_along_axis(pick if bias_in_weights else s, used, 1)
    if sz["renorm"]:
        chosen = chosen / (jnp.sum(chosen, -1, keepdims=True) + ROUTE_EPS)
    return own, used, chosen * sz["scale"], turned


def experts(gate, up, down, at: tuple, h, used, weights):
    """``Σ_k w_k · expert_k(h)`` [T, D]: every expert over every position,
    weighed by zero where it was not chosen (a departure: see the module's
    doc).  ``gate`` / ``up`` / ``down`` are the stacks as they lie; ``at`` is
    the layer's index into their leading axes."""
    E = gate.shape[-3]
    lead = tuple(jnp.int32(i) for i in at)

    def one(stack, e):
        tail = stack.shape[-2:]
        got = jax.lax.dynamic_slice(
            stack, lead + (jnp.int32(e), jnp.int32(0), jnp.int32(0)),
            (1,) * (len(at) + 1) + tail)
        return f32(got.reshape(tail))

    def body(e, acc):
        share = jnp.sum(jnp.where(used == e, weights, 0.0), -1, keepdims=True)
        y = (jax.nn.silu(h @ one(gate, e)) * (h @ one(up, e))) @ one(down, e)
        return acc + share * y

    return jax.lax.fori_loop(0, E, body, jnp.zeros_like(h))


@functools.lru_cache(maxsize=None)
def _fns(frozen: tuple, faults: frozenset):
    sz = dict(frozen)
    eps = sz["eps"]

    def real_of(x, length):
        return jnp.arange(x.shape[0]) < length

    @jax.jit
    def conv(w, x, length):
        out = short_conv(w, rms_norm(x, w["ln1"], eps), sz)
        real = real_of(x, length)
        return x + out, _rms(out, real) / _rms(x, real)

    @jax.jit
    def attn(w, x, length):
        out, std = attention(w, rms_norm(x, w["ln1"], eps), length, sz,
                             "no_qk_norm" not in faults)
        real = real_of(x, length)
        return x + out, _rms(out, real) / _rms(x, real), std

    @jax.jit
    def dense(w, x, length):
        y = dense_ffn(w, rms_norm(x, w["ln2"], eps))
        real = real_of(x, length)
        return x + y, _rms(y, real) / _rms(x, real)

    @functools.partial(jax.jit, static_argnums=(3,))
    def moe(w, stacks, x, at, forced, length):
        h = rms_norm(x, w["ln2"], eps)
        own, used, weights, turned = route(
            h @ f32(w["router"]), w["router_bias"], forced, sz,
            "bias_in_weights" in faults)
        y = experts(*stacks, at, h, used, weights)
        real = real_of(x, length)
        n = jnp.sum(real)
        top1 = jnp.sum(jnp.where(real, jnp.max(weights, -1), 0.0)) / n
        return (x + y, own, _rms(y, real) / _rms(x, real), top1,
                jnp.sum(jnp.where(real, turned, False)) / n)

    @jax.jit
    def embed(emb, tokens):
        return f32(emb[tokens])

    @jax.jit
    def head_block(rows, g, x, at):
        return rms_norm(x[at], g, eps) @ f32(rows).T

    return conv, attn, dense, moe, embed, head_block


def layer_weights(params: dict, sz: dict, l: int):
    """(the layer's small tensors by leaf name, its three expert stacks as
    they lie — None for a dense layer —, the layer's index into the stacks'
    leading axes)."""
    if l < sz["nd"]:
        prefix, at = "d.", (l,)
    else:
        p, j = divmod(l - sz["nd"], sz["period"])
        prefix, at = ("pa.", (p,)) if j == 0 else ("pc.", (p, j - 1))
    n = len(prefix)
    w = {k[n:]: v for k, v in params.items() if k.startswith(prefix)}
    stacks = None if l < sz["nd"] else tuple(
        w.pop(k) for k in ("e_gate", "e_up", "e_down"))
    return {k: v[at] for k, v in w.items()}, stacks, at


def forward(params: dict, cfg: dict, tokens, length, out_positions,
            forced=None, faults=()):
    """tokens [T] int32 (positions from ``length`` on are padding; a ``T``
    past :data:`Q_BLOCK` is padded on to a multiple of it), out_positions [n]
    int32 (each below ``length``), forced [Le, T, K] int32 or None (the expert
    layers', in layer order) → (logits [n, V] float32, the reference's own
    chosen experts [Le, T, K], its own readings {name of :data:`STATS`: one
    number a layer it is taken in}: a branch's root mean square over the
    residual's it is added to — the convolution's, the attention's, the
    feed-forward unit's —, the standard deviation of the visible attention
    scores, the mean largest routing weight, the share of real positions whose
    chosen set the selection bias turned)."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown planted faults {sorted(unknown)}")
    sz = sizes(cfg)
    conv, attn, dense, moe, embed, head_block = _fns(
        tuple(sorted(sz.items())), frozenset(faults))
    length = jnp.int32(length)
    own_ids, stats = [], {name: [] for name in STATS}
    tokens = jnp.asarray(tokens)
    T = tokens.shape[0]
    pad = -T % Q_BLOCK if T > Q_BLOCK else 0
    tokens = jnp.pad(tokens, (0, pad))
    if forced is not None:
        forced = jnp.pad(jnp.asarray(forced), ((0, 0), (0, pad), (0, 0)))
    with jax.default_matmul_precision("highest"):
        x = embed(params["emb"], tokens)
        for l in range(sz["L"]):
            w, stacks, at = layer_weights(params, sz, l)
            if sz["types"][l] == "conv":
                x, r = conv(w, x, length)
                stats["conv_rms"].append(r)
            else:
                x, r, std = attn(w, x, length)
                stats["attn_rms"].append(r)
                stats["attn_logit_std"].append(std)
            if stacks is None:
                x, r = dense(w, x, length)
            else:
                e = l - sz["nd"]
                x, own, r, top1, turned = moe(
                    w, stacks, x, at, None if forced is None else forced[e],
                    length)
                own_ids.append(own[:T])
                stats["top1_weight"].append(top1)
                stats["bias_turns_share"].append(turned)
            stats["ffn_rms"].append(r)
        at = jnp.asarray(out_positions)
        logits = jnp.concatenate(
            [head_block(params["emb"][r:r + HEAD_ROWS], params["final_norm"],
                        x, at)
             for r in range(0, params["emb"].shape[0], HEAD_ROWS)], axis=1)
    return logits, jnp.stack(own_ids), \
        {k: jnp.stack(v) for k, v in stats.items()}


def router_scores(router, u):
    """The router alone on given rows: u [n, D] (the program's own normed
    inputs) → logits [n, E] float32 at the highest precision."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda w, u: f32(u) @ f32(w))(router, u)


def route_weights(cfg: dict, r, bias, used, faults=()):
    """The routing alone on given router logits: r [n, E], the layer's
    selection bias [E], the experts used [n, K] → their weights [n, K]
    float32 by the equations above."""
    return route(f32(r), bias, jnp.asarray(used), sizes(cfg),
                 "bias_in_weights" in faults)[2]
