"""Plain reference of the Kimi-Linear stack (``model_type`` kimi_linear;
moonshotai/Kimi-Linear-48B-A3B-Instruct's ``config.json``; the Kimi Linear
technical report, arXiv:2510.26692): one full causal forward of one sequence
in float32 ``jax.numpy`` at the highest matmul precision — every position
through every layer, the gated delta rule ONE POSITION AT A TIME (a
``lax.scan``: no chunks), dense masked attention a block of queries at a
time, the held experts one after another over every position, no cache, no
batching, no kernel.  Nothing is imported from the program.

``cfg`` is the published ``config.json`` as a dict (plus ``router_experts``
and ``first_expert`` where a share of the experts is held, below).  ``RMS(x)
= x · rsqrt(mean(x²) + rms_norm_eps) · g``.  For layer ``l`` (``linear_attn_
config`` counts from 1) and a token's residual ``x``: ``x ← x + Mixer_l(RMS(
x))``, then ``x ← x + FF_l(RMS(x))``, where

- ``Mixer_l`` is **KDA** where ``l`` is in ``kda_layers``, ``H`` heads of
  ``K`` channels: ``q~, k~, v = SiLU(conv(u W_q)), SiLU(conv(u W_k)),
  SiLU(conv(u W_v))`` (depthwise, causal, ``short_conv_kernel_size`` taps,
  zeros before the sequence, no bias); ``q = q~ / ‖q~‖ · K^-½``, ``k = k~ /
  ‖k~‖`` (``‖x‖ = √(Σ x² + 1e-6)``); ``a = −exp(A_log_h) · softplus(W_f2
  (W_f1 u) + dt_bias)`` a channel; ``b = sigmoid(W_b u)`` a head; with ``S``
  [K (key), K (value)] a head from zeros: ``S' = Diag(exp a_t) S_{t−1}``,
  ``S_t = S' + b_t k_t (v_t − S'ᵀ k_t)ᵀ``, ``o_t = S_tᵀ q_t``; ``y = W_o
  [RMSNorm_K(o_t) ⊙ sigmoid(W_g2 (W_g1 u))]``;
- ``Mixer_l`` is **latent attention** where ``l`` is in ``full_attn_layers``:
  ``q = u W_q`` (full rank), ``[c~ | k_pe] = u W_kva``, ``c = RMS(c~)``,
  ``[k_nope | v] = c W_kvb`` a head, the key ``[k_nope | k_pe]`` with
  ``k_pe`` shared by the heads and **not rotated** (``mla_use_nope``), scores
  ``q kᵀ · (nope + rope)^-½``, one causal softmax a head, ``o W_o``;
- ``FF_l`` is the dense SwiGLU for ``l ≤ first_k_dense_replace`` and after
  that ``s = sigmoid(u W_r)`` over ALL the router's experts; the
  ``num_experts_per_token`` with the largest ``s + bias``, weighed by ``s``
  itself: ``w_i = s_i / Σ_chosen s_j`` (``moe_renormalize``) times
  ``routed_scaling_factor``; ``Σ_i w_i SwiGLU_i(u)`` plus one shared SwiGLU
  of ``num_shared_experts`` experts' width on every token;

a final RMS norm and an untied head.

**A share.**  ``num_experts`` counts the experts whose matrices ``params``
HOLDS, ``router_experts`` (absent: the same) is the router's width and
``first_expert`` (absent: 0) the first held.  The router, its choice and the
renormalisation are over all of them; the sum runs over the held experts
only, and what the others would add is left out.

Departures from the published code, each at its line below: the experts are
looped over ALL positions with a weight of zero where an expert was not
chosen (the sum is the same); the dense unit is computed a slice of its width
at a time and the head a block of vocabulary rows at a time (the same sums);
``forced`` hands the layer the experts to use (the program's own choices, so
that a near tie turned by bf16 activations does not count as an error of
everything downstream; the reference's OWN choices are returned beside);
``faults`` plants a mechanism that the model does NOT have, for the controls
that must fail.

Weights are the program's name → array dict, any float dtype: ``emb`` [V, D],
``final_norm`` [D], ``head`` [D, V]; the dense layers stacked as ``d.*`` [nd,
…] and the layers at place ``j`` of a period as ``p<j>.*`` [P, …] (layer ``l ≥
nd``, counted from 0, is ``p<(l − nd) mod period>[(l − nd) // period]``):
``ln1``, ``ln2`` [D]; KDA ``wqkv`` [D, 3·H·K] (``[q | k | v]``), ``conv_w``
[taps, 3·H·K] (``w[taps − 1]`` weighs the current position), ``wf1`` [D, K],
``wf2`` [K, H·K], ``dt_bias`` [H·K], ``a_log`` [H], ``wb`` [D, H], ``wg1``,
``wg2``, ``o_norm`` [K], ``wo`` [H·K, D]; latent ``wq``, ``wkva``,
``kv_norm``, ``wkvb``, ``wo``; ``w_gate``, ``w_up`` [D, F], ``w_down`` [F,
D]; ``router`` [D, Er], ``router_bias`` [Er], ``e_gate``, ``e_up`` [E, D,
Fe], ``e_down`` [E, Fe, D], ``s_gate``, ``s_up`` [D, Fs], ``s_down`` [Fs, D].
They are widened to float32 ONE MATRIX AT A TIME, because the check runs
beside a live engine that holds most of the chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 128           # queries a block of the dense attention
HEAD_ROWS = 16384       # vocabulary rows a block of the head
FF_SLICE = 2304         # columns of the dense unit a trip, at most
L2_EPS = 1e-6
# the reference's own readings, by name (``forward``'s third result): each a
# list over the layers it is taken in
STATS = ("kda_rms", "mla_rms", "ffn_rms", "attn_logit_std", "top1_weight",
         "bias_turns_share", "held_choice_share", "decay_strongest",
         "decay_weakest", "delta_share")
# mechanisms the model does NOT have (the controls)
FAULTS = ("scalar_decay", "no_delta", "no_qk_norm", "rotate_keys",
          "renorm_held")


def f32(a):
    return jnp.asarray(a, jnp.float32)


def sizes(cfg: dict) -> dict:
    L, nd = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    lac = cfg["linear_attn_config"]
    kda = set(lac["kda_layers"])
    kinds = tuple("kda" if i in kda else "mla" for i in range(1, L + 1))
    rest = kinds[nd:]
    period = next(p for p in range(1, len(rest) + 1) if len(rest) % p == 0
                  and rest == rest[:p] * (len(rest) // p))
    E = int(cfg["num_experts"])
    return {"D": int(cfg["hidden_size"]), "L": L, "nd": nd, "kinds": kinds,
            "period": period, "E": E,
            "Er": int(cfg.get("router_experts") or E),
            "first": int(cfg.get("first_expert") or 0),
            "K": int(cfg["num_experts_per_token"]),
            "H": int(lac["num_heads"]), "dk": int(lac["head_dim"]),
            "taps": int(lac["short_conv_kernel_size"]),
            "nh": int(cfg["num_attention_heads"]),
            "dn": int(cfg["qk_nope_head_dim"]),
            "dr": int(cfg["qk_rope_head_dim"]),
            "dv": int(cfg["v_head_dim"]), "r": int(cfg["kv_lora_rank"]),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg.get("rope_theta") or 10000.0),
            "renorm": bool(cfg["moe_renormalize"]),
            "scale": float(cfg["routed_scaling_factor"])}


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(g)


def _rms(a, real):
    n = jnp.sum(real) * a.shape[-1]
    return jnp.sqrt(jnp.sum(jnp.where(real[:, None], a * a, 0.0)) / n)


def l2_norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def rotate(x, theta):
    """x [T, heads, d] at positions 0 .. T − 1, rotate-half (a planted fault:
    this model's keys carry no position)."""
    T, half = x.shape[0], x.shape[-1] // 2
    inv = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * (-math.log(theta) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def delta_rule(q, k, v, a, b, delta: bool = True):
    """The recurrence of the module's doc, one position at a time: q, k, v, a
    [T, H, K], b [T, H] → (o [T, H, K], ‖S'ᵀ k‖ / ‖v‖ a position: what the
    delta correction takes off, the state after the last position [H, K
    (key), K (value)]).  ``delta=False`` drops the correction (a planted
    fault)."""
    H, K = q.shape[1:]

    def step(S, row):                       # S [H, K (key), K (value)]
        qt, kt, vt, at, bt = row
        S = jnp.exp(at)[:, :, None] * S
        pred = jnp.einsum("hkv,hk->hv", S, kt)
        d = vt - pred if delta else vt
        S = S + bt[:, None, None] * kt[:, :, None] * d[:, None, :]
        share = jnp.linalg.norm(pred) / jnp.linalg.norm(vt)
        return S, (jnp.einsum("hkv,hk->hv", S, qt), share)

    S, (o, share) = jax.lax.scan(step, jnp.zeros((H, K, K), jnp.float32),
                                 (q, k, v, a, b))
    return o, share, S


def kda(w, u, length, sz, faults=()):
    """u [T, D] → (the mixer's output [T, D], the strongest and the weakest
    log-decay a position a channel over the real positions, the delta
    correction's share, the state after the last REAL position [H, K (key), K
    (value)]: the padding after it neither decays nor writes — it lies after
    every real position, so no real output sees that)."""
    T, H, K = u.shape[0], sz["H"], sz["dk"]
    W, taps = H * K, sz["taps"]

    def branch(i):                          # q, k or v: a third of the width
        z = u @ f32(w["wqkv"][:, i * W:(i + 1) * W])
        z = jnp.concatenate([jnp.zeros((taps - 1, W), jnp.float32), z])
        tap = f32(w["conv_w"][:, i * W:(i + 1) * W])
        c = sum(tap[j][None, :] * z[j:j + T] for j in range(taps))
        return jax.nn.silu(c).reshape(T, H, K)

    q, k, v = branch(0), branch(1), branch(2)
    if "no_qk_norm" not in faults:          # a head's q and k are unit
        q, k = l2_norm(q), l2_norm(k)
    q = q * K ** -0.5
    a = -jnp.exp(f32(w["a_log"]))[None, :, None] * jax.nn.softplus(
        (u @ f32(w["wf1"])) @ f32(w["wf2"]) + f32(w["dt_bias"])
    ).reshape(T, H, K)
    if "scalar_decay" in faults:            # a decay a head, not a channel
        a = jnp.broadcast_to(jnp.mean(a, -1, keepdims=True), a.shape)
    b = jax.nn.sigmoid(u @ f32(w["wb"]))
    real = (jnp.arange(T) < length)
    seen = real[:, None, None]
    o, share, S = delta_rule(q, k, v, jnp.where(seen, a, 0.0),
                             jnp.where(real[:, None], b, 0.0),
                             "no_delta" not in faults)
    gate = jax.nn.sigmoid((u @ f32(w["wg1"])) @ f32(w["wg2"]))
    y = (rms_norm(o, w["o_norm"], sz["eps"]).reshape(T, W) * gate) \
        @ f32(w["wo"])
    return (y, jnp.min(jnp.where(seen, a, 0.0)),
            jnp.max(jnp.where(seen, a, -jnp.inf)),
            jnp.sum(jnp.where(real, share, 0.0)) / jnp.sum(real), S)


def latent_attention(w, u, length, sz, faults=()):
    """u [T, D] → (o W_o [T, D], the standard deviation of the visible scores
    of the real queries).  ``T`` is a multiple of :data:`Q_BLOCK` or below
    it."""
    T = u.shape[0]
    nh, dn, dr, dv, r = sz["nh"], sz["dn"], sz["dr"], sz["dv"], sz["r"]
    q = (u @ f32(w["wq"])).reshape(T, nh, dn + dr)
    kva = u @ f32(w["wkva"])
    c = rms_norm(kva[:, :r], w["kv_norm"], sz["eps"])
    kv = (c @ f32(w["wkvb"])).reshape(T, nh, dn + dv)
    k_pe, q_pe = kva[:, None, r:], q[..., dn:]
    if "rotate_keys" in faults:             # this model's keys are not
        k_pe, q_pe = rotate(k_pe, sz["theta"]), rotate(q_pe, sz["theta"])
    q = jnp.concatenate([q[..., :dn], q_pe], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (T, nh, dr))], -1)
    v = kv[..., dn:]
    qb = min(T, Q_BLOCK)
    q = q.reshape(T // qb, qb, nh, dn + dr)
    keys = jnp.arange(T)

    def block(args):
        qs, first = args
        t = first + jnp.arange(qb)
        s = jnp.einsum("qhd,jhd->hqj", qs, k) * (dn + dr) ** -0.5
        keep = keys[None, :] <= t[:, None]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        seen = keep & (t[:, None] < length)
        n = jnp.sum(seen) * nh
        tot = jnp.sum(jnp.where(seen, s, 0.0))
        sq = jnp.sum(jnp.where(seen, s * s, 0.0))
        return jnp.einsum("hqj,jhd->qhd", p, v), jnp.stack([n, tot, sq])

    o, acc = jax.lax.map(block, (q, jnp.arange(T // qb) * qb))
    n, tot, sq = jnp.sum(acc, 0)
    std = jnp.sqrt(jnp.maximum(sq / n - (tot / n) ** 2, 0.0))
    return o.reshape(T, nh * dv) @ f32(w["wo"]), std


def swiglu(u, gate, up, down):
    """``(silu(u W_g) ⊙ u W_u) W_d``, a slice of the width at a time (a
    departure in the order of the sums only)."""
    F = gate.shape[-1]
    step = max(d for d in range(1, min(F, FF_SLICE) + 1) if F % d == 0)

    def body(i, acc):
        def cols(m):
            return f32(jax.lax.dynamic_slice_in_dim(m, i * step, step, 1))
        a = jax.nn.silu(u @ cols(gate)) * (u @ cols(up))
        return acc + a @ f32(jax.lax.dynamic_slice_in_dim(
            down, i * step, step, 0))

    return jax.lax.fori_loop(0, F // step, body, jnp.zeros_like(u))


def route(r, bias, forced, sz, renorm_held: bool = False):
    """Router logits r [T, Er] → (the reference's own K experts [T, K], the
    experts used [T, K] — ``forced`` where given —, their weights [T, K],
    whether the bias turned the token's choice [T]).  Everything is over ALL
    the router's experts, held here or not."""
    K = sz["K"]
    s = jax.nn.sigmoid(r)
    _, own = jax.lax.top_k(s + f32(bias), K)    # the lower index on a tie
    _, plain = jax.lax.top_k(s, K)
    turned = (jnp.sort(own, -1) != jnp.sort(plain, -1)).any(-1)
    used = own if forced is None else forced
    chosen = jnp.take_along_axis(s, used, 1)    # the bias does not weigh
    if sz["renorm"]:
        total = chosen
        if renorm_held:     # the planted fault: over the held choices only
            held = (used >= sz["first"]) & (used < sz["first"] + sz["E"])
            total = jnp.where(held, chosen, 0.0)
        total = jnp.sum(total, -1, keepdims=True)
        chosen = chosen / jnp.where(total > 0, total, 1.0)
    return own, used, chosen * sz["scale"], turned


def experts(gate, up, down, at: tuple, h, used, weights, first: int):
    """``Σ w_k · expert_k(h)`` [T, D] over the HELD experts: every one of them
    over every position, weighed by zero where it was not chosen (a
    departure: see the module's doc); a choice of an expert that is not held
    adds nothing.  ``gate`` / ``up`` / ``down`` are the stacks as they lie
    (expert ``e`` of them is the router's ``first + e``); ``at`` is the
    layer's index into their leading axes."""
    E = gate.shape[-3]
    lead = tuple(jnp.int32(i) for i in at)

    def one(stack, e):
        tail = stack.shape[-2:]
        got = jax.lax.dynamic_slice(
            stack, lead + (jnp.int32(e), jnp.int32(0), jnp.int32(0)),
            (1,) * (len(at) + 1) + tail)
        return f32(got.reshape(tail))

    def body(e, acc):
        share = jnp.sum(jnp.where(used == first + e, weights, 0.0), -1,
                        keepdims=True)
        y = (jax.nn.silu(h @ one(gate, e)) * (h @ one(up, e))) @ one(down, e)
        return acc + share * y

    return jax.lax.fori_loop(0, E, body, jnp.zeros_like(h))


def expert_block(w, stacks, at: tuple, h, sz, forced=None, faults=()):
    """One expert layer's feed-forward half on normed rows h [T, D] → (the
    held routed experts' part [T, D], the shared expert's [T, D], the
    reference's own choices [T, K], the weights used [T, K], whether the bias
    turned the choice [T], the share of the choices used that are held)."""
    own, used, weights, turned = route(
        h @ f32(w["router"]), w["router_bias"], forced, sz,
        "renorm_held" in faults)
    routed = experts(*stacks, at, h, used, weights, sz["first"])
    shared = swiglu(h, w["s_gate"], w["s_up"], w["s_down"])
    held = (used >= sz["first"]) & (used < sz["first"] + sz["E"])
    return routed, shared, own, weights, turned, held


@functools.lru_cache(maxsize=None)
def _fns(frozen: tuple, faults: frozenset):
    sz = dict(frozen)
    eps = sz["eps"]

    def real_of(x, length):
        return jnp.arange(x.shape[0]) < length

    @jax.jit
    def kda_layer(w, x, length):
        out, strongest, weakest, share, S = kda(
            w, rms_norm(x, w["ln1"], eps), length, sz, faults)
        real = real_of(x, length)
        return x + out, _rms(out, real) / _rms(x, real), strongest, weakest, \
            share, S

    @jax.jit
    def mla_layer(w, x, length):
        out, std = latent_attention(w, rms_norm(x, w["ln1"], eps), length,
                                    sz, faults)
        real = real_of(x, length)
        return x + out, _rms(out, real) / _rms(x, real), std

    @jax.jit
    def dense(w, x, length):
        y = swiglu(rms_norm(x, w["ln2"], eps), w["w_gate"], w["w_up"],
                   w["w_down"])
        real = real_of(x, length)
        return x + y, _rms(y, real) / _rms(x, real)

    @functools.partial(jax.jit, static_argnums=(3,))
    def moe(w, stacks, x, at, forced, length):
        h = rms_norm(x, w["ln2"], eps)
        routed, shared, own, weights, turned, held = expert_block(
            w, stacks, at, h, sz, forced, faults)
        y = routed + shared
        real = real_of(x, length)
        n = jnp.sum(real)
        top1 = jnp.sum(jnp.where(real, jnp.max(weights, -1), 0.0)) / n
        return (x + y, own, _rms(y, real) / _rms(x, real), top1,
                jnp.sum(jnp.where(real, turned, False)) / n,
                jnp.sum(jnp.where(real[:, None], held, False))
                / (n * held.shape[1]))

    @jax.jit
    def embed(emb, tokens):
        return f32(emb[tokens])

    @jax.jit
    def head_block(cols, g, x, at):
        return rms_norm(x[at], g, eps) @ f32(cols)

    return kda_layer, mla_layer, dense, moe, embed, head_block


def layer_weights(params: dict, sz: dict, l: int):
    """(the layer's small tensors by leaf name, its three expert stacks as
    they lie — None for a dense layer —, the layer's index into the stacks'
    leading axes); ``l`` counts from 0."""
    if l < sz["nd"]:
        prefix, at = "d.", (l,)
    else:
        p, j = divmod(l - sz["nd"], sz["period"])
        prefix, at = f"p{j}.", (p,)
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
    residual's it is added to — the KDA mixer's, the latent attention's, the
    feed-forward unit's —, the standard deviation of the visible attention
    scores, the mean largest routing weight, the share of real positions whose
    chosen set the selection bias turned, the share of the choices used that
    are held, the strongest and the weakest log-decay a position a channel,
    and ‖S'ᵀ k‖ / ‖v‖: what the delta correction takes off; and, under
    ``states``, every KDA layer's state after position ``length − 1`` [KDA
    layers, H, K (key), K (value)])."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown planted faults {sorted(unknown)}")
    sz = sizes(cfg)
    kda_layer, mla_layer, dense, moe, embed, head_block = _fns(
        tuple(sorted(sz.items())), frozenset(faults))
    length = jnp.int32(length)
    own_ids, states, stats = [], [], {name: [] for name in STATS}
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
            if sz["kinds"][l] == "kda":
                x, r, strongest, weakest, share, S = kda_layer(w, x, length)
                states.append(S)
                stats["kda_rms"].append(r)
                stats["decay_strongest"].append(strongest)
                stats["decay_weakest"].append(weakest)
                stats["delta_share"].append(share)
            else:
                x, r, std = mla_layer(w, x, length)
                stats["mla_rms"].append(r)
                stats["attn_logit_std"].append(std)
            if stacks is None:
                x, r = dense(w, x, length)
            else:
                e = l - sz["nd"]
                x, own, r, top1, turned, held = moe(
                    w, stacks, x, at, None if forced is None else forced[e],
                    length)
                own_ids.append(own[:T])
                stats["top1_weight"].append(top1)
                stats["bias_turns_share"].append(turned)
                stats["held_choice_share"].append(held)
            stats["ffn_rms"].append(r)
        at = jnp.asarray(out_positions)
        V = params["head"].shape[1]
        logits = jnp.concatenate(
            [head_block(params["head"][:, c:c + HEAD_ROWS],
                        params["final_norm"], x, at)
             for c in range(0, V, HEAD_ROWS)], axis=1)
    stats = {k: jnp.stack(v) for k, v in stats.items()}
    stats["states"] = jnp.stack(states)
    return logits, jnp.stack(own_ids), stats


def router_scores(router, u):
    """The router alone on given rows: u [n, D] (the program's own normed
    inputs) → logits [n, Er] float32 at the highest precision."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda w, u: f32(u) @ f32(w))(router, u)


def route_weights(cfg: dict, r, bias, used, faults=()):
    """The routing alone on given router logits: r [n, Er], the layer's
    selection bias [Er], the experts used [n, K] → their weights [n, K]
    float32 by the equations above."""
    return route(f32(r), bias, jnp.asarray(used), sizes(cfg),
                 "renorm_held" in faults)[2]
