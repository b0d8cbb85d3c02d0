"""Plain reference of the Command A+ stack (``model_type`` cohere2_moe;
CohereLabs/command-a-plus-05-2026's ``config.json``): one full causal forward
of one sequence in float32 ``jax.numpy`` at the highest matmul precision —
every position through every layer, dense masked attention a block of queries
at a time, the held experts one after another over every position, no cache,
no batching, no kernel.  Nothing is imported from the program.

``cfg`` is the published ``config.json`` as a dict (plus ``router_experts``
and ``first_expert`` where a share of the experts is held, below).  For layer
``l`` and a token's residual ``x`` (``use_parallel_block``: ONE norm, three
branches read from it, summed):

- ``u = (x − mean x) · rsqrt(var x + layer_norm_eps) · g`` (a LayerNorm that
  subtracts the mean; no bias);
- ``q = u W_q`` [nh × dh], ``k = u W_k``, ``v = u W_v`` [nkv × dh]
  (``use_qk_norm`` false); where ``layer_types[l]`` is ``sliding_attention``
  q and k rotated in PAIRS ``(2i, 2i + 1)`` over the whole head
  (``position_embedding_type`` rope_gptj, ``rotary_pct`` 1) at ``rope_theta``
  and key ``j`` visible iff ``0 ≤ t − j < sliding_window``; where
  ``full_attention`` nothing rotated and every ``j ≤ t`` visible; scores ``q
  kᵀ / √head_dim``, one softmax a head; ``A = o W_o``;
- ``s = sigmoid(u W_r)`` over ALL the router's experts; the
  ``num_experts_per_tok`` largest chosen (no bias, no groups), ``w_e = s_e /
  Σ_chosen s`` (``norm_topk_prob``), no scaling factor; ``R = Σ_e w_e
  (silu(u Wg_e) ⊙ (u Wu_e)) Wd_e``;
- ``S = (1 / n) Σ_j (silu(u Wg'_j) ⊙ (u Wu'_j)) Wd'_j`` over the
  ``num_shared_experts`` shared experts
  (``shared_expert_combination_strategy`` average), each computed ON ITS OWN
  here;
- ``x' = x + A + R + S``;

a final LayerNorm and the head tied to the embedding: ``logit_scale ·
LN_f(x) Eᵀ``.

**A share.**  ``num_experts`` counts the experts whose matrices ``params``
HOLDS, ``router_experts`` (absent: the same) is the router's width and
``first_expert`` (absent: 0) the first held.  The router, its choice and the
renormalisation are over all of them; the sum runs over the held experts
only, and what the others would add is left out.  ``vocab_size`` counts the
held rows of the tied table.

Departures from the published description, each at its line below: the
experts are looped over ALL positions with a weight of zero where an expert
was not chosen (the sum is the same); the head is computed a block of
vocabulary rows at a time at the judged positions only (the same sums);
``forced`` hands the layer the experts to use (the program's own choices, so
that a near tie turned by bf16 activations does not count as an error of
everything downstream; the reference's OWN choices are returned beside);
``faults`` plants a mechanism or a precision that the model does NOT have,
for the controls that must fail.

Weights are the program's name → array dict, any float dtype: ``emb`` [V, D]
(table and head), ``final_norm`` [D], the window layers stacked as ``pw.*``
[P, period − 1, …] and the full layers as ``pf.*`` [P, …] (layer ``l`` is
``pw[l // period, l mod period]`` where ``l mod period < period − 1``, else
``pf[l // period]``): ``ln`` [D], ``router`` [D, Er], ``wqkv`` [D, (nh +
2·nkv)·dh] (``[q | k | v]``), ``wo`` [nh·dh, D], ``e_gate``, ``e_up`` [E, D,
F], ``e_down`` [E, F, D], ``s_gate``, ``s_up`` [D, n·F], ``s_down`` [n·F, D]
(shared expert ``j`` is columns — of ``s_down`` rows — ``j·F … (j + 1)·F``).
They are widened to float32 ONE MATRIX AT A TIME, because the check runs
beside a live engine that holds most of the chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 128           # queries a block of the dense attention (16 heads
#                         x 128 x eight thousand keys of float32: 68 MB)
HEAD_ROWS = 16384       # vocabulary rows a block of the head
# a layer's own readings, in this order (``forward``'s third result)
STATS = ("attn_rms", "routed_rms", "shared_rms", "attn_logit_std",
         "top1_weight", "held_choice_share")
# what the model does NOT have (the controls): the rotate-half pairing, the
# shared experts summed and not averaged, the chosen scores left as they are,
# — one precision below the stated float32 — the norm's statistics and the
# attention's scores and probabilities in bfloat16, and a ring that files
# position p at row (p − 1) mod window
FAULTS = ("rotate_half", "shared_sum", "no_renorm", "bf16_norm_stats",
          "bf16_softmax", "ring_off_by_a_row")


def f32(a):
    return jnp.asarray(a, jnp.float32)


def _bf16(a):
    """``a`` at bfloat16's widths (``reduce_precision``: a pair of casts may
    be dropped inside one program)."""
    return jax.lax.reduce_precision(a, 8, 7)


def sizes(cfg: dict) -> dict:
    L = int(cfg["num_hidden_layers"])
    kinds = tuple(str(k) for k in cfg["layer_types"][:L])
    period = kinds.index("full_attention") + 1
    E = int(cfg["num_experts"])
    return {"D": int(cfg["hidden_size"]), "L": L, "kinds": kinds,
            "period": period, "F": int(cfg["intermediate_size"]), "E": E,
            "Er": int(cfg.get("router_experts") or E),
            "first": int(cfg.get("first_expert") or 0),
            "K": int(cfg["num_experts_per_tok"]),
            "ns": int(cfg["num_shared_experts"]),
            "nh": int(cfg["num_attention_heads"]),
            "nkv": int(cfg["num_key_value_heads"]), "dh": int(cfg["head_dim"]),
            "eps": float(cfg["layer_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "window": int(cfg["sliding_window"]),
            "renorm": bool(cfg["norm_topk_prob"]),
            "scale": float(cfg["logit_scale"])}


def layer_norm(x, g, eps, low: bool = False):
    """``(x − mean x) · rsqrt(var x + eps) · g``; ``low``: the mean and the
    scale at bfloat16's widths (a planted precision)."""
    mean = jnp.mean(x, -1, keepdims=True)
    if low:
        mean = _bf16(mean)
    c = x - mean
    scale = jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + eps)
    return c * (_bf16(scale) if low else scale) * f32(g)


def _rms(a, real):
    n = jnp.sum(real) * a.shape[-1]
    return jnp.sqrt(jnp.sum(jnp.where(real[:, None], a * a, 0.0)) / n)


def _turns(T: int, half: int, theta):
    """cos and sin [T, 1, half] of positions 0 .. T − 1 times the ``half``
    frequencies theta^(−i/half)."""
    inv = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * (-math.log(theta) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]


def rotate_pairs(x, theta):
    """x [T, heads, dh] at positions 0 .. T − 1, the GPT-J layout: lanes
    ``(2i, 2i + 1)`` are pair ``i``, frequencies theta^(−2i/dh)."""
    half = x.shape[-1] // 2
    cos, sin = _turns(x.shape[0], half, theta)
    p = x.reshape(x.shape[:-1] + (half, 2))
    a, b = p[..., 0], p[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def rotate_half(x, theta):
    """The rotate-half pairing (lane i with lane i + dh/2): a planted fault,
    this model pairs neighbours."""
    half = x.shape[-1] // 2
    cos, sin = _turns(x.shape[0], half, theta)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(w, u, length, sz, rope: bool, window, faults=()):
    """u [T, D] → (o W_o [T, D], the standard deviation of the visible
    scores of the real queries, the cache rows ``[k | v]`` [T, 2·nkv·dh] — the
    key after its rotation).  One K/V head and its group of query heads at a
    time, a block of queries at a time (the same sums: 128 heads' scores of
    eight thousand keys do not fit beside a live engine).  ``T`` is a
    multiple of :data:`Q_BLOCK` or below it."""
    T = u.shape[0]
    nh, nkv, dh = sz["nh"], sz["nkv"], sz["dh"]
    group = nh // nkv
    low = "bf16_softmax" in faults
    turn = rotate_half if "rotate_half" in faults else rotate_pairs
    qb = min(T, Q_BLOCK)
    keys = jnp.arange(T)

    def cols(m, first, n):
        return f32(jax.lax.dynamic_slice_in_dim(m, first, n, 1))

    def head(carry, g):
        out, acc = carry
        q = (u @ cols(w["wqkv"], g * group * dh, group * dh)
             ).reshape(T, group, dh)
        k = (u @ cols(w["wqkv"], (nh + g) * dh, dh))[:, None]
        v = u @ cols(w["wqkv"], (nh + nkv + g) * dh, dh)
        if rope:
            q, k = turn(q, sz["theta"]), turn(k, sz["theta"])
        k = k[:, 0]

        def block(args):
            qs, first = args
            t = first + jnp.arange(qb)
            s = jnp.einsum("qrd,jd->rqj", qs, k) / math.sqrt(dh)
            keep = keys[None, :] <= t[:, None]
            if window is not None:
                keep = keep & (t[:, None] - keys[None, :] < window)
            if low:     # scores, exponentials and their sum at bf16's widths
                s = _bf16(s)
                e = _bf16(jnp.exp(jnp.where(
                    keep, s - jnp.max(jnp.where(keep, s, -jnp.inf), -1,
                                      keepdims=True), -jnp.inf)))
                p = _bf16(e / _bf16(jnp.sum(e, -1, keepdims=True)))
            else:
                p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
            seen = keep & (t[:, None] < length)
            n = jnp.sum(seen) * group
            tot = jnp.sum(jnp.where(seen, s, 0.0))
            sq = jnp.sum(jnp.where(seen, s * s, 0.0))
            return jnp.einsum("rqj,jd->qrd", p, v), jnp.stack([n, tot, sq])

        o, got = jax.lax.map(block, (q.reshape(T // qb, qb, group, dh),
                                     jnp.arange(T // qb) * qb))
        out = out + o.reshape(T, group * dh) @ f32(
            jax.lax.dynamic_slice_in_dim(w["wo"], g * group * dh, group * dh,
                                         0))
        return (out, acc + jnp.sum(got, 0)), (k, v)

    (out, (n, tot, sq)), (k, v) = jax.lax.scan(
        head, (jnp.zeros_like(u), jnp.zeros((3,), jnp.float32)),
        jnp.arange(nkv))
    std = jnp.sqrt(jnp.maximum(sq / n - (tot / n) ** 2, 0.0))
    rows = jnp.concatenate([jnp.swapaxes(k, 0, 1).reshape(T, -1),
                            jnp.swapaxes(v, 0, 1).reshape(T, -1)], -1)
    return out, std, rows


def route(r, forced, sz, renorm: bool = True):
    """Router logits r [T, Er] → (the reference's own K experts [T, K], the
    experts used [T, K] — ``forced`` where given —, their weights [T, K]).
    Everything is over ALL the router's experts, held here or not."""
    s = jax.nn.sigmoid(r)
    _, own = jax.lax.top_k(s, sz["K"])          # the lower index on a tie
    used = own if forced is None else forced
    chosen = jnp.take_along_axis(s, used, 1)
    if sz["renorm"] and renorm:
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    return own, used, chosen


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


def shared(w, u, sz, mean: bool = True):
    """The shared experts, each on its own, averaged (``mean`` False: summed,
    a planted fault)."""
    F, ns = sz["F"], sz["ns"]

    def body(j, acc):
        def cols(m):
            return f32(jax.lax.dynamic_slice_in_dim(m, j * F, F, 1))
        a = jax.nn.silu(u @ cols(w["s_gate"])) * (u @ cols(w["s_up"]))
        return acc + a @ f32(jax.lax.dynamic_slice_in_dim(
            w["s_down"], j * F, F, 0))

    total = jax.lax.fori_loop(0, ns, body, jnp.zeros_like(u))
    return total / ns if mean else total


@functools.lru_cache(maxsize=None)
def _fns(frozen: tuple, faults: frozenset):
    sz = dict(frozen)
    eps = sz["eps"]
    low_norm = "bf16_norm_stats" in faults

    @functools.partial(jax.jit, static_argnums=(3, 4))
    def attn(w, x, length, rope, window):
        u = layer_norm(x, w["ln"], eps, low_norm)
        out, std, rows = attention(w, u, length, sz, rope, window, faults)
        real = jnp.arange(x.shape[0]) < length
        return u, out, _rms(out, real) / _rms(x, real), std, rows

    @functools.partial(jax.jit, static_argnums=(4,))
    def ffn(w, stacks, x, u, at, forced, length):
        own, used, weights = route(u @ f32(w["router"]), forced, sz,
                                   "no_renorm" not in faults)
        r = experts(*stacks, at, u, used, weights, sz["first"])
        s = shared(w, u, sz, "shared_sum" not in faults)
        real = jnp.arange(x.shape[0]) < length
        n = jnp.sum(real)
        held = (used >= sz["first"]) & (used < sz["first"] + sz["E"])
        base = _rms(x, real)
        return (r, s, own, _rms(r, real) / base, _rms(s, real) / base,
                jnp.sum(jnp.where(real, jnp.max(weights, -1), 0.0)) / n,
                jnp.sum(jnp.where(real[:, None], held, False))
                / (n * held.shape[1]))

    @jax.jit
    def embed(emb, tokens):
        return f32(emb[tokens])

    @jax.jit
    def head_block(rows, g, x, at):
        return layer_norm(x[at], g, eps, low_norm) @ f32(rows).T * sz["scale"]

    @functools.partial(jax.jit, static_argnums=(2,))
    def ring_rows(rows, fed, window):
        """What a ring of ``window`` rows holds after ``fed`` positions: row
        ``r`` the last position that is ``r mod window`` (and whether there
        is one)."""
        r = jnp.arange(window)
        if "ring_off_by_a_row" in faults:
            r = (r + 1) % window
        at = r + window * ((fed - 1 - r) // window)
        return rows[jnp.clip(at, 0, rows.shape[0] - 1)], at >= 0

    return attn, ffn, embed, head_block, ring_rows


def layer_weights(params: dict, sz: dict, l: int):
    """(the layer's small tensors by leaf name, its three expert stacks as
    they lie, the layer's index into the stacks' leading axes)."""
    p, j = divmod(l, sz["period"])
    prefix, at = ("pf.", (p,)) if j == sz["period"] - 1 else ("pw.", (p, j))
    w = {k[3:]: v for k, v in params.items() if k.startswith(prefix)}
    stacks = tuple(w.pop(k) for k in ("e_gate", "e_up", "e_down"))
    small = {k: v[at] for k, v in w.items()}
    return small, stacks, at


def forward(params: dict, cfg: dict, tokens, length, out_positions,
            forced=None, faults=(), rings: bool = False):
    """tokens [T] int32 (positions from ``length`` on are padding; a ``T``
    past :data:`Q_BLOCK` is padded on to a multiple of it), out_positions [n]
    int32 (each below ``length``), forced [L, T, K] int32 or None → (logits
    [n, V] float32, the reference's own chosen experts [L, T, K], a dict of
    its own readings: ``stats`` [L, len(STATS)] in the order of :data:`STATS`
    — the root mean square of the attention's, the routed experts' and the
    shared experts' output over the residual's they are added to, the
    standard deviation of the visible attention scores, the mean largest
    routing weight, the share of the choices used that are held —, ``u`` [n,
    L, D] every layer's normed input at ``out_positions`` and, with ``rings``,
    ``rings`` [window layers, window, 2·nkv·dh] what a window layer's ring
    holds after ``length`` positions and ``ring_rows`` [window] which of its
    rows hold one)."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown planted faults {sorted(unknown)}")
    sz = sizes(cfg)
    attn, ffn, embed, head_block, ring_rows = _fns(
        tuple(sorted(sz.items())), frozenset(faults))
    length = jnp.int32(length)
    own_ids, stats, us, held_rings, ring_mask = [], [], [], [], None
    tokens = jnp.asarray(tokens)
    T = tokens.shape[0]
    pad = -T % Q_BLOCK if T > Q_BLOCK else 0
    tokens = jnp.pad(tokens, (0, pad))
    if forced is not None:
        forced = jnp.pad(jnp.asarray(forced), ((0, 0), (0, pad), (0, 0)))
    at_out = jnp.asarray(out_positions)
    with jax.default_matmul_precision("highest"):
        x = embed(params["emb"], tokens)
        for l in range(sz["L"]):
            w, stacks, at = layer_weights(params, sz, l)
            windowed = sz["kinds"][l] == "sliding_attention"
            u, a, r_attn, std, rows = attn(
                w, x, length, windowed, sz["window"] if windowed else None)
            r, s, own, r_routed, r_shared, top1, held = ffn(
                w, stacks, x, u, at, None if forced is None else forced[l],
                length)
            if rings and windowed:
                got, ring_mask = ring_rows(rows, length, sz["window"])
                held_rings.append(got)
            x = x + a + r + s
            own_ids.append(own[:T])
            us.append(u[at_out])
            stats.append(jnp.stack([r_attn, r_routed, r_shared, std, top1,
                                    held]))
        V = params["emb"].shape[0]
        logits = jnp.concatenate(
            [head_block(params["emb"][r:r + HEAD_ROWS], params["final_norm"],
                        x, at_out) for r in range(0, V, HEAD_ROWS)], axis=1)
    own = {"stats": jnp.stack(stats), "u": jnp.stack(us, axis=1)}
    if rings:
        own.update(rings=jnp.stack(held_rings), ring_rows=ring_mask)
    return logits, jnp.stack(own_ids), own


def router_scores(router, u):
    """The router alone on given rows: u [n, D] (the program's own normed
    inputs) → logits [n, Er] float32 at the highest precision."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda w, u: f32(u) @ f32(w))(router, u)


def route_weights(cfg: dict, r, used, faults=()):
    """The routing alone on given router logits: r [n, Er], the experts used
    [n, K] → their weights [n, K] float32 by the equations above."""
    return route(f32(r), jnp.asarray(used), sizes(cfg),
                 "no_renorm" not in faults)[2]


def norm_unit_error(u, g):
    """|var(u / g) − 1| a row: u [n, D] rows that left a LayerNorm of weight g
    [D] — 0 but for the output's rounding where the statistics are float32,
    up to 2^-8 where the scale was rounded to bfloat16."""
    z = f32(u) / f32(g)
    c = z - jnp.mean(z, -1, keepdims=True)
    return jnp.abs(jnp.mean(c * c, -1) - 1.0)
