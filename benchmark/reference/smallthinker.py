"""Plain reference of the SmallThinker stack (``model_name``
smallthinker_21b_instruct; arXiv:2507.20984): one full causal forward of one
sequence in float32 ``jax.numpy`` at the highest matmul precision — every
position through every layer, dense masked attention a block of queries at a
time, the experts one after another over every position, no cache, no
batching, no kernel.  Nothing is imported from the program.

``cfg`` is the published ``config.json`` as a dict.  For layer ``l`` and a
token's residual ``x``:

- ``u = RMSNorm₁(x)``; the router's logits ``r = u W_r`` — read HERE, from the
  layer's normed input, before its attention;
- ``q = u W_q``, ``k = u W_k``, ``v = u W_v``; where ``rope_layout[l]`` is 1
  rotate-half rotary positions over the whole head at ``rope_theta``, where 0
  no positions at all; scores ``q kᵀ / √head_dim``, one softmax a head over
  the keys ``j`` with ``0 ≤ t − j`` and, where ``sliding_window_layout[l]`` is
  1, ``t − j < sliding_window_size``; ``x₁ = x + o W_o``;
- ``h = RMSNorm₂(x₁)``; the ``moe_num_active_primary_experts`` largest of
  ``r``, weights the softmax over those logits (``norm_topk_prob`` with
  ``moe_primary_router_apply_softmax``: the full softmax renormalised over
  the chosen is the same numbers); ``y = Σ_k w_k (relu(h Wg_e) ⊙ (h Wu_e))
  Wd_e``; ``x₂ = x₁ + y``;

a final RMSNorm and an untied head.

Departures from the published code, each at its line below: the experts are
looped over ALL positions with a weight of zero where an expert was not
chosen (the published code gathers an expert's tokens; the sum is the same);
``forced`` hands the layer the experts to use (the program's own choices, so
that a near tie turned by bf16 activations does not count as an error of
everything downstream; the reference's OWN choices are returned beside, for
the comparison that judges the routing); ``faults`` plants a mechanism that
the model does NOT have, for the controls that must fail.

Weights are the program's name → array dict, any float dtype: ``emb``, ``head``
[V, D], ``final_norm`` [D], the full layers stacked as ``pf.*`` [P, …] and
the window layers as ``pw.*`` [P, period − 1, …] (layer ``l`` is ``pf[l //
period]`` where ``l mod period`` is 0, else ``pw[l // period, l mod period −
1]``): ``ln1``, ``ln2`` [D], ``router`` [D, E], ``wqkv`` [D, (nh + 2·nkv)·dh]
(``[q | k | v]``), ``wo`` [nh·dh, D], ``e_gate``, ``e_up`` [E, D, F],
``e_down`` [E, F, D].  They are widened to float32 ONE MATRIX AT A TIME — a
layer is several jitted calls, the experts one expert a trip of a loop, the
head in blocks of vocabulary rows at the judged positions only — because the
check runs beside a live engine that holds most of the chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 128           # queries a block of the dense attention
HEAD_ROWS = 16384       # vocabulary rows a block of the head
# a layer's own readings, in this order (``forward``'s third result)
STATS = ("attn_rms", "moe_rms", "attn_logit_std", "top1_weight")
# mechanisms the model does NOT have (the controls)
FAULTS = ("silu_gate", "route_from_h")


def f32(a):
    return jnp.asarray(a, jnp.float32)


def period_of(layout) -> int:
    """The layer pattern's period: the index of the second full layer."""
    layout = [int(v) for v in layout]
    return layout.index(0, 1) if 0 in layout[1:] else len(layout)


def sizes(cfg: dict) -> dict:
    L = int(cfg["num_hidden_layers"])
    return {"D": int(cfg["hidden_size"]), "L": L,
            "F": int(cfg["moe_ffn_hidden_size"]),
            "E": int(cfg["moe_num_primary_experts"]),
            "K": int(cfg["moe_num_active_primary_experts"]),
            "nh": int(cfg["num_attention_heads"]),
            "nkv": int(cfg["num_key_value_heads"]), "dh": int(cfg["head_dim"]),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "window": int(cfg["sliding_window_size"]),
            "rope": tuple(int(v) for v in cfg["rope_layout"][:L]),
            "swa": tuple(int(v) for v in cfg["sliding_window_layout"][:L]),
            "period": period_of(cfg["sliding_window_layout"][:L])}


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


def attention(w, u, length, sz, rope: bool, window):
    """u [T, D] → (o W_o [T, D], the standard deviation of the visible
    scores of the real queries).  ``T`` is a multiple of :data:`Q_BLOCK` or
    below it."""
    T = u.shape[0]
    nh, nkv, dh = sz["nh"], sz["nkv"], sz["dh"]
    qkv = u @ f32(w["wqkv"])
    q = qkv[:, :nh * dh].reshape(T, nh, dh)
    k = qkv[:, nh * dh:(nh + nkv) * dh].reshape(T, nkv, dh)
    v = qkv[:, (nh + nkv) * dh:].reshape(T, nkv, dh)
    if rope:
        q, k = rotate(q, sz["theta"]), rotate(k, sz["theta"])
    qb = min(T, Q_BLOCK)
    q = q.reshape(T // qb, qb, nkv, nh // nkv, dh)
    keys = jnp.arange(T)

    def block(args):
        qs, first = args
        t = first + jnp.arange(qb)
        s = jnp.einsum("qgrd,jgd->grqj", qs, k) / math.sqrt(dh)
        keep = keys[None, :] <= t[:, None]
        if window is not None:
            keep = keep & (t[:, None] - keys[None, :] < window)
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


def route(r, forced, K: int):
    """Router logits r [T, E] → (the reference's own K experts [T, K], the
    experts used [T, K] — ``forced`` where given —, their weights [T, K]:
    the softmax over the used experts' logits)."""
    _, own = jax.lax.top_k(r, K)        # the lower index on a tie
    used = own if forced is None else forced
    return own, used, jax.nn.softmax(jnp.take_along_axis(r, used, 1), axis=-1)


def experts(gate, up, down, at: tuple, h, used, weights, act):
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
        y = (act(h @ one(gate, e)) * (h @ one(up, e))) @ one(down, e)
        return acc + share * y

    return jax.lax.fori_loop(0, E, body, jnp.zeros_like(h))


@functools.lru_cache(maxsize=None)
def _fns(frozen: tuple, faults: frozenset):
    sz = dict(frozen)
    eps, K = sz["eps"], sz["K"]
    act = jax.nn.silu if "silu_gate" in faults else jax.nn.relu

    @functools.partial(jax.jit, static_argnums=(3, 4))
    def attn(w, x, length, rope, window):
        u = rms_norm(x, w["ln1"], eps)
        out, std = attention(w, u, length, sz, rope, window)
        real = jnp.arange(x.shape[0]) < length
        x1 = x + out
        h = rms_norm(x1, w["ln2"], eps)
        # the router reads u, the layer's normed INPUT (the planted fault: h)
        r = (h if "route_from_h" in faults else u) @ f32(w["router"])
        return x1, h, r, _rms(out, real) / _rms(x, real), std

    @functools.partial(jax.jit, static_argnums=(3,))
    def moe(stacks, x1, h, at, r, forced, length):
        own, used, weights = route(r, forced, K)
        y = experts(*stacks, at, h, used, weights, act)
        real = jnp.arange(x1.shape[0]) < length
        top1 = jnp.sum(jnp.where(real, jnp.max(weights, -1), 0.0)) \
            / jnp.sum(real)
        return x1 + y, own, _rms(y, real) / _rms(x1, real), top1

    @jax.jit
    def embed(emb, tokens):
        return f32(emb[tokens])

    @jax.jit
    def head_block(rows, g, x, at):
        return rms_norm(x[at], g, eps) @ f32(rows).T

    return attn, moe, embed, head_block


def layer_weights(params: dict, sz: dict, l: int):
    """(the layer's small tensors by leaf name, its three expert stacks as
    they lie, the layer's index into the stacks' leading axes)."""
    p, j = divmod(l, sz["period"])
    prefix, at = ("pf.", (p,)) if j == 0 else ("pw.", (p, j - 1))
    w = {k[3:]: v for k, v in params.items() if k.startswith(prefix)}
    stacks = tuple(w.pop(k) for k in ("e_gate", "e_up", "e_down"))
    small = {k: v[at] for k, v in w.items()}
    return small, stacks, at


def forward(params: dict, cfg: dict, tokens, length, out_positions,
            forced=None, faults=()):
    """tokens [T] int32 (positions from ``length`` on are padding; a ``T``
    past :data:`Q_BLOCK` is padded on to a multiple of it), out_positions [n] int32 (each
    below ``length``), forced [L, T, K] int32 or None → (logits [n, V]
    float32, the reference's own chosen experts [L, T, K], every layer's own
    readings [L, len(STATS)] in the order of :data:`STATS`: the root mean
    square of the attention's and the experts' output over the residual's
    they are added to, the standard deviation of the visible attention
    scores, the mean largest routing weight)."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown planted faults {sorted(unknown)}")
    sz = sizes(cfg)
    attn, moe, embed, head_block = _fns(
        tuple(sorted(sz.items())), frozenset(faults))
    length = jnp.int32(length)
    own_ids, stats = [], []
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
            window = sz["window"] if sz["swa"][l] else None
            x1, h, r, r_attn, std = attn(w, x, length, bool(sz["rope"][l]),
                                         window)
            x, own, r_moe, top1 = moe(
                stacks, x1, h, at, r,
                None if forced is None else forced[l], length)
            own_ids.append(own[:T])
            stats.append(jnp.stack([r_attn, r_moe, std, top1]))
        at = jnp.asarray(out_positions)
        logits = jnp.concatenate(
            [head_block(params["head"][r:r + HEAD_ROWS], params["final_norm"],
                        x, at)
             for r in range(0, params["head"].shape[0], HEAD_ROWS)], axis=1)
    return logits, jnp.stack(own_ids), jnp.stack(stats)


def router_scores(router, u):
    """The router alone on given rows: u [n, D] (the program's own normed
    inputs) → logits [n, E] float32 at the highest precision."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda w, u: f32(u) @ f32(w))(router, u)
