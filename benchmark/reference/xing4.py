"""Plain reference of the ``xing4_0`` decoder (Xing4.0-29B-A4B's published
keys): one full causal forward of one sequence in float32 ``jax.numpy`` at the
highest matmul precision — no kernels, no cache, no batching; the *expanded*
attention formula only, every expert applied to every token and weighted by
the router (zero where it was not chosen).  Nothing is imported from the
program.  The residual path follows *mHC: Manifold-Constrained
Hyper-Connections* (arXiv:2512.24880) over *Hyper-Connections*
(arXiv:2409.19606), recalled without a network; every reading the published
``config.json`` does not fix is in the configuration file's ``assumed``.

Per token the residual is ``X`` [n, D], n = ``hc_mult``.  A sub-layer ``s`` of
a layer (``attn`` then ``ffn``) has ``Φ_s`` [n² + 2n, n·D], ``b_s`` [n² + 2n],
``α_s`` [3] and its norm gain ``g_s``:

    u = vec(X)                       ρ = (mean(u²) + rms_norm_eps)^(-1/2)
    m = ρ · (Φ_s u)
    H_pre  = σ(α_s[0] m[0:n] + b_s[0:n])
    H_post = 2 σ(α_s[1] m[n:2n] + b_s[n:2n])
    A_res  = α_s[2] mat(m[2n:]) + mat(b_s[2n:])          # rows: output streams
    M ← exp(clip(A_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    hc_sinkhorn_iters times:  M ← M / (rowsum(M) + hc_eps);
                              M ← M / (colsum(M) + hc_eps)
    h = Σ_j H_pre[j] X[j]            y = F_s(RMSNorm(h; g_s))
    X'[i] = Σ_j M[i, j] X[j] + H_post[i] y

``X⁰[i] = emb[token]`` for every i; logits = ``head(RMSNorm(Σ_i X^L[i];
final_norm))``.  ``F_attn``: ``q = wq_b · RMSNorm(wq_a · x; q_norm)``, then
DeepSeek-V2's latent attention (``wkva`` → ``[c | k_pe]``, ``c`` normed,
``wkvb`` → a head's ``[k_nope | v]``, rotary on the ``qk_rope_head_dim``
slices in the half-split form, YaRN's frequencies and softmax scale).
``F_ffn``: dense SwiGLU below ``first_k_dense_replace``; else scores ``σ(x
W_r)``, the ``num_experts_per_tok`` largest of ``scores + router_bias``
chosen, weights the chosen SCORES over their sum + 1e-20
(``norm_topk_prob``) times ``routed_scaling_factor``, plus the shared expert.

Weights are a name → array dict (any float dtype, widened to float32 a matrix
at a time): ``emb`` [V, D], ``final_norm`` [D], ``head`` [D, V], and a layer
``l<i>.``: ``attn_norm``, ``ffn_norm`` [D], ``wq_a`` [D, rq], ``q_norm``
[rq], ``wq_b`` [rq, H(nope+rope)], ``wkva``, ``kv_norm``, ``wkvb``, ``wo`` as
``reference/deepseek_v2.py`` has them, ``<s>_hc_phi``, ``<s>_hc_b``,
``<s>_hc_alpha`` for ``s`` in ``attn``, ``ffn``; dense layers ``w_gate``,
``w_up``, ``w_down``; expert layers ``router`` [D, E], ``router_bias`` [E],
``e_gate``, ``e_up`` [E, D, Fe], ``e_down`` [E, Fe, D], ``s_gate``, ``s_up``,
``s_down``.

The forward runs a layer's sub-layer a jitted call (one compile a kind of
sub-layer), attention in blocks of :data:`Q_BLOCK` queries and the head in
blocks of :data:`HEAD_COLS` columns, so that 8,192 positions fit beside a
served model.  :data:`FAULTS` are other models, for the tests and the
controls: each must be told apart from this one.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v2 import (f32, rms_norm, rope, swiglu,
                                             yarn)

Q_BLOCK = 512       # queries a block: the [H, Q_BLOCK, T] scores must fit
HEAD_COLS = 16384   # vocabulary columns a block of the head
ROUTE_EPS = 1e-20
FAULTS = ("no_q_norm", "no_clip", "transposed_hres")
STATS = ("hres_diag", "hres_token_std", "attn_rms", "ffn_rms", "stream_rms")


def sizes(cfg: dict) -> dict:
    n = int(cfg["hc_mult"])
    return {"n": n, "D": int(cfg["hidden_size"]),
            "eps": float(cfg["rms_norm_eps"]),
            "iters": int(cfg["hc_sinkhorn_iters"]),
            "hc_eps": float(cfg["hc_eps"]),
            "lo": float(cfg["mhc_h_res_clamp_min"]),
            "hi": float(cfg["mhc_h_res_clamp_max"]),
            "H": int(cfg["num_attention_heads"]),
            "dn": int(cfg["qk_nope_head_dim"]),
            "dr": int(cfg["qk_rope_head_dim"]), "dv": int(cfg["v_head_dim"]),
            "r": int(cfg["kv_lora_rank"]), "E": int(cfg["n_routed_experts"]),
            "K": int(cfg["num_experts_per_tok"]),
            "norm": bool(cfg["norm_topk_prob"]),
            "scale": float(cfg["routed_scaling_factor"])}


def maps(phi, b, alpha, X, sz: dict, faults=()):
    """X [T, n, D] float32 → (H_pre [T, n], H_post [T, n], H_res [T, n, n])
    by the equations above."""
    n = sz["n"]
    T = X.shape[0]
    u = X.reshape(T, -1)
    rho = jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True) + sz["eps"])
    m = (u @ f32(phi).T) * rho
    b, alpha = f32(b), f32(alpha)
    h_pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + b[n:2 * n])
    a = (alpha[2] * m[:, 2 * n:] + b[2 * n:]).reshape(T, n, n)
    if "no_clip" not in faults:
        a = jnp.clip(a, sz["lo"], sz["hi"])
    M = jnp.exp(a)
    for _ in range(sz["iters"]):
        M = M / (jnp.sum(M, -1, keepdims=True) + sz["hc_eps"])
        M = M / (jnp.sum(M, -2, keepdims=True) + sz["hc_eps"])
    if "transposed_hres" in faults:
        M = jnp.swapaxes(M, -1, -2)
    return h_pre, h_post, M


def mix(w, s: str, X, fn, sz: dict, faults=()):
    """One sub-layer round the streams: X [T, n, D] → (X', the maps, the
    branch's output y)."""
    h_pre, h_post, M = maps(w[s + "_hc_phi"], w[s + "_hc_b"],
                            w[s + "_hc_alpha"], X, sz, faults)
    h = jnp.einsum("tj,tjd->td", h_pre, X)
    y = fn(rms_norm(h, w[s + "_norm"], sz["eps"]))
    mixed = jnp.einsum("tij,tjd->tid", M, X) + h_post[:, :, None] * y[:, None]
    return mixed, (h_pre, h_post, M), y


def attention(w, cfg: dict, x, pos, length, faults=()):
    """x [T, D] (normed) → [T, D]: the low-rank query, then the expanded
    latent attention."""
    sz = sizes(cfg)
    H, dn, dr, dv, r = sz["H"], sz["dn"], sz["dr"], sz["dv"], sz["r"]
    inv_freq, factor, scale = yarn(cfg)
    T = x.shape[0]
    qa = x @ f32(w["wq_a"])
    if "no_q_norm" not in faults:
        qa = rms_norm(qa, w["q_norm"], sz["eps"])
    q = (qa @ f32(w["wq_b"])).reshape(T, H, dn + dr)
    kva = x @ f32(w["wkva"])
    c = rms_norm(kva[:, :r], w["kv_norm"], sz["eps"])
    k_pe = rope(kva[:, r:], pos, inv_freq, factor)
    q_pe = rope(q[..., dn:], pos, inv_freq, factor)
    kv = (c @ f32(w["wkvb"])).reshape(T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    blk = min(Q_BLOCK, T)
    assert T % blk == 0, (T, blk)

    def block(i):
        qs = i * blk + jnp.arange(blk)
        s = (jnp.einsum("qhd,khd->hqk", q[qs, :, :dn], k_nope)
             + jnp.einsum("qhd,kd->hqk", q_pe[qs], k_pe)) * scale
        keep = (pos[None, :] <= qs[:, None]) & (pos[None, :] < length)
        p = jax.nn.softmax(jnp.where(keep[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v).reshape(blk, H * dv)
    ctx = jax.lax.map(block, jnp.arange(T // blk)).reshape(T, H * dv)
    return ctx @ f32(w["wo"])


def route(logits, bias, forced, sz: dict):
    """Router logits [T, E] float32 → (the router's own choice [T, K], the
    weights of the experts used [T, E]: zero where an expert is not)."""
    s = jax.nn.sigmoid(logits)
    _, own = jax.lax.top_k(s + f32(bias), sz["K"])
    ids = own if forced is None else forced
    used = jnp.sum(jax.nn.one_hot(ids, sz["E"], dtype=jnp.float32), axis=1)
    wts = s * used
    if sz["norm"]:
        wts = wts / (jnp.sum(wts, -1, keepdims=True) + ROUTE_EPS)
    return own.astype(jnp.int32), wts * sz["scale"]


def moe(w, cfg: dict, x, forced=None, shared: bool = True):
    """x [T, D] (normed) → (output [T, D], the router's own choice [T, K]);
    ``forced`` [T, K]: those experts instead, at the weights this router
    gives them; ``shared`` False: the routed part alone."""
    own, wts = route(x @ f32(w["router"]), w["router_bias"], forced,
                     sizes(cfg))

    def one(acc, e):
        wg, wu, wd, we = e
        return acc + we[:, None] * swiglu(x, wg, wu, wd), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["e_gate"], w["e_up"], w["e_down"], wts.T))
    if shared:
        y = y + swiglu(x, w["s_gate"], w["s_up"], w["s_down"])
    return y, own


def _rms(a, real):
    """Root mean square of a [T, ...] over the real positions."""
    sq = jnp.mean(jnp.square(a).reshape(a.shape[0], -1), -1)
    return jnp.sqrt(jnp.sum(jnp.where(real, sq, 0.0)) / jnp.sum(real))


def _map_stats(M, real):
    """(mean diagonal of H_res, the standard deviation over the real tokens
    of an entry, meaned over the entries)."""
    n_real = jnp.sum(real)
    keep = real[:, None, None]
    mean = jnp.sum(jnp.where(keep, M, 0.0), 0) / n_real
    var = jnp.sum(jnp.where(keep, jnp.square(M - mean), 0.0), 0) / n_real
    return jnp.mean(jnp.diagonal(mean)), jnp.mean(jnp.sqrt(var))


@functools.lru_cache(maxsize=None)
def _fns(frozen: str, faults: frozenset):
    cfg = json.loads(frozen)
    sz = sizes(cfg)

    def real_of(X, length):
        return jnp.arange(X.shape[0]) < length

    def report(X, out, length):
        X2, (_, _, M), y = out
        real = real_of(X, length)
        return X2, _rms(y, real) / _rms(X, real), _map_stats(M, real)

    @jax.jit
    def attn(w, X, length):
        pos = jnp.arange(X.shape[0], dtype=jnp.int32)
        return report(X, mix(w, "attn", X, lambda h: attention(
            w, cfg, h, pos, length, faults), sz, faults), length)

    @jax.jit
    def dense(w, X, length):
        return report(X, mix(w, "ffn", X, lambda h: swiglu(
            h, w["w_gate"], w["w_up"], w["w_down"]), sz, faults), length)

    @jax.jit
    def experts(w, X, forced, length):
        own = []

        def fn(h):
            y, ids = moe(w, cfg, h, forced)
            own.append(ids)
            return y
        out = report(X, mix(w, "ffn", X, fn, sz, faults), length)
        return out + (own[0],)

    @jax.jit
    def embed(emb, tokens, length):
        X = jnp.repeat(f32(emb[tokens])[:, None], sz["n"], axis=1)
        return X, _rms(X, real_of(X, length))

    @jax.jit
    def leave(g, X, at, length):
        return rms_norm(jnp.sum(X[at], 1), g, sz["eps"]), \
            _rms(X, real_of(X, length))

    @jax.jit
    def head_block(cols, x):
        return x @ f32(cols)

    return attn, dense, experts, embed, leave, head_block


def layer_weights(params: dict, i: int) -> dict:
    L = f"l{i}."
    return {k[len(L):]: v for k, v in params.items() if k.startswith(L)}


def forward(params: dict, cfg: dict, tokens, length, out_positions,
            forced=None, faults=()):
    """tokens [T] int32 (one sequence; positions from ``length`` on are
    padding; ``T`` a multiple of :data:`Q_BLOCK` or below it), out_positions
    [P] int32 → (logits [P, V] float32, the routers' own choices [n_moe, T,
    K] int32, the reference's own readings {name of :data:`STATS`: one number
    a sub-layer it is taken in}: the mean diagonal of ``H_res`` and the
    standard deviation of its entries over the real tokens, a branch's root
    mean square over its streams', the streams' root mean square after the
    embedding and after the last layer).  ``forced`` [n_moe, T, K] makes
    every expert layer use the given experts."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown planted faults {sorted(unknown)}")
    attn, dense, experts, embed, leave, head_block = _fns(
        json.dumps(cfg, sort_keys=True), frozenset(faults))
    length = jnp.int32(length)
    tokens = jnp.asarray(tokens)
    own, stats = [], {name: [] for name in STATS}

    def note(kind, ratio, map_stats):
        stats[kind].append(ratio)
        stats["hres_diag"].append(map_stats[0])
        stats["hres_token_std"].append(map_stats[1])

    with jax.default_matmul_precision("highest"):
        X, r0 = embed(params["emb"], tokens, length)
        stats["stream_rms"].append(r0)
        m = 0
        for i in range(int(cfg["num_hidden_layers"])):
            w = layer_weights(params, i)
            X, ratio, ms = attn(w, X, length)
            note("attn_rms", ratio, ms)
            if i < int(cfg["first_k_dense_replace"]):
                X, ratio, ms = dense(w, X, length)
            else:
                X, ratio, ms, ids = experts(
                    w, X, None if forced is None else jnp.asarray(forced[m]),
                    length)
                own.append(ids)
                m += 1
            note("ffn_rms", ratio, ms)
        x, r1 = leave(params["final_norm"], X, jnp.asarray(out_positions),
                      length)
        stats["stream_rms"].append(r1)
        V = params["head"].shape[1]
        logits = jnp.concatenate(
            [head_block(params["head"][:, c:c + HEAD_COLS], x)
             for c in range(0, V, HEAD_COLS)], axis=1)
    K = int(cfg["num_experts_per_tok"])
    return logits, (jnp.stack(own) if own else jnp.zeros(
        (0, tokens.shape[0], K), jnp.int32)), \
        {k: jnp.stack(v) for k, v in stats.items()}


def sublayer_maps(params: dict, cfg: dict, layer: int, s: str, X):
    """The maps alone on given streams: X [rows, n·D] or [rows, n, D] (the
    program's own, any float dtype) → (H_pre, H_post, H_res) float32 of
    sub-layer ``s`` of ``layer``."""
    sz = sizes(cfg)
    L = f"l{layer}.{s}_hc_"
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda phi, b, a, X: maps(
            phi, b, a, f32(X).reshape(X.shape[0], sz["n"], sz["D"]), sz))(
            params[L + "phi"], params[L + "b"], params[L + "alpha"],
            jnp.asarray(X))


def experts_alone(params: dict, cfg: dict, layer: int, x, ids):
    """The routed experts alone on given rows: x [rows, D] (the program's own
    normed inputs), ids [rows, K] (its choices) → [rows, D] float32."""
    w = layer_weights(params, layer)
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda w, x, ids: moe(w, cfg, f32(x), ids,
                                             shared=False)[0])(
            {k: w[k] for k in ("router", "router_bias", "e_gate", "e_up",
                               "e_down")}, jnp.asarray(x), jnp.asarray(ids))


__all__ = ["forward", "maps", "mix", "attention", "moe", "route",
           "sublayer_maps", "experts_alone", "sizes", "FAULTS", "STATS"]
