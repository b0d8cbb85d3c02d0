"""Plain reference of the served decoder-only LM: one full causal forward in
float32 ``jax.numpy``, no cache, no batching tricks, no kernels, and nothing
imported from the program.

The block is this repo's ``decode.TransformerLM`` as its configuration file
describes it: token embedding scaled by sqrt(d_model) plus sinusoidal
positions, then ``n_layer`` post-LayerNorm blocks of multi-head causal
self-attention (no biases) and a ReLU feed-forward (no biases), then an
untied output projection.  Weights are a name -> array dict with the names
``emb``, ``l<i>.{wq,wk,wv,wo,fc1,fc2}``, ``l<i>.ln{1,2}.{g,b}``, ``out_proj``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5


def positions(max_len: int, d_model: int) -> np.ndarray:
    pos = np.arange(max_len, dtype=np.float64)[:, None]
    dim = np.arange(d_model // 2, dtype=np.float64)[None, :]
    angle = pos / np.power(10000.0, 2.0 * dim / d_model)
    table = np.zeros((max_len, d_model))
    table[:, 0::2] = np.sin(angle)
    table[:, 1::2] = np.cos(angle)
    return table.astype(np.float32)


def layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def forward(params: dict, cfg: dict, tokens, lengths):
    """tokens [B, T] int32, lengths [B] -> logits [B, T, vocab] float32.
    Position q attends to positions k <= q with k < length."""
    with jax.default_matmul_precision("highest"):
        d, h = int(cfg["d_model"]), int(cfg["n_head"])
        hd = d // h
        B, T = tokens.shape
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        x = p["emb"][tokens] * np.float32(np.sqrt(d)) \
            + jnp.asarray(positions(T, d))
        q_idx = jnp.arange(T)
        mask = (q_idx[:, None] >= q_idx[None, :])[None] \
            & (q_idx[None, None, :] < lengths[:, None, None])
        for i in range(int(cfg["n_layer"])):
            def heads(w):
                return (x @ p[f"l{i}.{w}"]).reshape(B, T, h, hd)
            q, k, v = heads("wq"), heads("wk"), heads("wv")
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.float32(np.sqrt(hd))
            s = jnp.where(mask[:, None], s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, T, d)
            x = layer_norm(x + ctx @ p[f"l{i}.wo"],
                           p[f"l{i}.ln1.g"], p[f"l{i}.ln1.b"])
            f = jax.nn.relu(x @ p[f"l{i}.fc1"]) @ p[f"l{i}.fc2"]
            x = layer_norm(x + f, p[f"l{i}.ln2.g"], p[f"l{i}.ln2.b"])
        return x @ p["out_proj"]


def token_gaps(params: dict, cfg: dict, tokens, lengths, next_tokens):
    """For every position: how far the reference logit of ``next_tokens``
    trails the reference argmax, and the largest |logit| of the row."""
    logits = forward(params, cfg, tokens, lengths)
    chosen = jnp.take_along_axis(logits, next_tokens[..., None], axis=-1)[..., 0]
    return (jnp.max(logits, axis=-1) - chosen,
            jnp.max(jnp.abs(logits), axis=-1))
