"""Plain reference of the trained encoder-decoder Transformer (Vaswani et al.
2017, section 3): forward pass and token-mean cross-entropy in float32
``jax.numpy``, no dropout, no kernels, nothing imported from the program.

Post-LayerNorm residual blocks; sinusoidal positions added to embeddings
scaled by sqrt(d_model); projections without biases, feed-forward layers with
biases; an output projection that is not tied to the embeddings.  Weights are a
name -> array dict under the program's own parameter names, with the
feed-forward biases as ``<prefix>.ffn.fc{1,2}.b``.
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


def attention(p, prefix, q_in, kv_in, n_head, causal):
    B, Tq, d = q_in.shape
    Tk = kv_in.shape[1]
    hd = d // n_head
    q = (q_in @ p[f"{prefix}.q.w"]).reshape(B, Tq, n_head, hd)
    k = (kv_in @ p[f"{prefix}.k.w"]).reshape(B, Tk, n_head, hd)
    v = (kv_in @ p[f"{prefix}.v.w"]).reshape(B, Tk, n_head, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.float32(np.sqrt(hd))
    if causal:
        allowed = jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :]
        s = jnp.where(allowed[None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, Tq, d)
    return ctx @ p[f"{prefix}.out.w"]


def block_tail(p, prefix, x):
    h = jax.nn.relu(x @ p[f"{prefix}.ffn.fc1.w"] + p[f"{prefix}.ffn.fc1.b"])
    f = h @ p[f"{prefix}.ffn.fc2.w"] + p[f"{prefix}.ffn.fc2.b"]
    return layer_norm(x + f, p[f"{prefix}.ffn.ln.scale"],
                      p[f"{prefix}.ffn.ln.bias"])


def loss(params: dict, cfg: dict, src_ids, tgt_ids, lbl_ids):
    """Token-mean cross-entropy of full-length sequences ([B, T] ids)."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        d, h, L = int(cfg["d_model"]), int(cfg["n_head"]), int(cfg["n_layer"])

        def embed(ids, name):
            return p[f"{name}.word_emb"][ids] * np.float32(np.sqrt(d)) \
                + jnp.asarray(positions(ids.shape[1], d))

        x = embed(src_ids, "src")
        for i in range(L):
            a = attention(p, f"enc.{i}.attn", x, x, h, causal=False)
            x = layer_norm(x + a, p[f"enc.{i}.attn.ln.scale"],
                           p[f"enc.{i}.attn.ln.bias"])
            x = block_tail(p, f"enc.{i}", x)
        y = embed(tgt_ids, "tgt")
        for i in range(L):
            a = attention(p, f"dec.{i}.self", y, y, h, causal=True)
            y = layer_norm(y + a, p[f"dec.{i}.self.ln.scale"],
                           p[f"dec.{i}.self.ln.bias"])
            c = attention(p, f"dec.{i}.cross", y, x, h, causal=False)
            y = layer_norm(y + c, p[f"dec.{i}.cross.ln.scale"],
                           p[f"dec.{i}.cross.ln.bias"])
            y = block_tail(p, f"dec.{i}", y)
        logits = y @ p["tgt.out_proj"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, lbl_ids[..., None], axis=-1)[..., 0]
        return -jnp.mean(picked)
