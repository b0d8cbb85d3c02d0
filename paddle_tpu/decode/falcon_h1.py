"""A parallel-hybrid LM (Falcon-H1, ``model_type`` falcon_h1) for the decode
plane: every layer holds a Mamba-2 (state-space duality) mixer AND a
grouped-query attention, side by side on ONE normed input, configured by the
published keys.

With ``u = RMSNorm(x)`` a layer is

    x ← x + out_ssm(u) + out_attn(u)
    x ← x + W_down(silu(W_gate v · m_gate) ⊙ W_up v) · m_down ,  v = RMSNorm'(x)

and the model ``x₀ = E[token] · embedding_multiplier``, a final RMSNorm and
an untied head ``logits = W_head x · lm_head_multiplier``.

- **state-space branch** (``kernels/ssd.py``): ``p = (W_in (u ·
  ssm_in_multiplier)) ⊙ µ`` with ``µ`` the vector that repeats
  ``ssm_multipliers[0..4]`` over the segments ``[z | x | B | C | dt]`` of
  ``p``; ``[x | B | C] ← silu(conv(·) + b)`` (depthwise, causal, ``d_conv``
  taps); ``Δ = softplus(dt + dt_bias)`` a head; for head ``h`` of group ``g``
  the recurrence ``S_t = exp(Δ_t A_h) S_{t−1} + B_{t,g} ⊗ (Δ_t x_{t,h})``,
  ``A_h = −exp(A_log_h)``, ``y_{t,h} = C_{t,g}ᵀ S_t + D_h x_{t,h}``; the gated
  group norm ``y ← RMSNorm_groups(y ⊙ silu(z)) ⊙ w`` (statistics over each
  group's channels, the gate BEFORE the norm); ``out_ssm = (W_out y) ·
  ssm_out_multiplier``.  A stream keeps ``S`` [heads, N, head channels]
  (float32) and the last ``d_conv − 1`` inputs ``[x | B | C]`` a layer.
- **attention branch** (``kernels/gqa.py``): ``q = W_q (u ·
  attention_in_multiplier)``, ``k = (W_k ·) · key_multiplier``, ``v = W_v ·``,
  rotate-half rotary positions over the whole head at ``rope_theta``, causal
  ``softmax(q kᵀ / √dh) v`` with ``num_attention_heads`` query heads over
  ``num_key_value_heads`` K/V heads, ``out_attn = (W_o ·) ·
  attention_out_multiplier``.  A stream keeps a row ``[k | v]`` a token a
  layer in the paged pool, the key after its rotation.

So a stream's state is of two kinds IN EVERY LAYER (:class:`~paddle_tpu.
decode.cache.HybridStateCache` with ``kv_layers`` = the depth and no window
rings): blocks of a paged pool of L layers, held by block table, and a
recurrent row and a convolution tail a slot a layer, addressed by slot
(``slot_state``).

Pad positions of a bucket have ``Δ = 0`` (the state passes through them); the
convolution's tail is taken at the last real positions; every position runs
through every layer and only the last real one through the head.  Programs
``lax.scan`` over the layers' stacked weights (``lay.*`` ``[L, …]``), so a
program holds one layer's code and the pool, the rows and the tails are the
loop's carry, updated in place with the layer as an index.

The model is an :class:`~paddle_tpu.decode.adapter.LMAdapter`, so a
:class:`~paddle_tpu.decode.engine.DecodeEngine` serves it as it is.  There
is no snapshot of a slot's rows and no suffix prefill from a saved state, so
``supports`` is empty: a prefix cache, overcommit and beam sessions refuse
this model at build.

Weights, residual stream, pool and tails are ``dtype`` (bf16 as deployed);
matmuls accumulate in float32; softmax, norm statistics, ``Δ``, ``exp(ΔA)``
and ``S`` are float32.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .adapter import (MODEL_TYPES, ConfigDict, LMAdapter, PoolObserver,
                      init_tensor as _init_tensor, mm, prompt_addresses,
                      rms_norm, rotary, sample, sample_first, step_addresses)
from .cache import HybridStateCache
from ..kernels import gqa as _gqa
from ..kernels import ssd as _ssd
from ..kernels import ssm as _ssm
from ..observability import trace as _trace

MODEL_TYPE = "falcon_h1"
_DT_MIN, _DT_MAX = 1e-3, 1e-1


@dataclasses.dataclass(frozen=True)
class FalconH1Config(ConfigDict):
    """The published keys this model reads, under their published names; the
    deployment's per-stream ``max_seq_len`` and the weights' ``dtype``."""

    vocab_size: int
    hidden_size: int = 64
    num_hidden_layers: int = 2
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    intermediate_size: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    mamba_d_ssm: int = 64
    mamba_n_heads: int = 4
    mamba_d_head: int = 16
    mamba_n_groups: int = 2
    mamba_d_state: int = 32
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: Tuple[float, ...] = (1.0, 1.0)
    max_seq_len: int = 128
    dtype: str = "bfloat16"
    model_type = MODEL_TYPE

    def __post_init__(self):
        for name, n in (("ssm_multipliers", 5), ("mlp_multipliers", 2)):
            v = tuple(float(m) for m in getattr(self, name))
            if len(v) != n:
                raise ValueError(f"{name} has {n} entries, got {len(v)}")
            object.__setattr__(self, name, v)
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError("mamba_d_ssm is mamba_n_heads x mamba_d_head")
        if self.mamba_n_heads % self.mamba_n_groups \
                or self.num_attention_heads % self.num_key_value_heads \
                or self.head_dim % 2:
            raise ValueError("groups divide the state-space heads, K/V heads "
                             "the query heads, and a head is rotated by "
                             "halves")

    @property
    def q_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def bc_width(self) -> int:
        """One of B and C: every group's state coefficients."""
        return self.mamba_n_groups * self.mamba_d_state

    @property
    def conv_width(self) -> int:
        """What the convolution covers: ``[x | B | C]``."""
        return self.mamba_d_ssm + 2 * self.bc_width

    @property
    def in_width(self) -> int:
        """``[z | x | B | C | dt]``."""
        return self.mamba_d_ssm + self.conv_width + self.mamba_n_heads

    @property
    def state_shape(self) -> tuple:
        """A stream's recurrent row of one layer, as ``kernels/ssd.py`` keeps
        it ([heads, N, head channels] at every width but 64)."""
        return _ssd.state_layout(self.mamba_n_heads, self.mamba_d_state,
                                 self.mamba_d_head)


def mup_vector(cfg: FalconH1Config) -> np.ndarray:
    """``µ``: ``ssm_multipliers[0..4]`` repeated over the segments ``[z | x |
    B | C | dt]`` of the input projection's output."""
    widths = (cfg.mamba_d_ssm, cfg.mamba_d_ssm, cfg.bc_width, cfg.bc_width,
              cfg.mamba_n_heads)
    return np.concatenate([np.full((w,), m, np.float32)
                           for w, m in zip(widths, cfg.ssm_multipliers)])


def param_shapes(cfg: FalconH1Config) -> Dict[str, tuple]:
    """name → (shape, init): a float is the std of a normal; ``norm`` a norm
    weight (1 + 0.1 N), ``bias`` a bias (0.02 N), ``a_log`` the log of a
    decay uniform in [1, 16] a head, ``dt_bias`` the inverse softplus of a
    step size log-uniform in [1e-3, 1e-1], ``skip`` ones (Mamba-2's own
    initialisation of A, dt_bias and D)."""
    D, F, V, L = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                  cfg.num_hidden_layers)
    Ds, H, K = cfg.mamba_d_ssm, cfg.mamba_n_heads, cfg.mamba_d_conv
    out = {"emb": ((V, D), D ** -0.5), "head": ((V, D), D ** -0.5),
           "final_norm": ((D,), "norm")}
    layer = {"ln1": ((D,), "norm"), "ln2": ((D,), "norm"),
             "in_proj": ((D, cfg.in_width), D ** -0.5),
             "conv_w": ((K, cfg.conv_width), K ** -0.5),
             "conv_b": ((cfg.conv_width,), "bias"),
             "dt_bias": ((H,), "dt_bias"), "a_log": ((H,), "a_log"),
             "d_skip": ((H,), "skip"), "ssm_norm": ((Ds,), "norm"),
             "out_proj": ((Ds, D), Ds ** -0.5),
             "wqkv": ((D, cfg.q_width + 2 * cfg.kv_width), D ** -0.5),
             "wo": ((cfg.q_width, D), cfg.q_width ** -0.5),
             "mlp_gate": ((D, F), D ** -0.5), "mlp_up": ((D, F), D ** -0.5),
             "mlp_down": ((F, D), F ** -0.5)}
    out.update({"lay." + k: ((L,) + shape, init)
                for k, (shape, init) in layer.items()})
    return out


def init_tensor(key, shape: tuple, init, dtype):
    """One tensor of :func:`param_shapes` from a PRNG key (jit-able with
    ``shape``, ``init`` and ``dtype`` static): Mamba-2's own three here, the
    rest by :func:`~paddle_tpu.decode.adapter.init_tensor`."""
    f32 = jnp.float32
    if init == "skip":
        w = jnp.ones(shape, f32)
    elif init == "a_log":
        w = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif init == "dt_bias":
        u = jax.random.uniform(key, shape, f32)
        dt = jnp.exp(u * (math.log(_DT_MAX) - math.log(_DT_MIN))
                     + math.log(_DT_MIN))
        w = dt + jnp.log(-jnp.expm1(-dt))
    else:
        return _init_tensor(key, shape, init, dtype)
    return w.astype(dtype)


def _times(x, m: float):
    """``x · m`` in ``x``'s dtype (nothing where the multiplier is 1)."""
    if m == 1.0:
        return x
    return (x.astype(jnp.float32) * m).astype(x.dtype)


class FalconH1Observer(PoolObserver):
    """``decode.<engine>.*`` series of this model: the common ones and the
    pool's (every layer walks it), and its own of the state-space branch."""

    def __init__(self, name: str, cache, config: FalconH1Config, table_shape):
        super().__init__(name, cache, config, table_shape)
        # a live stream's rows of every layer, read once and written once
        self.row_bytes = 2 * 4 * config.num_hidden_layers \
            * int(np.prod(config.state_shape))
        sc = self.series
        self.prefill_chunks = sc.counter(
            "prefill_scan_chunks", "chunks of mamba_chunk_size positions "
            "that hold a real position, summed over prefills (one layer)")
        self.state_bytes = sc.counter(
            "step_state_bytes", "bytes of recurrent rows the live streams' "
            "one-token updates read and wrote, every layer, summed over "
            "decode steps")
        sc.gauge("recurrent_state_bytes").set(cache.recurrent_state_bytes)

    def prefill(self, extra, prompt: int, bucket: int) -> None:
        with _trace.span("decode::prefill.observe") as sp:
            chunks = -(-prompt // self.config.mamba_chunk_size)
            self.count_prompt(prompt, bucket)
            self.prefill_chunks.inc(chunks)
            sp.annotate(prefill_real_tokens=prompt,
                        prefill_pad_tokens=bucket - prompt,
                        prefill_scan_chunks=chunks,
                        prefill_tokens_sq=prompt * prompt)

    def step(self, extra, contexts) -> None:
        with _trace.span("decode::step.observe") as sp:
            context, streams = self.count_streams(contexts)
            moved = streams * self.row_bytes
            self.state_bytes.inc(moved)
            sp.annotate(step_context_tokens=context, step_streams=streams,
                        step_state_bytes=moved)
        layers = self.config.num_hidden_layers
        self.count_walks(layers * self.pool_walk(contexts),
                         layers * self._slots * self._slot_blocks)


class FalconH1LM(LMAdapter):
    """One parallel-hybrid LM: config + the jit-ready functions."""

    # a layer's recurrent row and convolution tail live in slot rows
    slot_state = True
    config_class = FalconH1Config
    observer_class = FalconH1Observer
    param_shapes = staticmethod(param_shapes)
    init_tensor = staticmethod(init_tensor)

    def __init__(self, config: FalconH1Config):
        super().__init__(config)
        self._mu = mup_vector(config)

    # -- what an engine asks of a model ------------------------------------
    def _make_cache(self, num_blocks: int, block_tokens: int, dtype: str,
                    slots: int) -> HybridStateCache:
        cfg = self.config
        L = cfg.num_hidden_layers
        return HybridStateCache(
            cfg.kv_width, num_blocks, block_tokens, slots, dtype=dtype,
            kv_layers=L, recurrent=(L, cfg.state_shape),
            tails=(L, cfg.mamba_d_conv, cfg.conv_width))

    def _unpack(self, plist):
        """(the model's own tensors, the layers' stacked ones)."""
        p = dict(zip(self.param_names(), plist))
        return ({k: v for k, v in p.items() if not k.startswith("lay.")},
                {k[4:]: v for k, v in p.items() if k.startswith("lay.")})

    # -- shared layer math -------------------------------------------------
    def _rms(self, x, g):
        return rms_norm(x, g, self.config.rms_norm_eps)

    def _mlp(self, w, x):
        cfg = self.config
        v = self._rms(x, w["ln2"])
        with jax.named_scope("mlp"):
            g = jnp.dot(v, w["mlp_gate"], preferred_element_type=jnp.float32)
            up = jnp.dot(v, w["mlp_up"], preferred_element_type=jnp.float32)
            act = (jax.nn.silu(g * cfg.mlp_multipliers[0]) * up
                   ).astype(x.dtype)
            return x + _times(mm(act, w["mlp_down"]),
                              cfg.mlp_multipliers[1])

    def _ssm_in(self, w, u):
        """u [N, D] → z [N, Ds], ``[x | B | C]`` [N, conv width] (both in
        u's dtype), dt [N, H] float32."""
        cfg = self.config
        with jax.named_scope("ssd_in"):
            p = jnp.dot(_times(u, cfg.ssm_in_multiplier), w["in_proj"],
                        preferred_element_type=jnp.float32) * self._mu
            Ds, Cw = cfg.mamba_d_ssm, cfg.conv_width
            return (p[:, :Ds].astype(u.dtype),
                    p[:, Ds:Ds + Cw].astype(u.dtype), p[:, Ds + Cw:])

    def _ssm_split(self, w, c, dt):
        """The convolved ``[x | B | C]`` c [N, conv width] and dt [N, H] →
        x [N, H, P], Δ [N, H] float32, A [H] float32, B, C [N, G, N_state]."""
        cfg = self.config
        N, Ds, Bw = c.shape[0], cfg.mamba_d_ssm, cfg.bc_width
        G, St = cfg.mamba_n_groups, cfg.mamba_d_state
        delta = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(w["a_log"].astype(jnp.float32))
        return (c[:, :Ds].reshape(N, cfg.mamba_n_heads, cfg.mamba_d_head),
                delta, A, c[:, Ds:Ds + Bw].reshape(N, G, St),
                c[:, Ds + Bw:].reshape(N, G, St))

    def _ssm_out(self, w, y, xs, z):
        """The scan's output y [N, H, P] float32, its input xs and the gate
        z [N, Ds] → the branch's output [N, D]."""
        cfg = self.config
        f32 = jnp.float32
        with jax.named_scope("ssd_out"):
            y = y + w["d_skip"].astype(f32)[None, :, None] * xs.astype(f32)
            N, G = y.shape[0], cfg.mamba_n_groups
            y = y.reshape(N, G, -1) * jax.nn.silu(
                z.astype(f32)).reshape(N, G, -1)
            y = y * lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                              + cfg.rms_norm_eps)
            y = (y.reshape(N, -1) * w["ssm_norm"].astype(f32)).astype(z.dtype)
            return _times(mm(y, w["out_proj"]), cfg.ssm_out_multiplier)

    def _ssm_prompt(self, w, u, valid, length, dense: bool):
        """A prompt's rows u [T, D] → (the branch's output [T, D], S at the
        last real position [H, N, P] float32, the last K-1 real inputs of the
        convolution [K-1, conv width])."""
        cfg = self.config
        K = cfg.mamba_d_conv
        z, a, dt = self._ssm_in(w, u)
        with jax.named_scope("ssd_conv"):
            c = jax.nn.silu(_ssm.causal_conv(a, w["conv_w"], w["conv_b"])
                            ).astype(u.dtype)
            tail = lax.dynamic_slice_in_dim(
                jnp.concatenate([jnp.zeros((K - 1, a.shape[1]), a.dtype), a]),
                length, K - 1, axis=0)
        with jax.named_scope("ssd_scan"):
            xs, delta, A, B, C = self._ssm_split(w, c, dt)
            delta = jnp.where(valid[:, None], delta, 0.0)
            if dense:
                y, S = _ssd.ssd_scan_xla(xs, delta, A, B, C)
            else:
                y, S = _ssd.ssd_scan(xs, delta, A, B, C,
                                     chunk=cfg.mamba_chunk_size)
        return self._ssm_out(w, y, xs, z), S, tail

    def _qkv(self, w, u, positions, dtype):
        """u [N, D] → q [N, nh, dh] after its rotation, the cache rows [k
        (rotated) | v] [N, 2·kw]."""
        cfg = self.config
        N, dh = u.shape[0], cfg.head_dim
        with jax.named_scope("attn_qkv"):
            qkv = mm(_times(u, cfg.attention_in_multiplier), w["wqkv"])
            q = qkv[:, :cfg.q_width].reshape(N, cfg.num_attention_heads, dh)
            k = _times(qkv[:, cfg.q_width:cfg.q_width + cfg.kv_width],
                       cfg.key_multiplier
                       ).reshape(N, cfg.num_key_value_heads, dh)
            v = qkv[:, cfg.q_width + cfg.kv_width:]
        with jax.named_scope("attn_rope"):
            q = rotary(q, positions, cfg.rope_theta)
            k = rotary(k, positions, cfg.rope_theta)
        return q, jnp.concatenate([k.reshape(N, cfg.kv_width), v],
                                  axis=-1).astype(dtype)

    def _attn_out(self, w, o, dtype):
        with jax.named_scope("attn_out"):
            o = o.reshape(o.shape[0], -1).astype(dtype)
            return _times(mm(o, w["wo"]),
                          self.config.attention_out_multiplier)

    def _embed(self, p, tokens):
        return _times(p["emb"][tokens], self.config.embedding_multiplier)

    def _head(self, p, x):
        with jax.named_scope("lm_head"):
            return lax.dot_general(
                self._rms(x, p["final_norm"]), p["head"],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * self.config.lm_head_multiplier

    def _prompt_layer(self, w, x, positions, valid, length, cache_dtype,
                      dense: bool):
        """One layer over a prompt's rows x [T, D] → (x', S, tail, the
        layer's cache rows [T, 2·kw])."""
        cfg = self.config
        u = self._rms(x, w["ln1"])
        out_ssm, S, tail = self._ssm_prompt(w, u, valid, length, dense)
        q, rows = self._qkv(w, u, positions, cache_dtype)
        with jax.named_scope("attn"):
            o = _gqa.prefill_attention_xla(q, rows, cfg.num_key_value_heads) \
                if dense else _gqa.prefill_attention(
                    q, rows, cfg.num_key_value_heads, length)
        x = x + out_ssm + self._attn_out(w, o, x.dtype)
        return self._mlp(w, x), S, tail, rows

    # -- full forward (the parity anchor) ----------------------------------
    def full_logits(self, plist, tokens, lengths=None):
        """tokens [B, T] int32 → logits [B, T, V] float32: every position
        through every layer, dense masked attention, the recurrence one
        position at a time, no cache and no kernel."""
        p, lay = self._unpack(plist)
        B, T = tokens.shape
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)
        pos = jnp.arange(T, dtype=jnp.int32)

        def one(toks, length):
            def layer(x, w):
                x, _, _, _ = self._prompt_layer(
                    w, x, pos, pos < length, length,
                    jnp.dtype(self.config.dtype), dense=True)
                return x, None

            x, _ = lax.scan(layer, self._embed(p, toks), lay)
            return self._head(p, x)

        return jax.vmap(one)(tokens, lengths)

    # -- prefill -----------------------------------------------------------
    def prefill(self, plist, state, tokens, length, slot, block_table, seed,
                temperature, top_k):
        """state ``[kv pool, S, conv]``, tokens [1, Tb] (bucket-padded),
        length [] int32, slot [] int32 (the slot whose rows this prompt
        fills), block_table [MB] int32 → ([next_token [], logits [V]],
        state').  Every layer's row of every real position lands in the
        request's blocks, pad positions in trash block 0; the slot's
        recurrent rows and convolution tails are overwritten whole."""
        cfg = self.config
        p, lay = self._unpack(plist)
        kv, hs, conv = state
        bs = kv.shape[2]
        pos, valid, blocks, last = prompt_addresses(
            length, tokens.shape[1], block_table, bs)

        def layer(carry, xs):
            x, kv = carry
            w, i = xs
            x, S, tail, rows = self._prompt_layer(
                w, x, pos, valid, length, kv.dtype, dense=False)
            with jax.named_scope("kv_cache_write"):
                kv = kv.at[i, blocks, pos % bs].set(rows)
            return (x, kv), (S, tail)

        (x, kv), (S_new, tails) = lax.scan(
            layer, (self._embed(p, tokens[0]), kv),
            (lay, jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32)))
        zero = jnp.zeros((), slot.dtype)
        with jax.named_scope("ssd_scan"):
            hs = lax.dynamic_update_slice(
                hs, S_new[:, None], (zero, slot, zero, zero, zero))
            conv = lax.dynamic_update_slice(
                conv, tails[:, None].astype(conv.dtype),
                (zero, slot, zero, zero))
        logits = self._head(p, x[last][None])[0]
        with jax.named_scope("sampling"):
            tok = sample_first(logits, seed, temperature, top_k)
        return [tok, logits], [kv, hs, conv]

    # -- decode step -------------------------------------------------------
    def decode_step(self, plist, state, tokens, positions, block_tables,
                    seeds, steps, temperature, top_k, attn_impl=None):
        """state ``[kv pool, S, conv]``, tokens / positions [S],
        block_tables [S, MB] → ([next_tokens [S], logits [S, V]], state')."""
        del attn_impl           # one path: the kernels choose by shape alone
        cfg = self.config
        p, lay = self._unpack(plist)
        kv, hs, conv = state
        bs = kv.shape[2]
        cl, _, _, blocks = step_addresses(positions, block_tables, bs)

        def layer(carry, xs):
            x, kv, hs, conv = carry
            w, i = xs
            u = self._rms(x, w["ln1"])
            z, a, dt = self._ssm_in(w, u)
            with jax.named_scope("ssd_conv"):
                c, tail = _ssm.conv_step(
                    lax.dynamic_index_in_dim(conv, i, keepdims=False), a,
                    w["conv_w"], w["conv_b"])
                c = jax.nn.silu(c).astype(u.dtype)
                conv = lax.dynamic_update_index_in_dim(
                    conv, tail.astype(conv.dtype), i, 0)
            with jax.named_scope("ssd_scan"):
                xs_, delta, A, B, C = self._ssm_split(w, c, dt)
                y, hs = _ssd.ssd_state_step(hs, i, xs_, delta, A, B, C)
            out_ssm = self._ssm_out(w, y, xs_, z)
            q, rows = self._qkv(w, u, positions, kv.dtype)
            with jax.named_scope("kv_cache_write"):
                kv = kv.at[i, blocks, positions % bs].set(rows)
            with jax.named_scope("attn"):
                o = _gqa.decode_attention(q, kv, block_tables, cl, i,
                                          cfg.num_key_value_heads)
            x = x + out_ssm + self._attn_out(w, o, x.dtype)
            return (self._mlp(w, x), kv, hs, conv), None

        (x, kv, hs, conv), _ = lax.scan(
            layer, (self._embed(p, tokens), kv, hs, conv),
            (lay, jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32)))
        logits = self._head(p, x)
        with jax.named_scope("sampling"):
            toks = sample(logits, seeds, steps, temperature, top_k)
        return [toks, logits], [kv, hs, conv]


MODEL_TYPES[MODEL_TYPE] = FalconH1LM.from_dict

__all__ = ["FalconH1Config", "FalconH1LM", "FalconH1Observer",
           "param_shapes", "init_tensor", "mup_vector"]
