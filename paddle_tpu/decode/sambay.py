"""A decoder-hybrid-decoder LM (SambaY, arXiv:2507.06607) for the decode
plane: Phi-4-mini-flash-reasoning's stack, configured by its published keys.

Every layer is ``x ← x + mixer(LN(x))``, ``x ← x + W_down(silu(W_gate u) ⊙
W_up u)`` with ``u = LN'(x)`` (LayerNorm with weight and bias); a final
LayerNorm; the head is the embedding (tied).  There is NO positional
encoding.  With ``L`` layers the mixers are, by index:

- **state-space (Mamba-1)**, layers 0, 2, …, L/2: ``[a; z] = W_in u``, a
  causal depthwise convolution of ``a`` and ``silu``, ``[δ; B; C] = W_x c``,
  ``Δ = softplus(W_dt δ + b_dt)``, the selective scan ``h_t = exp(Δ_t ⊗ A) ⊙
  h_{t−1} + (Δ_t ⊙ c_t) ⊗ B_t``, ``y_t = h_t C_t + D ⊙ c_t``, output
  ``W_out (y ⊙ silu(z))`` (``kernels/ssm.py``).  A stream keeps ``h``
  (float32) and the last ``d_conv − 1`` inputs ``a``.  Layer L/2 also hands
  ``m_t = y_t`` (before the gate) on as the *memory*.
- **window attention**, layers 1, 3, …, L/2 − 1: differential attention
  (two softmaxes a head, ``kernels/diffattn.py``), key ``j`` visible to
  query ``t`` iff ``0 ≤ t − j < sliding_window``.  A stream keeps its last
  ``sliding_window`` rows in a ring.
- **full attention**, layer L/2 + 1: the same with every ``j ≤ t`` visible.
  Its K/V rows are the only rows of the paged pool.
- **gated memory unit**, layers L/2 + 2, L/2 + 4, …: ``W_2 (silu(W_1 u_t) ⊙
  m_t)`` with the memory of the same token.  Stateless.
- **cross attention**, layers L/2 + 3, L/2 + 5, …: a query of its own over
  the FULL layer's K/V rows, differential with the layer's own ``λ`` and
  norm.  It writes no cache.

So a stream's state is of three kinds (:class:`~paddle_tpu.decode.cache.
HybridStateCache`): blocks of one paged pool that eight layers read, a
window ring a slot a window layer, and a recurrent row a slot a state-space
layer.  The last two are addressed by slot (``slot_state``).

**Two prefill depths.**  Nothing above layer L/2 + 1 writes a cache, so
``prefill`` runs the prompt through layers 0 … L/2 + 1 and only the last
real position through the rest and the head — the architecture's linear-time
prefill, exact.  Pad positions of a bucket have ``Δ = 0`` (the state passes
through them), and the convolution's tail and the ring are taken at the last
real positions.

**Programs scan over layers**: the (state-space, window) pairs and the
(memory unit, cross attention) pairs are ``lax.scan``s over stacked weights
(``sp.*`` ``[L/4, …]``, ``cp.*`` ``[L/4 − 1, …]``) with layers L/2 and L/2 + 1
(``ms.*``, ``mf.*``) between, so a program holds one pair's code, not L
layers'.

The model is an :class:`~paddle_tpu.decode.adapter.LMAdapter`, so a
:class:`~paddle_tpu.decode.engine.DecodeEngine` serves it as it is.  There
is no suffix prefill over recurrent state, so ``supports`` is empty: a prefix
cache, overcommit and beam sessions refuse this model at build.

Weights, residual stream, pool and rings are ``dtype`` (bf16 as deployed);
matmuls accumulate in float32; softmax, norm statistics, ``Δ``, ``exp(ΔA)``
and ``h`` are float32.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .adapter import (MODEL_TYPES, ConfigDict, LMAdapter, PoolObserver,
                      init_tensor as _init_tensor, mm, prompt_addresses,
                      sample, sample_first, step_addresses, sub,
                      walked_blocks)
from .cache import HybridStateCache
from ..kernels import diffattn as _da
from ..kernels import ssm as _ssm
from ..observability import trace as _trace

MODEL_TYPE = "phi4flash"
_SUBLN_EPS = 1e-5
_DT_MIN, _DT_MAX, _DT_FLOOR = 1e-3, 1e-1, 1e-4


@dataclasses.dataclass(frozen=True)
class SambaYConfig(ConfigDict):
    """The published keys this model reads, under their published names; the
    state-space sizes, which the published config leaves to the family's
    defaults; the deployment's per-stream ``max_seq_len`` and the weights'
    ``dtype``."""

    vocab_size: int
    hidden_size: int = 256
    num_hidden_layers: int = 8
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    intermediate_size: int = 512
    sliding_window: int = 8
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None
    max_seq_len: int = 128
    dtype: str = "bfloat16"
    model_type = MODEL_TYPE

    def __post_init__(self):
        L = self.num_hidden_layers
        if L < 8 or L % 4 or self.mb_per_layer != 2:
            raise ValueError(
                f"the stack needs num_hidden_layers a multiple of 4, at "
                f"least 8, and mb_per_layer 2 (got {L}, "
                f"{self.mb_per_layer})")
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2 \
                or (self.num_attention_heads // 2) % self.n_kv:
            raise ValueError("differential attention pairs the heads: even "
                             "numbers, K/V pairs dividing the query pairs")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def n_heads(self) -> int:
        """Differential heads: pairs of the published heads."""
        return self.num_attention_heads // 2

    @property
    def n_kv(self) -> int:
        return self.num_key_value_heads // 2

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def rank(self) -> int:
        return self.dt_rank or -(-self.hidden_size // 16)

    @property
    def self_pairs(self) -> int:
        return self.num_hidden_layers // 4

    @property
    def cross_pairs(self) -> int:
        return self.num_hidden_layers // 4 - 1

    @property
    def ssm_layers(self) -> int:
        return self.self_pairs + 1


def lambda_init(i) -> np.ndarray:
    """Differential attention's ``λ_init`` of layer ``i``."""
    return (0.8 - 0.6 * np.exp(-0.3 * np.asarray(i, np.float64))
            ).astype(np.float32)


def param_shapes(cfg: SambaYConfig) -> Dict[str, tuple]:
    """name → (shape, init): a float is the std of a normal; ``norm`` a norm
    weight (1 + 0.1 N), ``bias`` a bias (0.02 N), ``lam`` a λ vector (0.1 N),
    ``a_log`` log(1..N) a channel, ``dt_bias`` the inverse softplus of a
    step size log-uniform in [1e-3, 1e-1], ``skip`` ones (Mamba's own
    initialisation of A, b_dt and D)."""
    D, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    Di, N, K, R = cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.rank
    pair, kw = 2 * cfg.head_dim, cfg.kv_width
    P, C = cfg.self_pairs, cfg.cross_pairs

    def block(lead):
        return {"ln1_g": (lead + (D,), "norm"), "ln1_b": (lead + (D,), "bias"),
                "ln2_g": (lead + (D,), "norm"), "ln2_b": (lead + (D,), "bias"),
                "mlp_gate": (lead + (D, F), D ** -0.5),
                "mlp_up": (lead + (D, F), D ** -0.5),
                "mlp_down": (lead + (F, D), F ** -0.5)}

    def ssm(lead):
        return dict(block(lead), **{
            "in_proj": (lead + (D, 2 * Di), D ** -0.5),
            "conv_w": (lead + (K, Di), K ** -0.5),
            "conv_b": (lead + (Di,), "bias"),
            "x_proj": (lead + (Di, R + 2 * N), (3 * Di) ** -0.5),
            "dt_w": (lead + (R, Di), (3 * R) ** -0.5),
            "dt_b": (lead + (Di,), "dt_bias"),
            "a_log": (lead + (N, Di), "a_log"),
            "skip": (lead + (Di,), "skip"),
            "out_proj": (lead + (Di, D), Di ** -0.5)})

    def lam(lead):
        return {"wo": (lead + (D, D), D ** -0.5), "bo": (lead + (D,), "bias"),
                "subln": (lead + (pair,), "norm"),
                **{k: (lead + (cfg.head_dim,), "lam")
                   for k in ("lam_q1", "lam_k1", "lam_q2", "lam_k2")}}

    def attn(lead):
        return dict(block(lead), **lam(lead), **{
            "wqkv": (lead + (D, D + 2 * kw), D ** -0.5),
            "bqkv": (lead + (D + 2 * kw,), "bias")})

    def cross(lead):
        return dict(block(lead), **lam(lead), **{
            "wq": (lead + (D, D), D ** -0.5), "bq": (lead + (D,), "bias")})

    def gmu(lead):
        return dict(block(lead), **{"w1": (lead + (D, Di), D ** -0.5),
                                    "w2": (lead + (Di, D), Di ** -0.5)})

    out = {"emb": ((V, D), D ** -0.5), "final_g": ((D,), "norm"),
           "final_b": ((D,), "bias")}
    for prefix, make, lead in (("sp.s.", ssm, (P,)), ("sp.w.", attn, (P,)),
                               ("ms.", ssm, ()), ("mf.", attn, ()),
                               ("cp.g.", gmu, (C,)), ("cp.c.", cross, (C,))):
        out.update({prefix + k: v for k, v in make(lead).items()})
    return out


def init_tensor(key, shape: tuple, init, dtype):
    """One tensor of :func:`param_shapes` from a PRNG key (jit-able with
    ``shape``, ``init`` and ``dtype`` static): Mamba's own three and λ here,
    the rest by :func:`~paddle_tpu.decode.adapter.init_tensor`."""
    f32 = jnp.float32
    if init == "skip":
        w = jnp.ones(shape, f32)
    elif init == "a_log":
        n = jnp.log(jnp.arange(1, shape[-2] + 1, dtype=f32))
        w = jnp.broadcast_to(n[:, None], shape)
    elif init == "dt_bias":
        u = jax.random.uniform(key, shape, f32)
        dt = jnp.maximum(jnp.exp(u * (math.log(_DT_MAX) - math.log(_DT_MIN))
                                 + math.log(_DT_MIN)), _DT_FLOOR)
        w = dt + jnp.log(-jnp.expm1(-dt))
    else:
        return _init_tensor(key, shape, 0.1 if init == "lam" else init,
                            dtype)
    return w.astype(dtype)


class SambaYObserver(PoolObserver):
    """``decode.<engine>.*`` series of this model: the common ones and the
    pool's, and its own of the selective scans and the window.

    Its walks (:meth:`~paddle_tpu.decode.adapter.PoolObserver.count_walks`):
    the pool's readers (the full layer and the cross layers) fetch a live
    stream's ``ceil(context / block_tokens)`` blocks of the engine's ``blocks
    a slot``, a window layer's ring ``ceil(min(context, W) / ring_rows)`` of
    its ``W / ring_rows``, and each of them one block of an idle slot."""

    def __init__(self, name: str, cache, config: SambaYConfig, table_shape):
        super().__init__(name, cache, config, table_shape)
        sc = self.series
        self.prefill_scan = sc.counter(
            "prefill_scan_tokens", "real prompt positions the selective "
            "scans of prefills ran, summed over the state-space layers")
        self.prefill_pairs = sc.counter(
            "prefill_window_pairs", "(query, visible key) pairs of one "
            "window layer, summed over prefills")
        self.window_tokens = sc.counter(
            "step_window_tokens", "ring rows a decode step's streams hold "
            "(context cut at the window), summed over steps (one layer)")
        sc.gauge("window_state_bytes").set(cache.window_state_bytes)
        sc.gauge("recurrent_state_bytes").set(cache.recurrent_state_bytes)

    def prefill(self, extra, prompt: int, bucket: int) -> None:
        with _trace.span("decode::prefill.observe") as sp:
            W = self.config.sliding_window
            full = min(prompt, W)
            pairs = full * (full + 1) // 2 + (prompt - full) * W
            scan = prompt * self.config.ssm_layers
            self.count_prompt(prompt, bucket)
            self.prefill_scan.inc(scan)
            self.prefill_pairs.inc(pairs)
            sp.annotate(prefill_scan_tokens=scan, prefill_window_pairs=pairs,
                        prefill_tokens_sq=prompt * prompt)

    def step(self, extra, contexts) -> None:
        cfg, cache = self.config, self.cache
        with _trace.span("decode::step.observe") as sp:
            context, streams = self.count_streams(contexts)
            window = int(np.minimum(contexts, cfg.sliding_window).sum())
            self.window_tokens.inc(window)
            sp.annotate(step_context_tokens=context,
                        step_window_tokens=window, step_streams=streams)
        readers = 1 + cfg.cross_pairs
        ring = walked_blocks(np.minimum(contexts, cfg.sliding_window),
                             cache.ring_rows, self._slots)
        self.count_walks(
            readers * self.pool_walk(contexts) + cfg.self_pairs * ring,
            self._slots * (readers * self._slot_blocks
                           + cfg.self_pairs * cache.ring_blocks))


class SambaYLM(LMAdapter):
    """One decoder-hybrid-decoder LM: config + the jit-ready functions.  The
    one kernel choice is the engine's ``attn_impl`` for the decode step's
    attention over the pool and the rings."""

    # two of this model's three kinds of state live in slot rows
    slot_state = True
    config_class = SambaYConfig
    observer_class = SambaYObserver
    param_shapes = staticmethod(param_shapes)
    init_tensor = staticmethod(init_tensor)

    def __init__(self, config: SambaYConfig):
        super().__init__(config)
        half = config.num_hidden_layers // 2
        self._lam_swa = lambda_init(np.arange(1, half, 2))
        self._lam_full = lambda_init(half + 1)
        self._lam_cross = lambda_init(
            np.arange(half + 3, config.num_hidden_layers, 2))

    # -- what an engine asks of a model ------------------------------------
    def _make_cache(self, num_blocks: int, block_tokens: int, dtype: str,
                    slots: int) -> HybridStateCache:
        cfg = self.config
        return HybridStateCache(
            cfg.kv_width, num_blocks, block_tokens, slots, dtype=dtype,
            rings=(cfg.self_pairs, cfg.sliding_window),
            recurrent=(cfg.ssm_layers, (cfg.d_state, cfg.d_inner)),
            tails=(cfg.ssm_layers, cfg.d_conv, cfg.d_inner))

    def _unpack(self, plist) -> Dict[str, jnp.ndarray]:
        return dict(zip(self.param_names(), plist))

    # -- shared layer math -------------------------------------------------
    def _ln(self, x, g, b):
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
        return ((x32 - mu) * lax.rsqrt(var + self.config.layer_norm_eps)
                * g.astype(jnp.float32) + b.astype(jnp.float32)
                ).astype(x.dtype)

    def _block(self, w, x, mixer):
        """One layer over rows x [N, D]; ``mixer(u)`` is the layer's own."""
        x = x + mixer(self._ln(x, w["ln1_g"], w["ln1_b"]))
        u = self._ln(x, w["ln2_g"], w["ln2_b"])
        with jax.named_scope("mlp"):
            g = jnp.dot(u, w["mlp_gate"], preferred_element_type=jnp.float32)
            up = jnp.dot(u, w["mlp_up"], preferred_element_type=jnp.float32)
            return x + mm((jax.nn.silu(g) * up).astype(x.dtype),
                           w["mlp_down"])

    def _ssm_in(self, w, u):
        with jax.named_scope("ssm_in"):
            az = mm(u, w["in_proj"])
        Di = self.config.d_inner
        return az[:, :Di], az[:, Di:]

    def _ssm_coeffs(self, w, c):
        """c [N, Di] → Δ [N, Di], B, C [N, d_state], A [d_state, Di], all
        float32."""
        cfg = self.config
        R, N = cfg.rank, cfg.d_state
        dbc = jnp.dot(c, w["x_proj"], preferred_element_type=jnp.float32)
        delta = jax.nn.softplus(
            jnp.dot(dbc[:, :R].astype(c.dtype), w["dt_w"],
                    preferred_element_type=jnp.float32)
            + w["dt_b"].astype(jnp.float32))
        A = -jnp.exp(w["a_log"].astype(jnp.float32))
        return delta, dbc[:, R:R + N], dbc[:, R + N:], A

    def _ssm_out(self, w, y, c, z):
        """y, the scan's output, [N, Di] float32 → (the mixer's output
        [N, D], the memory ``y + D ⊙ c`` [N, Di])."""
        m = y + w["skip"].astype(jnp.float32) * c.astype(jnp.float32)
        with jax.named_scope("ssm_out"):
            gated = (m * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)
            return mm(gated, w["out_proj"]), m.astype(z.dtype)

    def _ssm_prompt(self, w, u, valid, length, dense: bool):
        """A prompt's rows u [T, D] → (output [T, D], memory [T, Di], h at
        the last real position [N, Di] float32, the last K-1 real inputs
        [K-1, Di])."""
        K = self.config.d_conv
        a, z = self._ssm_in(w, u)
        with jax.named_scope("ssm_conv"):
            c = jax.nn.silu(_ssm.causal_conv(a, w["conv_w"], w["conv_b"])
                            ).astype(u.dtype)
            tail = lax.dynamic_slice_in_dim(
                jnp.concatenate([jnp.zeros((K - 1, a.shape[1]), a.dtype), a]),
                length, K - 1, axis=0)
        with jax.named_scope("ssm_scan"):
            delta, B, C, A = self._ssm_coeffs(w, c)
            delta = jnp.where(valid[:, None], delta, 0.0)
            scan = _ssm.selective_scan_xla if dense else _ssm.selective_scan
            y, h = scan(c, delta, A, B, C)
        out, m = self._ssm_out(w, y, c, z)
        return out, m, h, tail

    def _ssm_step(self, w, u, h, tail):
        """One token a slot: u [S, D], h [S, N, Di], tail [S, K-1, Di] →
        (output, memory, h', tail')."""
        a, z = self._ssm_in(w, u)
        with jax.named_scope("ssm_conv"):
            conv, tail = _ssm.conv_step(tail, a, w["conv_w"], w["conv_b"])
            c = jax.nn.silu(conv).astype(u.dtype)
        with jax.named_scope("ssm_scan"):
            delta, B, C, A = self._ssm_coeffs(w, c)
            y, h = _ssm.selective_step(h, c, delta, A, B, C)
        out, m = self._ssm_out(w, y, c, z)
        return out, m, h, tail

    def _qkv(self, w, u, dtype):
        """u [N, D] → q [N, nh, 2·dh], the cache rows [k | v] [N, 2·kw]."""
        cfg = self.config
        qkv = mm(u, w["wqkv"]) + w["bqkv"]
        D = cfg.hidden_size
        return (qkv[:, :D].reshape(-1, cfg.n_heads, 2 * cfg.head_dim),
                qkv[:, D:].astype(dtype))

    def _cross_q(self, w, u):
        cfg = self.config
        with jax.named_scope("cross_q"):
            q = mm(u, w["wq"]) + w["bq"]
        return q.reshape(-1, cfg.n_heads, 2 * cfg.head_dim)

    def _diff_out(self, w, o2, lam0, dtype):
        """Both components' outputs o2 [N, nh, 2, 2·dh] float32 → the
        mixer's output [N, D]: ``(1 − λ_init) · RMSNorm(o¹ − λ o²)``, heads
        concatenated, ``W_o``."""
        with jax.named_scope("attn_out"):
            f32 = jnp.float32
            lam = jnp.exp(jnp.sum(w["lam_q1"].astype(f32)
                                  * w["lam_k1"].astype(f32))) \
                - jnp.exp(jnp.sum(w["lam_q2"].astype(f32)
                                  * w["lam_k2"].astype(f32))) + lam0
            o = o2[:, :, 0] - lam * o2[:, :, 1]
            o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + _SUBLN_EPS) * w["subln"].astype(f32)
            o = ((1.0 - lam0) * o).reshape(o.shape[0], -1).astype(dtype)
            return mm(o, w["wo"]) + w["bo"]

    def _gmu(self, w, u, m):
        with jax.named_scope("gmu"):
            g = jnp.dot(u, w["w1"], preferred_element_type=jnp.float32)
            return mm((jax.nn.silu(g) * m.astype(jnp.float32)
                        ).astype(u.dtype), w["w2"])

    def _head(self, p, x):
        with jax.named_scope("lm_head"):
            return lax.dot_general(
                self._ln(x, p["final_g"], p["final_b"]), p["emb"],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    # -- a prompt's trunk: layers 0 .. L/2 + 1 over every position ---------
    def _trunk(self, p, tokens, length, ring_src, cache_dtype, dense: bool):
        """tokens [T] → (x [T, D] after layer L/2 + 1, the memory [T, Di],
        the full layer's rows [T, 2·kw], h [P + 1, N, Di], tails [P + 1, K-1,
        Di], the window layers' ring rows [P, len(ring_src), 2·kw])."""
        cfg = self.config
        T = tokens.shape[0]
        valid = jnp.arange(T, dtype=jnp.int32) < length
        attend = _da.prefill_attention_xla if dense else _da.prefill_attention
        x = p["emb"][tokens]

        def self_pair(x, xs):
            w, lam0 = xs
            ws, ww = sub(w, "s."), sub(w, "w.")
            got = {}

            def ssm_mixer(u):
                out, _, got["h"], got["tail"] = self._ssm_prompt(
                    ws, u, valid, length, dense)
                return out

            def swa_mixer(u):
                with jax.named_scope("swa_qkv"):
                    q, rows = self._qkv(ww, u, cache_dtype)
                with jax.named_scope("swa_cache_write"):
                    got["ring"] = rows[ring_src]
                with jax.named_scope("swa_attn"):
                    o2 = attend(q, rows, cfg.n_kv, cfg.sliding_window)
                return self._diff_out(ww, o2, lam0, u.dtype)

            x = self._block(ws, x, ssm_mixer)
            x = self._block(ww, x, swa_mixer)
            return x, (got["h"], got["tail"], got["ring"])

        x, (hs, tails, rings) = lax.scan(
            self_pair, x, (sub(p, "sp."), jnp.asarray(self._lam_swa)))
        ws, wf = sub(p, "ms."), sub(p, "mf.")
        got = {}

        def ssm_mixer(u):
            out, got["m"], got["h"], got["tail"] = self._ssm_prompt(
                ws, u, valid, length, dense)
            return out

        def full_mixer(u):
            with jax.named_scope("full_qkv"):
                q, got["rows"] = self._qkv(wf, u, cache_dtype)
            with jax.named_scope("full_attn"):
                o2 = attend(q, got["rows"], cfg.n_kv, None)
            return self._diff_out(wf, o2, float(self._lam_full), u.dtype)

        x = self._block(ws, x, ssm_mixer)
        x = self._block(wf, x, full_mixer)
        hs = jnp.concatenate([hs, got["h"][None]])
        tails = jnp.concatenate([tails, got["tail"][None]])
        return x, got["m"], got["rows"], hs, tails, rings

    def _upper(self, p, x, m, attend):
        """Layers L/2 + 2 .. L − 1 over rows x [N, D] with their memory m
        [N, Di]; ``attend(q)`` is the cross attention over the full layer's
        rows (the paths differ only there)."""
        def cross_pair(x, xs):
            w, lam0 = xs
            wg, wc = sub(w, "g."), sub(w, "c.")

            def cross_mixer(u):
                with jax.named_scope("cross_attn"):
                    o2 = attend(self._cross_q(wc, u))
                return self._diff_out(wc, o2, lam0, u.dtype)

            x = self._block(wg, x, lambda u: self._gmu(wg, u, m))
            return self._block(wc, x, cross_mixer), None

        x, _ = lax.scan(cross_pair, x,
                        (sub(p, "cp."), jnp.asarray(self._lam_cross)))
        return x

    # -- full forward (the parity anchor) ----------------------------------
    def full_logits(self, plist, tokens, lengths=None):
        """tokens [B, T] int32 → logits [B, T, V] float32: every position
        through every layer, dense masked attention, a sequential scan, no
        cache and no kernel."""
        p = self._unpack(plist)
        cfg = self.config
        B, T = tokens.shape
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)
        src = jnp.zeros((1,), jnp.int32)

        def one(toks, length):
            x, m, rows, _, _, _ = self._trunk(
                p, toks, length, src, jnp.dtype(cfg.dtype), dense=True)
            x = self._upper(p, x, m, lambda q: _da.prefill_attention_xla(
                q, rows, cfg.n_kv, None))
            return self._head(p, x)

        return jax.vmap(one)(tokens, lengths)

    # -- prefill -----------------------------------------------------------
    def prefill(self, plist, state, tokens, length, slot, block_table, seed,
                temperature, top_k):
        """state ``[kv pool, rings, h, conv]``, tokens [1, Tb]
        (bucket-padded), length [] int32, slot [] int32 (the slot whose rows
        this prompt fills), block_table [MB] int32 → ([next_token [], logits
        [V]], state').  The full layer's row of every real position lands in
        the request's blocks, pad positions in trash block 0; the slot's
        rings, ``h`` rows and convolution tails are overwritten whole."""
        cfg = self.config
        p = self._unpack(plist)
        kv, rings, hs, conv = state
        Tb = tokens.shape[1]
        bs, W = kv.shape[2], cfg.sliding_window
        pos, _, blocks, last = prompt_addresses(length, Tb, block_table, bs)
        # ring index r holds the last real position that is r mod W
        r = jnp.arange(W, dtype=jnp.int32)
        src = jnp.clip(r + W * ((length - 1 - r) // W), 0, Tb - 1)
        x, m, rows, h_new, tails, ring_rows = self._trunk(
            p, tokens[0], length, src, kv.dtype, dense=False)
        zero = jnp.zeros((), slot.dtype)
        with jax.named_scope("kv_cache_write"):
            kv = kv.at[0, blocks, pos % bs].set(rows)
        with jax.named_scope("swa_cache_write"):
            nrb = W // rings.shape[2]
            rings = lax.dynamic_update_slice(
                rings, ring_rows.reshape(ring_rows.shape[0], nrb,
                                         *rings.shape[2:]),
                (zero, slot * nrb, zero, zero))
        with jax.named_scope("ssm_scan"):
            at = (zero, slot, zero, zero)
            hs = lax.dynamic_update_slice(hs, h_new[:, None], at)
            conv = lax.dynamic_update_slice(
                conv, tails[:, None].astype(conv.dtype), at)
        x = self._upper(
            p, x[last][None], m[last][None],
            lambda q: _da.row_attention(q[0], rows, pos <= last,
                                        cfg.n_kv)[None])
        logits = self._head(p, x)[0]
        with jax.named_scope("sampling"):
            tok = sample_first(logits, seed, temperature, top_k)
        return [tok, logits], [kv, rings, hs, conv]

    # -- decode step -------------------------------------------------------
    def decode_step(self, plist, state, tokens, positions, block_tables,
                    seeds, steps, temperature, top_k, attn_impl=None):
        """state ``[kv pool, rings, h, conv]``, tokens / positions [S],
        block_tables [S, MB] → ([next_tokens [S], logits [S, V]], state')."""
        cfg = self.config
        p = self._unpack(plist)
        kv, rings, hs, conv = state
        bs, W = kv.shape[2], cfg.sliding_window
        rb = rings.shape[2]
        nrb = W // rb
        cl, _, slots, blocks = step_addresses(positions, block_tables, bs)
        wl = jnp.minimum(cl, W)
        at = positions % W
        ring_tables = slots[:, None] * nrb + jnp.arange(nrb,
                                                        dtype=jnp.int32)
        ring_blocks = slots * nrb + at // rb
        x = p["emb"][tokens]

        def ssm_mixer(ws, i, hs, conv, got):
            def mixer(u):
                out, got["m"], h, tail = self._ssm_step(
                    ws, u, lax.dynamic_index_in_dim(hs, i, keepdims=False),
                    lax.dynamic_index_in_dim(conv, i, keepdims=False))
                got["hs"] = lax.dynamic_update_index_in_dim(hs, h, i, 0)
                got["conv"] = lax.dynamic_update_index_in_dim(
                    conv, tail.astype(conv.dtype), i, 0)
                return out
            return mixer

        def self_pair(carry, xs):
            x, rings, hs, conv = carry
            w, i, lam0 = xs
            ws, ww = sub(w, "s."), sub(w, "w.")
            got = {}

            def swa_mixer(u):
                with jax.named_scope("swa_qkv"):
                    q, rows = self._qkv(ww, u, rings.dtype)
                with jax.named_scope("swa_cache_write"):
                    got["rings"] = rings.at[i, ring_blocks, at % rb].set(rows)
                with jax.named_scope("swa_attn"):
                    o2 = _da.decode_attention(
                        q, got["rings"], ring_tables, wl, i, cfg.n_kv,
                        impl=attn_impl, name="diff_ring_decode_attn")
                return self._diff_out(ww, o2, lam0, u.dtype)

            x = self._block(ws, x, ssm_mixer(ws, i, hs, conv, got))
            x = self._block(ww, x, swa_mixer)
            return (x, got["rings"], got["hs"], got["conv"]), None

        P = cfg.self_pairs
        (x, rings, hs, conv), _ = lax.scan(
            self_pair, (x, rings, hs, conv),
            (sub(p, "sp."), jnp.arange(P, dtype=jnp.int32),
             jnp.asarray(self._lam_swa)))
        ws, wf = sub(p, "ms."), sub(p, "mf.")
        got = {}

        def full_mixer(u):
            with jax.named_scope("full_qkv"):
                q, rows = self._qkv(wf, u, kv.dtype)
            with jax.named_scope("kv_cache_write"):
                got["kv"] = kv.at[0, blocks, positions % bs].set(rows)
            with jax.named_scope("full_attn"):
                o2 = _da.decode_attention(q, got["kv"], block_tables, cl, 0,
                                          cfg.n_kv, impl=attn_impl)
            return self._diff_out(wf, o2, float(self._lam_full), u.dtype)

        x = self._block(ws, x, ssm_mixer(ws, P, hs, conv, got))
        x = self._block(wf, x, full_mixer)
        hs, conv, kv = got["hs"], got["conv"], got["kv"]
        x = self._upper(p, x, got["m"], lambda q: _da.decode_attention(
            q, kv, block_tables, cl, 0, cfg.n_kv, impl=attn_impl))
        logits = self._head(p, x)
        with jax.named_scope("sampling"):
            toks = sample(logits, seeds, steps, temperature, top_k)
        return [toks, logits], [kv, rings, hs, conv]


MODEL_TYPES[MODEL_TYPE] = SambaYLM.from_dict

__all__ = ["SambaYConfig", "SambaYLM", "SambaYObserver", "param_shapes",
           "init_tensor", "lambda_init"]
