"""A latent-attention (MLA) mixture-of-experts LM for the decode plane:
DeepSeek-V2's block, configured by its published keys, and what three later
groups of keys make of it (``model_type`` ``xing4_0``: a low-rank query, a
sigmoid router with a selection bias, and several residual streams).

``x ← x + attn(RMSNorm(x))``, ``x ← x + ffn(RMSNorm(x))``, a final RMSNorm,
untied embedding and head, no bias anywhere — the residual path of
``hc_mult`` 0 (:class:`PlainResidual`).  With ``hc_mult`` n > 0 a token keeps
n streams ``X`` [n, D] and every sub-layer ``F`` mixes them by
manifold-constrained hyper-connections (:class:`HyperResidual`,
``kernels/mhc.py``): ``h = Σ_j H_pre[j] X[j]``, ``X'[i] = Σ_j H_res[i, j]
X[j] + H_post[i] F(RMSNorm(h))``, the three maps functions of the token's own
streams and ``H_res`` projected towards the doubly stochastic matrices by
``hc_sinkhorn_iters`` Sinkhorn rounds; the embedding is replicated into the
streams and their sum goes to the final norm.

- **Attention** is multi-head latent attention; the query is one ``W_q``, or
  with a ``q_lora_rank`` ``W_qb · RMSNorm(W_qa · x)``.  A token's keys and
  values
  are functions of one compressed row ``c`` (``kv_lora_rank`` wide, after its
  own RMSNorm) and one rotary key ``k_pe`` shared by all heads, and that row
  is ALL the cache holds (:class:`~paddle_tpu.decode.cache.PagedLatentCache`).
  Two paths compute the same scores: a prompt's *prefill* expands ``k_nope``
  and ``v`` from ``c`` and runs causal flash attention at 192 (q·k) and 128
  (v) a head; a *decode step* never expands the cache — ``W_kvb``'s key part
  is absorbed into the query and its value part applied after the weighted
  sum of rows (``kernels/mla.py``).
- **Positions** are rotary on the ``qk_rope_head_dim`` slice of each head, in
  the half-split form, with YaRN's frequency interpolation
  (:func:`yarn_inv_freq`) and its softmax temperature (:func:`softmax_scale`).
- **Feed-forward** is SwiGLU: dense in the first ``first_k_dense_replace``
  layers, then ``n_routed_experts`` routed experts at top-k of a float32
  router beside ``n_shared_experts`` shared ones as one wide SwiGLU
  (``kernels/moe.py``).  ``scoring_func`` ``softmax``: the weights are the
  chosen scores themselves unless ``norm_topk_prob``.  ``sigmoid`` with
  ``topk_method`` ``noaux_tc``: the experts are chosen by ``scores +
  router_bias`` (``e_score_correction_bias``) and weighed by the SCORES, over
  their sum + 1e-20 where ``norm_topk_prob``; both times
  ``routed_scaling_factor``.  Every assignment is computed; none is dropped.

The model is an :class:`~paddle_tpu.decode.adapter.LMAdapter`, so a
:class:`~paddle_tpu.decode.engine.DecodeEngine` serves it as it is.  Its
programs return, beside token and logits, each MoE layer's load figures and
chosen experts and the first MoE layer's routed experts' input and output at
the rows the logits are taken at (what a check of the very programs that
served needs to hold the experts alone against a reference; they stay on the
device unless read) and, with several streams, that layer's feed-forward
sub-layer's streams and three maps at those rows; :class:`MLAObserver` turns
the load figures into ``decode.<engine>.*`` counters.  There is no suffix
prefill over the latent pool yet, so ``supports`` is empty: an engine asked for a prefix cache or
overcommit, and a beam session, refuse this model at build.

Weights and residual streams are ``dtype`` (bf16 as deployed); matmuls
accumulate in float32; softmax, router scores and norm statistics are float32,
as are the hyper-connections' ``Φ``, ``b``, ``α``, maps, Sinkhorn rounds and
both weighted sums.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .adapter import (MODEL_TYPES, ConfigDict, LatentAttention,
                      LaunchObserver, LMAdapter, RoutedLoadSeries,
                      prompt_addresses, rms_norm, sample, sample_first,
                      step_addresses, sub, swiglu)
from .cache import PagedLatentCache
from ..kernels import mhc as _mhc
from ..kernels import moe as _moe
from ..observability import trace as _trace

MODEL_TYPE = "deepseek_v2"
HYPER_MODEL_TYPE = "xing4_0"
ROUTE_EPS = 1e-20       # beside the chosen scores' sum (noaux_tc)
_ROPE_DEFAULT = {"factor": 1.0, "original_max_position_embeddings": 4096,
                 "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
                 "mscale_all_dim": 0.0}


@dataclasses.dataclass(frozen=True)
class MLAConfig(ConfigDict):
    """The published keys this model reads, under their published names,
    plus the deployment's per-stream ``max_seq_len`` and the weights'
    ``dtype``."""

    vocab_size: int
    hidden_size: int = 64
    num_hidden_layers: int = 3
    num_attention_heads: int = 4
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    kv_lora_rank: int = 32
    intermediate_size: int = 96
    moe_intermediate_size: int = 32
    n_routed_experts: int = 8
    num_experts_per_tok: int = 3
    n_shared_experts: int = 2
    first_k_dense_replace: int = 1
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict = dataclasses.field(
        default_factory=lambda: dict(_ROPE_DEFAULT))
    max_seq_len: int = 128
    dtype: str = "bfloat16"
    # the keys of ``xing4_0``; as they stand here they are DeepSeek-V2's model
    q_lora_rank: Optional[int] = None       # None: one W_q
    scoring_func: str = "softmax"
    topk_method: str = "greedy"             # noaux_tc: a selection bias
    hc_mult: int = 0                        # residual streams; 0: x + f(x)
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    model_type = MODEL_TYPE

    def __post_init__(self):
        if self.scoring_func not in _moe.SCORES:
            raise ValueError(f"unknown scoring_func {self.scoring_func!r}")
        if self.topk_method not in ("greedy", "noaux_tc"):
            raise ValueError(f"topk_method {self.topk_method!r} is not "
                             f"written down here (group limits are not)")
        if self.hc_mult and \
                self.mhc_h_res_clamp_min != -self.mhc_h_res_clamp_max:
            raise ValueError("the clip on H_res is symmetric here")


@dataclasses.dataclass(frozen=True)
class HyperMLAConfig(MLAConfig):
    """``xing4_0``: the same keys, saved under its own ``model_type``."""

    model_type = HYPER_MODEL_TYPE


def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1.0 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, rs: dict) -> np.ndarray:
    """``dim // 2`` rotary frequencies: below the correction range as they
    are, above it divided by ``factor``, a linear ramp between."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    factor = float(rs["factor"])
    if factor <= 1.0:
        return f.astype(np.float32)
    orig = float(rs["original_max_position_embeddings"])

    def corr(beta):
        return dim * math.log(orig / (beta * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(corr(float(rs["beta_fast"]))), 0)
    hi = min(math.ceil(corr(float(rs["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - lo)
                   / max(hi - lo, 1e-3), 0.0, 1.0)
    return ((f / factor) * ramp + f * (1.0 - ramp)).astype(np.float32)


def softmax_scale(cfg: MLAConfig) -> float:
    rs = cfg.rope_scaling
    m = _yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def rope_factor(cfg: MLAConfig) -> float:
    """What YaRN multiplies cos and sin by: ``m(mscale) / m(mscale_all_dim)``
    (1 where the two are equal, as published)."""
    rs = cfg.rope_scaling
    s = float(rs["factor"])
    return _yarn_mscale(s, float(rs["mscale"])) \
        / _yarn_mscale(s, float(rs["mscale_all_dim"]))


def is_moe_layer(cfg: MLAConfig, i: int) -> bool:
    return i >= cfg.first_k_dense_replace


def param_shapes(cfg: MLAConfig) -> Dict[str, tuple]:
    """name → (shape, std of a seeded random init; None: a norm weight)."""
    D, H, V = cfg.hidden_size, cfg.num_attention_heads, cfg.vocab_size
    dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim, cfg.kv_lora_rank)
    E, F = cfg.n_routed_experts, cfg.moe_intermediate_size
    Fs = cfg.n_shared_experts * F
    out = {"emb": ((V, D), 1.0), "final_norm": ((D,), None),
           "head": ((D, V), D ** -0.5)}
    n, rq = cfg.hc_mult, cfg.q_lora_rank
    for i in range(cfg.num_hidden_layers):
        L = f"l{i}."
        out.update({
            L + "attn_norm": ((D,), None), L + "ffn_norm": ((D,), None),
            L + "kv_norm": ((r,), None)})
        if rq is None:
            out[L + "wq"] = ((D, H * (dn + dr)), D ** -0.5)
        else:
            out.update({L + "wq_a": ((D, rq), D ** -0.5),
                        L + "q_norm": ((rq,), None),
                        L + "wq_b": ((rq, H * (dn + dr)), rq ** -0.5)})
        for s in ("attn", "ffn") if n else ():
            # float32 (:func:`param_dtype`); Φ as it is contracted
            out.update({
                f"{L}{s}_hc_phi": ((_mhc.n_maps(n), n * D), (n * D) ** -0.5),
                f"{L}{s}_hc_b": ((_mhc.n_maps(n),), 1.0),
                f"{L}{s}_hc_alpha": ((3,), None)})
        out.update({
            L + "wkva": ((D, r + dr), D ** -0.5),
            L + "wkvb": ((r, H * (dn + dv)), r ** -0.5),
            L + "wo": ((H * dv, D), (H * dv) ** -0.5)})
        if is_moe_layer(cfg, i):
            if cfg.topk_method == "noaux_tc":
                out[L + "router_bias"] = ((E,), 0.1)
            out.update({
                L + "router": ((D, E), D ** -0.5),
                L + "e_gate": ((E, D, F), D ** -0.5),
                L + "e_up": ((E, D, F), D ** -0.5),
                L + "e_down": ((E, F, D), F ** -0.5),
                L + "s_gate": ((D, Fs), D ** -0.5),
                L + "s_up": ((D, Fs), D ** -0.5),
                L + "s_down": ((Fs, D), Fs ** -0.5)})
        else:
            Fd = cfg.intermediate_size
            out.update({L + "w_gate": ((D, Fd), D ** -0.5),
                        L + "w_up": ((D, Fd), D ** -0.5),
                        L + "w_down": ((Fd, D), Fd ** -0.5)})
    return out


def param_dtype(cfg: MLAConfig, name: str):
    """The hyper-connections' tensors are float32; every other ``dtype``."""
    return jnp.dtype("float32" if "_hc_" in name else cfg.dtype)


class PlainResidual:
    """One stream: ``x + F(x)``."""

    def __init__(self, cfg: MLAConfig):
        pass

    def enter(self, emb):
        return emb

    def apply(self, p, prefix, x, fn, probe=None):
        """``fn``: the normed sub-layer, rows [N, D] → [N, D]."""
        return x + fn(x)

    def leave(self, x):
        return x


class HyperResidual:
    """``hc_mult`` streams a token, side by side along the lanes (``X`` [N,
    n·D]), mixed round every sub-layer by ``kernels/mhc.py``."""

    def __init__(self, cfg: MLAConfig):
        self.n, self.D = cfg.hc_mult, cfg.hidden_size
        self.args = (cfg.rms_norm_eps, cfg.hc_sinkhorn_iters,
                     cfg.mhc_h_res_clamp_max, cfg.hc_eps)

    def enter(self, emb):
        return jnp.tile(emb, (1, self.n))

    def apply(self, p, prefix, x, fn, probe=None):
        """``probe(x, H_pre, H_post, H_res)`` is handed what went into the
        mixing and its maps."""
        with jax.named_scope("mhc_pre"):
            h, h_pre, h_post, h_res = _mhc.mhc_pre(
                x, p[prefix + "_hc_phi"], p[prefix + "_hc_b"],
                p[prefix + "_hc_alpha"], *self.args)
        if probe is not None:
            probe(x, h_pre, h_post, h_res)
        y = fn(h)
        with jax.named_scope("mhc_post"):
            return _mhc.mhc_post(x, y, h_post, h_res)

    def leave(self, x):
        D = self.D
        total = x[:, :D].astype(jnp.float32)
        for j in range(1, self.n):
            total = total + x[:, j * D:(j + 1) * D].astype(jnp.float32)
        return total.astype(x.dtype)


class MLAObserver(LaunchObserver):
    """``decode.<engine>.*`` series of a routed latent-attention model: the
    common ones, the routed load (``extra[0]``: each MoE layer's
    ``[assignments, experts touched, largest load]``) and the latent pool's
    two gauges."""

    def __init__(self, name: str, cache, config, table_shape):
        super().__init__(name, cache, config, table_shape)
        self.routed = RoutedLoadSeries(
            self.series, buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512,
                                  1024, 2048, 4096, 8192))
        self.live_tokens = self.series.gauge("latent_live_tokens")
        self.series.gauge("latent_pool_bytes").set(cache.nbytes)
        # a model with several residual streams mixes them twice a layer a
        # real token; one stream: no series, no argument
        self._mixes = 2 * config.num_hidden_layers if config.hc_mult else 0
        if self._mixes:
            self.prefill_mhc = self.series.counter(
                "prefill_mhc_rows", "rows the prefills' residual mixing "
                "took: real prompt tokens x sub-layers")
            self.step_mhc = self.series.counter(
                "step_mhc_rows", "rows the decode steps' residual mixing "
                "took: live streams x sub-layers")

    def prefill(self, extra, prompt: int, bucket: int) -> None:
        with _trace.span("decode::prefill.observe") as sp:
            assignments = self.routed.count_prefill(np.asarray(extra[0]))
            self.count_prompt(prompt, bucket)
            sp.annotate(prefill_routed_assignments=assignments,
                        prefill_tokens_sq=prompt * prompt)
            if self._mixes:
                self.prefill_mhc.inc(prompt * self._mixes)
                sp.annotate(prefill_mhc_rows=prompt * self._mixes)

    def step(self, extra, contexts) -> None:
        with _trace.span("decode::step.observe") as sp:
            live_tokens = int(np.sum(contexts))
            assignments, touched = self.routed.count_step(
                np.asarray(extra[0]))
            self.context_tokens.inc(live_tokens)
            self.live_tokens.set(live_tokens)
            sp.annotate(step_routed_assignments=assignments,
                        step_experts_touched=touched,
                        step_context_tokens=live_tokens)
            if self._mixes:
                self.step_mhc.inc(len(contexts) * self._mixes)
                sp.annotate(step_mhc_rows=len(contexts) * self._mixes)


class _MoEOuts:
    """What a prefill or a step returns of its expert layers, collected a
    layer at a time (:meth:`MLATransformerLM._layer`)."""

    def __init__(self, cfg: MLAConfig):
        self.cfg = cfg
        self.loads, self.ids, self.probe, self.maps = [], [], None, None

    def add(self, load, ids, h, routed, rows) -> None:
        self.loads.append(load)
        self.ids.append(ids)
        if self.probe is None:
            self.probe = [h, routed] if rows is None else \
                [h[rows], routed[rows]]

    def mixing(self, rows):
        """The probe of the next expert layer's feed-forward mixing, if it
        is the first (:meth:`HyperResidual.apply`)."""
        if self.maps is not None:
            return None

        def probe(*parts):
            self.maps = [a if rows is None else a[rows] for a in parts]
        return probe

    def outs(self, tokens: int) -> list:
        """[load [n_moe, 3], ids [n_moe, tokens, K], x [rows, D], routed
        [rows, D] float32] and, of a model with several streams, the first
        expert layer's feed-forward mixing at those rows [X [rows, n·D],
        H_pre [rows, n], H_post [rows, n], H_res [rows, n, n] float32]; a
        model with no expert layer returns the first four empty."""
        if self.loads:
            return [jnp.stack(self.loads), jnp.stack(self.ids)] \
                + self.probe + (self.maps or [])
        cfg = self.cfg
        return [jnp.zeros((0, 3), jnp.int32),
                jnp.zeros((0, tokens, cfg.num_experts_per_tok), jnp.int32),
                jnp.zeros((0, cfg.hidden_size), jnp.dtype(cfg.dtype)),
                jnp.zeros((0, cfg.hidden_size), jnp.float32)]


class MLATransformerLM(LMAdapter):
    """One latent-attention MoE LM: config + the jit-ready functions.  As
    with :class:`~paddle_tpu.decode.model.TransformerLM`, the one kernel
    choice is the engine's ``attn_impl`` for the decode step's attention."""

    config_class = MLAConfig
    observer_class = MLAObserver
    param_shapes = staticmethod(param_shapes)

    def __init__(self, config: MLAConfig):
        super().__init__(config)
        self._inv_freq = jnp.asarray(yarn_inv_freq(
            config.qk_rope_head_dim, config.rope_theta, config.rope_scaling))
        self._rope_factor = rope_factor(config)
        # the latent attention's layer math is the adapter's; the rotation
        # (YaRN's) is this model's
        self._attn = LatentAttention(
            config.num_attention_heads, config.qk_nope_head_dim,
            config.qk_rope_head_dim, config.v_head_dim, config.kv_lora_rank,
            config.rms_norm_eps, softmax_scale(config), rope=self._rope)
        self._scale, self._row = self._attn.scale, self._attn.row
        self._residual = (HyperResidual if config.hc_mult
                          else PlainResidual)(config)

    # -- what an engine asks of a model ------------------------------------
    def _make_cache(self, num_blocks: int, block_tokens: int, dtype: str,
                    slots=None) -> PagedLatentCache:
        cfg = self.config
        return PagedLatentCache(cfg.num_hidden_layers, cfg.kv_lora_rank,
                                cfg.qk_rope_head_dim, self._row, num_blocks,
                                block_tokens, dtype=dtype)

    # -- parameters --------------------------------------------------------
    def init_params(self, seed: int = 0) -> Dict[str, np.ndarray]:
        """Seeded random weights (norm weights scattered about 1, so that a
        norm left out shows)."""
        rng = np.random.RandomState(seed)
        out = {}
        for name, (shape, std) in param_shapes(self.config).items():
            w = (1.0 + 0.1 * rng.randn(*shape) if std is None
                 else rng.randn(*shape) * std)
            out[name] = np.asarray(w, np.float32).astype(
                param_dtype(self.config, name))
        return out

    def _unpack(self, plist) -> Dict[str, jnp.ndarray]:
        return dict(zip(self.param_names(), plist))

    # -- shared layer math -------------------------------------------------
    def _rms(self, x, w):
        return rms_norm(x, w, self.config.rms_norm_eps)

    def _rope(self, x, pos):
        """x [..., dr] at positions pos [...] (broadcastable to x's leading
        dims), half-split rotation."""
        ang = pos[..., None].astype(jnp.float32) * self._inv_freq
        cos = jnp.cos(ang) * self._rope_factor
        sin = jnp.sin(ang) * self._rope_factor
        x32 = x.astype(jnp.float32)
        half = x.shape[-1] // 2
        x1, x2 = x32[..., :half], x32[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(x.dtype)

    def _ffn(self, p, i, x, valid):
        """x [N, D] → (ffn(x) [N, D], load [3] int32, ids [N, K] int32, the
        routed experts' part alone [N, D] float32); a dense layer has none
        of the last three."""
        cfg = self.config
        L = f"l{i}."
        if not is_moe_layer(cfg, i):
            with jax.named_scope("dense_ffn"):
                return swiglu(x, p[L + "w_gate"], p[L + "w_up"],
                               p[L + "w_down"]), None, None, None
        with jax.named_scope("moe_router"):
            logits = jnp.dot(x, p[L + "router"],
                             preferred_element_type=jnp.float32)
            # a softmax router that chooses by its scores asks for nothing
            how = {} if cfg.scoring_func == "softmax" else {
                "score": cfg.scoring_func}
            if cfg.topk_method == "noaux_tc":
                how.update(bias=p[L + "router_bias"], eps=ROUTE_EPS)
            ids, w = _moe.route_topk(logits, cfg.num_experts_per_tok,
                                     cfg.routed_scaling_factor,
                                     cfg.norm_topk_prob, **how)
        with jax.named_scope("moe_routed"):
            y, load = _moe.routed_experts(x, ids, w, valid, p[L + "e_gate"],
                                          p[L + "e_up"], p[L + "e_down"])
        with jax.named_scope("moe_shared"):
            sh = swiglu(x, p[L + "s_gate"], p[L + "s_up"], p[L + "s_down"])
        return (y + sh.astype(jnp.float32)).astype(x.dtype), load, ids, y

    def _layer(self, p, i, x, valid, attend, moe: Optional["_MoEOuts"] = None,
               rows=None):
        """One block over rows x [N, D]; ``attend(h)`` is the attention of
        the normed rows (the paths differ only there).  ``moe`` collects an
        expert layer's outputs, the first one's experts' input and output at
        ``rows`` (None: every row) among them."""
        res, L = self._residual, f"l{i}."
        x = res.apply(p, L + "attn", x,
                      lambda h: attend(self._rms(h, p[L + "attn_norm"])))

        def ffn(h):
            h = self._rms(h, p[L + "ffn_norm"])
            f, load, ids, routed = self._ffn(p, i, h, valid)
            if moe is not None and load is not None:
                moe.add(load, ids, h, routed, rows)
            return f
        probe = moe.mixing(rows) if moe is not None \
            and is_moe_layer(self.config, i) else None
        return res.apply(p, L + "ffn", x, ffn, probe)

    def _head(self, p, x):
        x = self._residual.leave(x)
        with jax.named_scope("lm_head"):
            return jnp.dot(self._rms(x, p["final_norm"]), p["head"],
                           preferred_element_type=jnp.float32)

    # -- full forward (the parity anchor) ----------------------------------
    def full_logits(self, plist, tokens, lengths=None):
        """tokens [B, T] int32 → logits [B, T, V] float32: plain causal
        attention by the expanded formula, no cache."""
        p = self._unpack(plist)
        cfg = self.config
        B, T = tokens.shape
        pos = jnp.tile(jnp.arange(T, dtype=jnp.int32), B)
        valid = jnp.ones((B * T,), bool) if lengths is None else \
            (jnp.arange(T)[None, :] < lengths[:, None]).reshape(B * T)
        qi = jnp.arange(T)
        mask = (qi[:, None] >= qi[None, :])[None, None]

        def heads(a):                       # [B*T, H, d] → [B, H, T, d]
            return a.reshape(B, T, *a.shape[1:]).transpose(0, 2, 1, 3)

        x = self._residual.enter(p["emb"][tokens.reshape(B * T)])
        for i in range(cfg.num_hidden_layers):
            def attend(h, lw=sub(p, f"l{i}.")):
                q_nope, q_pe, c, k_pe = self._attn.project(lw, h, pos)
                k_nope, v = self._attn.expand(lw, c)
                q = heads(jnp.concatenate([q_nope, q_pe], -1))
                k = heads(jnp.concatenate(
                    [k_nope, jnp.broadcast_to(k_pe[:, None], q_pe.shape)],
                    -1))
                s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                               k.astype(jnp.float32)) * self._scale
                w = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
                ctx = jnp.einsum("bhqk,bhkd->bqhd", w,
                                 heads(v).astype(jnp.float32))
                return self._attn.out(
                    lw, ctx.reshape(B * T, *ctx.shape[2:]).astype(h.dtype))
            x = self._layer(p, i, x, valid, attend)
        return self._head(p, x).reshape(B, T, -1)

    # -- prefill -----------------------------------------------------------
    def prefill(self, plist, state, tokens, length, block_table, seed,
                temperature, top_k):
        """state ``[latent pool]``, tokens [1, Tb] (bucket-padded), length []
        int32, block_table [MB] int32 → ([next_token [], logits [V], load
        [n_moe, 3], ids [n_moe, Tb, K], the first MoE layer's experts' input
        [1, D] and routed output [1, D] float32 at the last prompt
        position], state').  Every real position's row lands in the
        request's blocks, pad positions in trash block 0; pad tokens are
        routed to no expert."""
        cfg = self.config
        p = self._unpack(plist)
        (pool,) = state
        Tb = tokens.shape[1]
        bs = pool.shape[2]
        pos, valid, blocks, last = prompt_addresses(length, Tb, block_table,
                                                    bs)
        offsets = pos % bs
        moe = _MoEOuts(cfg)
        x = self._residual.enter(p["emb"][tokens[0]])
        for i in range(cfg.num_hidden_layers):
            def attend(h, i=i, w=sub(p, f"l{i}.")):
                nonlocal pool
                q_nope, q_pe, c, k_pe = self._attn.project(w, h, pos)
                with jax.named_scope("mla_cache_write"):
                    pool = pool.at[i, blocks, offsets].set(
                        self._attn.rows(c, k_pe, pool.dtype))
                return self._attn.prompt(w, q_nope, q_pe, c, k_pe)
            x = self._layer(p, i, x, valid, attend, moe, last[None])
        logits = self._head(p, x[last][None])[0]
        with jax.named_scope("sampling"):
            tok = sample_first(logits, seed, temperature, top_k)
        return [tok, logits] + moe.outs(Tb), [pool]

    # -- decode step -------------------------------------------------------
    def decode_step(self, plist, state, tokens, positions, block_tables,
                    seeds, steps, temperature, top_k, attn_impl=None):
        """state ``[latent pool]``, tokens / positions [S], block_tables
        [S, MB] → ([next_tokens [S], logits [S, V], load [n_moe, 3], ids
        [n_moe, S, K], the first MoE layer's experts' input [S, D] and routed
        output [S, D] float32], state').  A slot without a stream is routed
        to no expert."""
        cfg = self.config
        p = self._unpack(plist)
        (pool,) = state
        S = tokens.shape[0]
        bs = pool.shape[2]
        cl, live, _, blocks = step_addresses(positions, block_tables, bs)
        offsets = positions % bs
        moe = _MoEOuts(cfg)
        x = self._residual.enter(p["emb"][tokens])
        for i in range(cfg.num_hidden_layers):
            def attend(h, i=i, w=sub(p, f"l{i}.")):
                nonlocal pool
                q_nope, q_pe, c, k_pe = self._attn.project(w, h, positions)
                with jax.named_scope("mla_cache_write"):
                    pool = pool.at[i, blocks, offsets].set(
                        self._attn.rows(c, k_pe, pool.dtype))
                return self._attn.step(w, q_nope, q_pe, pool, block_tables,
                                       cl, i, impl=attn_impl)
            x = self._layer(p, i, x, live, attend, moe)
        logits = self._head(p, x)
        with jax.named_scope("sampling"):
            toks = sample(logits, seeds, steps, temperature, top_k)
        return [toks, logits] + moe.outs(S), [pool]


class HyperMLATransformerLM(MLATransformerLM):
    """``xing4_0``: the same body under its own ``model_type``."""

    config_class = HyperMLAConfig


MODEL_TYPES[MODEL_TYPE] = MLATransformerLM.from_dict
MODEL_TYPES[HYPER_MODEL_TYPE] = HyperMLATransformerLM.from_dict

__all__ = ["MLAConfig", "MLATransformerLM", "MLAObserver", "param_shapes",
           "param_dtype", "HyperMLAConfig", "HyperMLATransformerLM",
           "PlainResidual", "HyperResidual", "yarn_inv_freq", "softmax_scale",
           "rope_factor", "is_moe_layer"]
