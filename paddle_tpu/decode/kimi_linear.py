"""A linear-attention / latent-attention mixture-of-experts LM (``model_type:
kimi_linear``, Moonshot's Kimi-Linear-48B-A3B) for the decode plane,
configured by its published keys.

Every layer is ``x ← x + Mixer(RMS(x))``, then ``x ← x + FF(RMS(x))``; no bias
anywhere, a final RMSNorm, an untied head.  ``Mixer`` by
``linear_attn_config`` (its lists count layers from 1), ``FF`` by the layer's
place:

- **KDA** (``kda_layers``; ``kernels/kda.py``), ``H`` heads of ``K``
  channels: ``[q~ | k~ | v] = SiLU(conv(u W_qkv))`` (depthwise, causal,
  ``short_conv_kernel_size`` taps, zeros before the prompt, no bias); ``q = q~
  / ‖q~‖ · K^-½``, ``k = k~ / ‖k~‖`` a head a position; a log-decay a CHANNEL
  ``a = −exp(A_log_h) · softplus(W_f2 (W_f1 u) + dt_bias)``; a step size a
  head ``b = sigmoid(W_b u)``; the gated delta rule ``S' = Diag(exp a_t)
  S_{t−1}``, ``S_t = S' + b_t k_t (v_t − S'ᵀ k_t)ᵀ``, ``o_t = S_tᵀ q_t``;
  ``y = W_o [RMSNorm_K(o) ⊙ sigmoid(W_g2 (W_g1 u))]`` (the norm's weight
  shared by the heads).  A stream keeps ``Sᵀ`` [H, K, K] float32 a layer and
  the last ``taps − 1`` inputs of the convolution, and nothing that grows
  with its context.
- **latent attention** (``full_attn_layers``): DeepSeek-V2's
  (:class:`~paddle_tpu.decode.adapter.LatentAttention`, ``q_lora_rank``
  null) with **no rotation** of the ``qk_rope_head_dim`` slices
  (``mla_use_nope``): position comes from the recurrence of the layers
  around it.  A row ``[c | k_pe | 0]`` a token in the paged pool, one pool
  layer a latent layer.
- **dense SwiGLU** (layers below ``first_k_dense_replace``).
- **routed experts** (the rest; ``kernels/moe.py``): ``s = sigmoid(u W_r)``
  in float32 over ALL the router's experts; the ``num_experts_per_token``
  chosen by ``top_k(s + bias)``, weighed by ``s`` itself, ``w_i = s_i / Σ
  chosen s_j`` (``moe_renormalize``) times ``routed_scaling_factor``; beside
  them ``num_shared_experts`` shared ones as one wide SwiGLU on every token.

**A share of the experts.**  ``num_experts`` is how many experts' matrices
the model HOLDS; ``router_experts`` (the published count; none: the same) is
the router's width, and ``first_expert`` the first held.  The router's
scores, choice and renormalisation are over all ``router_experts``; only
assignments to held experts are planned and computed, and what the experts
held elsewhere would add is left out — the partial sum plus the shared
expert goes on to the next layer.  Nothing stands in for the other chips.

The stack is the dense layers (KDA mixers) and then whole *periods* of the
published pattern (``kda, kda, mla, kda``).  Programs ``lax.scan`` over the
dense layers' stacked weights (``d.*`` ``[nd, …]``) and over the periods'
(``p<j>.*`` ``[P, …]`` the layers at place ``j`` of a period, run in turn
inside one), pool, recurrent rows and tails the loops' carry, updated in
place with the layer as an index.  The experts' matrices are NOT scanned
over: the grouped kernel is handed a place's whole stack and the period's
index.

The model is an :class:`~paddle_tpu.decode.adapter.LMAdapter`, so a
:class:`~paddle_tpu.decode.engine.DecodeEngine` serves it as it is; its state
is a :class:`~paddle_tpu.decode.cache.HybridStateCache` with a latent row a
token, recurrent rows and tails a slot (``slot_state``).  Beside token and
logits the programs return every expert layer's load figures ``[Le, 5]``
(assignments to held experts, held experts touched, the largest load, the
plan's padded rows, all the router's choices of real tokens), the chosen
experts ``[Le, tokens, K]`` and, at the rows that reach the head, the routing
weights, the router's input and its logits.  There is no snapshot of a
slot's rows and no suffix prefill from one, so ``supports`` is empty.

Weights, residual stream, pool and tails are ``dtype`` (bf16 as deployed);
matmuls accumulate in float32; the recurrent state, the decays, the L2 and
RMS norms' statistics, the router's logits, scores and weights and the
softmax are float32.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .adapter import (EXPERT_LEAVES, MODEL_TYPES, ConfigDict, LatentAttention,
                      LMAdapter, PoolObserver, RoutedLoadSeries, mm,
                      prompt_addresses, rms_norm, sample, sample_first,
                      step_addresses, sub, swiglu, unscanned)
from .adapter import init_tensor as _init_tensor
from .cache import HybridStateCache
from ..kernels import kda as _kda
from ..kernels import moe as _moe
from ..kernels import ssm as _ssm
from ..observability import trace as _trace

MODEL_TYPE = "kimi_linear"
L2_EPS = 1e-6           # under the root of a head's q and k norms
_DT_MIN, _DT_MAX = 1e-3, 1e-1
_DEFAULT_LAYOUT = {"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
                   "head_dim": 16, "num_heads": 2,
                   "short_conv_kernel_size": 4}


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig(ConfigDict):
    """The published keys this model reads, under their published names; the
    share of the experts it holds (``router_experts``, ``first_expert``: class
    doc); the deployment's per-stream ``max_seq_len`` and the weights'
    ``dtype``.  ``linear_attn_config`` may be the published model's whole: a
    cut in depth reads the layers it has."""

    vocab_size: int
    hidden_size: int = 64
    intermediate_size: int = 96
    moe_intermediate_size: int = 32
    num_hidden_layers: int = 5
    num_attention_heads: int = 4
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    kv_lora_rank: int = 32
    q_lora_rank: Optional[int] = None
    mla_use_nope: bool = True
    first_k_dense_replace: int = 1
    num_experts: int = 8
    num_experts_per_token: int = 2
    num_shared_experts: int = 1
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    routed_scaling_factor: float = 1.0
    num_expert_group: int = 1
    topk_group: int = 1
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    linear_attn_config: dict = dataclasses.field(
        default_factory=lambda: dict(_DEFAULT_LAYOUT))
    router_experts: Optional[int] = None
    first_expert: int = 0
    max_seq_len: int = 128
    dtype: str = "bfloat16"
    model_type = MODEL_TYPE

    def __post_init__(self):
        if self.q_lora_rank is not None or not self.mla_use_nope \
                or self.tie_word_embeddings \
                or self.moe_router_activation_func != "sigmoid" \
                or self.num_expert_group != 1 or self.topk_group != 1:
            raise ValueError(
                "written down here: full-rank queries, position-free latent "
                "keys, an untied head, a sigmoid router with no groups")
        if self.router_experts is None:
            object.__setattr__(self, "router_experts", self.num_experts)
        if not 0 <= self.first_expert \
                <= self.router_experts - self.num_experts:
            raise ValueError(
                f"experts {self.first_expert} … +{self.num_experts} are not "
                f"among the router's {self.router_experts}")
        kinds, nd = self.kinds, self.first_k_dense_replace
        if not 0 < nd < len(kinds) or "mla" in kinds[:nd]:
            raise ValueError(
                "the stack is dense layers with KDA mixers and then whole "
                f"periods of one pattern (got {kinds} with {nd} dense)")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """``kda`` or ``mla`` for each layer this stage has."""
        lac = self.linear_attn_config
        kda, full = set(lac["kda_layers"]), set(lac["full_attn_layers"])
        out = []
        for i in range(1, self.num_hidden_layers + 1):
            if (i in kda) == (i in full):
                raise ValueError(f"layer {i} is not one of KDA and latent "
                                 "attention")
            out.append("kda" if i in kda else "mla")
        return tuple(out)

    @property
    def period(self) -> int:
        """The shortest pattern the layers after the dense ones repeat."""
        rest = self.kinds[self.first_k_dense_replace:]
        return next(p for p in range(1, len(rest) + 1)
                    if len(rest) % p == 0
                    and rest == rest[:p] * (len(rest) // p))

    @property
    def pattern(self) -> Tuple[str, ...]:
        nd = self.first_k_dense_replace
        return self.kinds[nd:nd + self.period]

    @property
    def periods(self) -> int:
        return (self.num_hidden_layers - self.first_k_dense_replace) \
            // self.period

    @property
    def kda_layers(self) -> int:
        return self.kinds.count("kda")

    @property
    def mla_layers(self) -> int:
        return self.kinds.count("mla")

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def kda_heads(self) -> int:
        return int(self.linear_attn_config["num_heads"])

    @property
    def kda_dim(self) -> int:
        return int(self.linear_attn_config["head_dim"])

    @property
    def taps(self) -> int:
        return int(self.linear_attn_config["short_conv_kernel_size"])

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_dim


def param_shapes(cfg: KimiLinearConfig) -> Dict[str, tuple]:
    """name → (shape, init): a float is the std of a normal; ``norm`` a norm
    weight (1 + 0.1 N); ``a_log`` the log of a decay uniform in [1, 16] a
    head, ``dt_bias`` the inverse softplus of a step log-uniform in [1e-3,
    1e-1] a channel (the family's own initialisation)."""
    D, V = cfg.hidden_size, cfg.vocab_size
    H, K, W = cfg.kda_heads, cfg.kda_dim, cfg.kda_width
    nh, dn, dr, dv, r = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim,
                         cfg.kv_lora_rank)
    F, Fe = cfg.intermediate_size, cfg.moe_intermediate_size
    Fs, E = cfg.num_shared_experts * Fe, cfg.num_experts
    norms = {"ln1": ((D,), "norm"), "ln2": ((D,), "norm")}
    kda = {"wqkv": ((D, 3 * W), D ** -0.5),
           "conv_w": ((cfg.taps, 3 * W), cfg.taps ** -0.5),
           "wf1": ((D, K), D ** -0.5), "wf2": ((K, W), K ** -0.5),
           "dt_bias": ((W,), "dt_bias"), "a_log": ((H,), "a_log"),
           "wb": ((D, H), D ** -0.5),
           "wg1": ((D, K), D ** -0.5), "wg2": ((K, W), K ** -0.5),
           "o_norm": ((K,), "norm"), "wo": ((W, D), W ** -0.5)}
    mla = {"wq": ((D, nh * (dn + dr)), D ** -0.5),
           "wkva": ((D, r + dr), D ** -0.5), "kv_norm": ((r,), "norm"),
           "wkvb": ((r, nh * (dn + dv)), r ** -0.5),
           "wo": ((nh * dv, D), (nh * dv) ** -0.5)}
    dense = {"w_gate": ((D, F), D ** -0.5), "w_up": ((D, F), D ** -0.5),
             "w_down": ((F, D), F ** -0.5)}
    experts = {"router": ((D, cfg.router_experts), D ** -0.5),
               "router_bias": ((cfg.router_experts,), 0.1),
               "e_gate": ((E, D, Fe), D ** -0.5),
               "e_up": ((E, D, Fe), D ** -0.5),
               "e_down": ((E, Fe, D), Fe ** -0.5),
               "s_gate": ((D, Fs), D ** -0.5), "s_up": ((D, Fs), D ** -0.5),
               "s_down": ((Fs, D), Fs ** -0.5)}
    mixers = {"kda": kda, "mla": mla}
    out = {"emb": ((V, D), 1.0), "final_norm": ((D,), "norm"),
           "head": ((D, V), D ** -0.5)}
    stacks = [("d.", cfg.first_k_dense_replace, {**norms, **kda, **dense})]
    stacks += [(f"p{j}.", cfg.periods, {**norms, **mixers[kind], **experts})
               for j, kind in enumerate(cfg.pattern)]
    for prefix, lead, layer in stacks:
        out.update({prefix + k: ((lead,) + shape, init)
                    for k, (shape, init) in layer.items()})
    return out


def init_tensor(key, shape: tuple, init, dtype):
    """One tensor of :func:`param_shapes` from a PRNG key: the decay's two
    here, the rest by :func:`~paddle_tpu.decode.adapter.init_tensor`."""
    f32 = jnp.float32
    if init == "a_log":
        w = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif init == "dt_bias":
        u = jax.random.uniform(key, shape, f32)
        dt = jnp.exp(u * (math.log(_DT_MAX) - math.log(_DT_MIN))
                     + math.log(_DT_MIN))
        w = dt + jnp.log(-jnp.expm1(-dt))
    else:
        return _init_tensor(key, shape, init, dtype)
    return w.astype(dtype)


class KimiLinearObserver(PoolObserver):
    """``decode.<engine>.*`` series of this model: the common ones, the
    pool's (a latent layer walks it), the routed load (``extra[0]``: each
    expert layer's ``[assignments to held experts, held experts touched,
    largest load, the plan's padded rows, all the router's choices]``) and
    its own: the choices the router made of ALL its experts (so that
    ``step_routed_assignments / step_choices`` is the share that is held),
    the prefills' grouped plans and the bytes of recurrent rows the steps
    moved."""

    def __init__(self, name: str, cache, config: KimiLinearConfig,
                 table_shape):
        super().__init__(name, cache, config, table_shape)
        sc = self.series
        self.routed = RoutedLoadSeries(
            sc, buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048,
                         4096, 8192, 16384))
        self.step_choices = sc.counter(
            "step_choices", "experts chosen by decode steps' routers (live "
            "slots x top-k x expert layers), held here or not")
        self.prefill_choices = sc.counter(
            "prefill_choices", "experts chosen by prefills' routers (real "
            "tokens x top-k x expert layers), held here or not")
        self.state_bytes = sc.counter(
            "step_state_bytes", "bytes of recurrent rows the decode steps "
            "read and wrote for live streams, every KDA layer")
        self.prefill_dispatches = sc.counter(
            "prefill_moe_dispatches", "expert layers run by prefills")
        self.prefill_touched = sc.counter(
            "prefill_experts_touched", "experts with at least one row, "
            "summed over the prefills' dispatches")
        self.prefill_load_max_sum = sc.counter(
            "prefill_expert_load_max_sum", "largest load of one expert, "
            "summed over the prefills' dispatches")
        self.prefill_plan_rows = sc.counter(
            "prefill_plan_rows", "rows of the prefills' grouped plans: every "
            "held expert's assignments padded to whole row tiles")
        self.prefill_plan_pad = sc.counter(
            "prefill_plan_pad_rows", "of them, rows that hold no assignment")
        sc.gauge("recurrent_state_bytes").set(cache.recurrent_state_bytes)
        # one stream's rows of every KDA layer, read once and written once
        self._row_bytes = 2 * int(cache.h.size // cache.slots) \
            * cache.h.dtype.itemsize

    def prefill(self, extra, prompt: int, bucket: int) -> None:
        with _trace.span("decode::prefill.observe") as sp:
            load = np.asarray(extra[0])
            assignments = self.routed.count_prefill(load)
            rows, choices = int(load[:, 3].sum()), int(load[:, 4].sum())
            self.prefill_dispatches.inc(int(load.shape[0]))
            self.prefill_touched.inc(int(load[:, 1].sum()))
            self.prefill_load_max_sum.inc(int(load[:, 2].sum()))
            self.prefill_plan_rows.inc(rows)
            self.prefill_plan_pad.inc(rows - assignments)
            self.prefill_choices.inc(choices)
            self.count_prompt(prompt, bucket)
            sp.annotate(prefill_routed_assignments=assignments,
                        prefill_choices=choices, prefill_plan_rows=rows,
                        prefill_real_tokens=prompt,
                        prefill_tokens_sq=prompt * prompt)

    def step(self, extra, contexts) -> None:
        with _trace.span("decode::step.observe") as sp:
            load = np.asarray(extra[0])
            assignments, touched = self.routed.count_step(load)
            choices = int(load[:, 4].sum())
            context, streams = self.count_streams(contexts)
            state_bytes = streams * self._row_bytes
            self.step_choices.inc(choices)
            self.state_bytes.inc(state_bytes)
            sp.annotate(step_routed_assignments=assignments,
                        step_experts_touched=touched, step_choices=choices,
                        step_context_tokens=context, step_streams=streams,
                        step_state_bytes=state_bytes)
        layers = self.config.mla_layers
        self.count_walks(layers * self.pool_walk(contexts),
                         layers * self._slots * self._slot_blocks)


def _l2(x):
    """x [..., K] float32 → x / ‖x‖ over the last axis."""
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


class KimiLinearLM(LMAdapter):
    """One KDA / latent-attention expert LM: config + the jit-ready
    functions."""

    # a KDA layer's recurrent row and its convolution's tail live in slot rows
    slot_state = True
    config_class = KimiLinearConfig
    observer_class = KimiLinearObserver
    param_shapes = staticmethod(param_shapes)
    init_tensor = staticmethod(init_tensor)

    def __init__(self, config: KimiLinearConfig):
        super().__init__(config)
        self._attn = LatentAttention(
            config.num_attention_heads, config.qk_nope_head_dim,
            config.qk_rope_head_dim, config.v_head_dim, config.kv_lora_rank,
            config.rms_norm_eps,
            (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5,
            rope=None)

    # -- what an engine asks of a model ------------------------------------
    def _make_cache(self, num_blocks: int, block_tokens: int, dtype: str,
                    slots: int) -> HybridStateCache:
        cfg = self.config
        H, K = cfg.kda_heads, cfg.kda_dim
        return HybridStateCache(
            0, num_blocks, block_tokens, slots, dtype=dtype,
            kv_layers=cfg.mla_layers, row_width=self._attn.row,
            recurrent=(cfg.kda_layers, (H, K, K)),
            tails=(cfg.kda_layers, cfg.taps, 3 * cfg.kda_width))

    def _unpack(self, plist):
        """(the model's own tensors, the dense layers' stacks, a period's
        layers' stacks in the pattern's order)."""
        p = dict(zip(self.param_names(), plist))
        return ({k: v for k, v in p.items() if "." not in k}, sub(p, "d."),
                tuple(sub(p, f"p{j}.")
                      for j in range(len(self.config.pattern))))

    # -- shared layer math -------------------------------------------------
    def _rms(self, x, g):
        return rms_norm(x, g, self.config.rms_norm_eps)

    def _kda_in(self, w, u):
        """u [N, D] → (the convolution's input [q | k | v] [N, 3W], the
        decay's low-rank output [N, W], the step size's logits [N, H], the
        output gate's [N, W])."""
        with jax.named_scope("kda_in"):
            return (mm(u, w["wqkv"]), mm(mm(u, w["wf1"]), w["wf2"]),
                    jnp.dot(u, w["wb"], preferred_element_type=jnp.float32),
                    mm(mm(u, w["wg1"]), w["wg2"]))

    def _kda_qkvab(self, w, c, f, bl, keep):
        """The convolution's output c [N, 3W] float32, f, bl of
        :meth:`_kda_in` and ``keep`` [N] (False: the position is a pad and
        leaves the state as it is) → q, k [N, H, K] (normalised), v [N, H,
        K], the log-decay a [N, H, K] float32 and the step size b [N, H]
        float32."""
        cfg = self.config
        N, H, K, W = c.shape[0], cfg.kda_heads, cfg.kda_dim, cfg.kda_width
        dtype = jnp.dtype(cfg.dtype)
        c = jax.nn.silu(c)
        q = (_l2(c[:, :W].reshape(N, H, K)) * K ** -0.5).astype(dtype)
        k = _l2(c[:, W:2 * W].reshape(N, H, K)).astype(dtype)
        v = c[:, 2 * W:].reshape(N, H, K).astype(dtype)
        f32 = jnp.float32
        a = -jnp.exp(w["a_log"].astype(f32))[None, :, None] * jax.nn.softplus(
            f.astype(f32) + w["dt_bias"].astype(f32)).reshape(N, H, K)
        b = jax.nn.sigmoid(bl)
        return (q, k, v, jnp.where(keep[:, None, None], a, 0.0),
                jnp.where(keep[:, None], b, 0.0))

    def _kda_out(self, w, x, o, gate):
        """The recurrence's output o [N, H, K] and the gate's logits [N, W]
        → x + the mixer's output."""
        with jax.named_scope("kda_out"):
            y = self._rms(o, w["o_norm"]).reshape(o.shape[0], -1)
            y = (y.astype(jnp.float32)
                 * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)
            return x + mm(y, w["wo"])

    def _dense_ffn(self, w, x):
        h = self._rms(x, w["ln2"])
        with jax.named_scope("dense_ffn"):
            return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"])

    def _expert_ffn(self, w, stacks, at, x, valid, tile: int, dense: bool):
        """x [N, D] → (x + the held experts' and the shared expert's output
        on ``RMS(x)``, (load [5], ids [N, K], weights [N, K], the router's
        input [N, D], its logits [N, router_experts]))."""
        cfg = self.config
        h = self._rms(x, w["ln2"])
        with jax.named_scope("moe_router"):
            logits = jnp.dot(h, w["router"],
                             preferred_element_type=jnp.float32)
            ids, weights = _moe.route_topk(
                logits, cfg.num_experts_per_token, cfg.routed_scaling_factor,
                cfg.moe_renormalize, score="sigmoid", bias=w["router_bias"])
        with jax.named_scope("moe_routed"):
            plan = _moe.plan_groups(ids, valid, cfg.num_experts, tile,
                                    first=cfg.first_expert)
            load = jnp.concatenate([
                plan.load, jnp.sum(plan.padded_sizes, dtype=jnp.int32)[None],
                (jnp.sum(valid, dtype=jnp.int32) * ids.shape[1])[None]])
            y = _moe.planned_experts(
                h, weights, plan, *stacks, tile, act="silu", layer=at,
                impl="xla" if dense else None, out_dtype=h.dtype)
        with jax.named_scope("moe_shared"):
            sh = swiglu(h, w["s_gate"], w["s_up"], w["s_down"])
        return x + (y + sh.astype(jnp.float32)).astype(x.dtype), \
            (load, ids, weights, h, logits)

    def _head(self, p, x):
        with jax.named_scope("lm_head"):
            return jnp.dot(self._rms(x, p["final_norm"]), p["head"],
                           preferred_element_type=jnp.float32)

    def _scan_layers(self, pd, pp, x, carry, mixer, ffn, pick):
        """Every layer in turn: ``lax.scan`` over the dense layers, then over
        the periods, a period's layers one after another inside.
        ``mixer(w, x, carry, kind, index) → (x, carry)`` is a layer's mixer
        with its residual (``kind`` ``kda`` / ``mla``, ``index`` the layer's
        place among its kind); ``ffn(w, stacks, index, x) → (x, got)`` an
        expert layer's feed-forward half (``stacks`` its place's experts as
        they lie, ``index`` the period).  Returns (x, carry, every expert
        layer's ``pick(got)`` stacked ``[Le, …]`` in layer order)."""
        cfg = self.config
        nd, pattern = cfg.first_k_dense_replace, cfg.pattern
        n_kda, n_mla = pattern.count("kda"), pattern.count("mla")
        stacks = tuple(tuple(w[k] for k in EXPERT_LEAVES) for w in pp)

        def dense(state, xs):
            w, i = xs
            x, carry = mixer(w, *state, "kda", i)
            return (self._dense_ffn(w, x), carry), None

        (x, carry), _ = lax.scan(dense, (x, carry),
                                 (pd, jnp.arange(nd, dtype=jnp.int32)))

        def period(state, xs):
            ws, i = xs
            x, carry = state
            gots = []
            for j, kind in enumerate(pattern):
                before = pattern[:j].count(kind)
                at = nd + i * n_kda + before if kind == "kda" \
                    else i * n_mla + before
                x, carry = mixer(ws[j], x, carry, kind, at)
                x, got = ffn(ws[j], stacks[j], i, x)
                gots.append(pick(got))
            return (x, carry), tuple(jnp.stack(g) for g in zip(*gots))

        (x, carry), got = lax.scan(
            period, (x, carry),
            (tuple(unscanned(w) for w in pp),
             jnp.arange(cfg.periods, dtype=jnp.int32)))
        return x, carry, tuple(g.reshape((-1,) + g.shape[2:]) for g in got)

    # -- a prompt's layers -------------------------------------------------
    def _prompt_layers(self, p, pd, pp, tokens, length, cache_dtype,
                       dense: bool, rows_out, state_out, carry):
        """tokens [T] through every layer → (x [T, D], carry', (load [Le,
        5], ids [Le, T, K], and at the last real position the routing weights
        [Le, K], the router's input [Le, D] and its logits [Le, E])).
        ``rows_out(index, rows, carry)`` files a latent layer's cache rows [T,
        row], ``state_out(index, S, tail, carry)`` a KDA layer's state [H, K,
        K] after the last real position and its convolution's tail [taps − 1,
        3W] (the inputs at the last real positions, zeros before the
        prompt)."""
        cfg = self.config
        T, taps = tokens.shape[0], cfg.taps
        pos = jnp.arange(T, dtype=jnp.int32)
        valid = pos < length
        last = jnp.maximum(length - 1, 0)
        tile = _moe.row_tile(T, jnp.dtype(cfg.dtype))
        scan = _kda.kda_scan_xla if dense else functools.partial(
            _kda.kda_scan, out_dtype=jnp.dtype(cfg.dtype))

        def mixer(w, x, carry, kind, at):
            u = self._rms(x, w["ln1"])
            if kind == "mla":
                q_nope, q_pe, c, k_pe = self._attn.project(w, u, pos)
                with jax.named_scope("mla_cache_write"):
                    carry = rows_out(at, self._attn.rows(c, k_pe,
                                                         cache_dtype), carry)
                return x + self._attn.prompt(
                    w, q_nope, q_pe, c, k_pe,
                    impl="xla" if dense else None), carry
            qkv, f, bl, gate = self._kda_in(w, u)
            with jax.named_scope("kda_conv"):
                c = _ssm.causal_conv(qkv, w["conv_w"])
                # the inputs at the last real positions, zeros before the
                # prompt: three rows gathered, not a padded copy of [T, 3W]
                at_ = length - (taps - 1) + jnp.arange(taps - 1)
                tail = jnp.where((at_ >= 0)[:, None],
                                 qkv[jnp.maximum(at_, 0)], 0)
                q, k, v, a, b = self._kda_qkvab(w, c, f, bl, valid)
            with jax.named_scope("kda_scan"):
                o, S = scan(q, k, v, a, b)
                carry = state_out(at, S, tail.astype(cache_dtype), carry)
            return self._kda_out(w, x, o.astype(x.dtype), gate), carry

        def ffn(w, stacks, at, x):
            return self._expert_ffn(w, stacks, at, x, valid, tile, dense)

        return self._scan_layers(
            pd, pp, p["emb"][tokens], carry, mixer, ffn,
            lambda got: got[:2] + tuple(g[last] for g in got[2:]))

    # -- full forward (the parity anchor) ----------------------------------
    def full_logits(self, plist, tokens, lengths=None):
        """tokens [B, T] int32 → logits [B, T, V] float32: every position
        through every layer, the recurrence one position at a time, dense
        masked attention, the experts through ``lax.ragged_dot``, no cache
        and no kernel."""
        p, pd, pp = self._unpack(plist)
        B, T = tokens.shape
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)

        def one(toks, length):
            x, _, _ = self._prompt_layers(
                p, pd, pp, toks, length, jnp.dtype(self.config.dtype), True,
                lambda at, rows, carry: carry,
                lambda at, S, tail, carry: carry, jnp.zeros((), jnp.int32))
            return self._head(p, x)

        # one sequence after another: lax.ragged_dot has no batched form
        return lax.map(lambda a: one(*a), (tokens, lengths))

    # -- prefill -----------------------------------------------------------
    def prefill(self, plist, state, tokens, length, slot, block_table, seed,
                temperature, top_k):
        """state ``[latent pool, recurrent rows, tails]``, tokens [1, Tb]
        (bucket-padded), length [] int32, slot [] int32 (the slot whose rows
        this prompt fills), block_table [MB] int32 → ([next_token [], logits
        [V], load [Le, 5], ids [Le, Tb, K], routing weights [Le, 1, K], u [Le,
        1, D], router logits [Le, 1, E]], state').  A latent layer's row of
        every real position lands in the request's blocks, pad positions in
        trash block 0; the slot's recurrent rows and tails are overwritten
        whole."""
        p, pd, pp = self._unpack(plist)
        pool, rec, conv = state
        bs = pool.shape[2]
        pos, _, blocks, last = prompt_addresses(
            length, tokens.shape[1], block_table, bs)
        zero = jnp.zeros((), slot.dtype)

        def rows_out(at, rows, carry):
            pool_, rec_, conv_ = carry
            return pool_.at[at, blocks, pos % bs].set(rows), rec_, conv_

        def state_out(at, S, tail, carry):
            pool_, rec_, conv_ = carry
            return (pool_, lax.dynamic_update_slice(
                rec_, S[None, None], (at, slot, zero, zero, zero)),
                lax.dynamic_update_slice(
                    conv_, tail[None, None], (at, slot, zero, zero)))

        x, state, (load, ids, rw, u, rl) = self._prompt_layers(
            p, pd, pp, tokens[0], length, pool.dtype, False, rows_out,
            state_out, (pool, rec, conv))
        logits = self._head(p, x[last][None])[0]
        with jax.named_scope("sampling"):
            tok = sample_first(logits, seed, temperature, top_k)
        return [tok, logits, load, ids, rw[:, None], u[:, None],
                rl[:, None]], list(state)

    # -- decode step -------------------------------------------------------
    def decode_step(self, plist, state, tokens, positions, block_tables,
                    seeds, steps, temperature, top_k, attn_impl=None):
        """state ``[latent pool, recurrent rows, tails]``, tokens / positions
        [S], block_tables [S, MB] → ([next_tokens [S], logits [S, V], load
        [Le, 5], ids [Le, S, K], routing weights [Le, S, K], u [Le, S, D],
        router logits [Le, S, E]], state').  A slot without a stream is
        routed to no expert and scribbles on its own rows only."""
        cfg = self.config
        p, pd, pp = self._unpack(plist)
        S = tokens.shape[0]
        bs = state[0].shape[2]
        cl, live, _, blocks = step_addresses(positions, block_tables, bs)
        tile = _moe.row_tile(S, jnp.dtype(cfg.dtype))
        every = jnp.ones((S,), bool)

        def mixer(w, x, carry, kind, at):
            pool, rec, conv = carry
            u = self._rms(x, w["ln1"])
            if kind == "mla":
                q_nope, q_pe, c, k_pe = self._attn.project(w, u, positions)
                with jax.named_scope("mla_cache_write"):
                    pool = pool.at[at, blocks, positions % bs].set(
                        self._attn.rows(c, k_pe, pool.dtype))
                return x + self._attn.step(
                    w, q_nope, q_pe, pool, block_tables, cl, at,
                    impl=attn_impl), (pool, rec, conv)
            qkv, f, bl, gate = self._kda_in(w, u)
            with jax.named_scope("kda_conv"):
                c, tail = _ssm.conv_step(
                    lax.dynamic_index_in_dim(conv, at, keepdims=False), qkv,
                    w["conv_w"])
                conv = lax.dynamic_update_index_in_dim(
                    conv, tail.astype(conv.dtype), at, 0)
                q, k, v, a, b = self._kda_qkvab(w, c, f, bl, every)
            with jax.named_scope("kda_scan"):
                o, rec = _kda.kda_state_step(rec, at, q, k, v, a, b)
            return self._kda_out(w, x, o.astype(x.dtype), gate), \
                (pool, rec, conv)

        def ffn(w, stacks, at, x):
            return self._expert_ffn(w, stacks, at, x, live, tile, False)

        x, state, (load, ids, rw, u, rl) = self._scan_layers(
            pd, pp, p["emb"][tokens], tuple(state), mixer, ffn,
            lambda got: got)
        logits = self._head(p, x)
        with jax.named_scope("sampling"):
            toks = sample(logits, seeds, steps, temperature, top_k)
        return [toks, logits, load, ids, rw, u, rl], list(state)


MODEL_TYPES[MODEL_TYPE] = KimiLinearLM.from_dict

__all__ = ["KimiLinearConfig", "KimiLinearLM", "KimiLinearObserver",
           "param_shapes", "init_tensor", "EXPERT_LEAVES", "L2_EPS"]
