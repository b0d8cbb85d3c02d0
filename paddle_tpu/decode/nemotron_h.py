"""A one-mixer-a-layer hybrid LM (``model_type: nemotron_h``, NVIDIA's
Nemotron-H / Nemotron 3 Nano) for the decode plane, configured by its
published keys.

A layer is ONE mixer behind one RMSNorm and nothing else,

    x ← x + mixer_l(RMSNorm_l(x)) ,

and which mixer is the character ``hybrid_override_pattern[l]``: ``M`` a
Mamba-2 (state-space duality) mixer, ``E`` a layer of routed experts beside a
shared one, ``*`` a grouped-query attention.  The model is ``x₀ = E[token]``,
a final RMSNorm and an UNTIED head.  No bias but the convolution's, no rotary
position anywhere: what orders the tokens is the recurrence and the causal
mask.

- ``M`` (``kernels/ssd.py``): ``[z | xBC | dt] = W_in u`` of widths ``d_inner
  | d_inner + 2·n_groups·ssm_state_size | mamba_num_heads`` (``d_inner =
  mamba_num_heads · mamba_head_dim``); ``xBC ← silu(conv(xBC) + b)``
  (depthwise, causal, ``conv_kernel`` taps); ``Δ = softplus(dt + dt_bias)``
  (no clamp); for head ``h`` of group ``g`` the recurrence ``S_t = exp(Δ_t
  A_h) S_{t−1} + B_{t,g} ⊗ (Δ_t x_{t,h})``, ``A_h = −exp(A_log_h)``,
  ``y_{t,h} = C_{t,g}ᵀ S_t + D_h x_{t,h}``; ``y ← RMSNorm_groups(y ⊙ silu(z))
  ⊙ w`` (the gate BEFORE the norm, statistics over each of ``n_groups``
  groups of channels); ``W_out y``.  A stream keeps ``S`` float32 (in
  ``ssd.state_layout``: 64-wide heads two to a lane tile) and the last
  ``conv_kernel − 1`` inputs ``xBC`` a Mamba layer.
- ``E`` (``kernels/moe.py``): ``s = sigmoid(u W_r)`` in float32 over ALL the
  router's experts, the ``num_experts_per_tok`` largest of ``s + b`` chosen
  (``e_score_correction_bias``: it chooses and does not weigh; ``n_group =
  topk_group = 1``: no groups), weights ``s_e / (Σ chosen s + 1e-20) ·
  routed_scaling_factor``; an expert is the UNGATED unit ``relu(u W_upᵀ)²
  W_down`` — two matrices, both kept ``[F, D]`` — and ONE shared expert of
  the same form at ``moe_shared_expert_intermediate_size`` is added.
- ``*`` (``kernels/gqa.py``): ``q = u W_q`` [heads × head_dim], ``k, v``
  [K/V heads × head_dim], no bias, no q/k norm, NO rotation, causal
  ``softmax(q kᵀ / √head_dim) v``, ``W_o``.  A row ``[k | v]`` a token in the
  paged pool, one pool layer an attention layer.

**A share of the experts and of the vocabulary.**  ``n_routed_experts`` is how
many experts' matrices the model HOLDS; ``router_experts`` (the published
count; none: the same) is the router's width, and ``first_expert`` the first
held.  Scores, choice and renormalisation are over all ``router_experts``;
only assignments to held experts are planned and computed — every one of them
— and what the experts held elsewhere would add is left out.  ``vocab_size``
counts the rows of table and head this chip holds.  Nothing stands in for the
other chips.

So a stream's state is of two kinds with layer counts of their own
(:class:`~paddle_tpu.decode.cache.HybridStateCache`): blocks of a paged pool
of the ``*`` layers, held by block table, and a recurrent row and a
convolution tail a slot an ``M`` layer, addressed by slot (``slot_state``).

The stack need not be whole periods of anything (the published pattern is
not), so the programs walk the pattern a layer at a time: each kind's tensors
are one stack (``m.*``, ``e.*``, ``a.*``) indexed by the layer's place among
its kind, and pool, rows and tails are updated in place with that index.

The model is an :class:`~paddle_tpu.decode.adapter.LMAdapter`, so a
:class:`~paddle_tpu.decode.engine.DecodeEngine` serves it as it is.  Beside
token and logits the programs return every expert layer's load figures ``[Le,
5]`` (assignments to held experts, held experts touched, the largest load,
the plan's padded rows, all the router's choices of real tokens), the chosen
experts ``[Le, tokens, K]`` and, at the rows that reach the head, the routing
weights, the router's input ``u``, its logits and the layer's output.  There
is no snapshot of a slot's rows and no suffix prefill from a saved state, so
``supports`` is empty.

Weights, residual stream, pool and tails are ``dtype`` (bf16 as deployed);
matmuls accumulate in float32; softmax, norm statistics, the router's logits,
scores and weights, ``Δ``, ``exp(ΔA)`` and ``S`` are float32.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .adapter import (MODEL_TYPES, ConfigDict, LMAdapter, PoolObserver,
                      RoutedLoadSeries, init_tensor as _init_tensor, mm,
                      prompt_addresses, rms_norm, sample, sample_first,
                      step_addresses, sub)
from .cache import HybridStateCache
from ..kernels import gqa as _gqa
from ..kernels import moe as _moe
from ..kernels import ssd as _ssd
from ..kernels import ssm as _ssm
from ..observability import trace as _trace

MODEL_TYPE = "nemotron_h"
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
# the stacks of a kind's tensors, by the kind's character
STACKS = {MAMBA: "m.", EXPERTS: "e.", ATTENTION: "a."}
# rows of a prompt the shared expert takes at once
_SHARED_ROWS = 2048


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(ConfigDict):
    """The published keys this model reads, under their published names; the
    share of the experts it holds (``router_experts``, ``first_expert``:
    module doc); the deployment's per-stream ``max_seq_len`` and the weights'
    ``dtype``.  ``hybrid_override_pattern`` may be the published model's
    whole: a cut in depth reads its first ``num_hidden_layers`` characters."""

    vocab_size: int
    hidden_size: int = 64
    num_hidden_layers: int = 7
    hybrid_override_pattern: str = "MEMEM*E"
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    mamba_num_heads: int = 4
    mamba_head_dim: int = 16
    n_groups: int = 2
    ssm_state_size: int = 32
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    mamba_hidden_act: str = "silu"
    mamba_proj_bias: bool = False
    moe_intermediate_size: int = 32
    moe_shared_expert_intermediate_size: int = 64
    n_routed_experts: int = 8
    n_shared_experts: int = 1
    num_experts_per_tok: int = 2
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    n_group: int = 1
    topk_group: int = 1
    mlp_hidden_act: str = "relu2"
    mlp_bias: bool = False
    attention_bias: bool = False
    use_bias: bool = False
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = False
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    router_experts: Optional[int] = None
    first_expert: int = 0
    max_seq_len: int = 128
    dtype: str = "bfloat16"
    model_type = MODEL_TYPE

    def __post_init__(self):
        L = self.num_hidden_layers
        pattern = str(self.hybrid_override_pattern)[:L]
        if len(pattern) != L or set(pattern) - set(STACKS):
            raise ValueError(
                f"hybrid_override_pattern gives {len(pattern)} layers of "
                f"{sorted(set(pattern))} for {L}: one of "
                f"{sorted(STACKS)} a layer")
        object.__setattr__(self, "hybrid_override_pattern", pattern)
        if set(pattern) != set(STACKS):
            raise ValueError("a stack served here has a layer of every kind: "
                             "Mamba (slot rows), experts (the load figures) "
                             "and attention (the pool)")
        if not (self.use_conv_bias and self.mamba_hidden_act == "silu"
                and self.mlp_hidden_act == "relu2" and self.norm_topk_prob
                and self.n_group == 1 and self.topk_group == 1
                and self.n_shared_experts == 1) \
                or self.mamba_proj_bias or self.mlp_bias \
                or self.attention_bias or self.use_bias \
                or self.tie_word_embeddings:
            raise ValueError(
                "written down here: a convolution with a bias under SiLU, "
                "ungated relu2 experts behind a sigmoid router without "
                "groups and renormalised, one shared expert, no other bias, "
                "an untied head")
        if self.router_experts is None:
            object.__setattr__(self, "router_experts", self.n_routed_experts)
        if not 0 <= self.first_expert \
                <= self.router_experts - self.n_routed_experts:
            raise ValueError(
                f"experts {self.first_expert} … +{self.n_routed_experts} are "
                f"not among the router's {self.router_experts}")
        if self.mamba_num_heads % self.n_groups \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("groups divide the state-space heads and K/V "
                             "heads the query heads")

    def count(self, kind: str) -> int:
        return self.hybrid_override_pattern.count(kind)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def bc_width(self) -> int:
        """One of B and C: every group's state coefficients."""
        return self.n_groups * self.ssm_state_size

    @property
    def conv_width(self) -> int:
        """What the convolution covers: ``[x | B | C]``."""
        return self.d_inner + 2 * self.bc_width

    @property
    def in_width(self) -> int:
        """``[z | x | B | C | dt]``."""
        return self.d_inner + self.conv_width + self.mamba_num_heads

    @property
    def q_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def state_shape(self) -> tuple:
        """A stream's recurrent row of one Mamba layer, as it is kept."""
        return _ssd.state_layout(self.mamba_num_heads, self.ssm_state_size,
                                 self.mamba_head_dim)


def param_shapes(cfg: NemotronHConfig) -> Dict[str, tuple]:
    """name → (shape, init): a float is the std of a normal; ``norm`` a norm
    weight (1 + 0.1 N), ``bias`` a bias (0.02 N), ``a_log`` the log of a
    decay uniform in [1, 16] a head, ``dt_bias`` the inverse softplus of a
    step size log-uniform in [``time_step_min``, ``time_step_max``] floored
    at ``time_step_floor``, ``skip`` ones (the family's own initialisation of
    A, dt_bias and D).  An expert's two matrices both lie ``[F, D]``."""
    D, V = cfg.hidden_size, cfg.vocab_size
    Di, H, K = cfg.d_inner, cfg.mamba_num_heads, cfg.conv_kernel
    E, F, Fs = (cfg.n_routed_experts, cfg.moe_intermediate_size,
                cfg.moe_shared_expert_intermediate_size)
    kinds = {
        MAMBA: {"ln": ((D,), "norm"),
                "in_proj": ((D, cfg.in_width), D ** -0.5),
                "conv_w": ((K, cfg.conv_width), K ** -0.5),
                "conv_b": ((cfg.conv_width,), "bias"),
                "dt_bias": ((H,), "dt_bias"), "a_log": ((H,), "a_log"),
                "d_skip": ((H,), "skip"), "ssm_norm": ((Di,), "norm"),
                "out_proj": ((Di, D), Di ** -0.5)},
        EXPERTS: {"ln": ((D,), "norm"),
                  "router": ((D, cfg.router_experts), D ** -0.5),
                  "router_bias": ((cfg.router_experts,), "bias"),
                  "e_up": ((E, F, D), D ** -0.5),
                  "e_down": ((E, F, D), F ** -0.5),
                  "s_up": ((D, Fs), D ** -0.5),
                  "s_down": ((Fs, D), Fs ** -0.5)},
        ATTENTION: {"ln": ((D,), "norm"),
                    "wqkv": ((D, cfg.q_width + 2 * cfg.kv_width), D ** -0.5),
                    "wo": ((cfg.q_width, D), cfg.q_width ** -0.5)}}
    out = {"emb": ((V, D), 1.0), "head": ((V, D), D ** -0.5),
           "final_norm": ((D,), "norm")}
    for kind, layer in kinds.items():
        n = cfg.count(kind)
        if n:
            out.update({STACKS[kind] + k: ((n,) + shape, init)
                        for k, (shape, init) in layer.items()})
    return out


def step_bias(u, low: float, high: float, floor: float):
    """``dt_bias`` from uniforms ``u`` in [0, 1): the inverse softplus of a
    step size log-uniform in [low, high], floored."""
    dt = jnp.maximum(jnp.exp(u * (math.log(high) - math.log(low))
                             + math.log(low)), floor)
    return dt + jnp.log(-jnp.expm1(-dt))


def init_tensor(key, shape: tuple, init, dtype,
                steps: tuple = (1e-3, 1e-1, 1e-4)):
    """One tensor of :func:`param_shapes` from a PRNG key (jit-able with all
    but ``key`` static): Mamba-2's own three here (``steps``: the config's
    ``time_step_min``, ``time_step_max`` and ``time_step_floor``), the rest
    by :func:`~paddle_tpu.decode.adapter.init_tensor`."""
    f32 = jnp.float32
    if init == "skip":
        w = jnp.ones(shape, f32)
    elif init == "a_log":
        w = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif init == "dt_bias":
        w = step_bias(jax.random.uniform(key, shape, f32), *steps)
    else:
        return _init_tensor(key, shape, init, dtype)
    return w.astype(dtype)


# ``relu(v)²``: the experts' own activation, the grouped kernel's
relu2 = _moe.UNGATED["relu2"][0]


class NemotronHObserver(PoolObserver):
    """``decode.<engine>.*`` series of this model: the common ones, the
    pool's (the ``*`` layers walk it), the routed load (``extra[0]``: each
    expert layer's ``[assignments to held experts, held experts touched,
    largest load, the plan's padded rows, all the router's choices]``), the
    choices the router made of ALL its experts — so that
    ``step_routed_assignments / step_choices`` is the share that is held —
    and the state-space layers' own."""

    def __init__(self, name: str, cache, config: NemotronHConfig,
                 table_shape):
        super().__init__(name, cache, config, table_shape)
        # a live stream's rows of every Mamba layer, read once and written
        # once
        self.row_bytes = 2 * 4 * config.count(MAMBA) \
            * int(np.prod(config.state_shape))
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
        self.prefill_plan_rows = sc.counter(
            "prefill_plan_rows", "rows of the prefills' grouped plans: every "
            "held expert's assignments padded to whole row tiles")
        self.prefill_chunks = sc.counter(
            "prefill_scan_chunks", "chunks of chunk_size positions that hold "
            "a real position, summed over prefills (one layer)")
        self.state_bytes = sc.counter(
            "step_state_bytes", "bytes of recurrent rows the live streams' "
            "one-token updates read and wrote, every Mamba layer, summed "
            "over decode steps")
        sc.gauge("recurrent_state_bytes").set(cache.recurrent_state_bytes)

    def prefill(self, extra, prompt: int, bucket: int) -> None:
        with _trace.span("decode::prefill.observe") as sp:
            load = np.asarray(extra[0])
            assignments = self.routed.count_prefill(load)
            rows, choices = int(load[:, 3].sum()), int(load[:, 4].sum())
            self.prefill_plan_rows.inc(rows)
            self.prefill_choices.inc(choices)
            chunks = -(-prompt // self.config.chunk_size)
            self.prefill_chunks.inc(chunks)
            self.count_prompt(prompt, bucket)
            sp.annotate(prefill_routed_assignments=assignments,
                        prefill_choices=choices, prefill_plan_rows=rows,
                        prefill_real_tokens=prompt,
                        prefill_pad_tokens=bucket - prompt,
                        prefill_scan_chunks=chunks,
                        prefill_tokens_sq=prompt * prompt)

    def step(self, extra, contexts) -> None:
        with _trace.span("decode::step.observe") as sp:
            load = np.asarray(extra[0])
            assignments, touched = self.routed.count_step(load)
            choices = int(load[:, 4].sum())
            self.step_choices.inc(choices)
            context, streams = self.count_streams(contexts)
            moved = streams * self.row_bytes
            self.state_bytes.inc(moved)
            sp.annotate(step_routed_assignments=assignments,
                        step_experts_touched=touched, step_choices=choices,
                        step_context_tokens=context, step_streams=streams,
                        step_state_bytes=moved)
        layers = self.config.count(ATTENTION)
        self.count_walks(layers * self.pool_walk(contexts),
                         layers * self._slots * self._slot_blocks)


class NemotronHLM(LMAdapter):
    """One one-mixer-a-layer hybrid LM: config + the jit-ready functions."""

    # a Mamba layer's recurrent row and convolution tail live in slot rows
    slot_state = True
    config_class = NemotronHConfig
    observer_class = NemotronHObserver
    param_shapes = staticmethod(param_shapes)

    def __init__(self, config: NemotronHConfig):
        super().__init__(config)
        # the step sizes are drawn from the range the configuration states
        self.init_tensor = functools.partial(init_tensor, steps=(
            config.time_step_min, config.time_step_max,
            config.time_step_floor))

    # -- what an engine asks of a model ------------------------------------
    def _make_cache(self, num_blocks: int, block_tokens: int, dtype: str,
                    slots: int) -> HybridStateCache:
        cfg = self.config
        return HybridStateCache(
            cfg.kv_width, num_blocks, block_tokens, slots, dtype=dtype,
            kv_layers=cfg.count(ATTENTION),
            recurrent=(cfg.count(MAMBA), cfg.state_shape),
            tails=(cfg.count(MAMBA), cfg.conv_kernel, cfg.conv_width))

    def _unpack(self, plist):
        """(the model's own tensors, {kind: the kind's stacks})."""
        p = dict(zip(self.param_names(), plist))
        return ({k: v for k, v in p.items() if k[:2] not in STACKS.values()},
                {kind: sub(p, prefix) for kind, prefix in STACKS.items()})

    def _layers(self):
        """(kind, the layer's place among its kind) a layer, in order."""
        seen = dict.fromkeys(STACKS, 0)
        for kind in self.config.hybrid_override_pattern:
            yield kind, seen[kind]
            seen[kind] += 1

    @staticmethod
    def _one(stack: dict, at: int, but=()) -> dict:
        """Layer ``at`` of a kind's stacks, less the leaves in ``but``."""
        return {k: v[at] for k, v in stack.items() if k not in but}

    # -- shared layer math -------------------------------------------------
    def _rms(self, x, g):
        return rms_norm(x, g, self.config.layer_norm_epsilon)

    def _ssm_in(self, w, u):
        """u [N, D] → z [N, Di], ``[x | B | C]`` [N, conv width] (both in
        u's dtype), dt [N, H] float32."""
        cfg = self.config
        with jax.named_scope("ssd_in"):
            p = jnp.dot(u, w["in_proj"], preferred_element_type=jnp.float32)
            Di, Cw = cfg.d_inner, cfg.conv_width
            return (p[:, :Di].astype(u.dtype),
                    p[:, Di:Di + Cw].astype(u.dtype), p[:, Di + Cw:])

    def _ssm_split(self, w, c, dt):
        """The convolved ``[x | B | C]`` c [N, conv width] and dt [N, H] →
        x [N, H, P], Δ [N, H] float32, A [H] float32, B, C [N, G, N_state]."""
        cfg = self.config
        N, Di, Bw = c.shape[0], cfg.d_inner, cfg.bc_width
        G, St = cfg.n_groups, cfg.ssm_state_size
        delta = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(w["a_log"].astype(jnp.float32))
        return (c[:, :Di].reshape(N, cfg.mamba_num_heads, cfg.mamba_head_dim),
                delta, A, c[:, Di:Di + Bw].reshape(N, G, St),
                c[:, Di + Bw:].reshape(N, G, St))

    def _ssm_out(self, w, y, xs, z):
        """The scan's output y [N, H, P] float32, its input xs and the gate
        z [N, Di] → the mixer's output [N, D]."""
        cfg = self.config
        f32 = jnp.float32
        with jax.named_scope("ssd_out"):
            y = y + w["d_skip"].astype(f32)[None, :, None] * xs.astype(f32)
            N, G = y.shape[0], cfg.n_groups
            y = y.reshape(N, G, -1) * jax.nn.silu(
                z.astype(f32)).reshape(N, G, -1)
            y = y * lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                              + cfg.layer_norm_epsilon)
            y = (y.reshape(N, -1) * w["ssm_norm"].astype(f32)).astype(z.dtype)
            return mm(y, w["out_proj"])

    def _mamba_prompt(self, w, u, valid, length, dense: bool):
        """A prompt's rows u [T, D] → (the mixer's output [T, D], S at the
        last real position (kept layout, float32), the last K-1 real inputs
        of the convolution [K-1, conv width])."""
        cfg = self.config
        K = cfg.conv_kernel
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
                S = _ssd.pack_state(S)
            else:
                y, S = _ssd.ssd_scan(xs, delta, A, B, C,
                                     chunk=cfg.chunk_size)
        return self._ssm_out(w, y, xs, z), S, tail

    def _route(self, w, u):
        """u [N, D] → (router logits [N, Er] float32, ids [N, K], weights [N,
        K] float32), over ALL the router's experts."""
        cfg = self.config
        with jax.named_scope("moe_router"):
            logits = jnp.dot(u, w["router"],
                             preferred_element_type=jnp.float32)
            ids, weights = _moe.route_topk(
                logits, cfg.num_experts_per_tok, cfg.routed_scaling_factor,
                True, score="sigmoid", bias=w["router_bias"], eps=1e-20)
        return logits, ids, weights

    def _routed(self, stack, at: int, u, ids, weights, valid, tile: int,
                dense: bool):
        """The held experts' part of the layer on u [N, D] → (R [N, D]
        float32, load [5]).  The experts' matrices are handed over as the
        whole stack with the layer's index.  A prefill's plan is walked in
        blocks of rows sized by the share, as many as the assignments need:
        no row is dropped."""
        cfg = self.config
        N, K = ids.shape
        with jax.named_scope("moe_routed"):
            plan = _moe.plan_groups(ids, valid, cfg.n_routed_experts, tile,
                                    first=cfg.first_expert)
            load = jnp.concatenate([
                plan.load, jnp.sum(plan.padded_sizes, dtype=jnp.int32)[None],
                (jnp.sum(valid, dtype=jnp.int32) * K)[None]])
            y = _moe.planned_experts(
                u, weights, plan, None, stack["e_up"], stack["e_down"], tile,
                act="relu2", layer=at, impl="xla" if dense else None,
                out_dtype=u.dtype, row_block=_moe.share_block_rows(
                    N, K, cfg.n_routed_experts, cfg.router_experts, tile))
        return y, load

    def _shared(self, w, u):
        """The shared expert on u [N, D] → [N, D] float32 — a prompt's rows
        :data:`_SHARED_ROWS` at a time, so that the float32 product of its
        width is of a block of rows and not of the prompt."""
        def unit(rows):
            h = relu2(jnp.dot(rows, w["s_up"],
                              preferred_element_type=jnp.float32)
                      ).astype(rows.dtype)
            return jnp.dot(h, w["s_down"], preferred_element_type=jnp.float32)

        N = u.shape[0]
        with jax.named_scope("moe_shared"):
            if N <= _SHARED_ROWS or N % _SHARED_ROWS:
                return unit(u)
            return lax.map(unit, u.reshape(-1, _SHARED_ROWS, u.shape[1])
                           ).reshape(N, -1)

    def _experts(self, stack, at: int, x, valid, tile: int, dense: bool):
        """One ``E`` layer on the residual rows x [N, D] → (x', (load, ids,
        weights, u, router logits, the layer's output float32))."""
        w = self._one(stack, at, but=("e_up", "e_down"))
        u = self._rms(x, w["ln"])
        logits, ids, weights = self._route(w, u)
        r, load = self._routed(stack, at, u, ids, weights, valid, tile,
                               dense)
        out = r + self._shared(w, u)
        return (x.astype(jnp.float32) + out).astype(x.dtype), \
            (load, ids, weights, u, logits, out)

    def _qkv(self, w, u, dtype):
        """u [N, D] → q [N, nh, dh], the cache rows [k | v] [N, 2·kw]:
        nothing is rotated."""
        cfg = self.config
        with jax.named_scope("attn_qkv"):
            qkv = mm(u, w["wqkv"])
            q = qkv[:, :cfg.q_width].reshape(
                u.shape[0], cfg.num_attention_heads, cfg.head_dim)
        return q, qkv[:, cfg.q_width:].astype(dtype)

    def _attn_out(self, w, o, dtype):
        with jax.named_scope("attn_out"):
            return mm(o.reshape(o.shape[0], -1).astype(dtype), w["wo"])

    def _head(self, p, x):
        with jax.named_scope("lm_head"):
            return lax.dot_general(
                self._rms(x, p["final_norm"]), p["head"],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    # -- a prompt's layers -------------------------------------------------
    def _prompt_layers(self, p, stacks, tokens, length, cache_dtype,
                       dense: bool, rows_out, carry):
        """tokens [T] through every layer → (x [T, D], carry', every Mamba
        layer's (S, tail) stacked, every expert layer's (load [5], ids [T,
        K], and at the last real position the routing weights [K], u [D],
        router logits [Er] and the layer's output [D]) stacked).
        ``rows_out(index, rows, carry) → carry`` files an attention layer's
        cache rows [T, 2·kw]."""
        cfg = self.config
        T = tokens.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        valid = pos < length
        last = jnp.maximum(length - 1, 0)
        tile = _moe.row_tile(T, jnp.dtype(cfg.dtype))
        x = p["emb"][tokens]
        rows_m, got_e = [], []
        for kind, at in self._layers():
            if kind == MAMBA:
                w = self._one(stacks[MAMBA], at)
                out, S, tail = self._mamba_prompt(
                    w, self._rms(x, w["ln"]), valid, length, dense)
                x = x + out
                rows_m.append((S, tail))
            elif kind == EXPERTS:
                x, (load, ids, weights, u, logits, out) = self._experts(
                    stacks[EXPERTS], at, x, valid, tile, dense)
                got_e.append((load, ids, weights[last], u[last],
                              logits[last], out[last]))
            else:
                w = self._one(stacks[ATTENTION], at)
                q, rows = self._qkv(w, self._rms(x, w["ln"]), cache_dtype)
                carry = rows_out(at, rows, carry)
                with jax.named_scope("attn"):
                    o = _gqa.prefill_attention_xla(
                        q, rows, cfg.num_key_value_heads) if dense \
                        else _gqa.group_prefill_attention(
                            q, rows, cfg.num_key_value_heads, length=length)
                x = x + self._attn_out(w, o, x.dtype)
        return x, carry, _stacked(rows_m), _stacked(got_e)

    # -- full forward (the parity anchor) ----------------------------------
    def full_logits(self, plist, tokens, lengths=None):
        """tokens [B, T] int32 → logits [B, T, V] float32: every position
        through every layer, dense masked attention, the recurrence one
        position at a time, the experts through ``lax.ragged_dot``, no cache
        and no kernel."""
        p, stacks = self._unpack(plist)
        B, T = tokens.shape
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)

        def one(toks, length):
            x, _, _, _ = self._prompt_layers(
                p, stacks, toks, length, jnp.dtype(self.config.dtype), True,
                lambda at, rows, carry: carry, jnp.zeros((), jnp.int32))
            return self._head(p, x)

        # one sequence after another: lax.ragged_dot has no batched form
        return lax.map(lambda a: one(*a), (tokens, lengths))

    # -- prefill -----------------------------------------------------------
    def prefill(self, plist, state, tokens, length, slot, block_table, seed,
                temperature, top_k):
        """state ``[kv pool, S, conv]``, tokens [1, Tb] (bucket-padded),
        length [] int32, slot [] int32 (the slot whose rows this prompt
        fills), block_table [MB] int32 → ([next_token [], logits [V], load
        [Le, 5], ids [Le, Tb, K], routing weights [Le, 1, K], u [Le, 1, D],
        router logits [Le, 1, Er], expert layers' outputs [Le, 1, D]],
        state').  An attention layer's row of every real position lands in
        the request's blocks, pad positions in trash block 0; the slot's
        recurrent rows and convolution tails are overwritten whole."""
        p, stacks = self._unpack(plist)
        kv, hs, conv = state
        bs = kv.shape[2]
        pos, _, blocks, last = prompt_addresses(
            length, tokens.shape[1], block_table, bs)

        def rows_out(at, rows, kv_):
            with jax.named_scope("kv_cache_write"):
                return kv_.at[at, blocks, pos % bs].set(rows)

        x, kv, (S_new, tails), got = self._prompt_layers(
            p, stacks, tokens[0], length, kv.dtype, False, rows_out, kv)
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
        load, ids, *judged = got
        return [tok, logits, load, ids] + [a[:, None] for a in judged], \
            [kv, hs, conv]

    # -- decode step -------------------------------------------------------
    def decode_step(self, plist, state, tokens, positions, block_tables,
                    seeds, steps, temperature, top_k, attn_impl=None):
        """state ``[kv pool, S, conv]``, tokens / positions [S], block_tables
        [S, MB] → ([next_tokens [S], logits [S, V], load [Le, 5], ids [Le, S,
        K], routing weights [Le, S, K], u [Le, S, D], router logits [Le, S,
        Er], expert layers' outputs [Le, S, D]], state').  A slot without a
        stream is routed to no expert."""
        del attn_impl           # one path: the kernels choose by shape alone
        cfg = self.config
        p, stacks = self._unpack(plist)
        kv, hs, conv = state
        bs = kv.shape[2]
        cl, live, _, blocks = step_addresses(positions, block_tables, bs)
        tile = _moe.row_tile(tokens.shape[0], jnp.dtype(cfg.dtype))
        x = p["emb"][tokens]
        got_e = []
        for kind, at in self._layers():
            if kind == MAMBA:
                w = self._one(stacks[MAMBA], at)
                z, a, dt = self._ssm_in(w, self._rms(x, w["ln"]))
                with jax.named_scope("ssd_conv"):
                    c, tail = _ssm.conv_step(conv[at], a, w["conv_w"],
                                             w["conv_b"])
                    c = jax.nn.silu(c).astype(x.dtype)
                    conv = conv.at[at].set(tail.astype(conv.dtype))
                with jax.named_scope("ssd_scan"):
                    xs, delta, A, B, C = self._ssm_split(w, c, dt)
                    y, hs = _ssd.ssd_state_step(hs, at, xs, delta, A, B, C)
                x = x + self._ssm_out(w, y, xs, z)
            elif kind == EXPERTS:
                x, got = self._experts(stacks[EXPERTS], at, x, live, tile,
                                       False)
                got_e.append(got)
            else:
                w = self._one(stacks[ATTENTION], at)
                q, rows = self._qkv(w, self._rms(x, w["ln"]), kv.dtype)
                with jax.named_scope("kv_cache_write"):
                    kv = kv.at[at, blocks, positions % bs].set(rows)
                with jax.named_scope("attn"):
                    o = _gqa.decode_attention(q, kv, block_tables, cl, at,
                                              cfg.num_key_value_heads)
                x = x + self._attn_out(w, o, x.dtype)
        logits = self._head(p, x)
        with jax.named_scope("sampling"):
            toks = sample(logits, seeds, steps, temperature, top_k)
        return [toks, logits, *_stacked(got_e)], [kv, hs, conv]


def _stacked(rows: list) -> tuple:
    """A list of equal tuples of arrays → the tuple of their stacks."""
    return tuple(jnp.stack(a) for a in zip(*rows))


MODEL_TYPES[MODEL_TYPE] = NemotronHLM.from_dict

__all__ = ["NemotronHConfig", "NemotronHLM", "NemotronHObserver",
           "param_shapes", "init_tensor", "step_bias", "relu2", "MAMBA",
           "EXPERTS", "ATTENTION", "STACKS"]
