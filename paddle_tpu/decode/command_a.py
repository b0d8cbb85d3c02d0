"""A parallel-block window-and-full attention mixture-of-experts LM
(``model_type: cohere2_moe``, Cohere's Command A+) for the decode plane,
configured by its published keys.

Every layer is ONE mean-subtracting LayerNorm and three branches read from
it and summed into the residual (``use_parallel_block``) — nothing orders
them:

    u  = (x − mean x) · rsqrt(var x + layer_norm_eps) ⊙ g
    A  = attn(u) W_o
    R  = Σ_{e ∈ C} w_e (silu(u Wg_e) ⊙ (u Wu_e)) Wd_e        (routed experts)
    S  = 1/n Σ_j (silu(u Wg'_j) ⊙ (u Wu'_j)) Wd'_j           (n shared experts)
    x' = x + A + R + S

a final LayerNorm and a head TIED to the embedding (``logit_scale · LN_f(x)
Eᵀ``); no bias anywhere, no q/k norm.  The layers come in *periods* —
``layer_types`` = ``(sliding_attention × (p − 1), full_attention) × n``
(``order_of_interleaved_layers: local_attn_first``): ``p − 1`` WINDOW layers,
then one FULL layer:

- **window attention**: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` K/V heads, rotary positions in the GPT-J layout
  (``position_embedding_type: rope_gptj``: lanes ``(2i, 2i + 1)`` are a pair,
  over the whole head at ``rope_theta``); key ``j`` visible to query ``t`` iff
  ``0 ≤ t − j < sliding_window``.  A stream keeps its last ``sliding_window``
  rows in a ring, at ``position mod window``, the key after its rotation.
- **full attention**: the same heads, every key ``j ≤ t`` visible and NOTHING
  rotated.  A row ``[k | v]`` a token in the paged pool, one pool layer a
  full layer.
- **routed experts** (``kernels/moe.py``): ``s = sigmoid(u W_r)`` in float32
  over ALL the router's experts, the ``num_experts_per_tok`` largest chosen
  (no selection bias, no groups), ``w_e = s_e / Σ_C s`` (``norm_topk_prob``),
  no scaling factor.
- **shared experts**: ``num_shared_experts`` SiLU-gated units of
  ``intermediate_size``, their outputs AVERAGED
  (``shared_expert_combination_strategy: average``) — computed as ONE gated
  product ``num_shared_experts · intermediate_size`` wide whose output is
  scaled by ``1 / num_shared_experts``: the same sum.

**A share of the experts and of the vocabulary.**  ``num_experts`` is how many
experts' matrices the model HOLDS; ``router_experts`` (the published count;
none: the same) is the router's width, and ``first_expert`` the first held.
The router's scores, choice and renormalisation are over all
``router_experts``; only assignments to held experts are planned and computed
— every one of them: a prefill's plan is walked in blocks of rows sized by
the share (``kernels/moe.py planned_experts(row_block=)``), as many blocks as
the assignments need — and what the experts held elsewhere would add is left
out.  ``vocab_size`` counts the rows of the tied table this chip holds: table
and head at once.  Nothing stands in for the other chips.

So a stream's state is of two kinds (:class:`~paddle_tpu.decode.cache.
HybridStateCache` with no recurrent rows): blocks of a paged pool of the full
layers, held by block table, and a ring a slot a window layer, addressed by
slot (``slot_state``).

Programs ``lax.scan`` over the periods' stacked weights (``pw.*`` ``[P,
period − 1, …]`` the window layers, scanned in turn inside a period, then
``pf.*`` ``[P, …]`` the full layer), pool and rings the loops' carry, updated
in place with the layer as an index.  The experts' matrices are NOT scanned
over: the grouped kernel is handed the whole stack and the layer's index.

The model is an :class:`~paddle_tpu.decode.adapter.LMAdapter`, so a
:class:`~paddle_tpu.decode.engine.DecodeEngine` serves it as it is.  Beside
token and logits the programs return every layer's load figures ``[L, 5]``
(assignments to held experts, held experts touched, the largest load, the
plan's padded rows, all the router's choices of real tokens), the chosen
experts ``[L, tokens, K]`` and, at the rows that reach the head, the routing
weights, the router's input ``u`` and its logits.  There is no suffix prefill
over a ring, so ``supports`` is empty.

Weights, residual stream, pool and rings are ``dtype`` (bf16 as deployed);
matmuls accumulate in float32; the norms' statistics, the router's logits,
scores and weights and the softmax are float32; the three branches and the
residual are summed in float32 and rounded once.
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

from .adapter import (EXPERT_LEAVES, MODEL_TYPES, ConfigDict, LMAdapter,
                      PoolObserver, RingSeries, RoutedLoadSeries, mm,
                      prompt_addresses, ring_of_prompt, ring_step_addresses,
                      sample, sample_first, step_addresses, sub, unscanned,
                      walked_blocks)
from .cache import HybridStateCache
from ..kernels import gqa as _gqa
from ..kernels import moe as _moe
from ..observability import trace as _trace

MODEL_TYPE = "cohere2_moe"
_WINDOW, _FULL = "sliding_attention", "full_attention"
# rows of a prompt the shared experts take at once
_SHARED_ROWS = 2048


@dataclasses.dataclass(frozen=True)
class CommandAConfig(ConfigDict):
    """The published keys this model reads, under their published names; the
    share of the experts it holds (``router_experts``, ``first_expert``:
    module doc); the deployment's per-stream ``max_seq_len`` and the weights'
    ``dtype``.  ``layer_types`` may be the published model's whole: a cut in
    depth reads its first ``num_hidden_layers`` entries."""

    vocab_size: int
    hidden_size: int = 64
    intermediate_size: int = 32
    num_hidden_layers: int = 4
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 16
    num_experts: int = 8
    num_experts_per_tok: int = 2
    num_shared_experts: int = 2
    norm_topk_prob: bool = True
    expert_selection_fn: str = "sigmoid"
    shared_expert_combination_strategy: str = "average"
    layer_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    position_embedding_type: str = "rope_gptj"
    rotary_pct: float = 1.0
    layer_types: Tuple[str, ...] = (_WINDOW, _WINDOW, _WINDOW, _FULL)
    sliding_window: int = 32
    logit_scale: float = 1.0
    tie_word_embeddings: bool = True
    use_parallel_block: bool = True
    use_qk_norm: bool = False
    use_gated_activation: bool = True
    attention_bias: bool = False
    hidden_act: str = "silu"
    first_k_dense_replace: int = 0
    router_experts: Optional[int] = None
    first_expert: int = 0
    max_seq_len: int = 128
    dtype: str = "bfloat16"
    model_type = MODEL_TYPE

    def __post_init__(self):
        L = self.num_hidden_layers
        kinds = tuple(str(k) for k in self.layer_types)[:L]
        if len(kinds) != L:
            raise ValueError(f"layer_types has {len(kinds)} entries for {L} "
                             "layers")
        object.__setattr__(self, "layer_types", kinds)
        p = self.period
        if L % p or p < 2 \
                or kinds != ((_WINDOW,) * (p - 1) + (_FULL,)) * (L // p):
            raise ValueError(
                "the stack is whole periods of window layers and then one "
                f"full layer (got layer_types {kinds})")
        if not (self.use_parallel_block and self.tie_word_embeddings
                and self.use_gated_activation
                and self.expert_selection_fn == "sigmoid"
                and self.shared_expert_combination_strategy == "average"
                and self.position_embedding_type == "rope_gptj"
                and self.rotary_pct == 1 and self.hidden_act == "silu"
                and self.num_shared_experts > 0) \
                or self.use_qk_norm or self.attention_bias \
                or self.first_k_dense_replace:
            raise ValueError(
                "written down here: the parallel block, a tied head, SiLU-"
                "gated experts behind a sigmoid router, shared experts "
                "averaged, GPT-J rotary pairs over the whole head, no q/k "
                "norm, no bias, no leading dense layer")
        if self.router_experts is None:
            object.__setattr__(self, "router_experts", self.num_experts)
        if not 0 <= self.first_expert \
                <= self.router_experts - self.num_experts:
            raise ValueError(
                f"experts {self.first_expert} … +{self.num_experts} are not "
                f"among the router's {self.router_experts}")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.head_dim % 2:
            raise ValueError("K/V heads divide the query heads, and a head "
                             "is rotated by pairs")

    @property
    def period(self) -> int:
        kinds = self.layer_types
        return kinds.index(_FULL) + 1 if _FULL in kinds else len(kinds) + 1

    @property
    def periods(self) -> int:
        return self.num_hidden_layers // self.period

    @property
    def window_layers(self) -> int:
        return self.periods * (self.period - 1)

    @property
    def q_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def shared_width(self) -> int:
        return self.num_shared_experts * self.intermediate_size


def param_shapes(cfg: CommandAConfig) -> Dict[str, tuple]:
    """name → (shape, init): a float is the std of a normal; ``norm`` a norm
    weight (1 + 0.1 N).  ``emb`` is table and head at once; the shared
    experts lie side by side in ``s_*`` (expert ``j``'s columns — of
    ``s_down`` rows — are ``j · F … (j + 1) · F``)."""
    D, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    E, Fs = cfg.num_experts, cfg.shared_width
    layer = {"ln": ((D,), "norm"),
             "router": ((D, cfg.router_experts), D ** -0.5),
             "wqkv": ((D, cfg.q_width + 2 * cfg.kv_width), D ** -0.5),
             "wo": ((cfg.q_width, D), cfg.q_width ** -0.5),
             "e_gate": ((E, D, F), D ** -0.5), "e_up": ((E, D, F), D ** -0.5),
             "e_down": ((E, F, D), F ** -0.5),
             "s_gate": ((D, Fs), D ** -0.5), "s_up": ((D, Fs), D ** -0.5),
             "s_down": ((Fs, D), F ** -0.5)}
    out = {"emb": ((V, D), 1.0), "final_norm": ((D,), "norm")}
    for prefix, lead in (("pw.", (cfg.periods, cfg.period - 1)),
                         ("pf.", (cfg.periods,))):
        out.update({prefix + k: (lead + shape, init)
                    for k, (shape, init) in layer.items()})
    return out


def layer_norm(x, g, eps: float):
    """``(x − mean x) · rsqrt(var x + eps) · g`` over the last axis, the
    statistics in float32, back in x's dtype; no bias."""
    x32 = x.astype(jnp.float32)
    c = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(c), axis=-1, keepdims=True)
    return (c * lax.rsqrt(var + eps) * g.astype(jnp.float32)).astype(x.dtype)


def rotary_gptj(x, positions, theta: float):
    """Rotary positions in the GPT-J layout over the whole head: lanes ``(2i,
    2i + 1)`` are pair ``i``, rotated by ``positions · theta^(−2i/dh)``.  x
    [N, heads, dh], positions [N] → the same shape and dtype, computed in
    float32.  The pairs stay where they lie, so the cached key is the
    published one: a lane meets its pair's other lane (``−x[2i + 1]`` at lane
    ``2i``, ``x[2i]`` at lane ``2i + 1``) through a product with the signed
    permutation of the lanes — exact in any dtype, and nothing of a
    prompt's [T, heads, dh] is sliced, rolled or held in float32."""
    dh = x.shape[-1]
    inv = jnp.exp(jnp.arange(dh // 2, dtype=jnp.float32)
                  * (-math.log(theta) / (dh // 2)))
    ang = jnp.repeat(positions.astype(jnp.float32)[:, None] * inv[None, :],
                     2, axis=-1)                                   # [N, dh]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    lane = np.arange(dh)
    swap = np.zeros((dh, dh), np.float32)
    swap[lane ^ 1, lane] = np.where(lane % 2 == 0, -1.0, 1.0)
    other = jnp.dot(x, jnp.asarray(swap, x.dtype),
                    precision=lax.Precision.HIGHEST,
                    preferred_element_type=x.dtype)
    return (x.astype(jnp.float32) * cos
            + other.astype(jnp.float32) * sin).astype(x.dtype)


class CommandAObserver(PoolObserver):
    """``decode.<engine>.*`` series of this model: the common ones, the
    pool's, the rings' (:class:`~paddle_tpu.decode.adapter.RingSeries`), the
    routed load (``extra[0]``: each layer's ``[assignments to held experts,
    held experts touched, largest load, the plan's padded rows, all the
    router's choices]``) and the choices the router made of ALL its experts,
    so that ``step_routed_assignments / step_choices`` is the share that is
    held.  Its walks: a full layer's over the pool, a window layer's over a
    ring up to the window."""

    def __init__(self, name: str, cache, config: CommandAConfig,
                 table_shape):
        super().__init__(name, cache, config, table_shape)
        sc = self.series
        self.routed = RoutedLoadSeries(
            sc, buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048,
                         4096, 8192, 16384))
        self.ring = RingSeries(sc, config.sliding_window)
        self.step_choices = sc.counter(
            "step_choices", "experts chosen by decode steps' routers (live "
            "slots x top-k x layers), held here or not")
        self.prefill_choices = sc.counter(
            "prefill_choices", "experts chosen by prefills' routers (real "
            "tokens x top-k x layers), held here or not")
        self.prefill_plan_rows = sc.counter(
            "prefill_plan_rows", "rows of the prefills' grouped plans: every "
            "held expert's assignments padded to whole row tiles")
        sc.gauge("window_state_bytes").set(cache.window_state_bytes)

    def prefill(self, extra, prompt: int, bucket: int) -> None:
        with _trace.span("decode::prefill.observe") as sp:
            load = np.asarray(extra[0])
            assignments = self.routed.count_prefill(load)
            rows, choices = int(load[:, 3].sum()), int(load[:, 4].sum())
            self.prefill_plan_rows.inc(rows)
            self.prefill_choices.inc(choices)
            pairs = self.ring.count_prompt(prompt)
            self.count_prompt(prompt, bucket)
            sp.annotate(prefill_routed_assignments=assignments,
                        prefill_choices=choices, prefill_plan_rows=rows,
                        prefill_real_tokens=prompt,
                        prefill_window_pairs=pairs,
                        prefill_tokens_sq=prompt * prompt)

    def step(self, extra, contexts) -> None:
        cfg, cache = self.config, self.cache
        with _trace.span("decode::step.observe") as sp:
            load = np.asarray(extra[0])
            assignments, touched = self.routed.count_step(load)
            choices = int(load[:, 4].sum())
            self.step_choices.inc(choices)
            context, streams = self.count_streams(contexts)
            live = self.ring.count_step(contexts)
            sp.annotate(step_routed_assignments=assignments,
                        step_experts_touched=touched, step_choices=choices,
                        step_context_tokens=context,
                        step_ring_rows_live=live, step_streams=streams)
        ring = walked_blocks(np.minimum(contexts, cfg.sliding_window),
                             cache.ring_rows, self._slots)
        self.count_walks(
            cfg.periods * self.pool_walk(contexts) + cfg.window_layers * ring,
            self._slots * (cfg.periods * self._slot_blocks
                           + cfg.window_layers * cache.ring_blocks))

    def decodez(self) -> dict:
        """… and the rings' live share."""
        return dict(super().decodez(), **self.ring.decodez())


class CommandALM(LMAdapter):
    """One parallel-block window-and-full attention expert LM: config + the
    jit-ready functions."""

    # a window layer's ring lives in slot rows
    slot_state = True
    config_class = CommandAConfig
    observer_class = CommandAObserver
    param_shapes = staticmethod(param_shapes)

    # -- what an engine asks of a model ------------------------------------
    def _make_cache(self, num_blocks: int, block_tokens: int, dtype: str,
                    slots: int) -> HybridStateCache:
        cfg = self.config
        return HybridStateCache(
            cfg.kv_width, num_blocks, block_tokens, slots, dtype=dtype,
            kv_layers=cfg.periods,
            rings=(cfg.window_layers, cfg.sliding_window))

    def _unpack(self, plist):
        """(the model's own tensors, the window layers' stacks, the full
        layers' stacks)."""
        p = dict(zip(self.param_names(), plist))
        return ({k: v for k, v in p.items() if k[:3] not in ("pw.", "pf.")},
                sub(p, "pw."), sub(p, "pf."))

    # -- shared layer math -------------------------------------------------
    def _ln(self, x, g):
        return layer_norm(x, g, self.config.layer_norm_eps)

    def _route(self, w, u):
        """u [N, D] → (router logits [N, Er] float32, ids [N, K], weights [N,
        K] float32), over ALL the router's experts."""
        cfg = self.config
        with jax.named_scope("moe_router"):
            logits = jnp.dot(u, w["router"],
                             preferred_element_type=jnp.float32)
            ids, weights = _moe.route_topk(
                logits, cfg.num_experts_per_tok, 1.0, cfg.norm_topk_prob,
                score="sigmoid")
        return logits, ids, weights

    def _routed(self, stacks, at, u, ids, weights, valid, tile: int, dense):
        """The held experts' part of the layer on u [N, D] → (R [N, D]
        float32, load [5]).  ``stacks`` are the experts' matrices as they lie
        and ``at`` the layer's index into their leading axis.  A prefill's
        plan is walked in blocks of rows sized by the share, as many as the
        assignments need: no row is dropped."""
        cfg = self.config
        N, K = ids.shape
        with jax.named_scope("moe_routed"):
            plan = _moe.plan_groups(ids, valid, cfg.num_experts, tile,
                                    first=cfg.first_expert)
            load = jnp.concatenate([
                plan.load, jnp.sum(plan.padded_sizes, dtype=jnp.int32)[None],
                (jnp.sum(valid, dtype=jnp.int32) * K)[None]])
            y = _moe.planned_experts(
                u, weights, plan, *stacks, tile, act="silu", layer=at,
                impl="xla" if dense else None, out_dtype=u.dtype,
                row_block=_moe.share_block_rows(
                    N, K, cfg.num_experts, cfg.router_experts, tile))
        return y, load

    def _shared(self, w, u):
        """The shared experts' average on u [N, D] → S [N, D] float32: one
        gated product of all of them side by side, scaled by 1 / n — a
        prompt's rows :data:`_SHARED_ROWS` at a time, so that the two float32
        products ``n · F`` wide are of a block of rows and not of the
        prompt."""
        def unit(rows):
            g = jnp.dot(rows, w["s_gate"], preferred_element_type=jnp.float32)
            v = jnp.dot(rows, w["s_up"], preferred_element_type=jnp.float32)
            h = (jax.nn.silu(g) * v).astype(rows.dtype)
            return jnp.dot(h, w["s_down"], preferred_element_type=jnp.float32
                           ) * (1.0 / self.config.num_shared_experts)

        N = u.shape[0]
        with jax.named_scope("moe_shared"):
            if N <= _SHARED_ROWS or N % _SHARED_ROWS:
                return unit(u)
            return lax.map(unit, u.reshape(-1, _SHARED_ROWS, u.shape[1])
                           ).reshape(N, -1)

    def _qkv(self, w, u, positions, rope: bool, dtype):
        """u [N, D] → q [N, nh, dh], the cache rows [k | v] [N, 2·kw]; where
        ``rope`` both q and k after their rotation at ``positions``."""
        cfg = self.config
        N, dh = u.shape[0], cfg.head_dim
        with jax.named_scope("attn_proj"):
            qkv = mm(u, w["wqkv"])
            q = qkv[:, :cfg.q_width].reshape(N, cfg.num_attention_heads, dh)
            k = qkv[:, cfg.q_width:cfg.q_width + cfg.kv_width]
            v = qkv[:, cfg.q_width + cfg.kv_width:]
        if rope:
            with jax.named_scope("attn_rope"):
                q = rotary_gptj(q, positions, cfg.rope_theta)
                k = rotary_gptj(k.reshape(N, cfg.num_key_value_heads, dh),
                                positions, cfg.rope_theta
                                ).reshape(N, cfg.kv_width)
        return q, jnp.concatenate([k, v], axis=-1).astype(dtype)

    def _attn_out(self, w, o, dtype):
        """o [N, nh, dh] float32 → A [N, D] float32."""
        with jax.named_scope("attn_proj"):
            return jnp.dot(o.reshape(o.shape[0], -1).astype(dtype), w["wo"],
                           preferred_element_type=jnp.float32)

    def _head(self, p, x):
        with jax.named_scope("lm_head"):
            return lax.dot_general(
                self._ln(x, p["final_norm"]), p["emb"],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * jnp.float32(self.config.logit_scale)

    def _block(self, w, stacks, at, x, valid, tile, dense, attend):
        """One layer: the norm, the three branches, the residual.
        ``attend(u) → (A [N, D] float32, carry')`` is the attention branch;
        returns (x', carry', (load, ids, weights, u, logits))."""
        u = self._ln(x, w["ln"])
        logits, ids, weights = self._route(w, u)
        a, carry = attend(u)
        r, load = self._routed(stacks, at, u, ids, weights, valid, tile,
                               dense)
        s = self._shared(w, u)
        x = (x.astype(jnp.float32) + a + r + s).astype(x.dtype)
        return x, carry, (load, ids, weights, u, logits)

    def _window_stacks(self, lay: dict):
        """The window layers' experts as ONE leading axis (period by period:
        layer ``p · (period − 1) + j``), a reshape of what lies there."""
        return tuple(lay[k].reshape((-1,) + lay[k].shape[2:])
                     for k in EXPERT_LEAVES)

    def _scan_periods(self, pw, pf, x, carry, layer):
        """Every layer in turn: ``lax.scan`` over the periods and, inside one,
        over its window layers, then its full layer.  ``layer(w, stacks,
        index, x, carry, kind) → (x, carry, got)`` is one layer (``kind``
        ``window`` / ``full``, ``index`` its place among its kind, ``stacks``
        its kind's experts as they lie); returns (x, carry, every layer's
        ``got`` stacked ``[L, …]`` in layer order)."""
        cfg = self.config
        win_stacks = self._window_stacks(pw)
        full_stacks = tuple(pf[k] for k in EXPERT_LEAVES)
        n_win = cfg.period - 1

        def period(state, xs):
            ww, wf, i = xs

            def window(state, xs):
                w, j = xs
                x, carry, got = layer(w, win_stacks, i * n_win + j, *state,
                                      "window")
                return (x, carry), got

            state, got_w = lax.scan(
                window, state, (ww, jnp.arange(n_win, dtype=jnp.int32)))
            x, carry, got_f = layer(wf, full_stacks, i, *state, "full")
            return (x, carry), tuple(jnp.concatenate([w, f[None]])
                                     for w, f in zip(got_w, got_f))

        (x, carry), got = lax.scan(
            period, (x, carry),
            (unscanned(pw), unscanned(pf),
             jnp.arange(cfg.periods, dtype=jnp.int32)))
        return x, carry, tuple(g.reshape((-1,) + g.shape[2:]) for g in got)

    # -- a prompt's layers -------------------------------------------------
    def _prompt_layers(self, p, pw, pf, tokens, length, cache_dtype,
                       dense: bool, rows_out, carry):
        """tokens [T] through every layer → (x [T, D], carry', (load [L, 5],
        ids [L, T, K], and at the last real position the routing weights [L,
        K], u [L, D] and router logits [L, Er])).  ``rows_out(kind, index,
        rows, carry) → carry`` files a layer's cache rows [T, 2·kw]."""
        cfg = self.config
        T = tokens.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        valid = pos < length
        last = jnp.maximum(length - 1, 0)
        tile = _moe.row_tile(T, jnp.dtype(cfg.dtype))
        attn = functools.partial(
            _gqa.prefill_attention_xla, n_kv=cfg.num_key_value_heads) \
            if dense else functools.partial(
                _gqa.group_prefill_attention, n_kv=cfg.num_key_value_heads,
                length=length)

        def layer(w, stacks, at, x, carry, kind):
            rope = kind == "window"

            def attend(u):
                q, rows = self._qkv(w, u, pos, rope, cache_dtype)
                new = rows_out(kind, at, rows, carry)
                with jax.named_scope("attn_window" if rope else "attn_full"):
                    o = attn(q, rows,
                             window=cfg.sliding_window if rope else None)
                return self._attn_out(w, o, x.dtype), new

            x, carry, (load, ids, weights, u, logits) = self._block(
                w, stacks, at, x, valid, tile, dense, attend)
            return x, carry, (load, ids, weights[last], u[last],
                              logits[last])

        return self._scan_periods(pw, pf, p["emb"][tokens], carry, layer)

    # -- full forward (the parity anchor) ----------------------------------
    def full_logits(self, plist, tokens, lengths=None):
        """tokens [B, T] int32 → logits [B, T, V] float32: every position
        through every layer, dense masked attention, the experts through
        ``lax.ragged_dot``, no cache and no kernel."""
        p, pw, pf = self._unpack(plist)
        B, T = tokens.shape
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)

        def one(toks, length):
            x, _, _ = self._prompt_layers(
                p, pw, pf, toks, length, jnp.dtype(self.config.dtype), True,
                lambda kind, at, rows, carry: carry, jnp.zeros((), jnp.int32))
            return self._head(p, x)

        # one sequence after another: lax.ragged_dot has no batched form
        return lax.map(lambda a: one(*a), (tokens, lengths))

    # -- prefill -----------------------------------------------------------
    def prefill(self, plist, state, tokens, length, slot, block_table, seed,
                temperature, top_k):
        """state ``[kv pool, rings]``, tokens [1, Tb] (bucket-padded), length
        [] int32, slot [] int32 (the slot whose rings this prompt fills),
        block_table [MB] int32 → ([next_token [], logits [V], load [L, 5],
        ids [L, Tb, K], routing weights [L, 1, K], u [L, 1, D], router logits
        [L, 1, Er]], state').  A full layer's row of every real position
        lands in the request's blocks, pad positions in trash block 0; the
        slot's rings are overwritten: ring row ``r`` gets the last real
        position that is ``r mod window`` (rows past a short prompt's end
        hold what the walk never reads)."""
        cfg = self.config
        p, pw, pf = self._unpack(plist)
        kv, rings = state
        Tb = tokens.shape[1]
        bs, W, rb = kv.shape[2], cfg.sliding_window, rings.shape[2]
        pos, _, blocks, last = prompt_addresses(length, Tb, block_table, bs)
        zero = jnp.zeros((), slot.dtype)
        fill = ring_of_prompt(length, Tb, W, rb)

        def rows_out(kind, at, rows, carry):
            kv_, rings_ = carry
            if kind == "full":
                with jax.named_scope("kv_cache_write"):
                    kv_ = kv_.at[at, blocks, pos % bs].set(rows)
            else:
                with jax.named_scope("ring_cache_write"):
                    rings_ = lax.dynamic_update_slice(
                        rings_, fill(rows),
                        (at, slot * (W // rb), zero, zero))
            return (kv_, rings_)

        x, (kv, rings), (load, ids, rw, u, rl) = self._prompt_layers(
            p, pw, pf, tokens[0], length, kv.dtype, False, rows_out,
            (kv, rings))
        logits = self._head(p, x[last][None])[0]
        with jax.named_scope("sampling"):
            tok = sample_first(logits, seed, temperature, top_k)
        return [tok, logits, load, ids, rw[:, None], u[:, None],
                rl[:, None]], [kv, rings]

    # -- decode step -------------------------------------------------------
    def decode_step(self, plist, state, tokens, positions, block_tables,
                    seeds, steps, temperature, top_k, attn_impl=None):
        """state ``[kv pool, rings]``, tokens / positions [S], block_tables
        [S, MB] → ([next_tokens [S], logits [S, V], load [L, 5], ids [L, S,
        K], routing weights [L, S, K], u [L, S, D], router logits [L, S,
        Er]], state').  A slot without a stream is routed to no expert."""
        del attn_impl           # one path: the kernels choose by shape alone
        cfg = self.config
        p, pw, pf = self._unpack(plist)
        S = tokens.shape[0]
        bs, W, rb = state[0].shape[2], cfg.sliding_window, state[1].shape[2]
        n_kv = cfg.num_key_value_heads
        cl, live, slots, blocks = step_addresses(positions, block_tables, bs)
        wl = jnp.minimum(cl, W)
        ring_tables, ring_blocks, ring_at = ring_step_addresses(
            positions, slots, W, rb)
        tile = _moe.row_tile(S, jnp.dtype(cfg.dtype))

        def layer(w, stacks, at, x, carry, kind):
            rope = kind == "window"

            def attend(u):
                kv, rings = carry
                q, rows = self._qkv(w, u, positions, rope, kv.dtype)
                if rope:
                    with jax.named_scope("ring_cache_write"):
                        rings = rings.at[at, ring_blocks, ring_at].set(rows)
                    with jax.named_scope("attn_window"):
                        o = _gqa.ring_decode_attention(
                            q, rings, ring_tables, wl, at, n_kv)
                else:
                    with jax.named_scope("kv_cache_write"):
                        kv = kv.at[at, blocks, positions % bs].set(rows)
                    with jax.named_scope("attn_full"):
                        o = _gqa.decode_attention(q, kv, block_tables, cl,
                                                  at, n_kv)
                return self._attn_out(w, o, x.dtype), (kv, rings)

            return self._block(w, stacks, at, x, live, tile, False, attend)

        x, (kv, rings), (load, ids, rw, u, rl) = self._scan_periods(
            pw, pf, p["emb"][tokens], tuple(state), layer)
        logits = self._head(p, x)
        with jax.named_scope("sampling"):
            toks = sample(logits, seeds, steps, temperature, top_k)
        return [toks, logits, load, ids, rw, u, rl], [kv, rings]


MODEL_TYPES[MODEL_TYPE] = CommandALM.from_dict

__all__ = ["CommandAConfig", "CommandALM", "CommandAObserver",
           "param_shapes", "layer_norm", "rotary_gptj", "EXPERT_LEAVES"]
