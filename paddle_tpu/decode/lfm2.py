"""A short-convolution and grouped-query attention mixture-of-experts LM
(``model_type: lfm2_moe``, LiquidAI's LFM2-24B-A2B) for the decode plane,
configured by its published keys.

Every layer is ``h ← h + Op(RMS_op(h))``, then ``h ← h + FF(RMS_ffn(h))``; no
bias anywhere, a final RMSNorm, the head the embedding transposed.  ``Op`` by
``layer_types``, ``FF`` by the layer's place:

- **gated short convolution** (``conv``): ``[B | C | x] = u W_in``; ``z = B ⊙
  x``; ``c_t = Σ_k w_k ⊙ z_{t−(K−1)+k}`` (depthwise, causal, ``K =
  conv_L_cache`` taps, zeros before the prompt, no activation); ``Op(u) = (C ⊙
  c) W_out``.  A stream keeps the last ``K − 1`` values of ``z`` a layer and
  nothing else: no K/V, nothing that grows with its context.
- **attention** (``full_attention``): ``num_attention_heads`` query heads over
  ``num_key_value_heads`` K/V heads; ``q`` and ``k`` each pass an RMS norm over
  the head's lanes (one gain vector for ``q`` and one for ``k`` a layer)
  BEFORE rotate-half rotary positions over the whole head at ``rope_theta``;
  causal softmax; a row ``[k | v]`` a token in the paged pool, the key after
  its rotation, one pool layer an attention layer.  At the published ``head_dim
  = 64`` a lane tile of a row is a pair of K/V heads (``kernels/gqa.py``).
- **dense SwiGLU** (layers below ``num_dense_layers``): ``(silu(u W_1) ⊙ u
  W_3) W_2``.
- **routed experts** (the rest; ``kernels/moe.py``): ``s = sigmoid(u W_r)`` in
  float32; the ``num_experts_per_tok`` experts are chosen by ``top_k(s + b)``,
  ``b`` the layer's selection bias (``use_expert_bias``), and weighed by ``s``
  itself — ``w_i = s_i / (Σ_chosen s_j + 1e-6)`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; every assignment computed, no shared expert.

The stack is ``num_dense_layers`` convolution layers with the dense unit and
then whole *periods* ``[full_attention, conv, …, conv]`` with experts (the
published model's ``layer_types`` from layer 2 on; a cut in depth reads their
first ``num_hidden_layers`` entries).  So a stream's state is of two kinds
(:class:`~paddle_tpu.decode.cache.HybridStateCache` with convolution tails and
no recurrent rows): blocks of a paged pool of the attention layers, held by
block table, and a tail a slot a convolution layer, addressed by slot
(``slot_state``): a prefill leaves there ``z`` at the prompt's last REAL
positions, zeros where the prompt is shorter than the tail.

Programs ``lax.scan`` over the dense layers' stacked weights (``d.*`` ``[nd,
…]``) and then over the periods' (``pa.*`` ``[P, …]`` the attention layers,
``pc.*`` ``[P, period − 1, …]`` the convolution layers, scanned in turn inside
a period), so a program holds one layer's code of each of the three shapes;
pool and tails are the loops' carry, updated in place with the layer as an
index.  The experts' matrices are NOT scanned over: the grouped kernel is
handed the whole stack and the layer's index.

The model is an :class:`~paddle_tpu.decode.adapter.LMAdapter`, so a
:class:`~paddle_tpu.decode.engine.DecodeEngine` serves it as it is.
Beside token and logits the programs return every expert layer's load figures
``[Le, 4]`` (assignments, experts touched, the largest load, the plan's
padded rows), the chosen experts ``[Le, tokens, K]`` and, at the rows that
reach the head, the routing weights, the router's input ``u`` and its logits
(what a reference check holds the routing to).  There is no snapshot of a
slot's tails and no suffix prefill from one, so ``supports`` is empty: a
prefix cache, overcommit and beam sessions refuse this model at build.

Weights, residual stream, pool and tails are ``dtype`` (bf16 as deployed);
matmuls accumulate in float32; the router's logits, scores and weights, the
softmax, the gates' products and the norm statistics are float32; an expert's
output row is an activation (``dtype``), and the chosen experts' rows are
weighed and summed in float32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .adapter import (EXPERT_LEAVES, MODEL_TYPES, ConfigDict, LMAdapter,
                      PoolObserver, RoutedLoadSeries, init_tensor, mm,
                      prompt_addresses, rms_norm, rotary, sample,
                      sample_first, step_addresses, sub, unscanned)
from .cache import HybridStateCache
from ..kernels import gqa as _gqa
from ..kernels import moe as _moe
from ..kernels import ssm as _ssm
from ..observability import trace as _trace

MODEL_TYPE = "lfm2_moe"
ROUTE_EPS = 1e-6        # the renormalisation's: sum of the chosen + this


@dataclasses.dataclass(frozen=True)
class LFM2Config(ConfigDict):
    """The published keys this model reads, under their published names
    (``rope_theta`` is ``rope_parameters.rope_theta``; ``head_dim`` is
    ``hidden_size / num_attention_heads`` unless given); the deployment's
    per-stream ``max_seq_len`` and the weights' ``dtype``.  ``layer_types``
    may be the published model's whole: a cut in depth reads its first
    ``num_hidden_layers`` entries."""

    vocab_size: int
    hidden_size: int = 64
    intermediate_size: int = 96
    moe_intermediate_size: int = 32
    num_hidden_layers: int = 5
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: Optional[int] = None
    num_dense_layers: int = 1
    num_experts: int = 8
    num_experts_per_tok: int = 2
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    conv_L_cache: int = 3
    conv_bias: bool = False
    layer_types: Tuple[str, ...] = ("conv", "full_attention", "conv", "conv",
                                    "conv")
    rope_theta: float = 1e6
    max_seq_len: int = 128
    dtype: str = "bfloat16"
    model_type = MODEL_TYPE

    def __post_init__(self):
        L, nd = self.num_hidden_layers, self.num_dense_layers
        types = tuple(str(t) for t in self.layer_types)[:L]
        if len(types) != L:
            raise ValueError(f"layer_types has {len(types)} entries for {L} "
                             "layers")
        object.__setattr__(self, "layer_types", types)
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim", self.hidden_size // self.num_attention_heads)
        rest, p = types[nd:], self.period
        if set(types) - {"conv", "full_attention"} or nd >= L \
                or "full_attention" in types[:nd] or p < 2 or len(rest) % p \
                or rest != (("full_attention",) + ("conv",) * (p - 1)) \
                * (len(rest) // p):
            raise ValueError(
                "the stack is num_dense_layers convolution layers and then "
                "whole periods of one attention layer and convolution layers "
                f"(got {types} with {nd} dense layers)")
        if self.conv_bias:
            raise ValueError("a convolution with a bias is not written down "
                             "here")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.head_dim % 2 or self.conv_L_cache < 2:
            raise ValueError("K/V heads divide the query heads, a head is "
                             "rotated by halves, and a filter has a tail")

    @property
    def period(self) -> int:
        rest = self.layer_types[self.num_dense_layers:]
        return rest.index("full_attention", 1) \
            if "full_attention" in rest[1:] else len(rest)

    @property
    def periods(self) -> int:
        return (self.num_hidden_layers - self.num_dense_layers) // self.period

    @property
    def conv_layers(self) -> int:
        return self.num_dense_layers + self.periods * (self.period - 1)

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    @property
    def q_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @classmethod
    def from_dict(cls, d: dict) -> "LFM2Config":
        d = dict(d)
        if "rope_theta" not in d and "rope_parameters" in d:
            d["rope_theta"] = d["rope_parameters"]["rope_theta"]
        return super().from_dict(d)


def param_shapes(cfg: LFM2Config) -> Dict[str, tuple]:
    """name → (shape, init): a float is the std of a normal; ``norm`` a norm
    weight (1 + 0.1 N)."""
    D, V, E = cfg.hidden_size, cfg.vocab_size, cfg.num_experts
    F, Fe, K = (cfg.intermediate_size, cfg.moe_intermediate_size,
                cfg.conv_L_cache)
    norms = {"ln1": ((D,), "norm"), "ln2": ((D,), "norm")}
    conv = {"conv_in": ((D, 3 * D), D ** -0.5),
            "conv_w": ((K, D), K ** -0.5),
            "conv_out": ((D, D), D ** -0.5)}
    attn = {"wqkv": ((D, cfg.q_width + 2 * cfg.kv_width), D ** -0.5),
            "q_norm": ((cfg.head_dim,), "norm"),
            "k_norm": ((cfg.head_dim,), "norm"),
            "wo": ((cfg.q_width, D), cfg.q_width ** -0.5)}
    dense = {"w1": ((D, F), D ** -0.5), "w3": ((D, F), D ** -0.5),
             "w2": ((F, D), F ** -0.5)}
    experts = {"router": ((D, E), D ** -0.5), "router_bias": ((E,), 0.1),
               "e_gate": ((E, D, Fe), D ** -0.5),
               "e_up": ((E, D, Fe), D ** -0.5),
               "e_down": ((E, Fe, D), Fe ** -0.5)}
    out = {"emb": ((V, D), 1.0), "final_norm": ((D,), "norm")}
    for prefix, lead, layer in (
            ("d.", (cfg.num_dense_layers,), {**norms, **conv, **dense}),
            ("pa.", (cfg.periods,), {**norms, **attn, **experts}),
            ("pc.", (cfg.periods, cfg.period - 1),
             {**norms, **conv, **experts})):
        out.update({prefix + k: (lead + shape, init)
                    for k, (shape, init) in layer.items()})
    return out


class LFM2Observer(PoolObserver):
    """``decode.<engine>.*`` series of this model: the common ones, the
    pool's (an attention layer walks it) and the routed load (``extra[0]``:
    each expert layer's ``[assignments, experts touched, largest load, the
    plan's padded rows]``), and its own of the prefills' grouped plans."""

    def __init__(self, name: str, cache, config: LFM2Config, table_shape):
        super().__init__(name, cache, config, table_shape)
        sc = self.series
        self.routed = RoutedLoadSeries(
            sc, buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048,
                         4096, 8192, 16384))
        self.prefill_dispatches = sc.counter(
            "prefill_moe_dispatches", "expert layers run by prefills")
        self.prefill_load_max_sum = sc.counter(
            "prefill_expert_load_max_sum", "largest load of one expert, "
            "summed over the prefills' dispatches")
        self.prefill_plan_rows = sc.counter(
            "prefill_plan_rows", "rows of the prefills' grouped plans: every "
            "expert's assignments padded to whole row tiles")
        self.prefill_plan_pad = sc.counter(
            "prefill_plan_pad_rows", "of them, rows that hold no assignment")
        sc.gauge("conv_state_bytes").set(cache.recurrent_state_bytes)

    def prefill(self, extra, prompt: int, bucket: int) -> None:
        with _trace.span("decode::prefill.observe") as sp:
            load = np.asarray(extra[0])
            assignments = self.routed.count_prefill(load)
            rows = int(load[:, 3].sum())
            self.prefill_dispatches.inc(int(load.shape[0]))
            self.prefill_load_max_sum.inc(int(load[:, 2].sum()))
            self.prefill_plan_rows.inc(rows)
            self.prefill_plan_pad.inc(rows - assignments)
            self.count_prompt(prompt, bucket)
            sp.annotate(prefill_routed_assignments=assignments,
                        prefill_plan_rows=rows, prefill_real_tokens=prompt,
                        prefill_tokens_sq=prompt * prompt)

    def step(self, extra, contexts) -> None:
        with _trace.span("decode::step.observe") as sp:
            assignments, touched = self.routed.count_step(
                np.asarray(extra[0]))
            context, streams = self.count_streams(contexts)
            sp.annotate(step_routed_assignments=assignments,
                        step_experts_touched=touched,
                        step_context_tokens=context, step_streams=streams)
        layers = self.config.periods
        self.count_walks(layers * self.pool_walk(contexts),
                         layers * self._slots * self._slot_blocks)


class LFM2LM(LMAdapter):
    """One short-convolution and attention expert LM: config + the jit-ready
    functions."""

    # a convolution layer's tail lives in slot rows
    slot_state = True
    config_class = LFM2Config
    observer_class = LFM2Observer
    param_shapes = staticmethod(param_shapes)

    # -- what an engine asks of a model ------------------------------------
    def _make_cache(self, num_blocks: int, block_tokens: int, dtype: str,
                    slots: int) -> HybridStateCache:
        cfg = self.config
        return HybridStateCache(
            cfg.kv_width, num_blocks, block_tokens, slots, dtype=dtype,
            kv_layers=cfg.periods,
            tails=(cfg.conv_layers, cfg.conv_L_cache, cfg.hidden_size))

    def _unpack(self, plist):
        """(the model's own tensors, the dense layers' stacks, the attention
        layers', the periods' convolution layers')."""
        p = dict(zip(self.param_names(), plist))
        return ({k: v for k, v in p.items() if "." not in k},
                sub(p, "d."), sub(p, "pa."), sub(p, "pc."))

    # -- shared layer math -------------------------------------------------
    def _rms(self, x, g):
        return rms_norm(x, g, self.config.norm_eps)

    def _conv_in(self, w, u):
        """u [N, D] → (z = B ⊙ x [N, D], the output gate C [N, D])."""
        D = self.config.hidden_size
        bcx = mm(u, w["conv_in"])
        z = bcx[:, :D].astype(jnp.float32) * bcx[:, 2 * D:].astype(jnp.float32)
        return z.astype(u.dtype), bcx[:, D:2 * D]

    def _conv_out(self, w, x, gate, c):
        y = (gate.astype(jnp.float32) * c).astype(x.dtype)
        return x + mm(y, w["conv_out"])

    def _qkv(self, w, u, positions, dtype):
        """u [N, D] → q [N, nh, dh], the cache rows [k | v] [N, 2·kw]: q and
        k after their per-head norm and then their rotation at
        ``positions``."""
        cfg = self.config
        N, dh = u.shape[0], cfg.head_dim
        qkv = mm(u, w["wqkv"])
        q = qkv[:, :cfg.q_width].reshape(N, cfg.num_attention_heads, dh)
        k = qkv[:, cfg.q_width:cfg.q_width + cfg.kv_width].reshape(
            N, cfg.num_key_value_heads, dh)
        v = qkv[:, cfg.q_width + cfg.kv_width:]
        q = rotary(self._rms(q, w["q_norm"]), positions, cfg.rope_theta)
        k = rotary(self._rms(k, w["k_norm"]), positions, cfg.rope_theta)
        return q, jnp.concatenate([k.reshape(N, cfg.kv_width), v],
                                  axis=-1).astype(dtype)

    def _attn_out(self, w, x, o):
        return x + mm(o.reshape(o.shape[0], -1).astype(x.dtype), w["wo"])

    def _dense_ffn(self, w, x):
        h = self._rms(x, w["ln2"])
        with jax.named_scope("dense_ffn"):
            g = mm(h, w["w1"]).astype(jnp.float32)
            a = (jax.nn.silu(g) * mm(h, w["w3"]).astype(jnp.float32)
                 ).astype(x.dtype)
            return x + mm(a, w["w2"])

    def _expert_ffn(self, w, stacks, at, x, valid, tile: int, dense: bool):
        """x [N, D] → (x + the routed experts on ``RMS_ffn(x)``, (load [4],
        ids [N, K], weights [N, K], the router's input [N, D], its logits [N,
        E]))."""
        cfg = self.config
        h = self._rms(x, w["ln2"])
        with jax.named_scope("moe"), jax.named_scope("route"):
            logits = jnp.dot(h, w["router"],
                             preferred_element_type=jnp.float32)
            ids, weights = _moe.route_topk(
                logits, cfg.num_experts_per_tok, cfg.routed_scaling_factor,
                cfg.norm_topk_prob, score="sigmoid",
                bias=w["router_bias"] if cfg.use_expert_bias else None,
                eps=ROUTE_EPS)
            plan = _moe.plan_groups(ids, valid, cfg.num_experts, tile)
            load = jnp.concatenate(
                [plan.load, jnp.sum(plan.padded_sizes, dtype=jnp.int32)[None]])
        with jax.named_scope("moe"), jax.named_scope("experts"):
            y = _moe.planned_experts(
                h, weights, plan, *stacks, tile, act="silu", layer=at,
                impl="xla" if dense else None, out_dtype=h.dtype)
        return x + y.astype(x.dtype), (load, ids, weights, h, logits)

    def _head(self, p, x):
        with jax.named_scope("head"):
            return lax.dot_general(
                self._rms(x, p["final_norm"]), p["emb"],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    def _scan_layers(self, pd, pa, pc, x, carry, mixer, ffn, pick):
        """Every layer in turn: ``lax.scan`` over the dense layers, then over
        the periods and, inside one, over its convolution layers.
        ``mixer(w, x, carry, kind, index) → (x, carry)`` is a layer's ``Op``
        with its residual (``kind`` ``conv`` / ``attn``, ``index`` the layer's
        place among its kind); ``ffn(w, stacks, index, x) → (x, got)`` an
        expert layer's feed-forward half (``stacks`` its kind's experts as
        they lie).  Returns (x, carry, every expert layer's ``pick(got)``
        stacked ``[Le, …]`` in layer order)."""
        cfg = self.config
        nd, n_conv = cfg.num_dense_layers, cfg.period - 1
        attn_stacks = tuple(pa[k] for k in EXPERT_LEAVES)
        conv_stacks = tuple(pc[k].reshape((-1,) + pc[k].shape[2:])
                            for k in EXPERT_LEAVES)

        def dense(state, xs):
            w, i = xs
            x, carry = mixer(w, *state, "conv", i)
            return (self._dense_ffn(w, x), carry), None

        (x, carry), _ = lax.scan(dense, (x, carry),
                                 (pd, jnp.arange(nd, dtype=jnp.int32)))

        def period(state, xs):
            wa, wc, i = xs
            x, carry = mixer(wa, *state, "attn", i)
            x, got_a = ffn(wa, attn_stacks, i, x)

            def conv(state, xs):
                w, j = xs
                at = i * n_conv + j
                x, carry = mixer(w, *state, "conv", nd + at)
                x, got = ffn(w, conv_stacks, at, x)
                return (x, carry), pick(got)

            state, got_c = lax.scan(
                conv, (x, carry), (wc, jnp.arange(n_conv, dtype=jnp.int32)))
            return state, tuple(jnp.concatenate([a[None], c])
                                for a, c in zip(pick(got_a), got_c))

        (x, carry), got = lax.scan(
            period, (x, carry),
            (unscanned(pa), unscanned(pc),
             jnp.arange(cfg.periods, dtype=jnp.int32)))
        return x, carry, tuple(g.reshape((-1,) + g.shape[2:]) for g in got)

    # -- a prompt's layers -------------------------------------------------
    def _prompt_layers(self, p, pd, pa, pc, tokens, length, cache_dtype,
                       dense: bool, rows_out, tail_out, carry):
        """tokens [T] through every layer → (x [T, D], carry', (load [Le,
        4], ids [Le, T, K], and at the last real position the routing weights
        [Le, K], the router's input [Le, D] and its logits [Le, E])).
        ``rows_out(index, rows, carry)`` files an attention layer's cache rows
        [T, 2·kw], ``tail_out(index, tail, carry)`` a convolution layer's
        tail [K − 1, D] (``z`` at the last real positions, zeros before the
        prompt)."""
        cfg = self.config
        T, K = tokens.shape[0], cfg.conv_L_cache
        pos = jnp.arange(T, dtype=jnp.int32)
        valid = pos < length
        last = jnp.maximum(length - 1, 0)
        tile = _moe.row_tile(T, jnp.dtype(cfg.dtype))
        attend = _gqa.prefill_attention_xla if dense else functools.partial(
            _gqa.group_prefill_attention, length=length)

        def mixer(w, x, carry, kind, at):
            u = self._rms(x, w["ln1"])
            if kind == "conv":
                with jax.named_scope("conv_mixer"):
                    z, gate = self._conv_in(w, u)
                    c = _ssm.causal_conv(z, w["conv_w"])
                    tail = lax.dynamic_slice_in_dim(
                        jnp.concatenate([jnp.zeros((K - 1, z.shape[1]),
                                                   z.dtype), z]),
                        length, K - 1, axis=0)
                    carry = tail_out(at, tail.astype(cache_dtype), carry)
                    return self._conv_out(w, x, gate, c), carry
            with jax.named_scope("attn"):
                q, rows = self._qkv(w, u, pos, cache_dtype)
                carry = rows_out(at, rows, carry)
                o = attend(q, rows, n_kv=cfg.num_key_value_heads)
                return self._attn_out(w, x, o), carry

        def ffn(w, stacks, at, x):
            return self._expert_ffn(w, stacks, at, x, valid, tile, dense)

        return self._scan_layers(
            pd, pa, pc, p["emb"][tokens], carry, mixer, ffn,
            lambda got: got[:2] + tuple(g[last] for g in got[2:]))

    # -- full forward (the parity anchor) ----------------------------------
    def full_logits(self, plist, tokens, lengths=None):
        """tokens [B, T] int32 → logits [B, T, V] float32: every position
        through every layer, dense masked attention, the experts through
        ``lax.ragged_dot``, no cache and no kernel."""
        p, pd, pa, pc = self._unpack(plist)
        B, T = tokens.shape
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)

        def keep(at, got, carry):
            return carry

        def one(toks, length):
            x, _, _ = self._prompt_layers(
                p, pd, pa, pc, toks, length, jnp.dtype(self.config.dtype),
                True, keep, keep, jnp.zeros((), jnp.int32))
            return self._head(p, x)

        # one sequence after another: lax.ragged_dot has no batched form
        return lax.map(lambda a: one(*a), (tokens, lengths))

    # -- prefill -----------------------------------------------------------
    def prefill(self, plist, state, tokens, length, slot, block_table, seed,
                temperature, top_k):
        """state ``[kv pool, tails]``, tokens [1, Tb] (bucket-padded), length
        [] int32, slot [] int32 (the slot whose tails this prompt fills),
        block_table [MB] int32 → ([next_token [], logits [V], load [Le, 4],
        ids [Le, Tb, K], routing weights [Le, 1, K], u [Le, 1, D], router
        logits [Le, 1, E]], state').  An
        attention layer's row of every real position lands in the request's
        blocks, pad positions in trash block 0; the slot's tails are
        overwritten whole."""
        p, pd, pa, pc = self._unpack(plist)
        kv, conv = state
        bs = kv.shape[2]
        pos, _, blocks, last = prompt_addresses(
            length, tokens.shape[1], block_table, bs)
        zero = jnp.zeros((), slot.dtype)

        def rows_out(at, rows, carry):
            kv_, conv_ = carry
            return kv_.at[at, blocks, pos % bs].set(rows), conv_

        def tail_out(at, tail, carry):
            kv_, conv_ = carry
            return kv_, lax.dynamic_update_slice(
                conv_, tail[None, None], (at, slot, zero, zero))

        x, (kv, conv), (load, ids, rw, u, rl) = self._prompt_layers(
            p, pd, pa, pc, tokens[0], length, kv.dtype, False, rows_out,
            tail_out, (kv, conv))
        logits = self._head(p, x[last][None])[0]
        with jax.named_scope("sampling"):
            tok = sample_first(logits, seed, temperature, top_k)
        return [tok, logits, load, ids, rw[:, None], u[:, None],
                rl[:, None]], [kv, conv]

    # -- decode step -------------------------------------------------------
    def decode_step(self, plist, state, tokens, positions, block_tables,
                    seeds, steps, temperature, top_k, attn_impl=None):
        """state ``[kv pool, tails]``, tokens / positions [S], block_tables
        [S, MB] → ([next_tokens [S], logits [S, V], load [Le, 4], ids [Le, S,
        K], routing weights [Le, S, K], u [Le, S, D], router logits [Le, S,
        E]], state').  A slot without a stream is routed to no expert."""
        del attn_impl           # one path: the kernels choose by shape alone
        cfg = self.config
        p, pd, pa, pc = self._unpack(plist)
        kv, conv = state
        S = tokens.shape[0]
        bs = kv.shape[2]
        cl, live, _, blocks = step_addresses(positions, block_tables, bs)
        tile = _moe.row_tile(S, jnp.dtype(cfg.dtype))

        def mixer(w, x, carry, kind, at):
            kv, conv = carry
            u = self._rms(x, w["ln1"])
            if kind == "conv":
                with jax.named_scope("conv_mixer"):
                    z, gate = self._conv_in(w, u)
                    c, tail = _ssm.conv_step(
                        lax.dynamic_index_in_dim(conv, at, keepdims=False),
                        z, w["conv_w"])
                    conv = lax.dynamic_update_index_in_dim(
                        conv, tail.astype(conv.dtype), at, 0)
                    return self._conv_out(w, x, gate, c), (kv, conv)
            with jax.named_scope("attn"):
                q, rows = self._qkv(w, u, positions, kv.dtype)
                kv = kv.at[at, blocks, positions % bs].set(rows)
                o = _gqa.decode_attention(q, kv, block_tables, cl, at,
                                          cfg.num_key_value_heads)
                return self._attn_out(w, x, o), (kv, conv)

        def ffn(w, stacks, at, x):
            return self._expert_ffn(w, stacks, at, x, live, tile, False)

        x, (kv, conv), (load, ids, rw, u, rl) = self._scan_layers(
            pd, pa, pc, p["emb"][tokens], (kv, conv), mixer, ffn,
            lambda got: got)
        logits = self._head(p, x)
        with jax.named_scope("sampling"):
            toks = sample(logits, seeds, steps, temperature, top_k)
        return [toks, logits, load, ids, rw, u, rl], [kv, conv]


MODEL_TYPES[MODEL_TYPE] = LFM2LM.from_dict

__all__ = ["LFM2Config", "LFM2LM", "LFM2Observer", "param_shapes",
           "init_tensor", "EXPERT_LEAVES", "ROUTE_EPS"]
