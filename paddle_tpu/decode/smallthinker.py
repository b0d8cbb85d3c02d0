"""A window-and-full attention mixture-of-experts LM (SmallThinker,
arXiv:2507.20984) for the decode plane: SmallThinker-21BA3B's stack,
configured by its published keys.

Every layer is, with ``u = RMSNorm₁(x)``,

    r  = u W_r                              (the router: read from u)
    x₁ = x + attn(u) W_o
    x₂ = x₁ + Σ_k w_k (relu(h Wg_e) ⊙ (h Wu_e)) Wd_e ,   h = RMSNorm₂(x₁)

a final RMSNorm and an untied head; no bias anywhere, no shared expert, no
dense layer.  The layers come in *periods* — ``sliding_window_layout`` =
``rope_layout`` = ``[0, 1, 1, 1] × n``: one FULL layer, then ``period − 1``
WINDOW layers:

- **full attention** (layout 0): ``num_attention_heads`` query heads over
  ``num_key_value_heads`` K/V heads, every key ``j ≤ t`` visible, and NO
  positional encoding at all (nothing is rotated).  A stream keeps a row ``[k |
  v]`` a token in the paged pool, one pool layer a full layer.
- **window attention** (layout 1): the same heads with rotate-half rotary
  positions over the whole head at ``rope_theta``; key ``j`` visible to query
  ``t`` iff ``0 ≤ t − j < sliding_window_size``.  A stream keeps its last
  ``sliding_window_size`` rows in a ring, at ``position mod window``, the key
  after its rotation; a prompt longer than the window leaves its last
  ``window`` rows there.
- **routed experts** (``kernels/moe.py``, the gate ``relu``): the
  ``moe_num_active_primary_experts`` largest of the router's softmax scores
  (float32), renormalised over the chosen (``norm_topk_prob``), every
  assignment computed.  The router reads the layer's normed INPUT, so a
  layer's routing — ``route_topk`` (a top-k) and ``plan_groups`` (a count of
  the assignments an expert; no assignment is sorted) — is issued before the
  layer's attention and is off the path from the attention to the experts.

So a stream's state is of two kinds (:class:`~paddle_tpu.decode.cache.
HybridStateCache` with no recurrent rows): blocks of a paged pool of the full
layers, held by block table, and a ring a slot a window layer, addressed by
slot (``slot_state``).

Programs ``lax.scan`` over the periods' stacked weights (``pf.*`` ``[P, …]``
the full layers, ``pw.*`` ``[P, period − 1, …]`` the window layers, scanned
in turn inside a period), so a program holds one full and one window layer's
code; pool and rings are the loops' carry, updated in place with the layer as
an index.  The experts' matrices are NOT scanned over: the grouped kernel is
handed the whole stack and the layer's index (no layer's 0.38 GB of experts
is sliced out of it).  Every position of a prompt runs through every layer
(every layer writes a cache) and only the last real one through the head.

The model is an :class:`~paddle_tpu.decode.adapter.LMAdapter`, so a
:class:`~paddle_tpu.decode.engine.DecodeEngine` serves it as it is.
Beside token and logits the programs return every layer's load
figures ``[L, 3]``, the chosen experts ``[L, tokens, K]`` and, at the rows
that reach the head, the router's input ``u`` and its logits (what a
reference check holds the routing to).  There is no suffix prefill over a
ring, so ``supports`` is empty: a prefix cache, overcommit and beam sessions
refuse this model at build.

Weights, residual stream, pool and rings are ``dtype`` (bf16 as deployed);
matmuls accumulate in float32; the router's logits, scores and weights, the
softmax and the norm statistics are float32; an expert's output row is an
activation (``dtype``), and the chosen experts' rows are weighed and summed in
float32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .adapter import (EXPERT_LEAVES, MODEL_TYPES, ConfigDict, LMAdapter,
                      PoolObserver, RingSeries, RoutedLoadSeries, init_tensor,
                      mm, prompt_addresses, ring_of_prompt,
                      ring_step_addresses, rms_norm, rotary, sample,
                      sample_first, step_addresses, sub, unscanned,
                      walked_blocks)
from .cache import HybridStateCache
from ..kernels import gqa as _gqa
from ..kernels import moe as _moe
from ..observability import trace as _trace

MODEL_TYPE = "smallthinker"


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig(ConfigDict):
    """The published keys this model reads, under their published names; the
    deployment's per-stream ``max_seq_len`` and the weights' ``dtype``.  The
    layouts may be the published model's whole: a cut in depth reads their
    first ``num_hidden_layers`` entries."""

    vocab_size: int
    hidden_size: int = 64
    num_hidden_layers: int = 4
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    moe_ffn_hidden_size: int = 32
    moe_num_primary_experts: int = 8
    moe_num_active_primary_experts: int = 3
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1)
    sliding_window_layout: Tuple[int, ...] = (0, 1, 1, 1)
    sliding_window_size: int = 32
    max_seq_len: int = 128
    dtype: str = "bfloat16"
    model_type = MODEL_TYPE

    def __post_init__(self):
        L = self.num_hidden_layers
        for name in ("rope_layout", "sliding_window_layout"):
            v = tuple(int(x) for x in getattr(self, name))[:L]
            if len(v) != L:
                raise ValueError(f"{name} has {len(v)} entries for {L} layers")
            object.__setattr__(self, name, v)
        swa, p = self.sliding_window_layout, self.period
        if L % p or swa != ((0,) + (1,) * (p - 1)) * (L // p) or p < 2 \
                or self.rope_layout != swa:
            raise ValueError(
                "the stack is whole periods of one full layer without "
                "positions and then window layers with rotary positions "
                f"(got sliding_window_layout {swa}, rope_layout "
                f"{self.rope_layout})")
        if not self.moe_primary_router_apply_softmax:
            raise ValueError("the router's scores are a softmax: another "
                             "score function is not written down here")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.head_dim % 2:
            raise ValueError("K/V heads divide the query heads, and a head "
                             "is rotated by halves")

    @property
    def period(self) -> int:
        swa = self.sliding_window_layout
        return swa.index(0, 1) if 0 in swa[1:] else len(swa)

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


def param_shapes(cfg: SmallThinkerConfig) -> Dict[str, tuple]:
    """name → (shape, init): a float is the std of a normal; ``norm`` a norm
    weight (1 + 0.1 N)."""
    D, F, V = cfg.hidden_size, cfg.moe_ffn_hidden_size, cfg.vocab_size
    E = cfg.moe_num_primary_experts
    layer = {"ln1": ((D,), "norm"), "ln2": ((D,), "norm"),
             "router": ((D, E), D ** -0.5),
             "wqkv": ((D, cfg.q_width + 2 * cfg.kv_width), D ** -0.5),
             "wo": ((cfg.q_width, D), cfg.q_width ** -0.5),
             "e_gate": ((E, D, F), D ** -0.5), "e_up": ((E, D, F), D ** -0.5),
             "e_down": ((E, F, D), F ** -0.5)}
    out = {"emb": ((V, D), 1.0), "head": ((V, D), D ** -0.5),
           "final_norm": ((D,), "norm")}
    for prefix, lead in (("pf.", (cfg.periods,)),
                         ("pw.", (cfg.periods, cfg.period - 1))):
        out.update({prefix + k: (lead + shape, init)
                    for k, (shape, init) in layer.items()})
    return out


class SmallThinkerObserver(PoolObserver):
    """``decode.<engine>.*`` series of this model: the common ones, the
    pool's and the routed load (``extra[0]``: each layer's ``[assignments,
    experts touched, largest load]``) and the rings'
    (:class:`~paddle_tpu.decode.adapter.RingSeries`).  Its walks: a full
    layer's over the pool, a window layer's over a ring up to the window."""

    def __init__(self, name: str, cache, config: SmallThinkerConfig,
                 table_shape):
        super().__init__(name, cache, config, table_shape)
        sc = self.series
        self.routed = RoutedLoadSeries(
            sc, buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048,
                         4096, 8192, 16384))
        self.ring = RingSeries(sc, config.sliding_window_size)
        sc.gauge("window_state_bytes").set(cache.window_state_bytes)

    def prefill(self, extra, prompt: int, bucket: int) -> None:
        with _trace.span("decode::prefill.observe") as sp:
            assignments = self.routed.count_prefill(np.asarray(extra[0]))
            pairs = self.ring.count_prompt(prompt)
            self.count_prompt(prompt, bucket)
            sp.annotate(prefill_routed_assignments=assignments,
                        prefill_real_tokens=prompt,
                        prefill_window_pairs=pairs,
                        prefill_tokens_sq=prompt * prompt)

    def step(self, extra, contexts) -> None:
        cfg, cache = self.config, self.cache
        W = cfg.sliding_window_size
        with _trace.span("decode::step.observe") as sp:
            assignments, touched = self.routed.count_step(
                np.asarray(extra[0]))
            context, streams = self.count_streams(contexts)
            live = self.ring.count_step(contexts)
            sp.annotate(step_routed_assignments=assignments,
                        step_experts_touched=touched,
                        step_context_tokens=context,
                        step_ring_rows_live=live, step_streams=streams)
        ring = walked_blocks(np.minimum(contexts, W), cache.ring_rows,
                             self._slots)
        self.count_walks(
            cfg.periods * self.pool_walk(contexts) + cfg.window_layers * ring,
            self._slots * (cfg.periods * self._slot_blocks
                           + cfg.window_layers * cache.ring_blocks))

    def decodez(self) -> dict:
        """… and the rings' live share."""
        return dict(super().decodez(), **self.ring.decodez())


class SmallThinkerLM(LMAdapter):
    """One window-and-full attention expert LM: config + the jit-ready
    functions."""

    # a window layer's ring lives in slot rows
    slot_state = True
    config_class = SmallThinkerConfig
    observer_class = SmallThinkerObserver
    param_shapes = staticmethod(param_shapes)

    # -- what an engine asks of a model ------------------------------------
    def _make_cache(self, num_blocks: int, block_tokens: int, dtype: str,
                    slots: int) -> HybridStateCache:
        cfg = self.config
        return HybridStateCache(
            cfg.kv_width, num_blocks, block_tokens, slots, dtype=dtype,
            kv_layers=cfg.periods,
            rings=(cfg.window_layers, cfg.sliding_window_size))

    def _unpack(self, plist):
        """(the model's own tensors, the full layers' stacks, the window
        layers' stacks)."""
        p = dict(zip(self.param_names(), plist))
        return ({k: v for k, v in p.items() if k[:3] not in ("pf.", "pw.")},
                sub(p, "pf."), sub(p, "pw."))

    # -- shared layer math -------------------------------------------------
    def _rms(self, x, g):
        return rms_norm(x, g, self.config.rms_norm_eps)

    def _route(self, w, u, valid, tile: int):
        """The layer's routing from its normed input u [N, D]: (logits [N, E]
        float32, ids [N, K], weights [N, K] float32, the grouped kernel's
        plan).  Issued before the layer's attention: nothing here waits for
        it."""
        cfg = self.config
        with jax.named_scope("route"):
            logits = jnp.dot(u, w["router"],
                             preferred_element_type=jnp.float32)
            ids, weights = _moe.route_topk(
                logits, cfg.moe_num_active_primary_experts, 1.0,
                cfg.norm_topk_prob)
            plan = _moe.plan_groups(ids, valid, cfg.moe_num_primary_experts,
                                    tile)
        return logits, ids, weights, plan

    def _experts(self, w, stacks, at, x1, weights, plan, tile: int, dense):
        """x₁ [N, D] → x₂: the routed experts on ``RMSNorm₂(x₁)`` by a plan
        made before the attention.  ``stacks`` are the experts' matrices as
        they lie and ``at`` the layer's index into their leading axis."""
        h = self._rms(x1, w["ln2"])
        with jax.named_scope("experts"):
            y = _moe.planned_experts(
                h, weights, plan, *stacks, tile, act="relu", layer=at,
                impl="xla" if dense else None, out_dtype=h.dtype)
        return x1 + y.astype(x1.dtype)

    def _qkv(self, w, u, positions, rope: bool, dtype):
        """u [N, D] → q [N, nh, dh], the cache rows [k | v] [N, 2·kw]; where
        ``rope`` both q and k after their rotation at ``positions``."""
        cfg = self.config
        N, dh = u.shape[0], cfg.head_dim
        with jax.named_scope("attn_qkv"):
            qkv = mm(u, w["wqkv"])
            q = qkv[:, :cfg.q_width].reshape(N, cfg.num_attention_heads, dh)
            k = qkv[:, cfg.q_width:cfg.q_width + cfg.kv_width]
            v = qkv[:, cfg.q_width + cfg.kv_width:]
        if rope:
            with jax.named_scope("attn_rope"):
                q = rotary(q, positions, cfg.rope_theta)
                k = rotary(k.reshape(N, cfg.num_key_value_heads, dh),
                           positions, cfg.rope_theta).reshape(N, cfg.kv_width)
        return q, jnp.concatenate([k, v], axis=-1).astype(dtype)

    def _attn_out(self, w, x, o):
        with jax.named_scope("attn_out"):
            return x + mm(o.reshape(o.shape[0], -1).astype(x.dtype), w["wo"])

    def _head(self, p, x):
        with jax.named_scope("lm_head"):
            return lax.dot_general(
                self._rms(x, p["final_norm"]), p["head"],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    def _stacks(self, lay: dict):
        return tuple(lay[k] for k in EXPERT_LEAVES)

    def _window_stacks(self, lay: dict):
        """The window layers' experts as ONE leading axis (period by period:
        layer ``p · (period − 1) + j``), a reshape of what lies there."""
        return tuple(lay[k].reshape((-1,) + lay[k].shape[2:])
                     for k in EXPERT_LEAVES)

    def _scan_periods(self, pf, pw, x, carry, layer):
        """Every layer in turn: ``lax.scan`` over the periods and, inside one,
        over its window layers.  ``layer(w, stacks, index, x, carry, kind) →
        (x, carry, got)`` is one layer (``kind`` ``full`` / ``window``,
        ``index`` its place among its kind, ``stacks`` its kind's experts as
        they lie); returns (x, carry, every layer's ``got`` stacked ``[L,
        …]`` in layer order)."""
        cfg = self.config
        full_stacks, win_stacks = self._stacks(pf), self._window_stacks(pw)
        n_win = cfg.period - 1

        def period(state, xs):
            wf, ww, i = xs
            x, carry, got_f = layer(wf, full_stacks, i, *state, "full")

            def window(state, xs):
                w, j = xs
                x, carry, got = layer(w, win_stacks, i * n_win + j, *state,
                                      "window")
                return (x, carry), got

            state, got_w = lax.scan(
                window, (x, carry), (ww, jnp.arange(n_win, dtype=jnp.int32)))
            return state, tuple(jnp.concatenate([f[None], w])
                                for f, w in zip(got_f, got_w))

        (x, carry), got = lax.scan(
            period, (x, carry),
            (unscanned(pf), unscanned(pw),
             jnp.arange(cfg.periods, dtype=jnp.int32)))
        return x, carry, tuple(g.reshape((-1,) + g.shape[2:]) for g in got)

    # -- a prompt's layers -------------------------------------------------
    def _prompt_layers(self, p, pf, pw, tokens, length, cache_dtype,
                       dense: bool, rows_out, carry):
        """tokens [T] through every layer → (x [T, D], carry', (load [L, 3],
        ids [L, T, K], u [L, D] and router logits [L, E] at the last real
        position)).  ``rows_out(kind, index, rows, carry) → carry`` files a
        layer's cache rows [T, 2·kw] (``kind`` ``full`` / ``window``,
        ``index`` the layer's place among its kind); ``carry`` is threaded
        through the scans."""
        cfg = self.config
        T = tokens.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        valid = pos < length
        last = jnp.maximum(length - 1, 0)
        tile = _moe.row_tile(T, jnp.dtype(cfg.dtype))
        attend = functools.partial(
            _gqa.prefill_attention_xla, n_kv=cfg.num_key_value_heads) \
            if dense else functools.partial(
                _gqa.group_prefill_attention, n_kv=cfg.num_key_value_heads,
                length=length)

        def layer(w, stacks, at, x, carry, kind):
            rope = kind == "window"
            u = self._rms(x, w["ln1"])
            logits, ids, weights, plan = self._route(w, u, valid, tile)
            q, rows = self._qkv(w, u, pos, rope, cache_dtype)
            carry = rows_out(kind, at, rows, carry)
            with jax.named_scope("attn_window" if rope else "attn_full"):
                o = attend(q, rows, window=cfg.sliding_window_size if rope
                           else None)
            x = self._experts(w, stacks, at, self._attn_out(w, x, o),
                              weights, plan, tile, dense)
            return x, carry, (plan.load, ids, u[last], logits[last])

        x, carry, got = self._scan_periods(pf, pw, p["emb"][tokens], carry,
                                           layer)
        return x, carry, got

    # -- full forward (the parity anchor) ----------------------------------
    def full_logits(self, plist, tokens, lengths=None):
        """tokens [B, T] int32 → logits [B, T, V] float32: every position
        through every layer, dense masked attention, the experts through
        ``lax.ragged_dot``, no cache and no kernel."""
        p, pf, pw = self._unpack(plist)
        B, T = tokens.shape
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)

        def one(toks, length):
            x, _, _ = self._prompt_layers(
                p, pf, pw, toks, length, jnp.dtype(self.config.dtype), True,
                lambda kind, at, rows, carry: carry, jnp.zeros((), jnp.int32))
            return self._head(p, x)

        # one sequence after another: lax.ragged_dot has no batched form
        return lax.map(lambda a: one(*a), (tokens, lengths))

    # -- prefill -----------------------------------------------------------
    def prefill(self, plist, state, tokens, length, slot, block_table, seed,
                temperature, top_k):
        """state ``[kv pool, rings]``, tokens [1, Tb] (bucket-padded), length
        [] int32, slot [] int32 (the slot whose rings this prompt fills),
        block_table [MB] int32 → ([next_token [], logits [V], load [L, 3],
        ids [L, Tb, K], u [L, 1, D], router logits [L, 1, E]], state').  A
        full layer's row of every real position lands in the request's
        blocks, pad positions in trash block 0; the slot's rings are
        overwritten: ring row ``r`` gets the last real position that is ``r
        mod window`` (rows past a short prompt's end hold what the walk never
        reads)."""
        cfg = self.config
        p, pf, pw = self._unpack(plist)
        kv, rings = state
        Tb = tokens.shape[1]
        bs, W, rb = kv.shape[2], cfg.sliding_window_size, rings.shape[2]
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

        x, (kv, rings), (load, ids, u, rl) = self._prompt_layers(
            p, pf, pw, tokens[0], length, kv.dtype, False, rows_out,
            (kv, rings))
        logits = self._head(p, x[last][None])[0]
        with jax.named_scope("sampling"):
            tok = sample_first(logits, seed, temperature, top_k)
        return [tok, logits, load, ids, u[:, None], rl[:, None]], [kv, rings]

    # -- decode step -------------------------------------------------------
    def decode_step(self, plist, state, tokens, positions, block_tables,
                    seeds, steps, temperature, top_k, attn_impl=None):
        """state ``[kv pool, rings]``, tokens / positions [S], block_tables
        [S, MB] → ([next_tokens [S], logits [S, V], load [L, 3], ids [L, S,
        K], u [L, S, D], router logits [L, S, E]], state').  A slot without a
        stream is routed to no expert."""
        del attn_impl           # one path: the kernels choose by shape alone
        cfg = self.config
        p, pf, pw = self._unpack(plist)
        kv, rings = state
        S = tokens.shape[0]
        bs, W, rb = kv.shape[2], cfg.sliding_window_size, rings.shape[2]
        n_kv = cfg.num_key_value_heads
        cl, live, slots, blocks = step_addresses(positions, block_tables, bs)
        wl = jnp.minimum(cl, W)
        ring_tables, ring_blocks, ring_at = ring_step_addresses(
            positions, slots, W, rb)
        tile = _moe.row_tile(S, jnp.dtype(cfg.dtype))

        def layer(w, stacks, at, x, carry, kind):
            kv, rings = carry
            rope = kind == "window"
            u = self._rms(x, w["ln1"])
            logits, ids, weights, plan = self._route(w, u, live, tile)
            q, rows = self._qkv(w, u, positions, rope, kv.dtype)
            if rope:
                with jax.named_scope("ring_cache_write"):
                    rings = rings.at[at, ring_blocks, ring_at].set(rows)
                with jax.named_scope("attn_window"):
                    o = _gqa.ring_decode_attention(q, rings, ring_tables, wl,
                                                   at, n_kv)
            else:
                with jax.named_scope("kv_cache_write"):
                    kv = kv.at[at, blocks, positions % bs].set(rows)
                with jax.named_scope("attn_full"):
                    o = _gqa.decode_attention(q, kv, block_tables, cl, at,
                                              n_kv)
            x = self._experts(w, stacks, at, self._attn_out(w, x, o),
                              weights, plan, tile, False)
            return x, (kv, rings), (plan.load, ids, u, logits)

        x, (kv, rings), (load, ids, u, rl) = self._scan_periods(
            pf, pw, p["emb"][tokens], (kv, rings), layer)
        logits = self._head(p, x)
        with jax.named_scope("sampling"):
            toks = sample(logits, seeds, steps, temperature, top_k)
        return [toks, logits, load, ids, u, rl], [kv, rings]


MODEL_TYPES[MODEL_TYPE] = SmallThinkerLM.from_dict

__all__ = ["SmallThinkerConfig", "SmallThinkerLM", "SmallThinkerObserver",
           "param_shapes", "init_tensor", "EXPERT_LEAVES"]
