"""What the served models have in common: the model protocol as a class, the
layer math they share, the paged pool's addressing and the observers' common
series.  A model module (:mod:`model`, :mod:`mla`, :mod:`sambay`,
:mod:`falcon_h1`, :mod:`smallthinker`, :mod:`lfm2`, :mod:`kimi_linear`,
:mod:`command_a`, :mod:`nemotron_h`) brings
its config, its
``param_shapes``, its layers, its two programs and the counters that are its
own; it imports this module and no sibling.

What is here is code the models had to the letter, or with another value in
it (an epsilon, a theta, a histogram's buckets).  No function here takes a
scope name or asks which model calls it: what differs in structure (``_qkv``,
``_head``, the scans, every mixer) stays with its model.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import mla as _mla
from ..observability import stats as _obs_stats

# static top-k ceiling compiled into the sampling epilogue: per-slot k
# varies at runtime UNDER it without a recompile (a fixed shape is the
# whole decode-plane contract)
TOPK_MAX = 64

# ``model_type`` of a saved config → builder of its model from the config's
# dict; a model module adds itself on import (``model.load_lm`` reads it)
MODEL_TYPES: Dict[str, Callable] = {}


# ---------------------------------------------------------------------------
# shared layer math
# ---------------------------------------------------------------------------

def mm(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def rms_norm(x, g, eps: float):
    """``x / rms(x) · g`` over the last axis, in float32, back in x's
    dtype."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * g.astype(jnp.float32)).astype(x.dtype)


def swiglu(x, wg, wu, wd):
    """``(silu(x W_g) ⊙ x W_u) W_d``: the gate's product in float32, the
    unit's output in x's dtype."""
    g = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    return mm((jax.nn.silu(g) * u).astype(x.dtype), wd)


def rotary(x, positions, theta: float):
    """Rotate-half rotary positions over the whole head: x [N, heads, dh],
    positions [N] → the same shape and dtype, computed in float32."""
    half = x.shape[-1] // 2
    inv = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * (-math.log(theta) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def sub(w: dict, prefix: str) -> dict:
    """The entries of ``w`` under ``prefix``, the prefix cut off."""
    n = len(prefix)
    return {k[n:]: v for k, v in w.items() if k.startswith(prefix)}


# the three matrices of a layer's routed experts in a ``param_shapes``: a
# program that scans over layers hands the grouped kernel their whole stack
# and the layer's index, and scans over the rest
EXPERT_LEAVES = ("e_gate", "e_up", "e_down")


def unscanned(w: dict) -> dict:
    """A layer stack's tensors less the experts' (those are not scanned)."""
    return {k: v for k, v in w.items() if k not in EXPERT_LEAVES}


def init_tensor(key, shape: tuple, init, dtype):
    """One tensor of a ``param_shapes`` from a PRNG key (jit-able with
    ``shape``, ``init`` and ``dtype`` static): a float is the std of a
    normal, ``norm`` a norm weight (1 + 0.1 N), ``bias`` a bias (0.02 N)."""
    w = jax.random.normal(key, shape, jnp.float32)
    if isinstance(init, str):
        scale, shift = {"norm": (0.1, 1.0), "bias": (0.02, 0.0)}[init]
        w = shift + scale * w
    else:
        w = w * init
    return w.astype(dtype)


class LatentAttention:
    """Multi-head latent attention's layer math (DeepSeek-V2's), for the
    models that have it (:mod:`mla`, :mod:`kimi_linear`).  A token's keys and
    values are functions of one compressed row ``c`` (``rank`` wide, after its
    own RMS norm) and one key slice ``k_pe`` (``rope_dim`` wide) shared by all
    heads, and ``[c | k_pe | 0]`` is the row the pool keeps.  A prompt expands
    ``k_nope`` and ``v`` from ``c`` and runs causal flash attention at ``nope
    + rope_dim`` (q·k) and ``v_dim`` a head; a decode step never expands the
    cache (``kernels/mla.py``).

    ``rope(x, pos)`` rotates the ``rope_dim`` slices of queries and keys (x
    [..., rope_dim], pos broadcastable to x's leading axes); ``None`` leaves
    them as they are projected — **position-free keys**, a model whose
    positions come from elsewhere.  ``w`` is ONE layer's tensors: ``wkva``,
    ``kv_norm``, ``wkvb``, ``wo`` and the query's — one ``wq``, or where the
    model has a ``q_lora_rank`` the low-rank pair round an RMS norm, ``wq_b ·
    RMSNorm(wq_a · x; q_norm)`` (``wq_a``, ``q_norm``, ``wq_b``)."""

    def __init__(self, heads: int, nope: int, rope_dim: int, v_dim: int,
                 rank: int, eps: float, scale: float,
                 rope: Optional[Callable] = None):
        self.heads, self.nope, self.rope_dim = heads, nope, rope_dim
        self.v_dim, self.rank, self.eps = v_dim, rank, eps
        self.scale, self.rope = scale, rope
        self.row = _mla.row_width(rank, rope_dim)

    def project(self, w, x, pos):
        """x [N, D] at positions pos [N] → q_nope [N, H, nope], q_pe [N, H,
        rope_dim], c [N, rank] (normed), k_pe [N, rope_dim]: the last two are
        what the cache holds."""
        dn, r = self.nope, self.rank
        with jax.named_scope("mla_wq"):
            q = mm(x, w["wq"]) if "wq" in w else mm(
                rms_norm(mm(x, w["wq_a"]), w["q_norm"], self.eps), w["wq_b"])
            q = q.reshape(x.shape[0], self.heads, dn + self.rope_dim)
        with jax.named_scope("mla_wkva"):
            kva = mm(x, w["wkva"])
            c = rms_norm(kva[:, :r], w["kv_norm"], self.eps)
        k_pe, q_pe = kva[:, r:], q[..., dn:]
        if self.rope is not None:
            with jax.named_scope("mla_rope"):
                k_pe = self.rope(k_pe, pos)
                q_pe = self.rope(q_pe, pos[:, None])
        return q[..., :dn], q_pe, c, k_pe

    def wkvb(self, w):
        """``W_kvb``'s key part [rank, H, nope] and value part [rank, H,
        v_dim]."""
        kvb = w["wkvb"].reshape(self.rank, self.heads, self.nope + self.v_dim)
        return kvb[..., :self.nope], kvb[..., self.nope:]

    def expand(self, w, c):
        """c [N, rank] → k_nope [N, H, nope], v [N, H, v_dim]."""
        wk, wv = self.wkvb(w)
        with jax.named_scope("mla_wkvb"):
            k = jnp.einsum("nr,rhd->nhd", c, wk,
                           preferred_element_type=jnp.float32)
            v = jnp.einsum("nr,rhd->nhd", c, wv,
                           preferred_element_type=jnp.float32)
        return k.astype(c.dtype), v.astype(c.dtype)

    def rows(self, c, k_pe, dtype):
        """The cache rows of N tokens: ``[c | k_pe | 0]`` [N, row]."""
        pad = self.row - c.shape[-1] - k_pe.shape[-1]
        return jnp.concatenate(
            [c, k_pe, jnp.zeros((c.shape[0], pad), c.dtype)],
            axis=-1).astype(dtype)

    def out(self, w, ctx):
        """ctx [N, H, v_dim] → [N, D]."""
        with jax.named_scope("mla_wo"):
            return mm(ctx.reshape(ctx.shape[0], -1), w["wo"])

    def prompt(self, w, q_nope, q_pe, c, k_pe, impl=None):
        """One prompt's causal attention by the expanded formula, through
        the flash forward → [N, D]."""
        k_nope, v = self.expand(w, c)
        q = jnp.concatenate([q_nope, q_pe], -1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, None], q_pe.shape)], -1)
        with jax.named_scope("mla_attn"):
            ctx = _mla.prefill_attention(
                q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                v.transpose(1, 0, 2), self.scale, impl=impl)
        return self.out(w, ctx.transpose(1, 0, 2))

    def step(self, w, q_nope, q_pe, pool, block_tables, context_lens, layer,
             impl=None):
        """One token a slot by the absorbed formula over the pool (this
        step's rows already in it; ``layer`` an int or a traced scalar) →
        [S, D]."""
        wk, wv = self.wkvb(w)
        dtype = q_nope.dtype
        with jax.named_scope("mla_wkvb"):
            q_abs = jnp.einsum(
                "shd,rhd->shr", q_nope, wk,
                preferred_element_type=jnp.float32).astype(dtype)
        q_row = jnp.concatenate(
            [q_abs, q_pe, jnp.zeros(
                q_pe.shape[:2] + (self.row - self.rank - self.rope_dim,),
                dtype)], -1)
        with jax.named_scope("mla_attn"):
            u = _mla.decode_attention(q_row, pool, block_tables, context_lens,
                                      layer, self.rank, self.scale, impl=impl)
        with jax.named_scope("mla_wkvb"):
            ctx = jnp.einsum(
                "shr,rhd->shd", u.astype(dtype), wv,
                preferred_element_type=jnp.float32).astype(dtype)
        return self.out(w, ctx)


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def _hash_uniform(seeds, steps, kk):
    """Counter-hash uniforms in (0, 1): one murmur-style mix per
    (request seed, token index, candidate lane) — the attention
    dropout hash's recipe, keyed PER REQUEST.  A seeded stream is
    replayable bit-for-bit regardless of which slot it lands on or
    what else shares the decode batch (an engine-global PRNG key
    could not promise that)."""
    S = seeds.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.uint32, (S, kk), 1)
    x = (seeds.astype(jnp.uint32)[:, None] * jnp.uint32(0x9E3779B1)
         ^ steps.astype(jnp.uint32)[:, None] * jnp.uint32(0x85EBCA77)
         ^ lane * jnp.uint32(0xC2B2AE3D))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    u = (jax.lax.bitcast_convert_type(x >> 8, jnp.int32)
         .astype(jnp.float32) * jnp.float32(1.0 / (1 << 24)))
    return jnp.clip(u, 1e-7, 1.0 - 1e-7)


def sample(logits, seeds, steps, temperature, top_k):
    """On-device sampling epilogue: logits [S, V], seeds [S] uint32
    (per REQUEST), steps [S] int32 (each request's token index),
    temperature [S] f32 (<= 0 ⇒ greedy), top_k [S] int32 (0 ⇒ full
    vocab) → tokens [S] int32.  Per-slot knobs vary at runtime under
    the static ``TOPK_MAX`` ceiling; sampling is Gumbel-max over the
    top slice with :func:`_hash_uniform` bits, so a request's sampled
    stream depends only on (its seed, its token indices) — replayable
    across slot placements and batch compositions.

    What a caller can rely on: a greedy row is an argmax (the first
    maximal index) and costs no sort.  ``lax.top_k`` — on a TPU a sort
    of the whole vocabulary — runs only in a launch that holds at least
    one ``temperature > 0`` row (the ``lax.cond`` below: one program,
    the branch taken on the device from this launch's own input), and
    then every row of that launch pays for it.  The tokens are the same
    either way: a greedy row's is column 0 of the sorted slice, whose
    ties go to the lower index as argmax's do."""
    S, V = logits.shape
    kk = min(TOPK_MAX, V)
    x = logits.astype(jnp.float32)
    greedy = jnp.argmax(x, axis=-1).astype(jnp.int32)

    def from_top_slice():
        vals, idx = jax.lax.top_k(x, kk)                        # [S, kk]
        lane = jnp.arange(kk, dtype=jnp.int32)[None, :]
        want = jnp.where(top_k > 0, jnp.minimum(top_k, kk), kk)[:, None]
        vals = jnp.where(lane < want, vals, -jnp.inf)
        g = -jnp.log(-jnp.log(_hash_uniform(seeds, steps, kk)))
        temp = jnp.maximum(temperature, 1e-6)[:, None]
        choice = jnp.argmax(vals / temp + g, axis=-1)
        return jnp.take_along_axis(
            idx, choice[:, None], axis=1)[:, 0].astype(jnp.int32)

    sampled = jax.lax.cond(jnp.any(temperature > 0.0), from_top_slice,
                           lambda: greedy)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def sample_first(logits, seed, temperature, top_k):
    """A prefill's sampling tail: logits [V] and the request's scalars →
    its first token [] (token index 0), so a joining request streams a
    token without waiting for a decode step."""
    return sample(logits[None], seed[None], jnp.zeros((1,), jnp.int32),
                  temperature[None], top_k[None])[0]


# ---------------------------------------------------------------------------
# the paged pool's addressing
# ---------------------------------------------------------------------------
# Block 0 of every pool is the TRASH block: no stream is ever given it.  A
# program has fixed shapes, so it writes a row for every position of a
# padded bucket and for every slot, with or without a stream; the rows that
# belong to nobody all go to block 0, where no table of a live stream points,
# and no mask or branch is needed around the write.  A slot without a stream
# has a table of zeros, which is how a step tells it (``live``).

def prompt_addresses(length, bucket: int, block_table, block_tokens: int):
    """Where a bucket-padded prompt's rows go: ``pos`` [Tb] int32, ``valid``
    [Tb] (a real position), ``blocks`` [Tb] (the block of each position — a
    pad's is the trash block, and a position past a short table is clamped
    onto its last entry) and ``last`` [] (the last real position, 0 for an
    empty prompt).  The row inside the block is ``pos % block_tokens``."""
    pos = jnp.arange(bucket, dtype=jnp.int32)
    valid = pos < length
    entry = jnp.minimum(pos // block_tokens, block_table.shape[0] - 1)
    blocks = jnp.where(valid, block_table[entry], 0)
    last = jnp.maximum(length - 1, 0)
    return pos, valid, blocks, last


def step_addresses(positions, block_tables, block_tokens: int):
    """Where a decode step's rows go: ``cl`` [S] (each slot's context
    length, this token included), ``live`` [S] (the slot holds a stream),
    ``slots`` [S] int32 and ``blocks`` [S] (the block each slot's token lands
    in — the trash block for a slot without a stream).  The row inside the
    block is ``positions % block_tokens``."""
    cl = positions + 1
    live = block_tables[:, 0] != 0
    slots = jnp.arange(positions.shape[0], dtype=jnp.int32)
    blocks = block_tables[slots, positions // block_tokens]
    return cl, live, slots, blocks


# A window layer's ring is ``window / ring_rows`` blocks of ``ring_rows`` rows
# that belong to a slot for good (``cache.HybridStateCache``): slot ``s``'s
# are blocks ``s · blocks …`` of the layer's, and position ``p`` lies at row
# ``p mod window`` of them.

def ring_of_prompt(length, bucket: int, window: int, ring_rows: int):
    """What a slot's ring holds after a bucket-padded prompt of ``length``
    real positions, as ``fill(rows [bucket, width]) → [1, window / ring_rows,
    ring_rows, width]`` (to be written over the slot's blocks whole): a
    bucket inside the window lies in the ring as it is; otherwise ring row
    ``r`` gets the last real position that is ``r mod window`` (rows past a
    short prompt's end hold what the walk never reads)."""
    direct = bucket <= window and bucket % ring_rows == 0
    if not direct:
        r = jnp.arange(window, dtype=jnp.int32)
        src = jnp.clip(r + window * ((length - 1 - r) // window), 0,
                       bucket - 1)

    def fill(rows):
        ring = rows if direct else rows[src]
        return ring.reshape(1, -1, ring_rows, ring.shape[-1])

    return fill


def ring_step_addresses(positions, slots, window: int, ring_rows: int):
    """Where a decode step's rows go in the rings, and what its walk reads:
    ``tables`` [S, window / ring_rows] (a slot's own ring blocks, the walk's
    table), ``blocks`` [S] (the block each slot's token lands in) and
    ``rows`` [S] (its row inside the block)."""
    nrb = window // ring_rows
    at = positions % window
    tables = slots[:, None] * nrb + jnp.arange(nrb, dtype=jnp.int32)
    return tables, slots * nrb + at // ring_rows, at % ring_rows


def walked_blocks(contexts, block_rows: int, slots: int) -> int:
    """Blocks ONE paged walk fetches in a decode step: a live stream's
    ``ceil(context / block_rows)`` and an idle slot's one (its length is one
    token).  ``contexts``: the live streams' lengths, one entry each."""
    contexts = np.asarray(contexts)
    return int(np.sum((contexts + block_rows - 1) // block_rows)) \
        + slots - int(contexts.size)


# ---------------------------------------------------------------------------
# the protocol
# ---------------------------------------------------------------------------

class ConfigDict:
    """``to_dict`` / ``from_dict`` of a config dataclass whose fields are
    published keys.  ``model_type`` (a class attribute, not a field) is what
    ``to_dict`` adds so that :func:`~paddle_tpu.decode.model.load_lm` finds
    the model in :data:`MODEL_TYPES`."""

    model_type = None

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        if self.model_type is not None:
            d["model_type"] = self.model_type
        return d

    @classmethod
    def from_dict(cls, d: dict):
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)
                      if f.name in d})


class LMAdapter:
    """What a :class:`~paddle_tpu.decode.engine.DecodeEngine` asks of a
    model: config + jit-ready functions over one parameter schema.

    A model gives its cache (``make_cache``: its ``state()`` list is the
    ``state`` the engine threads through every dispatch and hands back to
    ``update()``), its programs ``prefill`` and ``decode_step`` as ``(const,
    state, *feed) → ([token(s), logits, *extra], state')``, an ``observer``
    for ``extra``, ``full_logits`` (the parity anchor: no cache, no kernel)
    and ``supports``.  Params are a plain name → array dict; the engine
    device-puts ``param_list(params)`` once and passes it as ``const``.

    State is of two kinds, and the engine knows neither by name: blocks of a
    paged pool, held by block table to the stream's end (every model), and
    rows that belong to a SLOT — a window layer's ring, a state-space
    layer's recurrent row, a convolution's tail (a model that sets
    ``slot_state``).  Such a model is given the slot count in ``make_cache``
    and, in ``prefill``'s feed after the length, the slot the prompt fills:
    its prefill overwrites the slot's rows whole, which is the reset at a
    join; a decode step's row ``i`` is slot ``i``; a slot without a stream
    rides along and may scribble on its own rows only.

    A subclass sets ``config_class``, ``observer_class``, ``param_shapes``
    (``config → {name: (shape, init)}``, in ``const``'s order) and, where its
    tensors have rules of their own, ``init_tensor``."""

    # what of the engine's refcounted block lifecycle the model's entry
    # points can serve (``prefix_cache``, ``overcommit``, ``beam``): an
    # engine asked for another refuses at build
    supports = frozenset()
    # the engine adds the slot index to prefill's feed and the slot count to
    # make_cache
    slot_state = False
    config_class = None
    observer_class = None
    param_shapes: Callable = None
    init_tensor = staticmethod(init_tensor)

    def __init__(self, config):
        self.config = config

    @classmethod
    def from_dict(cls, raw: dict):
        return cls(cls.config_class.from_dict(raw))

    # -- parameters --------------------------------------------------------
    def param_names(self) -> List[str]:
        return list(self.param_shapes(self.config))

    def init_params(self, seed: int = 0) -> Dict[str, np.ndarray]:
        """Seeded random weights, a tensor a key by ``init_tensor``."""
        shapes = self.param_shapes(self.config)
        keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
        dt = jnp.dtype(self.config.dtype)
        return {name: np.asarray(self.init_tensor(k, tuple(shape), init, dt))
                for k, (name, (shape, init)) in zip(keys, shapes.items())}

    def param_list(self, params: Dict) -> List:
        """The ``const`` list in the fixed order the programs close over
        (missing names fail loudly here, not inside a trace)."""
        return [jnp.asarray(params[n]) for n in self.param_names()]

    # -- state -------------------------------------------------------------
    def make_cache(self, num_blocks: int, block_tokens: int,
                   dtype: str = "float32", slots: Optional[int] = None):
        """The state this model's streams need (:meth:`_make_cache`); a
        ``slot_state`` model is refused without the engine's slot count."""
        if self.slot_state and slots is None:
            raise ValueError("this model's state lives in slot rows: "
                             "make_cache needs the engine's slot count")
        return self._make_cache(num_blocks, block_tokens, dtype, slots)

    def _make_cache(self, num_blocks: int, block_tokens: int, dtype: str,
                    slots: Optional[int]):
        raise NotImplementedError

    def observer(self, name: str, cache, table_shape):
        """What the engine ``name`` hands each launch's extra outputs and
        context lengths to (an ``observer_class``): ``prefill(extra, prompt,
        bucket)``, ``step(extra, contexts)`` (the live streams' context
        lengths, this step's token included) and ``decodez()`` (what joins
        the engine's ``/decodez``).  ``table_shape``: the engine's (slots,
        blocks a slot)."""
        return self.observer_class(name, cache, self.config, table_shape)

    # -- programs ----------------------------------------------------------
    def full_logits(self, plist, tokens, lengths=None):
        """tokens [B, T] int32 → logits [B, T, V]; positions ≥ ``lengths``
        [B] masked out where given."""
        raise NotImplementedError

    def prefill(self, plist, state, tokens, length, *feed):
        """tokens [1, Tb] (bucket-padded), length [] int32, then ``slot`` []
        int32 for a ``slot_state`` model, then block_table [MB] int32, seed
        [] uint32, temperature [] f32, top_k [] int32 → ([next_token [],
        logits [V], *extra], state')."""
        raise NotImplementedError

    def decode_step(self, plist, state, tokens, positions, block_tables,
                    seeds, steps, temperature, top_k, attn_impl=None):
        """tokens / positions [S], block_tables [S, MB], seeds [S] uint32,
        steps [S] int32, temperature [S] f32, top_k [S] int32 →
        ([next_tokens [S], logits [S, V], *extra], state')."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# observers
# ---------------------------------------------------------------------------

class LaunchObserver:
    """The ``decode.<engine>.*`` series every drawn model has.  A model's
    observer derives from this (or from :class:`PoolObserver`), adds the
    series that are its own, and writes ``prefill`` and ``step``: each a span
    (``decode::prefill.observe`` / ``decode::step.observe``, inside the
    ``.wait`` of its launch) whose arguments are what it added to the
    counters of the same names — the launch's own work, for a reader of a
    trace that times that launch."""

    def __init__(self, name: str, cache, config, table_shape):
        self.cache, self.config = cache, config
        self._slots, self._slot_blocks = (int(n) for n in table_shape)
        self.series = sc = _obs_stats.scope(f"decode.{name}")
        self.prefill_real = sc.counter(
            "prefill_real_tokens", "real prompt tokens prefilled")
        self.prefill_pad = sc.counter(
            "prefill_pad_tokens", "pad tokens added snapping prompts onto "
            "the prefill ladder")
        self.prefill_sq = sc.counter(
            "prefill_tokens_sq", "sum over prefills of the prompt length "
            "squared (one layer's causal attention)")
        self.context_tokens = sc.counter(
            "step_context_tokens", "cached tokens a decode step's streams "
            "hold, summed over steps (one layer that reads them)")

    def count_prompt(self, prompt: int, bucket: int) -> None:
        self.prefill_real.inc(prompt)
        self.prefill_pad.inc(bucket - prompt)
        self.prefill_sq.inc(prompt * prompt)

    def decodez(self) -> dict:
        """Nothing of its own on ``/decodez``."""
        return {}


class PoolObserver(LaunchObserver):
    """… and the series of a model whose cache has a paged K/V pool
    (:class:`~paddle_tpu.decode.cache.HybridStateCache`).  A step's figures
    come from the live streams' context lengths, which the engine holds on
    the host.  ``step_live_blocks`` over ``step_table_blocks``
    (``decodez()``) is the share of the tables handed to the decode steps'
    attention kernels that their walks fetched, every reading layer counted:
    a live stream's ``ceil(context / block_tokens)`` blocks of the engine's
    ``blocks a slot`` and one of an idle slot; which layers read is the
    model's to say (:meth:`count_walks`)."""

    def __init__(self, name: str, cache, config, table_shape):
        super().__init__(name, cache, config, table_shape)
        sc = self.series
        self.streams = sc.counter(
            "step_streams", "live streams, summed over decode steps")
        self.live_blocks = sc.counter(
            "step_live_blocks", "blocks the decode steps' attention walks "
            "fetched, summed over the layers that read: a live stream's up "
            "to its context (a ring's up to the window), one of an idle "
            "slot")
        self.table_blocks = sc.counter(
            "step_table_blocks", "table entries those walks were handed: "
            "slots x blocks a slot (a ring: blocks a ring) a reading layer, "
            "a step")
        self.live_tokens = sc.gauge("kv_live_tokens")
        sc.gauge("kv_pool_bytes").set(cache.kv_pool_bytes)

    def count_streams(self, contexts):
        """A step's (cached tokens, live streams), counted."""
        context, streams = int(np.sum(contexts)), len(contexts)
        self.context_tokens.inc(context)
        self.streams.inc(streams)
        self.live_tokens.set(context)
        self.cache.live_tokens = context
        return context, streams

    def pool_walk(self, contexts) -> int:
        """Blocks one layer's walk over the pool fetches this step."""
        return walked_blocks(contexts, self.cache.block_tokens, self._slots)

    def count_walks(self, fetched: int, handed: int) -> None:
        self.live_blocks.inc(fetched)
        self.table_blocks.inc(handed)

    def decodez(self) -> dict:
        """The walks' share of their tables; the gauges ride ``cache``."""
        return {"step_live_blocks": self.live_blocks.value,
                "step_table_blocks": self.table_blocks.value}


class RoutedLoadSeries:
    """The routed-load series of a model with expert layers, fed by the
    ``load`` its programs return: a row a *dispatch* (one layer's experts in
    one program launch) of ``[assignments, experts touched, largest load,
    …]``.  ``buckets``: the ``expert_load_max`` histogram's."""

    def __init__(self, sc, buckets):
        self.prefill_assignments = sc.counter(
            "prefill_routed_assignments", "token-expert assignments "
            "computed by prefills (real prompt tokens only), every layer")
        self.step_assignments = sc.counter(
            "step_routed_assignments", "token-expert assignments computed "
            "by decode steps (live slots only), every layer")
        self.step_dispatches = sc.counter(
            "step_moe_dispatches", "expert layers run by decode steps")
        self.step_touched = sc.counter(
            "step_experts_touched", "experts with at least one row, summed "
            "over the decode steps' dispatches")
        self.step_load_max_sum = sc.counter(
            "step_expert_load_max_sum", "largest load of one expert, summed "
            "over the decode steps' dispatches")
        self.load_max = sc.histogram(
            "expert_load_max", buckets=buckets,
            help_str="largest load of one expert a dispatch (rows)")

    def count_prefill(self, load) -> int:
        """A prefill's assignments, counted."""
        assignments = int(load[:, 0].sum())
        self.prefill_assignments.inc(assignments)
        for m in load[:, 2]:
            self.load_max.observe(float(m))
        return assignments

    def count_step(self, load):
        """A step's (assignments, experts touched), counted."""
        assignments, touched = int(load[:, 0].sum()), int(load[:, 1].sum())
        self.step_assignments.inc(assignments)
        self.step_dispatches.inc(int(load.shape[0]))
        self.step_touched.inc(touched)
        self.step_load_max_sum.inc(int(load[:, 2].sum()))
        for m in load[:, 2]:
            self.load_max.observe(float(m))
        return assignments, touched


class RingSeries:
    """The series of a model whose window layers keep RINGS (a slot's last
    ``window`` rows a window layer), fed by the prompts' and the live
    streams' lengths.  ``step_ring_rows_live`` over ``step_ring_rows_held`` is
    the share of the rings' bytes that the live streams use: a stream holds
    ``window`` rows a window layer whatever its context, and ``min(context,
    window)`` of them are live."""

    def __init__(self, sc, window: int):
        self.window = int(window)
        self.prefill_pairs = sc.counter(
            "prefill_window_pairs", "(query, visible key) pairs of one "
            "window layer, summed over prefills")
        self.live = sc.counter(
            "step_ring_rows_live", "ring rows a decode step's streams read "
            "(context cut at the window), summed over steps (one window "
            "layer)")
        self.held = sc.counter(
            "step_ring_rows_held", "ring rows the live streams hold (the "
            "window a stream), summed over steps (one window layer)")
        self.past_window = sc.counter(
            "step_streams_past_window", "live streams whose context is "
            "longer than the window, summed over decode steps")

    def count_prompt(self, prompt: int) -> int:
        """A prompt's (query, visible key) pairs of one window layer,
        counted."""
        full = min(prompt, self.window)
        pairs = full * (full + 1) // 2 + (prompt - full) * self.window
        self.prefill_pairs.inc(pairs)
        return pairs

    def count_step(self, contexts) -> int:
        """A step's live ring rows of one window layer, counted."""
        contexts = np.asarray(contexts)
        live = int(np.minimum(contexts, self.window).sum())
        self.live.inc(live)
        self.held.inc(int(contexts.size) * self.window)
        self.past_window.inc(int(np.sum(contexts > self.window)))
        return live

    def decodez(self) -> dict:
        return {"step_ring_rows_live": self.live.value,
                "step_ring_rows_held": self.held.value}


__all__ = ["LMAdapter", "ConfigDict", "MODEL_TYPES", "TOPK_MAX", "mm",
           "rms_norm", "swiglu", "rotary", "sub", "unscanned",
           "EXPERT_LEAVES", "LatentAttention",
           "init_tensor", "sample", "sample_first", "prompt_addresses",
           "step_addresses", "ring_of_prompt", "ring_step_addresses",
           "walked_blocks", "LaunchObserver",
           "PoolObserver", "RoutedLoadSeries", "RingSeries"]
