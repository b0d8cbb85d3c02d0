"""Decoder-only transformer LM adapter for the decode plane.

The decode engine needs a model expressed as three pure-JAX functions
sharing one parameter schema — a full causal forward (the re-prefill
baseline and the parity anchor), a prompt prefill that WRITES the paged
cache, and a one-token decode step that READS it through the paged
attention kernel:

- :meth:`TransformerLM.full_logits` — ``tokens [B, T] → logits
  [B, T, V]``, plain causal attention over the whole prefix.
- :meth:`TransformerLM.prefill` — padded prompt ``[1, Tb]`` (Tb on the
  prefill bucket ladder) → last-position logits + first sampled token,
  with every real position's K/V scattered into the request's cache
  blocks (padded positions scatter into the reserved trash block 0).
- :meth:`TransformerLM.decode_step` — the continuous-batching hot
  dispatch: ``[S]`` last tokens at ``[S]`` positions, K/V appended to
  the cache, attention via
  :func:`paddle_tpu.kernels.attention.decode_attention`, next token
  sampled ON DEVICE (greedy / top-k / temperature — only the sampled
  ``[S]`` int32 vector needs a host readback per step).

The layer math (post-LN residuals, sinusoidal positions, sqrt(D) embed
scale) deliberately mirrors ``models/transformer.py``'s decoder stack
so "the tiny transformer" means the same architecture family; the
incremental path and the full forward share the SAME per-layer
functions, which is what makes the paged-cache greedy parity an
algebraic identity (same math, different association) rather than a
coincidence.

Persistence: :func:`save_lm` / :func:`load_lm` write a model dir
(``decode_config.json`` + ``params.npz``) that ``tools/serve.py
--decode`` serves directly.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .adapter import (MODEL_TYPES, TOPK_MAX, ConfigDict, LMAdapter, sample,
                      sample_first, walked_blocks)
from .cache import PagedKVCache
from ..kernels.attention import decode_attention, paged_attention_xla
from ..kernels.quant import (QMAX, SCALE_EPS, kv_dequantize, kv_head_amax,
                             kv_quantize)
from ..observability import stats as _obs_stats

_LN_EPS = 1e-5


@dataclasses.dataclass(frozen=True)
class LMConfig(ConfigDict):
    """Geometry of a decoder-only TransformerLM."""

    vocab: int
    d_model: int = 64
    n_head: int = 4
    d_ffn: int = 128
    n_layer: int = 2
    max_seq_len: int = 128
    dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head


def _pos_table(max_len: int, d_model: int) -> np.ndarray:
    """Sinusoidal positions (models/transformer.py `_pos_encoding_table`)."""
    pos = np.arange(max_len)[:, None].astype("float64")
    dim = np.arange(d_model // 2)[None, :].astype("float64")
    angle = pos / np.power(10000.0, 2 * dim / d_model)
    table = np.zeros((max_len, d_model))
    table[:, 0::2] = np.sin(angle)
    table[:, 1::2] = np.cos(angle)
    return table.astype("float32")


def _split_state(state):
    """``[kc, vc]`` or, quantized, ``[kc, vc, ks, vs]`` → all four (the
    scale pools None when the cache stores floats)."""
    kc, vc = state[0], state[1]
    ks, vs = (state[2], state[3]) if len(state) == 4 else (None, None)
    return kc, vc, ks, vs


def _join_state(kc, vc, ks, vs) -> list:
    return [kc, vc] if ks is None else [kc, vc, ks, vs]


class TableWalkObserver:
    """The observer of a model whose programs return a token and logits
    only (:meth:`TransformerLM.observer`): what it counts, it counts from
    the context lengths the host holds.  ``decode.<engine>.
    step_live_blocks`` over ``step_table_blocks`` is the share of the
    slots' block tables that the decode steps' attention kernel walked —
    a live stream's ``ceil(context / block_tokens)`` blocks and an idle
    slot's one (its table is all trash block, its position 0), of
    ``slots x blocks a slot`` a step."""

    def __init__(self, name: str, cache, config, table_shape):
        sc = _obs_stats.scope(f"decode.{name}")
        self.live_blocks = sc.counter(
            "step_live_blocks", "table entries the decode steps' attention "
            "fetched and computed (one layer): a live stream's blocks up "
            "to its context, one of an idle slot")
        self.table_blocks = sc.counter(
            "step_table_blocks", "table entries the decode steps were "
            "handed (one layer): slots x blocks a slot, a step")
        self._block_tokens = int(cache.block_tokens)
        self._slots, self._slot_blocks = (int(n) for n in table_shape)

    def prefill(self, extra, prompt: int, bucket: int) -> None:
        pass

    def step(self, extra, contexts) -> None:
        """``contexts``: the live streams' context lengths, this step's
        token included (an int array, one entry a live stream)."""
        self.live_blocks.inc(
            walked_blocks(contexts, self._block_tokens, self._slots))
        self.table_blocks.inc(self._slots * self._slot_blocks)

    def decodez(self) -> dict:
        return {"step_live_blocks": self.live_blocks.value,
                "step_table_blocks": self.table_blocks.value}


def _param_names(cfg: LMConfig) -> List[str]:
    names = ["emb"]
    for i in range(cfg.n_layer):
        names += [f"l{i}.wq", f"l{i}.wk", f"l{i}.wv", f"l{i}.wo",
                  f"l{i}.ln1.g", f"l{i}.ln1.b",
                  f"l{i}.fc1", f"l{i}.fc2",
                  f"l{i}.ln2.g", f"l{i}.ln2.b"]
    names.append("out_proj")
    return names


class TransformerLM(LMAdapter):
    """One decoder-only LM: config + the three jit-ready functions
    (:class:`~paddle_tpu.decode.adapter.LMAdapter`'s, and a suffix prefill:
    the one model that ``supports`` the block lifecycle's policies)."""

    supports = frozenset({"prefix_cache", "overcommit", "beam"})
    config_class = LMConfig
    observer_class = TableWalkObserver

    def __init__(self, config: LMConfig):
        super().__init__(config)
        self._pos = jnp.asarray(_pos_table(config.max_seq_len,
                                           config.d_model))

    # -- what an engine asks of a model ------------------------------------
    def param_names(self) -> List[str]:
        return _param_names(self.config)

    def _make_cache(self, num_blocks: int, block_tokens: int, dtype: str,
                    slots=None) -> PagedKVCache:
        """K and V of every layer, paged."""
        cfg = self.config
        return PagedKVCache(cfg.n_layer, cfg.n_head, cfg.head_dim,
                            num_blocks, block_tokens, dtype=dtype)

    # -- parameters --------------------------------------------------------
    def init_params(self, seed: int = 0) -> Dict[str, np.ndarray]:
        cfg = self.config
        rng = np.random.RandomState(seed)
        D, F, V = cfg.d_model, cfg.d_ffn, cfg.vocab

        def mat(m, n, scale=None):
            s = scale if scale is not None else (1.0 / np.sqrt(m))
            return (rng.randn(m, n) * s).astype("float32")

        p = {"emb": mat(V, D, scale=D ** -0.5), "out_proj": mat(D, V)}
        for i in range(cfg.n_layer):
            p[f"l{i}.wq"] = mat(D, D)
            p[f"l{i}.wk"] = mat(D, D)
            p[f"l{i}.wv"] = mat(D, D)
            p[f"l{i}.wo"] = mat(D, D)
            p[f"l{i}.ln1.g"] = np.ones((D,), "float32")
            p[f"l{i}.ln1.b"] = np.zeros((D,), "float32")
            p[f"l{i}.fc1"] = mat(D, F)
            p[f"l{i}.fc2"] = mat(F, D)
            p[f"l{i}.ln2.g"] = np.ones((D,), "float32")
            p[f"l{i}.ln2.b"] = np.zeros((D,), "float32")
        return p

    def _unpack(self, plist) -> Dict[str, jnp.ndarray]:
        return dict(zip(_param_names(self.config), plist))

    # -- shared layer math -------------------------------------------------
    @staticmethod
    def _ln(x, g, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + _LN_EPS) * g + b

    def _qkv(self, p, i, h):
        """h [..., D] → q, k, v [..., H, Dh]."""
        cfg = self.config
        hd = cfg.head_dim

        def split(x):
            return x.reshape(x.shape[:-1] + (cfg.n_head, hd))
        return (split(h @ p[f"l{i}.wq"]), split(h @ p[f"l{i}.wk"]),
                split(h @ p[f"l{i}.wv"]))

    def _post_attn(self, p, i, h, ctx):
        """Residual + FFN half of one layer; ctx is the attention
        output merged back to [..., D]."""
        cfg = self.config
        ctx = ctx.reshape(ctx.shape[:-2] + (cfg.d_model,))
        h = self._ln(h + ctx @ p[f"l{i}.wo"],
                     p[f"l{i}.ln1.g"], p[f"l{i}.ln1.b"])
        f = jax.nn.relu(h @ p[f"l{i}.fc1"]) @ p[f"l{i}.fc2"]
        return self._ln(h + f, p[f"l{i}.ln2.g"], p[f"l{i}.ln2.b"])

    # -- full forward (baseline / parity anchor) ---------------------------
    def full_logits(self, plist, tokens, lengths=None):
        """tokens [B, T] int32 → logits [B, T, V]; positions ≥ length
        masked out of attention when ``lengths`` [B] is given."""
        p = self._unpack(plist)
        cfg = self.config
        B, T = tokens.shape
        sc = float(1.0 / np.sqrt(cfg.head_dim))
        h = p["emb"][tokens] * (cfg.d_model ** 0.5) + self._pos[:T]
        qi = jnp.arange(T)
        causal = qi[:, None] >= qi[None, :]
        mask = causal[None]
        if lengths is not None:
            mask = jnp.logical_and(
                mask, qi[None, None, :] < lengths[:, None, None])
        for i in range(cfg.n_layer):
            q, k, v = self._qkv(p, i, h)          # [B, T, H, Dh]
            s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                           k.astype(jnp.float32)) * sc
            s = jnp.where(mask[:, None], s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", w,
                             v.astype(jnp.float32)).astype(h.dtype)
            h = self._post_attn(p, i, h, ctx)
        return h @ p["out_proj"]

    # -- cache writes ------------------------------------------------------
    @staticmethod
    def _scatter_kv(cache, layer, blocks, offsets, rows):
        """rows [N, H, Dh] into the pool's layer at (block, offset)
        pairs, as the pool's [N, H*Dh] lane-dense rows."""
        return cache.at[layer, blocks, offsets].set(
            rows.reshape(rows.shape[0], -1))

    @staticmethod
    def _scatter_kv_q(cache, scales, layer, blocks, offsets, rows, slot,
                      valid, block_table):
        """Quantized bulk scatter (prefill / suffix prefill): rows
        [T, H, Dh] land as int8 codes with one fresh abs-max scale per
        (destination block, head).

        ``slot`` [T] is each row's index into ``block_table`` (clamped
        for pad lanes), ``valid`` [T] masks real prompt lanes.  The
        per-block scale is the max over the VALID rows bound for that
        table slot; untouched slots (the already-resident prefix of a
        suffix prefill, and pad slots) keep their existing scale —
        prefill only ever writes FRESH blocks (suffix starts are
        block-aligned: prefix-cache hits and preemption resume both
        hand back whole blocks), so no stored code needs rescaling
        here."""
        T = rows.shape[0]
        MB = block_table.shape[0]
        ha = kv_head_amax(rows) * valid[:, None].astype(jnp.float32)
        onehot = jnp.logical_and(
            slot[:, None] == jnp.arange(MB, dtype=jnp.int32)[None, :],
            valid[:, None])                              # [T, MB]
        blk_amax = jnp.max(
            jnp.where(onehot[:, :, None], ha[:, None, :], 0.0),
            axis=0)                                      # [MB, H]
        touched = jnp.any(onehot, axis=0)                # [MB]
        old = scales[layer, block_table]                 # [MB, H]
        new = jnp.where(touched[:, None],
                        jnp.maximum(blk_amax, SCALE_EPS), old)
        scales = scales.at[layer, block_table].set(new)
        q = kv_quantize(rows, new[slot])                 # [T, H, Dh] int8
        cache = cache.at[layer, blocks, offsets].set(q.reshape(T, -1))
        return cache, scales

    @staticmethod
    def _append_kv_q(cache, scales, layer, blocks, offsets, rows):
        """Quantized single-row append (decode step): rows [S, H, Dh],
        one per slot, each into its OWN block (writable blocks are
        refcount-1 exclusive; shared blocks were COW-forked by the
        engine before this dispatch — inactive slots all target trash
        block 0, whose content and scale are never read unmasked).

        When a new row grows a (block, head)'s abs-max the block's
        stored codes requantize to the new scale in VMEM-register math
        (``round(q * old/new)`` — at most half a code of drift per
        growth, and the scale only ever grows over a block's
        residency, so drift is bounded by the growth count, not the
        token count)."""
        S, H, Dh = rows.shape
        ha = kv_head_amax(rows)                          # [S, H]
        old = scales[layer, blocks]                      # [S, H]
        new = jnp.maximum(old, ha)                       # [S, H]
        blk = cache[layer, blocks]                       # [S, bs, H*Dh]
        blk = blk.reshape(S, blk.shape[1], H, Dh)
        ratio = jnp.where(new > 0.0,
                          old / jnp.maximum(new, SCALE_EPS), 1.0)
        blk = jnp.clip(jnp.round(blk.astype(jnp.float32)
                                 * ratio[:, None, :, None]),
                       -QMAX, QMAX).astype(jnp.int8)
        q = kv_quantize(rows, new)                       # [S, H, Dh]
        blk = blk.at[jnp.arange(S), offsets].set(q)
        cache = cache.at[layer, blocks].set(blk.reshape(S, -1, H * Dh))
        scales = scales.at[layer, blocks].set(new)
        return cache, scales

    # -- prefill -----------------------------------------------------------
    def prefill(self, plist, state, tokens, length, block_table,
                seed, temperature, top_k):
        """state ``[kc, vc]`` — or, with the int8 scale pools threaded
        (quantized cache), ``[kc, vc, ks, vs]`` — tokens [1, Tb]
        (bucket-padded), length [] int32, block_table [MB] int32 →
        ([next_token [] int32, logits [V]], state').

        One full causal forward over the padded prompt; every real
        position's K/V lands in the request's blocks, pad positions
        land in trash block 0 (their attention contribution is masked
        by ``length`` either way).  Prefill attention always runs on
        the fresh f32 K/V computed THIS dispatch — quantization only
        affects what the cache stores, so the first token is exact
        either way.  The FIRST generated token samples here, so a
        joining request streams its first token without waiting for a
        decode step."""
        cfg = self.config
        p = self._unpack(plist)
        kc, vc, ks, vs = _split_state(state)
        Tb = tokens.shape[1]
        bs = kc.shape[2]
        MB = block_table.shape[0]
        sc = float(1.0 / np.sqrt(cfg.head_dim))
        pos_idx = jnp.arange(Tb, dtype=jnp.int32)
        valid = pos_idx < length
        slot = jnp.minimum(pos_idx // bs, MB - 1)
        blocks = jnp.where(valid, block_table[slot], 0)
        offsets = pos_idx % bs
        qi = jnp.arange(Tb)
        mask = jnp.logical_and(qi[:, None] >= qi[None, :],
                               qi[None, :] < length)[None]
        h = p["emb"][tokens] * (cfg.d_model ** 0.5) + self._pos[:Tb]
        for i in range(cfg.n_layer):
            q, k, v = self._qkv(p, i, h)          # [1, Tb, H, Dh]
            if ks is None:
                kc = self._scatter_kv(kc, i, blocks, offsets, k[0])
                vc = self._scatter_kv(vc, i, blocks, offsets, v[0])
            else:
                kc, ks = self._scatter_kv_q(kc, ks, i, blocks, offsets,
                                            k[0], slot, valid,
                                            block_table)
                vc, vs = self._scatter_kv_q(vc, vs, i, blocks, offsets,
                                            v[0], slot, valid,
                                            block_table)
            s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                           k.astype(jnp.float32)) * sc
            s = jnp.where(mask[:, None], s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", w,
                             v.astype(jnp.float32)).astype(h.dtype)
            h = self._post_attn(p, i, h, ctx)
        last = h[0, jnp.maximum(length - 1, 0)]
        logits = last @ p["out_proj"]
        tok = sample_first(logits, seed, temperature, top_k)
        return [tok, logits], _join_state(kc, vc, ks, vs)

    # -- suffix prefill (prefix-cache hits / preemption resume) ------------
    def prefill_suffix(self, plist, state, tokens, start, length,
                       block_table, seed, temperature, top_k):
        """state as :meth:`prefill`, tokens [1, Sb] (bucket-padded
        suffix), start [] int32 (how many leading positions are already
        resident in the cache — block-aligned prefix-cache hits), length
        [] int32 (total real sequence length; the suffix is positions
        start..length-1), block_table [MB] int32 → ([next_token [] int32,
        logits [V]], state'); with the int8 scale pools threaded the
        gathered context (cached prefix INCLUDED) is dequantized per
        block before the dense masked attention.

        The prompt's cached prefix is NOT recomputed: suffix K/V is
        scattered into the request's blocks first, then — because
        every suffix lane shares the SAME block table — the whole
        context is gathered ONCE per layer ([MB, bs] → [MB*bs] rows)
        and attention is a dense masked matmul of the Sb suffix
        queries against it (a lane at absolute position ``pos`` sees
        context rows 0..pos: the cached prefix plus the suffix rows
        written this dispatch, in the same layer).  That keeps the
        gather O(context) instead of the per-lane paged path's
        O(lanes x context).  Pad lanes scatter into trash block 0 and
        attend (masked) to position 0 only; their output is
        discarded.  Unwritten table slots are trash block 0 too — as
        flattened rows their positions exceed every real ``pos``, so
        the mask drops them.  Samples the first generated token like
        :meth:`prefill` (token index 0)."""
        cfg = self.config
        p = self._unpack(plist)
        kc, vc, ks, vs = _split_state(state)
        Sb = tokens.shape[1]
        bs = kc.shape[2]
        MB = block_table.shape[0]
        sc = float(1.0 / np.sqrt(cfg.head_dim))
        lane = jnp.arange(Sb, dtype=jnp.int32)
        n = length - start                      # real suffix length
        valid = lane < n
        pos = start + lane
        safe_pos = jnp.minimum(jnp.where(valid, pos, 0),
                               cfg.max_seq_len - 1)
        slot = jnp.minimum(safe_pos // bs, MB - 1)
        blocks = jnp.where(valid, block_table[slot], 0)
        offsets = safe_pos % bs
        tpos = jnp.arange(MB * bs, dtype=jnp.int32)
        mask = tpos[None, :] <= safe_pos[:, None]   # [Sb, MB*bs]
        h = (p["emb"][tokens[0]] * (cfg.d_model ** 0.5)
             + self._pos[safe_pos])
        for i in range(cfg.n_layer):
            q, k, v = self._qkv(p, i, h)          # [Sb, H, Dh]
            if ks is None:
                kc = self._scatter_kv(kc, i, blocks, offsets, k)
                vc = self._scatter_kv(vc, i, blocks, offsets, v)
                ck = kc[i, block_table].reshape(MB * bs, cfg.n_head,
                                                cfg.head_dim)
                cv = vc[i, block_table].reshape(MB * bs, cfg.n_head,
                                                cfg.head_dim)
            else:
                kc, ks = self._scatter_kv_q(kc, ks, i, blocks, offsets,
                                            k, slot, valid, block_table)
                vc, vs = self._scatter_kv_q(vc, vs, i, blocks, offsets,
                                            v, slot, valid, block_table)
                heads = (MB, bs, cfg.n_head, cfg.head_dim)
                ck = kv_dequantize(
                    kc[i, block_table].reshape(heads),
                    ks[i, block_table][:, None, :]).reshape(
                        MB * bs, cfg.n_head, cfg.head_dim)
                cv = kv_dequantize(
                    vc[i, block_table].reshape(heads),
                    vs[i, block_table][:, None, :]).reshape(
                        MB * bs, cfg.n_head, cfg.head_dim)
            s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                           ck.astype(jnp.float32)) * sc
            s = jnp.where(mask[None], s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("hqk,khd->qhd", w, cv.astype(jnp.float32))
            h = self._post_attn(p, i, h, ctx.astype(h.dtype))
        last = h[jnp.maximum(n - 1, 0)]
        logits = last @ p["out_proj"]
        tok = sample_first(logits, seed, temperature, top_k)
        return [tok, logits], _join_state(kc, vc, ks, vs)

    # -- decode step (the continuous-batching hot dispatch) ----------------
    def decode_step(self, plist, state, tokens, positions, block_tables,
                    seeds, steps, temperature, top_k, attn_impl=None):
        """state as :meth:`prefill`, tokens [S] int32 (each slot's last
        token), positions [S] int32 (where that token sits),
        block_tables [S, MB] int32, seeds [S] uint32 + steps [S] int32
        (per-request sampling identity — see :func:`~paddle_tpu.decode.
        adapter.sample`) → ([next_tokens [S], logits [S, V]], state'); with
        the int8 scale
        pools threaded the paged attention dequantizes
        per-block-per-head in the kernel.

        Writes each slot's K/V at (position // bs, position % bs) via
        its block table, then attends over positions 0..position
        through the paged kernel.  Inactive slots feed position 0 with
        an all-zero (trash) block table: they compute masked garbage
        into block 0 and their sampled token is ignored by the engine —
        fixed shapes, no branches."""
        cfg = self.config
        p = self._unpack(plist)
        kc, vc, ks, vs = _split_state(state)
        bs = kc.shape[2]
        cl = positions + 1
        blocks = block_tables[jnp.arange(tokens.shape[0]),
                              positions // bs]
        offsets = positions % bs
        h = p["emb"][tokens] * (cfg.d_model ** 0.5) + self._pos[positions]
        for i in range(cfg.n_layer):
            q, k, v = self._qkv(p, i, h)          # [S, H, Dh]
            if ks is None:
                kc = self._scatter_kv(kc, i, blocks, offsets, k)
                vc = self._scatter_kv(vc, i, blocks, offsets, v)
                ctx = decode_attention(q, kc, vc, block_tables, cl, i,
                                       impl=attn_impl)
            else:
                kc, ks = self._append_kv_q(kc, ks, i, blocks, offsets, k)
                vc, vs = self._append_kv_q(vc, vs, i, blocks, offsets, v)
                ctx = decode_attention(q, kc, vc, block_tables, cl, i,
                                       impl=attn_impl,
                                       k_scale=ks, v_scale=vs)
            h = self._post_attn(p, i, h, ctx.astype(h.dtype))
        logits = h @ p["out_proj"]
        toks = sample(logits, seeds, steps, temperature, top_k)
        return [toks, logits], _join_state(kc, vc, ks, vs)


# ---------------------------------------------------------------------------
# model-dir persistence (tools/serve.py --decode serves these)
# ---------------------------------------------------------------------------

_CONFIG_FILE = "decode_config.json"
_PARAMS_FILE = "params.npz"


_BF16_KEY = "::bf16"     # npz has no bfloat16: such arrays go as uint16 views


def save_lm(dirname: str, config, params: Dict) -> None:
    """Write a decode-servable model dir (config JSON + params npz);
    atomic per file (tmp + replace) like io.py's save discipline.
    ``config`` is an :class:`LMConfig` or another model's config whose
    ``to_dict()`` carries the ``model_type`` that tells :func:`load_lm`
    which model to build."""
    os.makedirs(dirname, exist_ok=True)
    cpath = os.path.join(dirname, _CONFIG_FILE)
    with open(cpath + ".tmp", "w") as f:
        json.dump(config.to_dict(), f, indent=2)
    os.replace(cpath + ".tmp", cpath)
    ppath = os.path.join(dirname, _PARAMS_FILE)
    arrays = {}
    for k, v in params.items():
        v = np.asarray(v)
        if v.dtype == jnp.bfloat16:
            arrays[k + _BF16_KEY] = v.view(np.uint16)
        else:
            arrays[k] = v
    np.savez(ppath + ".tmp.npz", **arrays)
    os.replace(ppath + ".tmp.npz", ppath)


def load_lm(dirname: str):
    """(model, params dict) from a :func:`save_lm` dir: a
    :class:`TransformerLM`, or the model that registered the config's
    ``model_type`` in :data:`MODEL_TYPES`."""
    with open(os.path.join(dirname, _CONFIG_FILE)) as f:
        raw = json.load(f)
    with np.load(os.path.join(dirname, _PARAMS_FILE)) as z:
        params = {}
        for k in z.files:
            if k.endswith(_BF16_KEY):
                params[k[:-len(_BF16_KEY)]] = z[k].view(jnp.bfloat16)
            else:
                params[k] = z[k].copy()
    model = MODEL_TYPES.get(raw.get("model_type"),
                            TransformerLM.from_dict)(raw)
    missing = [n for n in model.param_names() if n not in params]
    if missing:
        raise ValueError(f"model dir {dirname!r} is missing params: "
                         f"{missing[:4]}{'...' if len(missing) > 4 else ''}")
    return model, params


__all__ = ["LMConfig", "TransformerLM", "save_lm", "load_lm",
           "paged_attention_xla", "TOPK_MAX", "MODEL_TYPES",
           "TableWalkObserver"]
