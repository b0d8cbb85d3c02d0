"""Autoregressive decode plane: stateful generative serving.

The PR-8 serving plane (:mod:`paddle_tpu.serving`) does one-shot
fixed-shape inference; generative traffic — the transformer / LSTM
token-by-token story (survey §2.9 inference subsystem + the level-2
``beam_search_decode`` machinery the reference ships in
``contrib/decoder.py``) — needs per-request state that survives across
dispatches.  Without a KV cache every generated token re-prefills the
whole prefix, so latency scales quadratically in output length.  This
package is that state plane, built on the repo's own primitives:

- **Paged KV cache** (:mod:`cache`): per-request key/value state lives
  in device memory as fixed-size blocks (the engine's ``block_tokens``)
  drawn from a preallocated pool; a request holds a block TABLE, so
  admission/eviction moves table entries and never changes a compiled
  shape.  The cache arrays ride
  :meth:`~paddle_tpu.core.executor.Executor.run_callable` as donated
  cache-resident state — they update in place in HBM and never
  round-trip to host.
- **Token-level continuous batching** (:mod:`engine`): requests join
  and leave a running decode batch at token granularity — the serving
  batcher's bucket-ladder discipline applied to the TIME axis.
  Prefill dispatches are SPLIT from the decode step (their own
  prompt-length bucket ladder, the engine's ``prefill_buckets``), so a
  long new prompt never stalls in-flight streams.
- **Pallas decode-attention kernel**
  (:func:`paddle_tpu.kernels.attention.decode_attention`): one query
  token per slot against the live blocks of its block list, fetched by
  the kernel itself in chunks from the scalar-prefetched tables and
  lengths, with an XLA-gather path (``impl="xla"``) and interpret-mode
  CPU coverage (the ``kernels/sparse.py`` contract).
- **A second model behind the same engine** (:mod:`mla`): DeepSeek-V2's
  block — latent (MLA) attention over ONE latent pool
  (:class:`~paddle_tpu.decode.cache.PagedLatentCache`, no V pool), YaRN
  rotary positions, routed experts at top-k beside shared ones
  (``kernels/mla.py``, ``kernels/moe.py``).  A model describes its cache
  (``make_cache``), owns the state list its ``prefill`` / ``decode_step``
  thread as ``(const, state, *feed) → (outs, state')``, and says what of
  the block lifecycle it ``supports``; the engine has no branch on a
  model's kind.
- **A third model, with three kinds of state** (:mod:`sambay`):
  Phi-4-mini-flash-reasoning's decoder-hybrid-decoder stack — state-space
  layers, window attention, ONE full-attention layer whose K/V rows seven
  cross-attention layers read, gated memory units, differential attention
  (``kernels/ssm.py``, ``kernels/diffattn.py``), its programs
  ``lax.scan``s over stacked layer pairs.  Its cache
  (:class:`~paddle_tpu.decode.cache.HybridStateCache`) holds, under the
  one manager, blocks of a paged K/V pool (held to the stream's end,
  addressed by block table), window rings (a slot's last W rows: bounded
  by the window, not by the context) and recurrent rows (a slot's
  state-space state and convolution tail), the last two addressed by
  SLOT.  **The model protocol**: ``prefill(const, state, tokens, length,
  [slot,] block_table, seed, temperature, top_k)`` and
  ``decode_step(const, state, tokens, positions, block_tables, seeds,
  steps, temperature, top_k)``, each ``→ ([token(s), logits, *extra],
  state')`` with ``state`` the cache's own list; a model that sets
  ``slot_state`` is given the engine's slot count in ``make_cache`` and
  the joining request's slot in ``prefill``'s feed (its prefill overwrites
  the slot's rows whole — the reset at a join); a decode step's row ``i``
  is slot ``i``, and a slot without a stream scribbles on its own rows
  only.  ``extra`` goes to the model's ``observer`` (``prefill(extra,
  prompt, bucket)``; ``step(extra, contexts)`` with the live streams'
  context lengths, which the engine holds on the host: a program returns
  nothing for a count the host already has, and nothing for a check;
  ``decodez()``: what the observer adds to ``/decodez`` —
  ``step_live_blocks`` of ``step_table_blocks``, the share of the tables
  handed to the decode steps' attention kernels that their walks fetched:
  a live stream's blocks up to its context and one of an idle slot, of
  slots x blocks a slot — one layer's for a :class:`TransformerLM`
  (:class:`~paddle_tpu.decode.model.TableWalkObserver`), summed over every
  layer that reads for the two hybrid models (:mod:`sambay`: the pool's
  readers, and the window layers' rings up to ``min(context, W)``;
  :mod:`falcon_h1`: every layer)).
- **A fourth model, with state of two kinds in EVERY layer**
  (:mod:`falcon_h1`): Falcon-H1's parallel-hybrid block — a Mamba-2
  (state-space duality) mixer and a grouped-query attention with rotary
  positions side by side on one normed input, the published µP
  multipliers, an untied head (``kernels/ssd.py``, ``kernels/gqa.py``),
  its programs ``lax.scan``s over the layers' stacked weights.  Its cache
  is the same :class:`~paddle_tpu.decode.cache.HybridStateCache` with a
  paged K/V pool of L layers, recurrent rows ``[L, slots, heads, N, P]``
  and convolution tails of L layers, and no window rings: blocks are
  released at a leave, a slot's rows are overwritten whole at a join.
  Same protocol, same ``slot_state``; ``supports`` is empty for it too.
- **On-device sampling** (:mod:`model`): greedy (an argmax; the
  vocabulary is sorted only in a launch that holds a sampled request) /
  top-k / temperature inside the decode dispatch; incremental beam
  search rides
  :class:`paddle_tpu.contrib.decoder.IncrementalBeamDecoder` (the
  reference beam machinery, one ``beam_search`` step per decode step),
  and :class:`~paddle_tpu.decode.beam.PagedBeamDecoder` runs its beams
  as copy-on-write references into the paged cache (the parent gather
  becomes a block-table operation, not a state copy).
- **Two admission policies** over one refcounted block lifecycle (the
  engine's ``prefix_cache=True`` / ``overcommit=True``): with
  ``prefix_cache`` full prompt blocks are content-addressed in a
  :class:`~paddle_tpu.decode.cache.PrefixCache` so shared system
  prompts prefill once (later requests prefill only their suffix);
  with ``overcommit`` admission reserves lazily, with decode-step
  growth and newest-stream preemption + token-exact re-prefill resume
  under pressure.  With neither, a request reserves its worst case at
  admission and every block has one owner.
- **Streaming serving** (:mod:`server` / :mod:`client`): tokens stream
  to clients over a new framed ``DECODE`` msg type on the existing
  zero-copy transport (multi-frame replies — the transport's STREAM
  handler contract), with per-model replica announce/health riding the
  PR-8 registry path and ``decode.*`` counters + ``/decodez`` on the
  observability plane.

Nothing here is imported by the core framework: a process that never
builds an engine gets no new arrays, threads, or sockets.
"""
from __future__ import annotations

from .cache import (BlockAllocator, HybridStateCache,  # noqa: F401
                    PagedKVCache, PagedLatentCache, PrefixCache)
from .model import (LMConfig, TransformerLM, load_lm,  # noqa: F401
                    save_lm)
from .mla import MLAConfig, MLATransformerLM  # noqa: F401
from .sambay import SambaYConfig, SambaYLM  # noqa: F401
from .falcon_h1 import FalconH1Config, FalconH1LM  # noqa: F401
from .smallthinker import (SmallThinkerConfig,  # noqa: F401
                           SmallThinkerLM)
from .lfm2 import LFM2Config, LFM2LM  # noqa: F401
from .engine import (DecodeEngine, DecodeHandle,  # noqa: F401
                     DecodeRequest, SamplingParams)
from .beam import PagedBeamDecoder  # noqa: F401
from .server import DecodeServer, DecodeService  # noqa: F401
from .client import DecodeClient  # noqa: F401
from ..contrib.decoder import IncrementalBeamDecoder  # noqa: F401
from ..serving.batcher import (Draining, Overloaded,  # noqa: F401
                               RequestTooLong)

__all__ = [
    "BlockAllocator", "PagedKVCache", "PagedLatentCache", "HybridStateCache",
    "PrefixCache",
    "LMConfig", "TransformerLM", "save_lm", "load_lm",
    "MLAConfig", "MLATransformerLM", "SambaYConfig", "SambaYLM",
    "FalconH1Config", "FalconH1LM", "SmallThinkerConfig", "SmallThinkerLM",
    "LFM2Config", "LFM2LM",
    "DecodeEngine", "DecodeHandle", "DecodeRequest", "SamplingParams",
    "PagedBeamDecoder",
    "DecodeServer", "DecodeService", "DecodeClient",
    "IncrementalBeamDecoder", "Draining", "Overloaded",
    "RequestTooLong",
]
