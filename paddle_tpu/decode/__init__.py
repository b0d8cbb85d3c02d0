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
  CPU coverage.
- **The model side** (:mod:`adapter`): a served model is an
  :class:`~paddle_tpu.decode.adapter.LMAdapter` — it describes its cache
  (``make_cache``), owns the state list its ``prefill`` / ``decode_step``
  thread as ``(const, state, *feed) → ([token(s), logits, *extra], state')``,
  hands ``extra`` and the host's context lengths to its ``observer``, and
  says what of the block lifecycle it ``supports``; the engine has no branch
  on a model's kind.  State is blocks of a paged pool, held by block table,
  and — for a model that sets ``slot_state`` — rows addressed by SLOT
  (window rings, recurrent rows, convolution tails:
  :class:`~paddle_tpu.decode.cache.HybridStateCache`), overwritten whole by
  the prefill at a join.  The module also holds what the models share: the
  layer math, the sampling epilogue, the paged pool's addressing (the trash
  block's rule) and the observers' common ``decode.<engine>.*`` series —
  among them ``step_live_blocks`` of ``step_table_blocks`` on ``/decodez``,
  the share of the tables handed to the decode steps' attention kernels that
  their walks fetched.
- **Nine models behind the one engine**: :mod:`model` (the repo's LM block:
  K and V of every layer paged, a suffix prefill, so the one model that
  ``supports`` prefix cache, overcommit and beams); :mod:`mla` (DeepSeek-V2:
  latent attention over ONE latent pool, YaRN, routed experts beside shared
  ones; and under ``model_type`` ``xing4_0`` the same body with a low-rank
  query, a sigmoid router with a selection bias and FOUR residual streams a
  token mixed by manifold-constrained hyper-connections, ``kernels/mhc.py``);
  :mod:`sambay` (Phi-4-mini-flash-reasoning: state-space layers,
  window rings, ONE full-attention layer's pool that the cross-attention
  layers read, differential attention); :mod:`falcon_h1` (Falcon-H1: a
  Mamba-2 mixer and grouped-query attention side by side in every layer);
  :mod:`smallthinker` (SmallThinker: periods of one full and three window
  layers, 64 ReLU experts a layer, the router ahead of the attention);
  :mod:`lfm2` (LFM2: gated short convolutions and 64-wide-head attention,
  sigmoid-routed experts); :mod:`kimi_linear` (Kimi-Linear: three gated
  delta-rule layers — a decay a channel, a recurrent row and a convolution
  tail a slot — to one position-free latent-attention layer, a latent row a
  token beside them in ONE cache, and a SHARE of the router's experts);
  :mod:`command_a` (Command A+: ONE LayerNorm a layer and three branches
  summed — 128 query heads on 8 K/V heads over window rings or a
  position-free pool, a share of 128 sigmoid-routed experts, four shared
  experts averaged — and a slice of the tied embedding); :mod:`nemotron_h`
  (Nemotron-H / Nemotron 3 Nano: ONE mixer a layer by a pattern string —
  Mamba-2 of 64-wide heads kept two to a lane tile, ungated relu² experts
  held by share beside a shared one, position-free grouped-query attention
  — with a layer count a kind of state).
  Each module's docstring is its model's.
- **On-device sampling** (:func:`adapter.sample`): greedy (an argmax; the
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
from .mla import (HyperMLAConfig, HyperMLATransformerLM,  # noqa: F401
                  MLAConfig, MLATransformerLM)
from .sambay import SambaYConfig, SambaYLM  # noqa: F401
from .falcon_h1 import FalconH1Config, FalconH1LM  # noqa: F401
from .smallthinker import (SmallThinkerConfig,  # noqa: F401
                           SmallThinkerLM)
from .lfm2 import LFM2Config, LFM2LM  # noqa: F401
from .kimi_linear import KimiLinearConfig, KimiLinearLM  # noqa: F401
from .command_a import CommandAConfig, CommandALM  # noqa: F401
from .nemotron_h import NemotronHConfig, NemotronHLM  # noqa: F401
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
    "MLAConfig", "MLATransformerLM", "HyperMLAConfig",
    "HyperMLATransformerLM", "SambaYConfig", "SambaYLM",
    "FalconH1Config", "FalconH1LM", "SmallThinkerConfig", "SmallThinkerLM",
    "LFM2Config", "LFM2LM", "KimiLinearConfig", "KimiLinearLM",
    "CommandAConfig", "CommandALM", "NemotronHConfig", "NemotronHLM",
    "DecodeEngine", "DecodeHandle", "DecodeRequest", "SamplingParams",
    "PagedBeamDecoder",
    "DecodeServer", "DecodeService", "DecodeClient",
    "IncrementalBeamDecoder", "Draining", "Overloaded",
    "RequestTooLong",
]
