"""What the Kimi-Linear stack's kernels must do, in operations and bytes: the
counting functions of their roofline shares
(``benchmark/metrics/kernel_roofline.py``, which a metric's ``counts``
argument points here), beside ``kernel_counts.py`` and under its rules.

Only what a kernel MUST do is counted, whatever implements it — the
recurrence's own operations a real position (the chunked form's products are
more than that and are not counted), the live streams' recurrent rows read
once and written once (never a pad or an idle slot), the (query, visible key)
pairs of a prompt under the causal mask, the latent rows of the live context
at their unpadded width, the assignments the router made TO HELD EXPERTS
(a choice of an expert held elsewhere is no work here; a tile's padded rows
are not work) and the three matrices of the held experts a step really
touched — so a share above 100% is a counting fault, never a fast kernel.
``cfg`` is the configuration file, ``w`` what the timed launches added to the
``decode.<model>.*`` counters, under the counters' names; every function
returns ``(operations, bytes)`` over those launches, either of which may be 0
where the kernel is judged by the other alone.
"""
from __future__ import annotations

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}
# operations a state element a position: the decay (1), S'^T k (2), the
# rank-one update (2) and S^T q (2)
_STATE_OPS = 7.0


def _layers(cfg: dict, which: str) -> int:
    """KDA (``kda_layers``) or latent (``full_attn_layers``) layers of the
    stage: the list counts the published model's layers from 1."""
    return sum(1 for i in cfg["linear_attn_config"][which]
               if i <= int(cfg["num_hidden_layers"]))


def _kda(cfg: dict) -> tuple:
    lac = cfg["linear_attn_config"]
    return int(lac["num_heads"]), int(lac["head_dim"])


def _expert_weights(cfg: dict) -> int:
    """Numbers in one expert: gate, up and down."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def kda_chunk_prefill(cfg: dict, w: dict) -> tuple:
    """The chunked prefills, every KDA layer: a real position's recurrence (7
    operations an element of a head's [K, K] state), and its rows in (q, k
    and v in the activations' dtype, the log-decay a channel and the step
    size a head in float32) and out (o in the activations' dtype)."""
    heads, dim = _kda(cfg)
    item = _ITEM[str(cfg["dtype"])]
    positions = w["prefill_real_tokens"] * _layers(cfg, "kda_layers")
    row = heads * dim * (4 * item + 4) + 4 * heads
    return _STATE_OPS * heads * dim * dim * positions, float(positions * row)


def kda_state_step(cfg: dict, w: dict) -> tuple:
    """The one-token updates of decode steps: the live streams' recurrent
    rows of every KDA layer read once and written once (``step_state_bytes``
    is exactly that: 8 bytes a float32 element), and their recurrence."""
    moved = w["step_state_bytes"]
    return _STATE_OPS * moved / 8.0, float(moved)


def mla_prefill_attn(cfg: dict, w: dict) -> tuple:
    """A prompt's causal attention, every latent layer: n (n + 1) / 2 pairs
    of a prompt of n real positions, a head 2 x (nope + rope) operations a
    pair for the score and 2 x v for the value."""
    per_pair = 2.0 * int(cfg["num_attention_heads"]) * (
        int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
        + int(cfg["v_head_dim"]))
    pairs = (w["prefill_tokens_sq"] + w["prefill_real_tokens"]) / 2.0
    return per_pair * pairs * _layers(cfg, "full_attn_layers"), 0.0


def mla_decode_attn(cfg: dict, w: dict) -> tuple:
    """The absorbed decode attention, every latent layer: the latent row
    (rank + rope numbers) of every cached token of the live context read
    once, and a head 2 x (rank + rope) operations a token for the score and
    2 x rank for the value."""
    row = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    tokens = w["step_context_tokens"] * _layers(cfg, "full_attn_layers")
    ops = 2.0 * int(cfg["num_attention_heads"]) \
        * (row + int(cfg["kv_lora_rank"])) * tokens
    return ops, float(tokens * row * _ITEM[str(cfg["kv_dtype"])])


def moe_prefill(cfg: dict, w: dict) -> tuple:
    """The held experts in prefills, every expert layer (the counter sums
    the layers): 2 operations a weight an assignment to a held expert."""
    return 2.0 * _expert_weights(cfg) * w["prefill_routed_assignments"], 0.0


def moe_step(cfg: dict, w: dict) -> tuple:
    """The held experts in decode steps, every expert layer: the three
    matrices of every held expert touched, plus every assignment's row in
    and out (both in the activations' dtype)."""
    item = _ITEM[str(cfg["dtype"])]
    weights = w["step_experts_touched"] * _expert_weights(cfg) * item
    rows = w["step_routed_assignments"] * int(cfg["hidden_size"]) * 2 * item
    return 2.0 * _expert_weights(cfg) * w["step_routed_assignments"], \
        float(weights + rows)


COUNTS = {"kda_chunk_prefill": kda_chunk_prefill,
          "kda_state_step": kda_state_step,
          "mla_prefill_attn": mla_prefill_attn,
          "mla_decode_attn": mla_decode_attn,
          "moe_prefill": moe_prefill, "moe_step": moe_step}
