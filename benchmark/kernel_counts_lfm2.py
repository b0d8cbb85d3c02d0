"""What the LFM2 stack's kernels must do, in operations and bytes: the
counting functions of their roofline shares
(``benchmark/metrics/kernel_roofline.py``, which a metric's ``counts``
argument points here), beside ``kernel_counts.py`` and under its rules.

Only what a kernel MUST do is counted, whatever implements it — the
assignments the router really made (pad tokens and idle slots are routed
nowhere; a tile's padded rows are not work), the three matrices of the
experts a step really TOUCHED (the program's own counter), the (query,
visible key) pairs of a prompt under the causal mask at the heads' real width
of 64 (the kernels contract 128 lanes with a pair's other head zeroed: those
products are not work), the K/V rows of the live context at their width once
an attention layer (a group's four query heads share a K/V head's rows), never
a bucket's pads or an idle slot — so a share above 100% is a counting fault,
never a fast kernel.  ``cfg`` is the configuration file, ``w`` what the timed
launches added to the ``decode.<model>.*`` counters, under the counters'
names; every function returns ``(operations, bytes)`` over those launches,
either of which may be 0 where the kernel is judged by the other alone.
"""
from __future__ import annotations

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}


def _attention_layers(cfg: dict) -> int:
    """Attention layers of the stage."""
    return list(cfg["layer_types"][:int(cfg["num_hidden_layers"])]
                ).count("full_attention")


def _head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim")
               or int(cfg["hidden_size"]) // int(cfg["num_attention_heads"]))


def _expert_weights(cfg: dict) -> int:
    """Numbers in one expert: gate, up and down."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def _row_bytes(cfg: dict) -> int:
    """One cached token of one layer: keys and values of every K/V head."""
    return 2 * int(cfg["num_key_value_heads"]) * _head_dim(cfg) \
        * _ITEM[str(cfg["kv_dtype"])]


def _pair_ops(cfg: dict) -> float:
    """One (query, key) pair of one layer: 2 x head operations a head for
    the score and 2 x head for the value."""
    return int(cfg["num_attention_heads"]) * 4.0 * _head_dim(cfg)


def moe_prefill(cfg: dict, w: dict) -> tuple:
    """The experts in prefills, every expert layer (the counter sums the
    layers): 2 operations a weight an assignment."""
    return 2.0 * _expert_weights(cfg) * w["prefill_routed_assignments"], 0.0


def moe_step(cfg: dict, w: dict) -> tuple:
    """The experts in decode steps, every expert layer: the three matrices of
    every expert touched, plus every assignment's row in and out (both in the
    activations' dtype)."""
    item = _ITEM[str(cfg["dtype"])]
    weights = w["step_experts_touched"] * _expert_weights(cfg) * item
    rows = w["step_routed_assignments"] * int(cfg["hidden_size"]) * 2 * item
    return 2.0 * _expert_weights(cfg) * w["step_routed_assignments"], \
        float(weights + rows)


def gqa64_prefill_attn(cfg: dict, w: dict) -> tuple:
    """A prompt's causal attention, every attention layer: n (n + 1) / 2
    pairs."""
    pairs = (w["prefill_tokens_sq"] + w["prefill_real_tokens"]) / 2.0
    return _pair_ops(cfg) * pairs * _attention_layers(cfg), 0.0


def gqa64_decode_attn(cfg: dict, w: dict) -> tuple:
    """Decode attention over the pool: every cached token of the live
    context read once an attention layer."""
    tokens = w["step_context_tokens"] * _attention_layers(cfg)
    return _pair_ops(cfg) * tokens, float(tokens * _row_bytes(cfg))


COUNTS = {"moe_prefill": moe_prefill, "moe_step": moe_step,
          "gqa64_prefill_attn": gqa64_prefill_attn,
          "gqa64_decode_attn": gqa64_decode_attn}
