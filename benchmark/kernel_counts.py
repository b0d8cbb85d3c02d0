"""What the latent-attention expert LM's kernels must do, in operations and
bytes: the counting functions of the roofline shares
(``benchmark/metrics/kernel_roofline.py``).

Kept with the benchmark, beside ``peaks.py``: a later PR may change the
kernels, not what their work is counted as.  Only what a kernel MUST do is
counted — the assignments the router really made (pad tokens are routed
nowhere), the weights of the experts a step really touched (the program's own
counter), the causal half of a prompt's attention, the latent rows of the
live context at their unpadded width — so a share above 100% is a counting
fault, never a fast kernel.  ``cfg`` is the configuration file, ``w`` what
the timed launches added to the ``decode.<model>.*`` counters, under the
counters' names; every function returns ``(operations, bytes)`` over those
launches, either of which may be 0 where the kernel is judged by the other
alone.
"""
from __future__ import annotations

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}


def _expert_weights(cfg: dict) -> int:
    """Numbers in one routed expert: gate, up and down."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def moe_prefill(cfg: dict, w: dict) -> tuple:
    """Routed experts in prefills: 2 operations a weight an assignment."""
    return 2.0 * _expert_weights(cfg) * w["prefill_routed_assignments"], 0.0


def moe_step(cfg: dict, w: dict) -> tuple:
    """Routed experts in decode steps: the three matrices of every expert
    touched, plus every assignment's row in (the weights' dtype) and out
    (float32)."""
    item = _ITEM[str(cfg["dtype"])]
    weights = w["step_experts_touched"] * _expert_weights(cfg) * item
    rows = w["step_routed_assignments"] * int(cfg["hidden_size"]) * (item + 4)
    return 2.0 * _expert_weights(cfg) * w["step_routed_assignments"], \
        float(weights + rows)


def mla_prefill_attn(cfg: dict, w: dict) -> tuple:
    """A prompt's causal attention, every layer: the lower triangle's pairs
    (P squared over 2), a head 2 x (nope + rope) operations a pair for the
    score and 2 x v for the value."""
    per_pair = 2.0 * int(cfg["num_attention_heads"]) * (
        int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
        + int(cfg["v_head_dim"]))
    return per_pair * w["prefill_tokens_sq"] / 2.0 \
        * int(cfg["num_hidden_layers"]), 0.0


def mla_decode_attn(cfg: dict, w: dict) -> tuple:
    """The absorbed decode attention, every layer: the latent row (rank +
    rope numbers) of every cached token of the live context read once, and a
    head 2 x (rank + rope) operations a token for the score and 2 x rank for
    the value."""
    row = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    layers = int(cfg["num_hidden_layers"])
    tokens = w["step_context_tokens"] * layers
    ops = 2.0 * int(cfg["num_attention_heads"]) \
        * (row + int(cfg["kv_lora_rank"])) * tokens
    return ops, float(tokens * row * _ITEM[str(cfg["kv_dtype"])])


COUNTS = {"moe_prefill": moe_prefill, "moe_step": moe_step,
          "mla_prefill_attn": mla_prefill_attn,
          "mla_decode_attn": mla_decode_attn}
