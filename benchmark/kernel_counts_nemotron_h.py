"""What the Nemotron-H stack's kernels must do, in operations and bytes: the
counting functions of their roofline shares
(``benchmark/metrics/kernel_roofline.py``, which a metric's ``counts``
argument points here), beside ``kernel_counts.py`` and under its rules.

Only what a kernel MUST do is counted, whatever implements it — the
assignments the router made TO HELD EXPERTS (a choice of an expert held
elsewhere is no work here; pad tokens and idle slots are routed nowhere; a
tile's padded rows are not work) at the TWO matrices of an ungated expert,
2,688 x 1,856 each at the published widths (not three, and no padded column);
the two matrices of the held experts a step really TOUCHED (the program's own
counter); the recurrence's own operations a real position (whatever products
a chunked form regroups them into, and whatever half of a lane tile a 64-wide
head leaves idle); the recurrent rows of the LIVE streams read once and
written once (2,097,152 B a stream a Mamba layer: 64 heads x 128 x 64
float32); the causal half of a prompt's (query, key) pairs at 32 heads x 4 x
128 operations a pair; the K/V rows of the live context at their width once
an attention layer (a group's sixteen query heads share a K/V head's rows:
1,024 B a row) — never a bucket's pads or an idle slot — so a share above
100% is a counting fault, never a fast kernel.  ``cfg`` is the configuration
file, ``w`` what the timed launches added to the ``decode.<model>.*``
counters, under the counters' names; every function returns ``(operations,
bytes)`` over those launches, either of which may be 0 where the kernel is
judged by the other alone.
"""
from __future__ import annotations

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}
# one state element a position: decay x state, coefficient x input, their
# sum; coefficient x state and its sum into the output
_STATE_OPS = 5.0


def _layers(cfg: dict, kind: str) -> int:
    """Layers of one kind (``M`` / ``E`` / ``*``) in the stage."""
    return str(cfg["hybrid_override_pattern"]
               )[:int(cfg["num_hidden_layers"])].count(kind)


def _expert_weights(cfg: dict) -> int:
    """Numbers in one expert: up and down, no gate."""
    return 2 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def _state_numbers(cfg: dict) -> int:
    """One Mamba layer's recurrent row of one stream: heads x state x
    channels."""
    return int(cfg["mamba_num_heads"]) * int(cfg["mamba_head_dim"]) \
        * int(cfg["ssm_state_size"])


def _row_bytes(cfg: dict) -> int:
    """One cached token of one attention layer: keys and values of every K/V
    head."""
    return 2 * int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]) \
        * _ITEM[str(cfg["kv_dtype"])]


def _pair_ops(cfg: dict) -> float:
    """One (query, key) pair of one layer: 2 x head operations a head for
    the score and 2 x head for the value."""
    return int(cfg["num_attention_heads"]) * 4.0 * int(cfg["head_dim"])


def moe_prefill(cfg: dict, w: dict) -> tuple:
    """The held experts in prefills, every expert layer (the counter sums the
    layers): 2 operations a weight an assignment to a held expert."""
    return 2.0 * _expert_weights(cfg) * w["prefill_routed_assignments"], 0.0


def moe_step(cfg: dict, w: dict) -> tuple:
    """The held experts in decode steps, every expert layer: the two matrices
    of every held expert touched, plus every assignment's row in and out
    (both in the activations' dtype)."""
    item = _ITEM[str(cfg["dtype"])]
    weights = w["step_experts_touched"] * _expert_weights(cfg) * item
    rows = w["step_routed_assignments"] * int(cfg["hidden_size"]) * 2 * item
    return 2.0 * _expert_weights(cfg) * w["step_routed_assignments"], \
        float(weights + rows)


def ssd_scan_prefill(cfg: dict, w: dict) -> tuple:
    """The chunked scans of prefills, every Mamba layer: a real position's
    state updates, and its rows in (x, B and C in the weights' dtype, the
    step size in float32) and out (y at the weights' width)."""
    item = _ITEM[str(cfg["dtype"])]
    positions = w["prefill_real_tokens"] * _layers(cfg, "M")
    d_inner = int(cfg["mamba_num_heads"]) * int(cfg["mamba_head_dim"])
    coef = 2 * int(cfg["n_groups"]) * int(cfg["ssm_state_size"])
    row = (2 * d_inner + coef) * item + 4 * int(cfg["mamba_num_heads"])
    return _STATE_OPS * _state_numbers(cfg) * positions, float(positions * row)


def ssd_state_step(cfg: dict, w: dict) -> tuple:
    """The one-token updates of decode steps: the live streams' recurrent
    rows of every Mamba layer read once and written once
    (``step_state_bytes`` is exactly that), and their state updates."""
    moved = w["step_state_bytes"]
    return _STATE_OPS * moved / 8.0, float(moved)


def full_prefill_attn(cfg: dict, w: dict) -> tuple:
    """A prompt's causal attention, every attention layer: n (n + 1) / 2
    pairs of a prompt of n real positions."""
    pairs = (w["prefill_tokens_sq"] + w["prefill_real_tokens"]) / 2.0
    return _pair_ops(cfg) * pairs * _layers(cfg, "*"), 0.0


def full_decode_attn(cfg: dict, w: dict) -> tuple:
    """Decode attention over the pool: every cached token of the live
    context read once an attention layer."""
    tokens = w["step_context_tokens"] * _layers(cfg, "*")
    return _pair_ops(cfg) * tokens, float(tokens * _row_bytes(cfg))


COUNTS = {"moe_prefill": moe_prefill, "moe_step": moe_step,
          "ssd_scan_prefill": ssd_scan_prefill,
          "ssd_state_step": ssd_state_step,
          "full_prefill_attn": full_prefill_attn,
          "full_decode_attn": full_decode_attn}
