"""What the Falcon-H1 stack's new kernels must do, in operations and bytes:
the counting functions of their roofline shares
(``benchmark/metrics/kernel_roofline.py``, which a metric's ``counts``
argument points here), beside
``kernel_counts.py`` and under its rules.

Only what a kernel MUST do is counted — the recurrence's own operations a
real position (whatever products a chunked form regroups them into), the
recurrent rows of the LIVE streams read once and written once, the K/V rows
of the live context at their width, once a layer (a group's five query heads
share a K/V head's rows), the causal half of a prompt's (query, key) pairs,
never a bucket's pads or an idle slot — so a share above 100% is a counting
fault, never a fast kernel.  ``cfg`` is the configuration file, ``w`` what
the timed launches added to the ``decode.<model>.*`` counters, under the
counters' names; every function returns ``(operations, bytes)`` over those
launches, either of which may be 0 where the kernel is judged by the other
alone.
"""
from __future__ import annotations

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}
# one state element a position: decay x state, coefficient x input, their
# sum; coefficient x state and its sum into the output
_STATE_OPS = 5.0


def _state_numbers(cfg: dict) -> int:
    """One layer's recurrent row of one stream: heads x state x channels."""
    return int(cfg["mamba_d_ssm"]) * int(cfg["mamba_d_state"])


def _row_bytes(cfg: dict) -> int:
    """One cached token of one layer: keys and values of every K/V head."""
    return 2 * int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]) \
        * _ITEM[str(cfg["kv_dtype"])]


def _pair_ops(cfg: dict) -> float:
    """One (query, key) pair of one layer: 2 x head operations a head for
    the score and 2 x head for the value."""
    return int(cfg["num_attention_heads"]) * 4.0 * int(cfg["head_dim"])


def ssd_scan_prefill(cfg: dict, w: dict) -> tuple:
    """The chunked scans of prefills, every layer: a real position's state
    updates, and its rows in (x, B and C in the weights' dtype, the step
    size in float32) and out (y at the weights' width)."""
    layers = int(cfg["num_hidden_layers"])
    item = _ITEM[str(cfg["dtype"])]
    positions = w["prefill_real_tokens"] * layers
    coef = 2 * int(cfg["mamba_n_groups"]) * int(cfg["mamba_d_state"])
    row = (2 * int(cfg["mamba_d_ssm"]) + coef) * item \
        + 4 * int(cfg["mamba_n_heads"])
    return _STATE_OPS * _state_numbers(cfg) * positions, float(positions * row)


def ssd_state_step(cfg: dict, w: dict) -> tuple:
    """The one-token updates of decode steps: the live streams' recurrent
    rows of every layer read once and written once (``step_state_bytes`` is
    exactly that), and their state updates."""
    moved = w["step_state_bytes"]
    return _STATE_OPS * moved / 8.0, float(moved)


def gqa_decode_attn(cfg: dict, w: dict) -> tuple:
    """Decode attention over the pool: every cached token of the live
    context read once a layer."""
    tokens = w["step_context_tokens"] * int(cfg["num_hidden_layers"])
    return _pair_ops(cfg) * tokens, float(tokens * _row_bytes(cfg))


def gqa_prefill_attn(cfg: dict, w: dict) -> tuple:
    """A prompt's causal attention, every layer: n (n + 1) / 2 pairs of a
    prompt of n real positions."""
    pairs = (w["prefill_tokens_sq"] + w["prefill_real_tokens"]) / 2.0
    return _pair_ops(cfg) * pairs * int(cfg["num_hidden_layers"]), 0.0


COUNTS = {"ssd_scan_prefill": ssd_scan_prefill,
          "ssd_state_step": ssd_state_step,
          "gqa_decode_attn": gqa_decode_attn,
          "gqa_prefill_attn": gqa_prefill_attn}
