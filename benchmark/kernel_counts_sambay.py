"""What the SambaY stack's new kernels must do, in operations and bytes: the
counting functions of their roofline shares
(``benchmark/metrics/kernel_roofline.py``, which a metric's ``counts``
argument points here), beside
``kernel_counts.py`` and under its rules.

Only what a kernel MUST do is counted — the rows of the live context at
their unpadded width, once a reading layer (two heads of a group share a
K/V head's rows); a window layer's rows cut at the window; the real
positions of a prompt, not its bucket's pads; the causal, in-window pairs of
a prompt's attention, each component's score at its own 64 numbers — so a
share above 100% is a counting fault, never a fast kernel.  ``cfg`` is the
configuration file, ``w`` what the timed launches added to the
``decode.<model>.*`` counters, under the counters' names; every function
returns ``(operations, bytes)`` over those launches, either of which may be
0 where the kernel is judged by the other alone.
"""
from __future__ import annotations

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}


def _row_bytes(cfg: dict) -> int:
    """One cached token of one layer: keys and values of every K/V head."""
    head = int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])
    return 2 * int(cfg["num_key_value_heads"]) * head \
        * _ITEM[str(cfg["kv_dtype"])]


def _pair_ops(cfg: dict) -> float:
    """One (query, key) pair of one layer: a differential head has two
    components, each 2 x head operations for its score and 2 x (2 x head)
    for its value."""
    head = int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])
    heads = int(cfg["num_attention_heads"]) // 2
    return heads * 2 * (2.0 * head + 4.0 * head)


def _pairs(cfg: dict) -> tuple:
    """(window layers, layers that read the shared pool) of the stack."""
    quarter = int(cfg["num_hidden_layers"]) // 4
    return quarter, quarter


def shared_kv_decode_attn(cfg: dict, w: dict) -> tuple:
    """Decode attention over the shared pool: every cached token of the live
    context read once by the full layer and by each cross-attention layer."""
    tokens = w["step_context_tokens"] * _pairs(cfg)[1]
    return _pair_ops(cfg) * tokens, float(tokens * _row_bytes(cfg))


def swa_decode_attn(cfg: dict, w: dict) -> tuple:
    """Decode attention over the window rings: the live context cut at the
    window, every window layer."""
    tokens = w["step_window_tokens"] * _pairs(cfg)[0]
    return _pair_ops(cfg) * tokens, float(tokens * _row_bytes(cfg))


def ssm_scan_prefill(cfg: dict, w: dict) -> tuple:
    """The selective scans of prefills (``prefill_scan_tokens`` is positions
    x state-space layers): a position's d_inner x d_state state updates at 7
    operations (step x A, exp, two products and a sum for h; a product and a
    sum for y), and the rows in (the input in the weights' dtype, the step
    size in float32, B and C) and out (y in the weights' dtype)."""
    inner = int(cfg["expand"]) * int(cfg["hidden_size"])
    n = int(cfg["d_state"])
    item = _ITEM[str(cfg["dtype"])]
    tokens = w["prefill_scan_tokens"]
    return 7.0 * inner * n * tokens, \
        float(tokens * (inner * (2 * item + 4) + 2 * n * 4))


def swa_prefill_attn(cfg: dict, w: dict) -> tuple:
    """A prompt's window attention, every window layer: the causal,
    in-window (query, key) pairs only (``prefill_window_pairs``, one
    layer's)."""
    return _pair_ops(cfg) * w["prefill_window_pairs"] * _pairs(cfg)[0], 0.0


COUNTS = {"shared_kv_decode_attn": shared_kv_decode_attn,
          "swa_decode_attn": swa_decode_attn,
          "ssm_scan_prefill": ssm_scan_prefill,
          "swa_prefill_attn": swa_prefill_attn}
