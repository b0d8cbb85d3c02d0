"""What the Command A+ stack's kernels must do, in operations and bytes: the
counting functions of their roofline shares
(``benchmark/metrics/kernel_roofline.py``, which a metric's ``counts``
argument points here), beside ``kernel_counts.py`` and under its rules.

Only what a kernel MUST do is counted, whatever implements it — the
assignments the router made TO HELD EXPERTS (a choice of an expert held
elsewhere is no work here; pad tokens and idle slots are routed nowhere; a
tile's padded rows are not work; an expert fetched twice because its run
straddles two blocks of the plan is counted once), the three matrices of the
held experts a step really TOUCHED (the program's own counter), the (query,
visible key) pairs of a prompt under the causal mask and the window at 128
heads x 4 x 128 operations a pair, the K/V rows of the live context at their
width once a layer (a group's sixteen query heads share a K/V head's rows:
4,096 B a row) and of a ring its LIVE rows (``min(context, window)``), never
a bucket's pads or an idle slot — so a share above 100% is a counting fault,
never a fast kernel.  ``cfg`` is the configuration file, ``w`` what the timed
launches added to the ``decode.<model>.*`` counters, under the counters'
names; every function returns ``(operations, bytes)`` over those launches,
either of which may be 0 where the kernel is judged by the other alone.
"""
from __future__ import annotations

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}


def _layers(cfg: dict) -> tuple:
    """(full layers, window layers) of the stage."""
    kinds = [str(k) for k in
             cfg["layer_types"][:int(cfg["num_hidden_layers"])]]
    return kinds.count("full_attention"), kinds.count("sliding_attention")


def _expert_weights(cfg: dict) -> int:
    """Numbers in one expert: gate, up and down."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])


def _row_bytes(cfg: dict) -> int:
    """One cached token of one layer: keys and values of every K/V head."""
    return 2 * int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]) \
        * _ITEM[str(cfg["kv_dtype"])]


def _pair_ops(cfg: dict) -> float:
    """One (query, key) pair of one layer: 2 x head operations a head for
    the score and 2 x head for the value."""
    return int(cfg["num_attention_heads"]) * 4.0 * int(cfg["head_dim"])


def moe_prefill(cfg: dict, w: dict) -> tuple:
    """The held experts in prefills, every layer (the counter sums the
    layers): 2 operations a weight an assignment to a held expert."""
    return 2.0 * _expert_weights(cfg) * w["prefill_routed_assignments"], 0.0


def moe_step(cfg: dict, w: dict) -> tuple:
    """The held experts in decode steps, every layer: the three matrices of
    every held expert touched, plus every assignment's row in and out (both
    in the activations' dtype)."""
    item = _ITEM[str(cfg["dtype"])]
    weights = w["step_experts_touched"] * _expert_weights(cfg) * item
    rows = w["step_routed_assignments"] * int(cfg["hidden_size"]) * 2 * item
    return 2.0 * _expert_weights(cfg) * w["step_routed_assignments"], \
        float(weights + rows)


def window_prefill_attn(cfg: dict, w: dict) -> tuple:
    """A prompt's window attention, every window layer: a prompt of n real
    positions has m (m + 1) / 2 + (n - m) W pairs, m = min(n, W) (the
    observer's ``prefill_window_pairs``, one layer's)."""
    return _pair_ops(cfg) * w["prefill_window_pairs"] * _layers(cfg)[1], 0.0


def full_prefill_attn(cfg: dict, w: dict) -> tuple:
    """A prompt's causal attention, every full layer: n (n + 1) / 2 pairs."""
    pairs = (w["prefill_tokens_sq"] + w["prefill_real_tokens"]) / 2.0
    return _pair_ops(cfg) * pairs * _layers(cfg)[0], 0.0


def ring_decode_attn(cfg: dict, w: dict) -> tuple:
    """Decode attention over the rings: a live stream's ``min(context, W)``
    ring rows read once a window layer."""
    rows = w["step_ring_rows_live"] * _layers(cfg)[1]
    return _pair_ops(cfg) * rows, float(rows * _row_bytes(cfg))


def full_decode_attn(cfg: dict, w: dict) -> tuple:
    """Decode attention over the pool: every cached token of the live
    context read once a full layer."""
    tokens = w["step_context_tokens"] * _layers(cfg)[0]
    return _pair_ops(cfg) * tokens, float(tokens * _row_bytes(cfg))


COUNTS = {"moe_prefill": moe_prefill, "moe_step": moe_step,
          "window_prefill_attn": window_prefill_attn,
          "full_prefill_attn": full_prefill_attn,
          "ring_decode_attn": ring_decode_attn,
          "full_decode_attn": full_decode_attn}
