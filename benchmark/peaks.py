"""The table of peaks and the functions that count a model's operations.

Kept with the benchmark: a later PR may change the program, not what its
work is counted as.  Recomputed operations are never counted.
"""
from __future__ import annotations

import json
import os


def peaks_for(device_kind: str) -> dict:
    """The row of ``peaks.json`` for ``device_kind``; an unknown device is an
    error, not a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    row = table.get(device_kind)
    if not isinstance(row, dict):
        known = sorted(k for k, v in table.items() if isinstance(v, dict))
        raise KeyError(f"no peaks for device_kind {device_kind!r}; the table "
                       f"has {known}")
    return row


def encdec_train_flops_per_token(cfg: dict, src_len: int, tgt_len: int) -> float:
    """Forward + backward operations of one encoder-decoder Transformer step
    per TARGET position (one source position rides with each), as the model
    requires them: 2 per multiply-add forward, twice that again backward, so
    6 per weight touched and 6 per attention multiply-add pair.  Embedding
    look-ups are gathers and count nothing; the output projection counts."""
    d, f, L = int(cfg["d_model"]), int(cfg["d_ffn"]), int(cfg["n_layer"])
    v = int(cfg["tgt_vocab"])
    enc_layer = 4 * d * d + 2 * d * f                 # q,k,v,o + ffn
    dec_layer = 8 * d * d + 2 * d * f                 # self + cross + ffn
    weights = L * enc_layer * (src_len / tgt_len) + L * dec_layer + d * v
    # attention scores and weighted sums: QK^T and PV are d multiply-adds
    # each per (query, key) pair over all heads, so 4d operations forward and
    # 12d with the backward pass; the masked half of causal self-attention
    # is counted, because below the flash threshold the model computes it
    attn_pairs = L * (src_len * src_len / tgt_len     # encoder self
                      + tgt_len                       # decoder self
                      + src_len)                      # cross
    return 6.0 * weights + 12.0 * d * attn_pairs


def model_flops_util(tokens_per_s: float, flops_per_token: float, chips: int,
                     device_kind: str) -> float:
    """Percent of the chips' bf16 peak that the model's own operations use."""
    peak = peaks_for(device_kind)["bf16_flops_per_s"] * chips
    return 100.0 * tokens_per_s * flops_per_token / peak
