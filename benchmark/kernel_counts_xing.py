"""What the ``xing4_0`` stack's kernels must do, in operations and bytes: the
counting functions of their roofline shares
(``benchmark/metrics/kernel_roofline.py``, which a metric's ``counts``
argument points here), beside ``kernel_counts.py`` and under its rules.

The latent attention and the routed experts are DeepSeek-V2's kernels at this
model's widths, and what they must do is counted by ``kernel_counts.py``'s own
functions from this configuration's keys (imported, not copied).  The residual
mixing's two kernels are counted here: only what a kernel MUST move — a REAL
token's four streams read once (bf16), what it must write, and the maps
between the two kernels — a sub-layer; a rung's padded rows, on which the
kernels compute too, are no work, and ``Phi`` (1.4 MB a sub-layer, read once a
kernel call: 2% of a 2,048-token prefill's bytes) is left out because a
launch's span does not say how many launches were summed — so a share is read
low, never high, and one above 100% is a counting fault.  ``cfg`` is the
configuration file, ``w`` what the timed launches added to the
``decode.<model>.*`` counters, under the counters' names (``prefill_mhc_rows``:
real prompt tokens x sub-layers); every function returns ``(operations,
bytes)`` over those launches.
"""
from __future__ import annotations

from benchmark.kernel_counts import (_ITEM, mla_decode_attn, mla_prefill_attn,
                                     moe_prefill, moe_step)


def _shape(cfg: dict) -> tuple:
    """(streams n, hidden D, numbers in a sub-layer's maps, bytes a stream
    element)."""
    n = int(cfg["hc_mult"])
    return n, int(cfg["hidden_size"]), n * n + 2 * n, _ITEM[str(cfg["dtype"])]


def mhc_pre_prefill(cfg: dict, w: dict) -> tuple:
    """``mhc_pre`` in prefills, a real row a sub-layer: the n streams read
    (n D elements), ``h`` written (D elements) and ``H_post`` and ``H_res``
    written in float32 (n^2 + n numbers); 2 operations a weight of ``Phi``
    (n D x (n^2 + 2n)), and a multiply and an add an element for the sum of
    squares and for ``h``."""
    n, D, maps, item = _shape(cfg)
    rows = w["prefill_mhc_rows"]
    moved = item * n * D + item * D + 4 * (n * n + n)
    ops = 2.0 * n * D * maps + 4.0 * n * D
    return ops * rows, float(moved * rows)


def mhc_post_prefill(cfg: dict, w: dict) -> tuple:
    """``mhc_post`` in prefills, a real row a sub-layer: the n streams and the
    branch's output read (n D + D elements), ``H_post`` and ``H_res`` read
    (n^2 + n float32) and the n streams written; n + 1 multiplies and n adds
    an element written."""
    n, D, _, item = _shape(cfg)
    rows = w["prefill_mhc_rows"]
    moved = item * (2 * n * D + D) + 4 * (n * n + n)
    return (2.0 * n + 1.0) * n * D * rows, float(moved * rows)


COUNTS = {"mhc_pre_prefill": mhc_pre_prefill,
          "mhc_post_prefill": mhc_post_prefill,
          "mla_prefill_attn": mla_prefill_attn,
          "mla_decode_attn": mla_decode_attn,
          "moe_prefill": moe_prefill, "moe_step": moe_step}
