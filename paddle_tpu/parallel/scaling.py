"""Weak-scaling efficiency harness (the "8→64 chip scaling eff" target).

Reference precedent: ``benchmark/fluid/fluid_benchmark.py:137`` runs the
same model over 1..N GPUs and reports throughput ratios.  On this repo's
single-core CI host, wall-clock over a *virtual* 8-device CPU mesh would
measure core oversubscription (8 device programs time-sliced onto one
core), not sharding quality — so the harness measures what actually
predicts pod-scale behavior: the PER-DEVICE compiled cost of the SPMD
program.

Weak scaling holds per-device batch fixed while growing the mesh.  With
perfect sharding the per-device HLO does the same flops/bytes at any mesh
size (plus collectives); an accidentally-replicated tensor multiplies
per-device work by the mesh size and craters the ratio — exactly the
regression class that is invisible until a real pod run.

Reported:
- ``eff_flops``  = flops/device(dp=1) ÷ flops/device(dp=N)
- ``eff_bytes``  = bytes/device(dp=1) ÷ bytes/device(dp=N)
- ``allreduce_mb`` = per-step all-reduce traffic in the dp=N program
  (should be ≈ 2 × gradient bytes for kAllReduce, independent of batch)
"""
from __future__ import annotations

import re
from typing import Dict

import numpy as np


def _cost(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def _allreduce_bytes(compiled) -> float:
    """Sum output bytes of all-reduce DEFINITIONS (line-anchored on the
    instruction name, so consumer lines mentioning an %all-reduce operand
    are not double-counted; tuple-shaped combined all-reduces count every
    element)."""
    total = 0.0
    dt_bytes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4}
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*%(all-reduce|reduce-scatter)[\w.\-]* = (.*?) ?(all-reduce|reduce-scatter)\(",
                     line)
        if not m:
            continue
        for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", m.group(2)):
            if dt not in dt_bytes:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * dt_bytes[dt]
    return total


def scaling_report(per_device_batch: int = 4, big_dp: int = 8,
                   run_step: bool = True) -> Dict[str, float]:
    """Compare per-device compiled cost of the Transformer train step on a
    1-device vs ``big_dp``-device mesh at fixed per-device batch."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..core import unique_name
    from ..core.executor import Scope, scope_guard, Executor, _as_device_array
    from ..core.lowering import analyze_block, build_block_fn
    from ..core.program import Program, program_guard
    from ..models import transformer
    from .parallel_executor import make_mesh

    T = 32
    results = {}
    for dp in (1, big_dp):
        B = per_device_batch * dp
        prog, startup = Program(), Program()
        prog.random_seed = 5
        startup.random_seed = 5
        with program_guard(prog, startup), unique_name.guard():
            feeds, loss, _ = transformer.build(
                src_vocab=1000, tgt_vocab=1000, max_len=T, d_model=128,
                n_head=4, d_ffn=512, n_layer=2, dropout=0.1,
                attention_impl="base")
        mesh = make_mesh({"dp": dp}, jax.devices()[:dp])
        rng = np.random.RandomState(0)
        feed = {"src_ids": rng.randint(0, 1000, (B, T)).astype("int64"),
                "tgt_ids": rng.randint(0, 1000, (B, T)).astype("int64"),
                "lbl_ids": rng.randint(0, 1000, (B, T)).astype("int64"),
                "src_mask": np.ones((B, T), "float32"),
                "tgt_mask": np.ones((B, T), "float32")}
        scope, exe = Scope(), Executor()
        with scope_guard(scope):
            exe.run(startup)
            ordered = sorted(feed)
            plan = analyze_block(prog, 0, ordered, [loss.name])
            fn = build_block_fn(prog, plan, mesh=mesh)
            block = prog.global_block
            dp_shard = NamedSharding(mesh, P("dp"))
            repl = NamedSharding(mesh, P())
            feeds_d = [jax.device_put(
                _as_device_array(feed[n], block.var_or_none(n)), dp_shard)
                for n in ordered]
            donated = [jax.device_put(np.asarray(scope.find_var(n)), repl)
                       for n in plan.donated_reads]
            const = [jax.device_put(np.asarray(scope.find_var(n)), repl)
                     for n in plan.const_reads]
            rng_key = jax.random.PRNGKey(0)
            compiled = jax.jit(fn).lower(
                feeds_d, donated, const, rng_key).compile()
            results[dp] = _cost(compiled)
            if dp == big_dp:
                results["allreduce_mb"] = _allreduce_bytes(compiled) / 1e6
            if run_step:
                fetch, _, _ = compiled(feeds_d, donated, const, rng_key)
                loss_val = float(np.asarray(fetch[0]))
                assert np.isfinite(loss_val), loss_val

    eff_flops = results[1]["flops"] / max(results[big_dp]["flops"], 1.0)
    eff_bytes = results[1]["bytes"] / max(results[big_dp]["bytes"], 1.0)
    return {"devices": big_dp,
            "per_device_batch": per_device_batch,
            "eff_flops": round(eff_flops, 3),
            "eff_bytes": round(eff_bytes, 3),
            "allreduce_mb": round(results.get("allreduce_mb", 0.0), 2)}
