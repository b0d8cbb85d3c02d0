"""Lower-precision controls of the ``xing_serve`` driver's reference
comparison: the engine's own programs, run once as they are and three times
in a precision below the one the configuration states, each through the
driver's ``replay`` / ``read`` / ``judge`` on the same requests; a fifth,
``planted_faults``, holds the two limits that no precision moves.  The sound
program must come out correct and each control NOT correct, by the limit that
guards it (:data:`GUARDS`; PERF.md section 6 has every reading).

    python3 benchmark/xing_controls.py --workload <cell> --seeds <a,b> [--samples 4]

- **maps from bf16 products**: every sub-layer's ``Phi`` rounded to bfloat16
  (and widened back: the tensor stays float32) in the weights the programs
  are handed.  The streams are bf16 already, so the 24-wide product is then a
  bf16 x bf16 product that sums in float32 where the configuration states a
  float32 ``Phi``; the reference keeps the ``Phi`` the seed gives.  Guarded by
  ``hc_map_err_p50``.
- **fp8 latent pool** (``mla_controls.fp8_pool``, imported): after the
  prompts' prefills the pool is rounded to ``float8_e4m3fn`` and back, so
  every decode step reads a cache of 3 bits of mantissa where the
  configuration states bf16's 8.  Guarded by ``logit_err_p50`` / ``_p90``.
- **int8 expert weights** (``mla_controls.int8_experts_in_place``,
  imported): the routed experts' three matrices through int8 codes with one
  scale a (expert, output channel), in place; the reference then gets the
  weights made anew from the same seed.  Guarded by ``expert_err_p50``.
- **planted faults** (``mla_controls.tamper``, imported; no program runs):
  2% of the produced tokens replaced by random ones and the first of the
  chosen experts replaced by a random one at 5% of the (layer, position)
  pairs.  Guarded by ``token_gap_p99`` and ``route_differs_share``.

Needs a TPU, as ``run.py`` does; ``tests/benchmark/test_benchmark_xing.py``
drives the same functions at a toy size on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, loadgen  # noqa: E402
from benchmark.mla_controls import (fp8_pool,  # noqa: E402
                                    int8_experts_in_place, tamper, verdict)

# a control → the limits that must refuse it
GUARDS = {"bf16_maps": ("hc_map_err_p50",),
          "fp8_latent_pool": ("logit_err_p50", "logit_err_p90"),
          "planted_faults": ("token_gap_p99", "route_differs_share"),
          "int8_experts": ("expert_err_p50",)}


def bf16_phi(engine) -> list:
    """The engine's weight list with every ``Phi`` through bfloat16."""
    import jax.numpy as jnp
    return [w.astype(jnp.bfloat16).astype(w.dtype)
            if name.endswith("_hc_phi") else w
            for name, w in zip(engine.model.param_names(), engine._plist)]


def run_controls(driver, cfg: dict, params: dict, engine, asks) -> dict:
    """``asks``: (prompt, tokens the engine produced for it).  Returns the
    five verdicts and their readings.  The sound program and the planted
    faults are teacher-forced with the engine's tokens, as the driver's check
    of a window; a lower-precision control produces its own greedy tokens, so
    that its tokens are judged too.  The last control rewrites the experts'
    weights in place and leaves ``params`` and the engine made anew."""
    own = [(prompt, len(tokens)) for prompt, tokens in asks]
    out = {}
    samples = driver.replay(engine, asks)
    refs = driver.run_reference(params, cfg, samples)
    got = driver.read(params, cfg, samples, refs)
    out["sound"] = (verdict("sound", got, driver), got)
    got = driver.read(params, cfg, tamper(samples, cfg), refs)
    out["planted_faults"] = (verdict("planted_faults", got, driver), got)
    got = driver.read(params, cfg, driver.replay(engine, own,
                                                 const=bf16_phi(engine)))
    out["bf16_maps"] = (verdict("bf16_maps", got, driver), got)
    got = driver.read(params, cfg, driver.replay(engine, own,
                                                 after_prefill=fp8_pool))
    out["fp8_latent_pool"] = (verdict("fp8_latent_pool", got, driver), got)
    int8_experts_in_place(engine, params)
    samples = driver.replay(engine, own)
    params.clear()
    engine._plist[:] = [None] * len(engine._plist)
    params.update(driver.make_params(cfg))
    engine._plist[:] = engine.model.param_list(params)
    got = driver.read(params, cfg, samples)
    out["int8_experts"] = (verdict("int8_experts", got, driver), got)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated traffic seeds, one verdict each")
    ap.add_argument("--samples", type=int, default=4)
    args = ap.parse_args(argv)
    manifest = harness.load_manifest(ROOT)
    cell = harness.Cell(ROOT, manifest, args.workload)
    driver = cell.driver()
    driver.validate(cell, float(manifest["run_seconds"]))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("controls: no TPU here", file=sys.stderr)
        return 2
    from paddle_tpu.core import compile_cache
    compile_cache.wire_jax_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from paddle_tpu.decode import SamplingParams
    cfg, mix = cell.config, cell.mix
    params = driver.make_params(cfg)
    engine, server, _ = driver.build_server(cfg, mix, params)
    ok, record = True, {}
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            requests = loadgen.build_requests(
                mix, int(cfg["vocab_size"]), seed,
                float(manifest["run_seconds"]))
            picked = requests[:args.samples]
            handles = [engine.submit(r.prompt, SamplingParams(
                temperature=0.0, max_new_tokens=r.max_new)) for r in picked]
            asks = [(r.prompt, h.result(timeout=1800.0)["tokens"])
                    for r, h in zip(picked, handles)]
            print(f"controls: seed {seed} prompts",
                  [int(p.size) for p, _ in asks], "outputs",
                  [len(t) for _, t in asks], flush=True)
            out = run_controls(driver, cfg, params, engine, asks)
            record[str(seed)] = {k: {"correct": v[0], "readings": v[1]}
                                 for k, v in out.items()}
            ok = ok and out["sound"][0] and not any(
                out[k][0] for k in GUARDS)
            print(json.dumps({"seed": seed,
                              **{k: v[0] for k, v in out.items()}}),
                  flush=True)
    finally:
        server.stop()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "xing_controls.json"),
              "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({"ok": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
