"""Lower-precision controls of the ``mla_serve`` driver's reference
comparison: the engine's own programs, run once as they are and twice in a
precision below the one the configuration states, each through the driver's
``replay`` / ``run_reference`` / ``readings`` / ``judge``.  The sound program
must come out correct and each control NOT correct, by at least one limit;
a fourth, ``planted_faults``, holds the two limits that the precisions below
move least (:func:`tamper`; PERF.md section 6 has every reading).

    python3 benchmark/mla_controls.py --workload <cell> --seed <n> [--samples 4]

- **fp8 latent pool**: after the prompts' prefills the pool is rounded to
  ``float8_e4m3fn`` and back, so every decode step reads a cache of 3 bits of
  mantissa where the configuration states bf16's 8.
- **int8 expert weights**: the routed experts' three matrices are rounded to
  int8 codes with one abs-max scale a (expert, output channel) and widened
  back, in place (the chip cannot hold both copies); the reference then gets
  the weights made anew from the same seed.

Needs a TPU, as ``run.py`` does; ``tests/benchmark/test_benchmark_mla.py``
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

EXPERT_LEAVES = ("e_gate", "e_up", "e_down")


def fp8_pool(state):
    import jax.numpy as jnp
    return [a.astype(jnp.float8_e4m3fn).astype(a.dtype) for a in state]


def int8_round(w):
    """[E, in, out] → the same through int8 codes, a scale a (E, out)."""
    import jax.numpy as jnp
    w32 = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w32), axis=1, keepdims=True), 1e-30) \
        / 127.0
    return (jnp.clip(jnp.round(w32 / scale), -127, 127) * scale).astype(w.dtype)


def int8_experts_in_place(engine, params: dict) -> None:
    """Replace every routed expert matrix, in ``params`` and in the engine's
    weight list, dropping the original before the next is made."""
    import jax
    rounder = jax.jit(int8_round, donate_argnums=(0,))
    names = engine.model.param_names()
    for i, name in enumerate(names):
        if name.split(".")[-1] in EXPERT_LEAVES:
            w = params.pop(name)
            engine._plist[i] = None
            w = rounder(w)
            params[name] = engine._plist[i] = w


def tamper(samples, cfg: dict, seed: int = 0):
    """Planted faults, not precisions: 2% of the produced tokens replaced by
    random ones and the first of the chosen experts replaced by a random one
    at 5% of the (layer, position) pairs — what the token and the routing
    limits are there to catch."""
    import numpy as np
    rng = np.random.default_rng(seed)
    out = []
    for s in samples:
        produced, ids = s.produced.copy(), s.ids.copy()
        hit = rng.random(produced.shape) < 0.02
        produced[hit] = rng.integers(0, int(cfg["vocab_size"]), int(hit.sum()))
        hit = rng.random(ids.shape[:2]) < 0.05
        ids[hit, 0] = rng.integers(0, int(cfg["n_routed_experts"]),
                                   int(hit.sum()))
        out.append(s._replace(produced=produced, ids=ids))
    return out


def verdict(label: str, got: dict, driver) -> bool:
    checks = harness.Checks()
    driver.judge(checks, got)
    for line in checks.lines():
        print(f"[{label}]", line, flush=True)
    print(f"[{label}] correct = {checks.ok}", flush=True)
    return checks.ok


def run_controls(driver, cfg: dict, params: dict, engine, asks) -> dict:
    """``asks``: (prompt, tokens the engine produced for it).  Returns the
    four verdicts and their readings.  The sound program and the planted
    faults are teacher-forced with the engine's tokens, as the driver's
    check of a window; a lower-precision control produces its own greedy
    tokens, so that its tokens are judged too."""
    def read(samples, refs=None):
        refs = refs or driver.run_reference(params, cfg, samples)
        return driver.readings(samples, refs,
                               driver.reference_experts(params, cfg, samples))

    own = [(prompt, len(tokens)) for prompt, tokens in asks]
    out = {}
    samples = driver.replay(engine, asks)
    refs = driver.run_reference(params, cfg, samples)
    got = read(samples, refs)
    out["sound"] = (verdict("sound", got, driver), got)
    got = read(driver.replay(engine, own, after_prefill=fp8_pool))
    out["fp8_latent_pool"] = (verdict("fp8_latent_pool", got, driver), got)
    got = read(tamper(samples, cfg), refs)
    out["planted_faults"] = (verdict("planted_faults", got, driver), got)
    int8_experts_in_place(engine, params)
    samples = driver.replay(engine, own)
    params.clear()
    engine._plist[:] = [None] * len(engine._plist)
    params.update(driver.make_params(cfg))
    got = read(samples)
    out["int8_experts"] = (verdict("int8_experts", got, driver), got)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
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
    requests = loadgen.build_requests(mix, int(cfg["vocab_size"]), args.seed,
                                      float(manifest["run_seconds"]))
    params = driver.make_params(cfg)
    engine, server, _ = driver.build_server(cfg, mix, params)
    try:
        picked = requests[:args.samples]
        handles = [engine.submit(r.prompt, SamplingParams(
            temperature=0.0, max_new_tokens=r.max_new)) for r in picked]
        asks = [(r.prompt, h.result(timeout=1800.0)["tokens"])
                for r, h in zip(picked, handles)]
        print("controls: prompts", [int(p.size) for p, _ in asks], "outputs",
              [len(t) for _, t in asks], flush=True)
        out = run_controls(driver, cfg, params, engine, asks)
    finally:
        server.stop()
    print("controls readings:", json.dumps({k: v[1] for k, v in out.items()}),
          flush=True)
    ok = out["sound"][0] and not any(
        v[0] for k, v in out.items() if k != "sound")
    print(json.dumps({"ok": ok, **{k: v[0] for k, v in out.items()}}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
