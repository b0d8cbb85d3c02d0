"""Controls of the ``sambay_serve`` driver's reference comparison: the
engine's own programs, run once as they are, twice with a part of the
per-stream state kept in a precision below the one the configuration states,
and three times with a planted fault of logic, each through the driver's
``replay`` / ``run_reference`` / ``readings`` / ``judge`` on the same
requests and tokens.  The sound program must come out correct and every
control NOT correct, by at least one limit (PERF.md section 6 has every
reading).

    python3 benchmark/sambay_controls.py --workload <cell> --seeds <a,b,c>

Lower precision (what the precision limits stand between):

- **bf16 recurrent state**: after every dispatch the state-space layers'
  ``h`` is rounded to bfloat16's widths, so a stream's state carries 8 bits
  of mantissa from step to step where the configuration states float32.
- **8-bit pool and rings**: after every dispatch the K/V pool and the window
  rings are rounded to e4m3's widths (4 bits of exponent, 3 of mantissa), so
  attention reads rows of 3 bits of mantissa where the configuration states
  bf16's 8.

Planted faults (what the two limits that no precision moves stand under):

- **full layer dropped**: the engine's own executables with the full
  attention layer's ``wo``, ``bo`` and ``mlp_down`` zeroed, so that layer
  L/2 + 1 writes its K/V rows and adds nothing to the residual stream: a
  prompt whose trunk ends one layer early.
- **window short a tile**: a second, small engine of the same weights whose
  model has a window one ring block (16 rows) shorter than the reference's.
- **another stream's token**: one judged token of one sample replaced by
  the next sample's token at that step (no program runs: the sound replay's
  logits, judged against the swapped token).

Needs a TPU, as ``run.py`` does; ``tests/benchmark/test_benchmark_sambay.py``
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


def _rounder(which: tuple, exponent_bits: int, mantissa_bits: int):
    """state → state with the arrays at ``which`` rounded to a float of the
    given widths, in place (donated: the chip cannot hold two pools).
    ``lax.reduce_precision`` and not a pair of casts: inside one program
    XLA's TPU pipeline may drop a narrowing cast that is widened again
    (``xla_allow_excess_precision``), and the control would read as the
    sound program, to the digit."""
    import jax

    def through(*arrays):
        return tuple(jax.lax.reduce_precision(a, exponent_bits, mantissa_bits)
                     for a in arrays)

    rounded = jax.jit(through, donate_argnums=tuple(range(len(which))))

    def after_dispatch(state):
        state = list(state)
        for i, a in zip(which, rounded(*(state[i] for i in which))):
            state[i] = a
        return state

    return after_dispatch


def bf16_recurrent_state():
    return _rounder((2,), 8, 7)                 # state: [kv, rings, h, conv]


def fp8_pool_and_rings():
    return _rounder((0, 1), 4, 3)               # e4m3's widths


CONTROLS = {"bf16_recurrent_state": bf16_recurrent_state,
            "fp8_pool_and_rings": fp8_pool_and_rings}


def full_layer_dropped(engine) -> list:
    """The engine's weights with the full attention layer's way into the
    residual stream zeroed."""
    import jax.numpy as jnp
    names = engine.model.param_names()
    return [jnp.zeros_like(a) if n in ("mf.wo", "mf.bo", "mf.mlp_down")
            else a for n, a in zip(names, engine._plist)]


def window_short_a_tile(driver, cfg: dict, mix: dict, params, asks):
    """The asks replayed through a small engine whose window layers see one
    ring block of rows fewer: → samples."""
    from paddle_tpu.decode import DecodeEngine, SamplingParams
    from paddle_tpu.decode.cache import blocks_for
    from paddle_tpu.decode.sambay import SambaYLM
    eng = mix["engine"]
    W, bs = int(cfg["sliding_window"]), int(eng["block_tokens"])
    short = dict(cfg, sliding_window=W - min(16, W // 2))
    need = sum(blocks_for(int(p.size) + len(t), bs) for p, t in asks)
    engine = DecodeEngine(
        SambaYLM(driver.model_config(short)), params, name="lm_short_window",
        max_slots=len(asks), block_tokens=bs, num_blocks=need + len(asks) + 1,
        prefill_buckets=[int(b) for b in eng["prefill_buckets"]],
        max_queue=len(asks), attn_impl=str(cfg["attn_impl"]),
        cache_dtype=str(cfg["kv_dtype"]), prefix_cache=False, overcommit=False)
    try:
        # the replay compiles nothing: the rungs it needs and the step, here
        for h in [engine.submit(p, SamplingParams(temperature=0.0,
                                                  max_new_tokens=2))
                  for p, _ in asks]:
            h.result(timeout=1800.0)
        return driver.replay(engine, asks)
    finally:
        engine.close()


def another_streams_token(samples: list) -> list:
    """The first sample's last judged token replaced by the second sample's
    token at that step."""
    first, other = samples[0], samples[1]
    produced = first.produced.copy()
    at = int(first.at[-1])
    produced[at] = other.produced[at]
    return [first._replace(produced=produced)] + list(samples[1:])


def verdict(label: str, got: dict, driver) -> bool:
    checks = harness.Checks()
    driver.judge(checks, got)
    for line in checks.lines():
        print(f"[{label}]", line, flush=True)
    print(f"[{label}] correct = {checks.ok}", flush=True)
    return checks.ok


def run_controls(driver, cfg: dict, mix: dict, params: dict, engine, asks,
                 length=None) -> dict:
    """``asks``: (prompt, tokens the engine produced for it).  Returns the
    verdicts and their readings; every replay is teacher-forced with the
    engine's own tokens, so one run of the reference serves all."""
    samples = driver.replay(engine, asks)
    refs = driver.run_reference(params, cfg, samples, length)
    out = {}

    def read(name, samples):
        got = driver.readings(samples, refs)
        out[name] = (verdict(name, got, driver), got)

    read("sound", samples)
    for name, make in CONTROLS.items():
        read(name, driver.replay(engine, asks, after_dispatch=make()))
    read("full_layer_dropped",
         driver.replay(engine, asks, const=full_layer_dropped(engine)))
    read("window_short_a_tile",
         window_short_a_tile(driver, cfg, mix, params, asks))
    read("another_streams_token", another_streams_token(samples))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated; the engine is built once and "
                         "every seed draws its own requests")
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
    engine, server, client = driver.build_server(cfg, mix, params)
    every, ok = {}, True
    try:
        driver.warm_up(client, cfg, mix)    # the replay compiles nothing
        for seed in (int(s) for s in args.seeds.split(",")):
            requests = loadgen.build_requests(
                mix, int(cfg["vocab_size"]), seed,
                float(manifest["run_seconds"]))
            picked = sorted(requests[:64], key=lambda r: -r.max_new
                            )[:driver.SAMPLE]
            handles = [engine.submit(r.prompt, SamplingParams(
                temperature=0.0, max_new_tokens=min(
                    r.max_new, driver.REPLAY_TOKENS))) for r in picked]
            asks = [(r.prompt, h.result(timeout=1800.0)["tokens"])
                    for r, h in zip(picked, handles)]
            print(f"controls: seed {seed} prompts",
                  [int(p.size) for p, _ in asks], "outputs",
                  [len(t) for _, t in asks], flush=True)
            out = run_controls(driver, cfg, mix, params, engine, asks,
                               driver.reference_length(mix))
            every[seed] = {k: v[1] for k, v in out.items()}
            verdicts = {k: v[0] for k, v in out.items()}
            print(f"controls: seed {seed} verdicts", json.dumps(verdicts),
                  flush=True)
            ok = ok and verdicts.pop("sound") and not any(verdicts.values())
    finally:
        server.stop()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "sambay_controls.json"),
              "w") as f:
        json.dump(every, f)
    print("controls readings:", json.dumps(every), flush=True)
    print(json.dumps({"ok": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
