"""Controls of the ``falconh1_serve`` driver's reference comparison: the
engine's own programs, run once as they are, twice with a part of the
per-stream state kept in a precision below the one the configuration states,
and five times with a planted fault of logic, each through the driver's
``replay`` / ``run_reference`` / ``readings`` / ``judge`` on the same
requests and tokens.  The sound program must come out correct and every
control NOT correct, by the limit that guards it (PERF.md section 6 has every
reading).

    python3 benchmark/falconh1_controls.py --workload <cell> --seeds <a,b,c>

Lower precision (what the precision limits stand between):

- **bf16 recurrent state**: after every dispatch every layer's recurrent
  rows are rounded to bfloat16's widths, so a stream's state carries 8 bits
  of mantissa from step to step where the configuration states float32.
  Guarded by ``state_err_p50``.
- **8-bit pool**: after every dispatch the K/V pool is rounded to e4m3's
  widths (4 bits of exponent, 3 of mantissa), so attention reads rows of 3
  bits of mantissa where the configuration states bf16's 8.  Guarded by
  ``logit_err_decode_p50`` / ``_p90``.

Planted faults, each the engine's own executables with other weights (no
program is changed or compiled) but the last:

- **attention dropped**: every layer's ``wo`` zeroed — the attention branch
  writes its rows and adds nothing to the residual stream.
- **ssm dropped**: every layer's ``out_proj`` zeroed — the state-space
  branch keeps its rows and adds nothing.
- **mu left out**: the input projection's columns divided by ``µ``, which is
  what the program computes with ``µ = 1``.
- **rotary off by one**: the query columns of ``wqkv`` rotated by one
  position (rotations compose: the query of position t is rotated as that of
  t + 1, the keys are not).
- **another stream's token**: one judged token of one sample replaced by
  the next sample's token at that step (no program runs: the sound replay's
  logits, judged against the swapped token).

The first four are guarded by ``logit_err_prefill_max`` (and the decode
limits), the last by ``token_gap_p99``.

Needs a TPU, as ``run.py`` does; ``tests/benchmark/test_benchmark_falconh1.py``
drives the same functions at a toy size on the CPU.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, loadgen  # noqa: E402
from benchmark.sambay_controls import (_rounder,  # noqa: E402
                                       another_streams_token, verdict)

# what fails which: the limit that guards each control (the driver's LIMITS)
GUARDS = {"bf16_recurrent_state": "state_err_p50",
          "fp8_pool": "logit_err_decode_p90",
          "attention_dropped": "logit_err_prefill_max",
          "ssm_dropped": "logit_err_prefill_max",
          "mu_left_out": "logit_err_prefill_max",
          "rotary_off_by_one": "logit_err_prefill_max",
          "another_streams_token": "token_gap_p99"}


def bf16_recurrent_state():
    return _rounder((1,), 8, 7)                 # state: [kv, S, conv]


def fp8_pool():
    return _rounder((0,), 4, 3)                 # e4m3's widths


CONTROLS = {"bf16_recurrent_state": bf16_recurrent_state,
            "fp8_pool": fp8_pool}


def _replaced(engine, name: str, make) -> list:
    """The engine's weights with ``make(tensor)`` in the place of ``name``."""
    return [make(a) if n == name else a
            for n, a in zip(engine.model.param_names(), engine._plist)]


def attention_dropped(engine, cfg: dict) -> list:
    import jax.numpy as jnp
    return _replaced(engine, "lay.wo", jnp.zeros_like)


def ssm_dropped(engine, cfg: dict) -> list:
    import jax.numpy as jnp
    return _replaced(engine, "lay.out_proj", jnp.zeros_like)


def mu_left_out(engine, cfg: dict) -> list:
    import jax.numpy as jnp
    from paddle_tpu.decode.falcon_h1 import mup_vector
    mu = mup_vector(engine.model.config)    # the program's own, divided out
    return _replaced(engine, "lay.in_proj", lambda a: (
        a.astype(jnp.float32) / mu).astype(a.dtype))


def rotary_off_by_one(engine, cfg: dict) -> list:
    import jax.numpy as jnp
    nh, dh = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    half = dh // 2
    ang = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * (-math.log(float(cfg["rope_theta"])) / half))
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def rotate(w):
        q = w[..., :nh * dh].astype(jnp.float32)
        q = q.reshape(q.shape[:-1] + (nh, dh))
        a, b = q[..., :half], q[..., half:]
        q = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
        return jnp.concatenate([q.reshape(w.shape[:-1] + (nh * dh,)
                                          ).astype(w.dtype),
                                w[..., nh * dh:]], axis=-1)

    return _replaced(engine, "lay.wqkv", rotate)


FAULTS = {"attention_dropped": attention_dropped, "ssm_dropped": ssm_dropped,
          "mu_left_out": mu_left_out, "rotary_off_by_one": rotary_off_by_one}


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
    for name, make in FAULTS.items():
        const = make(engine, cfg)
        read(name, driver.replay(engine, asks, const=const))
        del const               # one changed tensor at a time beside the live
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
            guarded = {k: bool(every[seed][k][g] > driver.LIMITS[g])
                       for k, g in GUARDS.items()}
            print(f"controls: seed {seed} verdicts", json.dumps(verdicts),
                  "each over its own limit", json.dumps(guarded), flush=True)
            ok = ok and verdicts.pop("sound") \
                and not any(verdicts.values()) and all(guarded.values())
    finally:
        server.stop()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "falconh1_controls.json"),
              "w") as f:
        json.dump(every, f)
    print("controls readings:", json.dumps(every), flush=True)
    print(json.dumps({"ok": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
