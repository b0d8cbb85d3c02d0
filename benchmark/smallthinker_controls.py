"""Controls of the ``smallthinker_serve`` driver's reference comparison: the
engine's own programs, run once as they are, and then what must NOT pass —
two precisions below the ones the configuration states and five mechanisms
the model does not have — each through the driver's ``replay`` /
``run_reference`` / ``readings`` / ``judge`` on the same requests and tokens.
The sound program must come out correct and every control NOT correct, by
the limit that guards it (PERF.md section 6 has every reading).

    python3 benchmark/smallthinker_controls.py --workload <cell> --seeds <a,b,c>

Lower precision (what the precision limits stand between):

- **fp8 K/V**: after every dispatch the pool and the rings are rounded to
  e4m3's widths (4 bits of exponent, 3 of mantissa), so attention reads rows
  of 3 bits of mantissa where the configuration states bf16's 8.  Guarded by
  ``logit_err_decode_p50``.
- **bf16 router scores**: the router's logits as the programs returned them
  at the judged rows, rounded to bfloat16 — what a router that keeps its
  scores in the activations' dtype hands ``top_k`` and the softmax — where
  the configuration states float32.  Guarded by ``router_score_err_max``
  (the reference's product of the program's OWN router input, so that no
  rounding upstream of the router is in the reading).

Another mechanism, each the sound replay judged against the plain reference
made into ANOTHER model (the comparison is of two models: what reads as a
fault of the program when the reference is sound reads the same when the
program is sound and the reference has the fault):

- **silu for relu**: the experts' gate is ``silu`` (``faults``).
- **routing from h**: the router reads the post-attention norm's output, as
  in a layer whose router sits after its attention (``faults``).  The
  reference is still given the program's choices; its OWN choices, and the
  weights it gives the program's, are from the other input.  Guarded by
  ``route_differs_share``.
- **rotary on a full layer**: ``rope_layout`` all ones.
- **a window of 2,048**: ``sliding_window_size`` halved.
- **another stream's token**: one judged token of one sample replaced by
  the next sample's token at that step (no program runs: the sound replay's
  logits, judged against the swapped token).  Guarded by ``token_gap_p99``.

The rest are guarded by ``logit_err_prefill_max`` (and the decode limits).

Needs a TPU, as ``run.py`` does; ``tests/benchmark/
test_benchmark_smallthinker.py`` drives the same functions at a toy size on
the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, loadgen  # noqa: E402
from benchmark.sambay_controls import (_rounder,  # noqa: E402
                                       another_streams_token, verdict)

# what fails which: the limit that guards each control (the driver's LIMITS)
GUARDS = {"fp8_kv": "logit_err_decode_p50",
          "bf16_router_scores": "router_score_err_max",
          "silu_for_relu": "logit_err_prefill_max",
          "routing_from_h": "route_differs_share",
          "rotary_on_a_full_layer": "logit_err_prefill_max",
          "window_of_half": "logit_err_prefill_max",
          "another_streams_token": "token_gap_p99"}


def fp8_kv():
    return _rounder((0, 1), 4, 3)               # state: [kv, rings]


def bf16_router_scores(samples: list) -> list:
    """The samples with their router logits rounded to bfloat16."""
    import jax.numpy as jnp
    import numpy as np
    return [s._replace(router_r=np.asarray(
        jnp.asarray(s.router_r).astype(jnp.bfloat16).astype(jnp.float32)))
        for s in samples]


def other_models(cfg: dict) -> dict:
    """name → (faults, override) of the reference as another model."""
    layers = len(cfg["rope_layout"])
    return {
        "silu_for_relu": (("silu_gate",), None),
        "routing_from_h": (("route_from_h",), None),
        "rotary_on_a_full_layer": ((), {"rope_layout": [1] * layers}),
        "window_of_half": ((), {"sliding_window_size":
                                int(cfg["sliding_window_size"]) // 2}),
    }


def run_controls(driver, cfg: dict, mix: dict, params: dict, engine, asks,
                 lengths=None) -> dict:
    """``asks``: (prompt, tokens the engine produced for it).  Returns the
    verdicts and their readings; every replay is teacher-forced with the
    engine's own tokens."""
    samples = driver.replay(engine, asks)
    refs = driver.run_reference(params, cfg, samples, lengths)
    router_err = driver.router_errors(params, cfg, samples)
    out = {}

    def read(name, samples, refs=refs, router_err=router_err):
        got = driver.readings(samples, refs, router_err)
        out[name] = (verdict(name, got, driver), got)

    read("sound", samples)
    for name, rounded in (
            ("fp8_kv", driver.replay(engine, asks, after_dispatch=fp8_kv())),
            ("bf16_router_scores", bf16_router_scores(samples))):
        read(name, rounded,
             router_err=driver.router_errors(params, cfg, rounded))
    for name, (faults, override) in other_models(cfg).items():
        read(name, samples, refs=driver.run_reference(
            params, cfg, samples, lengths, faults=faults, override=override))
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
    window = int(cfg["sliding_window_size"])
    params = driver.make_params(cfg)
    engine, server, client = driver.build_server(cfg, mix, params)
    every, ok = {}, True
    try:
        driver.warm_up(client, cfg, mix)    # the replay compiles nothing
        for seed in (int(s) for s in args.seeds.split(",")):
            requests = loadgen.build_requests(
                mix, int(cfg["vocab_size"]), seed,
                float(manifest["run_seconds"]))
            # the driver's own sample: prompts past the window among them
            first = [r for r in requests[:96]
                     if r.max_new >= driver.REPLAY_TOKENS]
            past = [r for r in first if r.prompt.size > window
                    ][:driver.PAST_WINDOW]
            rest = [r for r in first if r.prompt.size <= window]
            picked = (past + rest)[:driver.SAMPLE]
            handles = [engine.submit(r.prompt, SamplingParams(
                temperature=0.0, max_new_tokens=driver.REPLAY_TOKENS))
                for r in picked]
            asks = [(r.prompt, h.result(timeout=1800.0)["tokens"])
                    for r, h in zip(picked, handles)]
            print(f"controls: seed {seed} prompts",
                  [int(p.size) for p, _ in asks], "outputs",
                  [len(t) for _, t in asks], flush=True)
            out = run_controls(driver, cfg, mix, params, engine, asks,
                               driver.reference_lengths(mix, cfg))
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
    with open(os.path.join(ROOT, "chiprun_out",
                           "smallthinker_controls.json"), "w") as f:
        json.dump(every, f)
    print("controls readings:", json.dumps(every), flush=True)
    print(json.dumps({"ok": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
