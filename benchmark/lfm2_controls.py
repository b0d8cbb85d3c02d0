"""Controls of the ``lfm2_serve`` driver's reference comparison: the engine's
own programs, run once as they are, and then what must NOT pass — precisions
below the ones the configuration states and wrong computations of the model's
new mechanisms — each through the driver's ``replay`` / ``run_reference`` /
``readings`` / ``judge`` on the same requests and tokens.  The sound program
must come out correct and every control NOT correct, by the limit that guards
it (PERF.md section 6 has every reading).

    python3 benchmark/lfm2_controls.py --workload <cell> --seeds <a,b,c>

Lower precision (what the precision limits stand between):

- **fp8 state**: after every dispatch the pool and the convolution tails are
  rounded to e4m3's widths (4 bits of exponent, 3 of mantissa) where the
  configuration states bf16's 8.  Guarded by ``logit_err_decode_p50``.
- **bf16 router scores**: the router's logits as the programs returned them
  at the judged rows, rounded to bfloat16 — what a router that keeps its
  scores in the activations' dtype hands the sigmoid and ``top_k`` — where the
  configuration states float32.  Guarded by ``router_score_err_max``.

A wrong computation of a new mechanism.  Two are the sound replay judged
against the plain reference made into ANOTHER model (the comparison is of two
models: what reads as a fault of the program when the reference is sound
reads the same when the program is sound and the reference has the fault):

- **the bias in the weights too**: the chosen experts are weighed by ``s +
  b`` and not by ``s`` (``faults``).  Guarded by ``route_weight_err_max``: the
  selection bias is small by design (it turns a choice between near ties), so
  the logits hardly move; the weights the programs returned, held to the
  equations on the programs' own router logits and choices, do.
- **q/k norms left out** (``faults``).  Guarded by ``logit_err_prefill_max``.

Two are the engine's own programs with the state at the prefill → decode join
rewritten, guarded by ``logit_err_join_max`` (the first two decode steps: a
filter of three taps has forgotten a tail by the third):

- **a tail of zeros**: the slot's tails zeroed after its prefill, as a
  prefill that writes none leaves them.
- **a tail from the padded rung's end**: the slot's tails as a prefill leaves
  them that takes ``z`` at the rung's last two positions and not at the
  prompt's (the same program told that the prompt fills its rung).

- **another stream's token**: one judged token of one sample replaced by the
  next sample's token at that step (no program runs).  Guarded by
  ``token_gap_p99``.

Needs a TPU, as ``run.py`` does; ``tests/benchmark/test_benchmark_lfm2.py``
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
from benchmark.sambay_controls import (_rounder,  # noqa: E402
                                       another_streams_token, verdict)
from benchmark.smallthinker_controls import bf16_router_scores  # noqa: E402

# what fails which: the limit that guards each control (the driver's LIMITS)
GUARDS = {"fp8_state": "logit_err_decode_p50",
          "bf16_router_scores": "router_score_err_max",
          "bias_in_weights": "route_weight_err_max",
          "no_qk_norm": "logit_err_prefill_max",
          "tail_of_zeros": "logit_err_join_max",
          "tail_from_rung_end": "logit_err_join_max",
          "another_streams_token": "token_gap_p99"}


def fp8_state():
    return _rounder((0, 1), 4, 3)               # state: [kv, tails]


def zero_tails(state):
    import jax.numpy as jnp
    return [state[0], jnp.zeros_like(state[1])]


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
    # another program state: the reference and the router's own check stand
    for name, other in (
            ("fp8_state", driver.replay(engine, asks,
                                        after_dispatch=fp8_state())),
            ("tail_of_zeros", driver.replay(engine, asks,
                                            after_prefill=zero_tails)),
            ("tail_from_rung_end", driver.replay(engine, asks,
                                                 tail_from_rung_end=True))):
        read(name, other, router_err=driver.router_errors(params, cfg, other))
    rounded = bf16_router_scores(samples)
    read("bf16_router_scores", rounded,
         router_err=driver.router_errors(params, cfg, rounded))
    # another model: the reference with a planted fault
    for fault in ("bias_in_weights", "no_qk_norm"):
        read(fault, samples,
             refs=driver.run_reference(params, cfg, samples, lengths,
                                       faults=(fault,)),
             router_err=driver.router_errors(params, cfg, samples,
                                             faults=(fault,)))
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
    vocab = int(cfg["vocab_size"])
    params = driver.make_params(cfg)
    engine, server, client = driver.build_server(cfg, mix, params)
    every, ok = {}, True
    try:
        driver.warm_up(client, cfg, mix)    # the replay compiles nothing
        for seed in (int(s) for s in args.seeds.split(",")):
            requests = driver.redraw_ids(loadgen.build_requests(
                mix, vocab, seed, float(manifest["run_seconds"])),
                mix, vocab, seed)
            picked = [r for r in requests[:96]
                      if r.max_new >= driver.REPLAY_TOKENS][:driver.SAMPLE]
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
    with open(os.path.join(ROOT, "chiprun_out", "lfm2_controls.json"),
              "w") as f:
        json.dump(every, f)
    print("controls readings:", json.dumps(every), flush=True)
    print(json.dumps({"ok": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
