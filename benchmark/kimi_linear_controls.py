"""Controls of the ``kimi_linear_serve`` driver's reference comparison: the
engine's own programs, run once as they are, and then what must NOT pass —
precisions below the ones the configuration states and wrong computations of
the model's new mechanisms — each through the driver's ``replay`` /
``run_reference`` / ``readings`` / ``judge`` on the same requests and tokens.
The sound program must come out correct and every control NOT correct, by the
limit that guards it (PERF.md section 6 has every reading).

    python3 benchmark/kimi_linear_controls.py --workload <cell> --seeds <a,b>

Lower precision (what the precision limits stand between):

- **bf16 recurrent state**: after every dispatch every KDA layer's recurrent
  rows are rounded to bfloat16's widths where the configuration states
  float32.  Guarded by ``state_err_p50``.
- **fp8 pool**, **fp8 tails**: after every dispatch the latent pool, or the
  convolutions' tails, rounded to e4m3's widths (4 bits of exponent, 3 of
  mantissa) where the configuration states bf16's 8 and 7.  Each guarded by
  ``logit_err_decode_p50``: the pool is read by two layers of nine, so it is
  the control that reads nearest the sound program and the one the limit
  stands under.
- **bf16 router scores**: the router's logits as the programs returned them
  at the judged rows, rounded to bfloat16, where the configuration states
  float32.  Guarded by ``router_score_err_max``.

A wrong computation of a new mechanism: the sound replay judged against the
plain reference made into ANOTHER model (the comparison is of two models:
what reads as a fault of the program when the reference is sound reads the
same when the program is sound and the reference has the fault):

- **a scalar decay a head** in place of a channel's (the mean over the head's
  channels): Mamba-2's gate under this model's name.
- **the delta correction dropped**: ``v_t`` written for ``v_t − S'ᵀ k_t`` — a
  gated linear attention that never overwrites.
- **q and k left unnormalised** (at the published lengths the delta rule
  then diverges and the reference reads NaN: not a number is not within a
  limit).
- **keys rotated in the latent layers**: rotate-half rotary positions at
  ``rope_theta`` on the 64-wide slices that this model leaves as projected.
  These four are guarded by ``logit_err_prefill_max``.
- **the share's renormalisation over the held choices only**: the chosen
  eight's weights divided by the sum of those that fall on the held 64 — a
  share that normalises as if it were the whole layer.  Guarded by
  ``route_weight_err_max``: the weights the programs returned, held to the
  equations on the programs' own router logits and choices.

Two are the engine's own programs with the state at the prefill → decode join
rewritten, guarded by ``logit_err_join_max`` (the first three decode steps: a
filter of four taps has forgotten a tail by the fourth):

- **a tail of zeros**: the slot's tails zeroed after its prefill, as a
  prefill that writes none leaves them.
- **a tail from the padded rung's end**: the slot's tails as a prefill leaves
  them that takes the convolution's inputs at the rung's last three positions
  and not at the prompt's (the same program told that the prompt fills its
  rung).

- **another stream's token**: one judged token of one sample replaced by the
  next sample's token at that step (no program runs).  Guarded by
  ``token_gap_max``.

Needs a TPU, as ``run.py`` does;
``tests/benchmark/test_benchmark_kimi_linear.py`` drives the same functions
at a toy size on the CPU.
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
GUARDS = {"bf16_recurrent_state": "state_err_p50",
          "fp8_pool": "logit_err_decode_p50",
          "fp8_tails": "logit_err_decode_p50",
          "tail_of_zeros": "logit_err_join_max",
          "tail_from_rung_end": "logit_err_join_max",
          "bf16_router_scores": "router_score_err_max",
          "scalar_decay": "logit_err_prefill_max",
          "no_delta": "logit_err_prefill_max",
          "no_qk_norm": "logit_err_prefill_max",
          "rotate_keys": "logit_err_prefill_max",
          "renorm_held": "route_weight_err_max",
          "another_streams_token": "token_gap_max"}
# the controls that are the plain reference as another model, and how many
# of the samples each is run on: another model moves every sample
OTHER_MODELS = ("scalar_decay", "no_delta", "no_qk_norm", "rotate_keys",
                "renorm_held")
OTHER_MODEL_SAMPLES = 4


def over(reading: float, limit: float) -> bool:
    """Whether a reading fails its limit: over it, or not a number at all (a
    delta rule whose keys are not unit vectors diverges, and the reference
    as that model reads NaN at these lengths: ``judge`` refuses it too)."""
    return not reading <= limit


def bf16_recurrent_state():
    return _rounder((1,), 8, 7)         # state: [pool, recurrent rows, tails]


def fp8(which: int):
    return _rounder((which,), 4, 3)     # e4m3's widths


def zero_tails(state):
    import jax.numpy as jnp
    return [state[0], state[1], jnp.zeros_like(state[2])]


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
    for name, how in (
            ("bf16_recurrent_state",
             {"after_dispatch": bf16_recurrent_state()}),
            ("fp8_pool", {"after_dispatch": fp8(0)}),
            ("fp8_tails", {"after_dispatch": fp8(2)}),
            ("tail_of_zeros", {"after_prefill": zero_tails}),
            ("tail_from_rung_end", {"tail_from_rung_end": True})):
        other = driver.replay(engine, asks, **how)
        read(name, other, router_err=driver.router_errors(params, cfg, other))
    rounded = bf16_router_scores(samples)
    read("bf16_router_scores", rounded,
         router_err=driver.router_errors(params, cfg, rounded))
    # another model: the reference with a planted fault
    few = samples[:OTHER_MODEL_SAMPLES]
    for fault in OTHER_MODELS:
        read(fault, few,
             refs=driver.run_reference(params, cfg, few, lengths,
                                       faults=(fault,)),
             router_err=driver.router_errors(params, cfg, few,
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
    params = driver.make_params(cfg)
    engine, server, client = driver.build_server(cfg, mix, params)
    every, ok = {}, True
    try:
        driver.warm_up(client, cfg, mix)    # the replay compiles nothing
        for seed in (int(s) for s in args.seeds.split(",")):
            requests = loadgen.build_requests(
                mix, int(cfg["vocab_size"]), seed,
                float(manifest["run_seconds"]))
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
            guarded = {k: over(every[seed][k][g], driver.LIMITS[g])
                       for k, g in GUARDS.items()}
            print(f"controls: seed {seed} verdicts", json.dumps(verdicts),
                  "each over its own limit", json.dumps(guarded), flush=True)
            ok = ok and verdicts.pop("sound") \
                and not any(verdicts.values()) and all(guarded.values())
    finally:
        server.stop()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "kimi_linear_controls.json"), "w") as f:
        json.dump(every, f)
    print("controls readings:", json.dumps(every), flush=True)
    print(json.dumps({"ok": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
