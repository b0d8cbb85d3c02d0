"""Controls of the ``command_a_serve`` driver's reference comparison: the
engine's own programs, run once as they are, and then what must NOT pass —
precisions below the ones the configuration states and mechanisms the model
does not have — each through the driver's ``replay`` / ``run_reference`` /
``own_row_errors`` / ``readings`` / ``judge`` on the same requests and
tokens.  The sound program must come out correct and every control NOT
correct, by the limit that guards it (PERF.md section 6 has every reading).

    python3 benchmark/command_a_controls.py --workload <cell> --seeds <a,b>

Lower precision, bfloat16 or below where the configuration states more:

- **fp8 K/V**: after every dispatch the pool and the rings are rounded to
  e4m3's widths, so attention reads rows of 3 bits of mantissa where the
  configuration states bf16's 8.  Guarded by ``logit_err_decode_p50`` (and
  ``ring_err_max`` reads the rounded rings themselves).
- **bf16 router scores**: the router's logits as the programs returned them
  at the judged rows, rounded to bfloat16, where the configuration states
  float32.  Guarded by ``router_score_err_max`` (the reference's product of
  the program's OWN router input).
- **bf16 norm statistics**: the LayerNorms' mean and scale at bfloat16's
  widths where the configuration states float32 — the plain reference as
  that model, its own normed rows at the judged positions (rounded to the
  activations' dtype, as the program's are) put where the program's stand.
  Guarded by ``norm_unit_err_max``: a statistic of the rows alone.
- **bf16 softmax**: the attention's scores, exponentials, their sum and the
  probabilities at bfloat16's widths (the reference as that model).  Scores
  with a standard deviation of 2 over thousands of keys average the rounding
  out: the control reads 0.0072-0.0077 at the prefills' last positions where
  the sound program reads 0.0066-0.0071 (my chip runs, PR 59) — inside the
  activations' own bf16 noise, and no limit of the logits can tell it; it
  is run and reported, and is not among :data:`GUARDS`.

Another mechanism, each the sound replay judged against the plain reference
made into ANOTHER model (the comparison is of two models: what reads as a
fault of the program when the reference is sound reads the same when the
program is sound and the reference has the fault):

- **rotate-half**: the window layers' rotary pairs lane ``i`` with lane ``i +
  64`` where this model pairs neighbours.
- **the shared experts summed**: the 1/4 of the average left out.
- **no renormalisation**: the chosen scores weigh as they are.  Guarded by
  ``route_weight_err_max`` (the equations' weights from the program's OWN
  router logits and choices).
- **a ring off by a row**: a ring that files position ``p`` at row ``(p − 1)
  mod window``: after the prefill and the steps every row holds another
  position's key.  Guarded by ``ring_err_max``.  (A window MASK off by one
  key of 4,096 moves the logits by less than bf16 does: what a run can hold
  is where the rows lie.)
- **another stream's token**: one judged token of one sample replaced by
  the next sample's token at that step.  Guarded by ``token_gap_max``.

Needs a TPU, as ``run.py`` does;
``tests/benchmark/test_benchmark_command_a.py`` drives the same functions at
a toy size on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, loadgen  # noqa: E402
from benchmark.kimi_linear_controls import over  # noqa: E402
from benchmark.sambay_controls import (_rounder,  # noqa: E402
                                       another_streams_token, verdict)
from benchmark.smallthinker_controls import bf16_router_scores  # noqa: E402

# what fails which: the limit that guards each control (the driver's LIMITS)
GUARDS = {"fp8_kv": "logit_err_decode_p50",
          "bf16_router_scores": "router_score_err_max",
          "bf16_norm_stats": "norm_unit_err_max",
          "rotate_half": "logit_err_prefill_max",
          "shared_sum": "logit_err_prefill_max",
          "no_renorm": "route_weight_err_max",
          "ring_off_by_a_row": "ring_err_max",
          "another_streams_token": "token_gap_max"}
# the controls that are the plain reference as another model, and how many
# of the samples each is run on: another model moves every sample
OTHER_MODELS = ("rotate_half", "shared_sum", "no_renorm", "bf16_norm_stats",
                "bf16_softmax", "ring_off_by_a_row")
OTHER_MODEL_SAMPLES = 4
# run and reported, guarded by no limit (the module's doc)
REPORTED = ("bf16_softmax",)


def fp8_kv():
    return _rounder((0, 1), 4, 3)               # state: [kv, rings]


def with_the_references_rows(samples: list, refs: list) -> list:
    """The samples with the REFERENCE's own normed rows at the judged
    positions where the program's stand, rounded to the dtype the program's
    came in."""
    import numpy as np
    return [s._replace(router_u=np.asarray(got["u"]).astype(
        s.router_u.dtype)) for s, (_, _, got) in zip(samples, refs)]


def run_controls(driver, cfg: dict, mix: dict, params: dict, engine, asks,
                 lengths=None) -> dict:
    """``asks``: (prompt, tokens the engine produced for it).  Returns the
    verdicts and their readings; every replay is teacher-forced with the
    engine's own tokens."""
    samples = driver.replay(engine, asks)
    refs = driver.run_reference(params, cfg, samples, lengths)
    row_err = driver.own_row_errors(params, cfg, samples)
    out = {}

    def read(name, samples, refs=refs, row_err=row_err):
        got = driver.readings(samples, refs, row_err)
        out[name] = (verdict(name, got, driver), got)

    read("sound", samples)
    other = driver.replay(engine, asks, after_dispatch=fp8_kv())
    read("fp8_kv", other, refs=driver.run_reference(params, cfg, other,
                                                    lengths),
         row_err=driver.own_row_errors(params, cfg, other))
    rounded = bf16_router_scores(samples)
    read("bf16_router_scores", rounded,
         row_err=driver.own_row_errors(params, cfg, rounded))
    # another model: the reference with a planted fault
    few = samples[:OTHER_MODEL_SAMPLES]
    for fault in OTHER_MODELS:
        as_it = driver.run_reference(params, cfg, few, lengths,
                                     faults=(fault,))
        rows = with_the_references_rows(few, as_it) \
            if fault == "bf16_norm_stats" else few
        read(fault, few, refs=as_it, row_err=driver.own_row_errors(
            params, cfg, rows, faults=(fault,)))
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
    window = int(cfg["sliding_window"])
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
            picked = (past + [r for r in first if not any(
                r is p for p in past)])[:driver.SAMPLE]
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
            for name in REPORTED:
                verdicts.pop(name)
            ok = ok and verdicts.pop("sound") \
                and not any(verdicts.values()) and all(guarded.values())
    finally:
        server.stop()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "command_a_controls.json"), "w") as f:
        json.dump(every, f)
    print("controls readings:", json.dumps(every), flush=True)
    print(json.dumps({"ok": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
