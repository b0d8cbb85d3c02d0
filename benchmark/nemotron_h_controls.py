"""Controls of the ``nemotron_h_serve`` driver's reference comparison: the
engine's own programs, run once as they are, then with a part of the state or
of the arithmetic kept in a precision below the one the configuration states,
and with a planted mechanism that the model does NOT have, each through the
driver's ``replay`` / ``run_reference`` / ``readings`` / ``judge`` on the same
requests and tokens.  The sound program must come out correct and every
control NOT correct, by the limit that guards it (PERF.md section 6 has every
reading).

    python3 benchmark/nemotron_h_controls.py --workload <cell> --seeds <a,b>

Lower precision (what the precision limits stand between):

- **bf16 recurrent rows**: after every dispatch every Mamba layer's
  recurrent rows are rounded to bfloat16's widths where the configuration
  states float32.  Guarded by ``state_bf16_share`` (after 64 steps the
  roundings are 0.002 beside the 0.011 that bf16 activations put into every
  row: ``state_err_p50`` reads 0.0136 against a sound 0.0117).
- **bf16 step size and decay**: the reference carries ``Δ`` and ``exp(Δ A)``
  with bfloat16's 8 bits where the configuration states float32 (the
  reference in the nearest precision below: the distance is the same from
  either side).  Guarded by ``state_err_p50``.
- **bf16 router scores**: the router's logits as the programs returned them
  at the judged rows, rounded to bfloat16, where the configuration states
  float32.  Guarded by ``router_score_err_max``.
- **8-bit pool**: after every dispatch the K/V pool is rounded to e4m3's
  widths where the configuration states bf16's (two of thirteen layers read
  it, so the logits see little of it).  Guarded by ``pool_err_max``.
- **8-bit tails**: the convolution tails rounded to e4m3's widths after
  every dispatch.  Guarded by ``tail_err_max``.

Another model, the plain reference with a planted mechanism
(``benchmark/reference/nemotron_h.py FAULTS``), on
:data:`OTHER_MODEL_SAMPLES` of the samples — another model moves every
sample:

- **a SiLU-gated unit in the experts' place** (``silu(h) ⊙ h`` for
  ``relu(h)²``).  Guarded by ``expert_out_err_p90``.
- **rotation on the attention layers** (rotate-half at ``rope_theta``).
- **the norm before the gate**; **one norm group for eight**; **D left
  out**.  Each guarded by ``logit_err_prefill_max``.
- **another stream's token**: one judged token of one sample replaced by the
  next sample's token at that step (no program runs).  Guarded by
  ``token_gap_max``.

Needs a TPU, as ``run.py`` does;
``tests/benchmark/test_benchmark_nemotron_h.py`` drives the same functions at
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
GUARDS = {"bf16_recurrent_rows": "state_bf16_share",
          "bf16_step": "state_err_p50",
          "bf16_router_scores": "router_score_err_max",
          "fp8_pool": "pool_err_max",
          "fp8_tails": "tail_err_max",
          "silu_unit": "expert_out_err_p90",
          "rotate_attention": "logit_err_prefill_max",
          "norm_before_gate": "logit_err_prefill_max",
          "one_norm_group": "logit_err_prefill_max",
          "no_skip": "logit_err_prefill_max",
          "another_streams_token": "token_gap_max"}
# the controls that are the plain reference as another model (or in a lower
# precision), and how many of the samples each is run on
OTHER_MODELS = ("bf16_step", "silu_unit", "rotate_attention",
                "norm_before_gate", "one_norm_group", "no_skip")
OTHER_MODEL_SAMPLES = 4
# state: [kv pool, recurrent rows, tails]
ROUNDED = {"bf16_recurrent_rows": ((1,), 8, 7), "fp8_pool": ((0,), 4, 3),
           "fp8_tails": ((2,), 4, 3)}


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
    for name, (which, exponent, mantissa) in ROUNDED.items():
        other = driver.replay(engine, asks, after_dispatch=_rounder(
            which, exponent, mantissa))
        # the program's own choices moved with its state: the reference is
        # given THESE
        read(name, other,
             refs=driver.run_reference(params, cfg, other, lengths),
             router_err=driver.router_errors(params, cfg, other))
    rounded = bf16_router_scores(samples)
    read("bf16_router_scores", rounded,
         router_err=driver.router_errors(params, cfg, rounded))
    few = samples[:OTHER_MODEL_SAMPLES]
    few_err = driver.router_errors(params, cfg, few)
    for fault in OTHER_MODELS:
        read(fault, few, refs=driver.run_reference(
            params, cfg, few, lengths, faults=(fault,)), router_err=few_err)
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
                           "nemotron_h_controls.json"), "w") as f:
        json.dump(every, f)
    print("controls readings:", json.dumps(every), flush=True)
    print(json.dumps({"ok": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
