"""One process, one cell, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds a TPU with the chips the cell asks for or exits non-zero (there is no
CPU mode), builds weights on the device and traffic from ``--seed``, warms
the cell's own shapes (JAX's persistent cache at its fixed in-checkout path),
measures for ``--seconds`` and prints, last, the one JSON object of the
contract.  ``--trace 0`` reports the cell's end-to-end metrics with the
profiler off; ``--trace 1`` is a run of its own that reports the per-layer
metrics and the breakdown.  Either prints a ``bench time:`` line: where the
run's wall time went, phase by phase, against the limit the driver stops a
run at (PERF.md, section 2).
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, trace_reduce  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        manifest = harness.load_manifest(ROOT)
        faults = harness.check_manifest(ROOT, manifest)
        if faults:
            raise harness.ConfigurationError("; ".join(faults))
        cell = harness.Cell(ROOT, manifest, args.workload)
        driver = cell.driver()
        driver.validate(cell, args.seconds)
    except harness.ConfigurationError as e:
        print(f"bench: configuration error: {e}", file=sys.stderr)
        return 2
    try:
        import paddle_tpu  # noqa: F401  the system under test
    except ImportError as e:
        print(f"bench: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: cell {cell.name!r} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} device(s) of platform "
              f"{devices[0].platform!r} — there is no CPU mode",
              file=sys.stderr)
        return 2
    from benchmark import peaks
    peaks.peaks_for(devices[0].device_kind)     # an unknown device is an error
    from paddle_tpu.core import compile_cache
    cache_dir = compile_cache.wire_jax_cache()
    # every program, however small, goes to the persistent cache, so that a
    # run after the first in a checkout loads all of them and compiles none
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log = harness.CompileLog()
    print(f"bench: cell {cell.name} = {cell.config_name} x {cell.mix_name}, "
          f"{cell.chips} chip(s) of {devices[0].device_kind!r}, seed "
          f"{args.seed}, {args.seconds} s, trace {args.trace}; jax cache at "
          f"{cache_dir}", flush=True)

    out = driver.run(cell, args, log, T_PROCESS_START, devices)
    acct, checks = out["acct"], out["checks"]
    # every end-to-end number the driver took, whichever of them the manifest
    # has this cell report: the others are there to be read in the log
    print("bench values:", json.dumps(out["values"], default=float),
          flush=True)
    print(acct.line(), flush=True)
    for ex in acct.examples:
        print("bench failure example:", ex, flush=True)
    for line in checks.lines():
        print(line, flush=True)
    print(f"bench compile log: {log.compiles} backend compile(s) in "
          f"{log.compile_s:.1f} s, {log.cache_hits} persistent-cache hit(s)",
          flush=True)
    device = dict(out["device"])
    phases = out["phases"]
    breakdown = None
    if args.trace:
        summary = out["summary"]
        if not summary:
            print("bench: the trace held no device plane or no window span",
                  file=sys.stderr)
            return 1
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = trace_reduce.breakdown(summary)
        values = harness.read_per_layer(cell, out["ctx"], phases)
        metrics = harness.select_metrics(cell.per_layer, values)
    else:
        metrics = harness.select_metrics(cell.end_to_end, out["values"])
    print(phases.line(), flush=True)
    # what was compared, beside its limit, where the driver's record of a run
    # that is not correct keeps it: the end of standard error, the result line
    for line in checks.lines():
        print(line, file=sys.stderr, flush=True)
    print(harness.result_line(checks.ok, acct, metrics, device, breakdown,
                              checks), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
