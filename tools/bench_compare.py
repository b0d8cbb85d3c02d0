"""Bench regression gate: structured comparison of two BENCH rounds.

Compares two bench.py summary JSONs (raw summary lines, or the driver's
``BENCH_r*.json`` wrapper whose ``tail`` holds the summary as its last
JSON line) per config, with noise bands:

    python tools/bench_compare.py BENCH_old.json BENCH_new.json
    python tools/bench_compare.py old.json new.json --threshold 0.15
    python tools/bench_compare.py --find-baseline .   # newest measured round

Per config the HEADLINE metric (first of images/sec, tokens/sec,
samples/sec, tflops, ... present in BOTH rounds) is compared as a
relative delta.  Deltas beyond ``--threshold`` (default 10%, a
single-run band that ROADMAP S2(e) replaces with a paired-run rule)
classify as regression/improvement; inside it, within-noise.  Skip/error/analysis tags from the orchestrator are
honored: a config skipped in either round is reported but NEVER counted
as a regression, and analysis-only entries (``analysis: true`` —
cost-model numbers, not on-chip wall time) are compared informationally
but excluded from the verdict.  Exit code: 0 when no regression, 1 on
any regression beyond the band, 2 when a round cannot be loaded —
so CI and the bench orchestrator (which records the verdict in its
summary JSON) can gate on it.

Stdlib only — no paddle_tpu import needed.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import List, Optional

# the frozen surface (tools/api_spec.txt): like cache_admin, the spec
# generator only sees functions listed here for non-package modules
__all__ = ["load_round", "measured_configs", "find_baseline", "compare",
           "render_text", "main"]

# headline throughput keys, in priority order; the first key present in
# BOTH rounds' config dicts is the compared metric (higher is better
# unless listed in LOWER_BETTER_KEYS)
METRIC_KEYS = (
    "images_per_sec",
    "tokens_per_sec",
    "samples_per_sec",
    "fused_samples_per_sec",
    "tflops",
    "implied_sp4_tokens_per_sec_per_device",
    "batched_storm_vars_per_sec",
    "batched_dense_mb_per_sec",
    "batched_qps",
    "decode_tokens_per_sec",
    "pipeline_samples_per_sec",
    "cold_vs_warm_speedup",
    "eff_flops",
    "pipeline_vs_link",
    "ckpt_overhead_frac",
    "recovery_mttr_s",
    "decode_ttft_ms_p99",
)

# cost-style headlines where SMALLER is the good direction (e.g. the
# async-snapshot step-loop overhead fraction): the delta sign flips for
# classification, the reported delta stays raw
LOWER_BETTER_KEYS = frozenset({"ckpt_overhead_frac", "recovery_mttr_s",
                               "decode_ttft_ms_p99", "canary_failures",
                               "kv_bytes_per_token",
                               "quant_accuracy_delta"})

# lower-better keys in ABSOLUTE units (seconds, not a fraction): their
# delta is relative when the baseline is positive — a 3 s -> 3.5 s MTTR
# drift is a 17% regression, while fraction keys (legitimately-0.0
# baselines) keep absolute-delta comparison
LOWER_BETTER_RELATIVE_KEYS = frozenset({"recovery_mttr_s",
                                        "decode_ttft_ms_p99"})

# tail-latency keys gated IN ADDITION to a config's headline: a round
# whose decode throughput held but whose TTFT p99 doubled must still
# read regression.  Each secondary present in BOTH rounds gets its own
# "<config>:<key>" entry with the same classification machinery.
# canary_failures rides the same gate: a round that got FASTER while
# the in-window golden canary started mismatching is a correctness
# regression, not a win.  prefix_hit_rate (higher-better, decode_prefix
# config) gates the same way: a dedup hit-rate collapse is a capacity
# regression even when the round's throughput happened to hold
SECONDARY_GATE_KEYS = ("decode_ttft_ms_p99", "canary_failures",
                       "prefix_hit_rate", "quant_accuracy_delta")

# informational keys carried through the comparison WITHOUT gating:
# recorded per config when present in either round (the evidence
# chain keeps capacity headroom + canary probe cost round-over-round),
# never classified, never part of the verdict
INFORMATIONAL_KEYS = ("headroom_frac", "canary_overhead_frac",
                      "kv_bytes_per_token", "unattributed_bytes")

DEFAULT_THRESHOLD = 0.10

# configs that are analysis-only BY NATURE (cost-model numbers): rounds
# older than the orchestrator's explicit ``analysis: true`` tagging
# carry them untagged, and an "all-skip except the cost model" round
# must not read as the last measured baseline
KNOWN_ANALYSIS_CONFIGS = frozenset({"scaling_dp8"})


def _is_analysis(name: str, cfg) -> bool:
    return bool(isinstance(cfg, dict) and cfg.get("analysis")) or \
        name in KNOWN_ANALYSIS_CONFIGS


def load_round(path: str) -> dict:
    """A bench summary dict from ``path``: either a raw summary JSON
    (has ``configs``) or the driver wrapper whose ``tail`` string holds
    the summary as its last parseable JSON line.  Raises ValueError
    when no summary is found (e.g. a timed-out round)."""
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "configs" in doc:
        return doc
    tail = doc.get("tail", "") if isinstance(doc, dict) else ""
    for line in reversed(tail.splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            cand = json.loads(line)
        except ValueError:
            continue
        if isinstance(cand, dict) and "configs" in cand:
            return cand
    raise ValueError(f"no bench summary (a 'configs' JSON) in {path}")


def _not_measured(cfg) -> Optional[str]:
    """Why a config record carries no measured number ('' = measured)."""
    if not isinstance(cfg, dict):
        return "malformed"
    if cfg.get("skipped"):
        return f"skipped: {cfg['skipped']}"
    if cfg.get("error"):
        return f"error: {cfg['error']}"
    return None


def _headline(old_cfg: dict, new_cfg: dict):
    for key in METRIC_KEYS:
        ov, nv = old_cfg.get(key), new_cfg.get(key)
        if isinstance(ov, (int, float)) and isinstance(nv, (int, float)):
            return key, float(ov), float(nv)
    return None, None, None


def measured_configs(summary: dict) -> List[str]:
    """Config names with a real on-chip measurement this round (not
    skipped/error/analysis, and carrying a headline metric)."""
    out = []
    for name, cfg in (summary.get("configs") or {}).items():
        if _not_measured(cfg) or not isinstance(cfg, dict) \
                or _is_analysis(name, cfg):
            continue
        if any(isinstance(cfg.get(k), (int, float)) for k in METRIC_KEYS):
            out.append(name)
    return sorted(out)


def find_baseline(dirname: str,
                  exclude: Optional[str] = None) -> Optional[str]:
    """Newest ``BENCH_r*.json`` under ``dirname`` that holds >= 1
    measured config — the last non-analysis round (an all-skip or
    timed-out round is passed over)."""
    paths = sorted(glob.glob(os.path.join(dirname, "BENCH_r*.json")),
                   reverse=True)
    for path in paths:
        if exclude and os.path.abspath(path) == os.path.abspath(exclude):
            continue
        try:
            summary = load_round(path)
        except (OSError, ValueError):
            continue
        if measured_configs(summary):
            return path
    return None


def compare(old: dict, new: dict,
            threshold: float = DEFAULT_THRESHOLD) -> dict:
    """Per-config delta classification of two summary dicts.

    Returns ``{"verdict", "threshold", "regressions", "improvements",
    "within_noise", "incomparable", "configs": {name: entry}}`` where
    each entry carries the compared metric, both values, the relative
    delta, and its classification.  Analysis-tagged configs compare
    informationally (``analysis: true``) and never drive the verdict.
    """
    old_cfgs = old.get("configs") or {}
    new_cfgs = new.get("configs") or {}
    out = {"threshold": threshold, "configs": {},
           "regressions": [], "improvements": [], "within_noise": [],
           "incomparable": []}
    for name in sorted(set(old_cfgs) | set(new_cfgs)):
        oc, nc = old_cfgs.get(name), new_cfgs.get(name)
        ent = {}
        why = None
        if oc is None:
            why = "new config (no baseline entry)"
        elif nc is None:
            why = "config absent from the new round"
        elif _not_measured(oc):
            why = f"baseline {_not_measured(oc)}"
        elif _not_measured(nc):
            why = f"new {_not_measured(nc)}"
        key = ov = nv = None
        if not why:
            key, ov, nv = _headline(oc, nc)
            if key is None:
                why = "no shared headline metric"
            elif (not ov or ov <= 0) and key not in LOWER_BETTER_KEYS:
                # a zero/negative baseline is a broken round, not a
                # clean within-noise verdict — surface, don't launder.
                # (Lower-better FRACTIONS compare by absolute delta, so
                # a 0.0 baseline there is legitimate — and excellent.)
                why = f"degenerate baseline value {key}={ov!r}"
        if why:
            ent["status"] = "incomparable"
            ent["reason"] = why
            out["incomparable"].append(name)
            out["configs"][name] = ent
            continue
        analysis = _is_analysis(name, oc) or _is_analysis(name, nc)
        _classify(out, name, ent, key, ov, nv, threshold, analysis)
        # informational carry-through: recorded, never classified
        for ikey in INFORMATIONAL_KEYS:
            iov, inv = oc.get(ikey), nc.get(ikey)
            if isinstance(iov, (int, float)) or \
                    isinstance(inv, (int, float)):
                ent.setdefault("info", {})[ikey] = {"old": iov,
                                                    "new": inv}
        # tail-latency secondaries gate NEXT TO the headline: a config
        # whose throughput held but whose TTFT p99 blew out must still
        # read regression (entries keyed "<config>:<metric>")
        for skey in SECONDARY_GATE_KEYS:
            if skey == key:
                continue
            sov, snv = oc.get(skey), nc.get(skey)
            if isinstance(sov, (int, float)) and \
                    isinstance(snv, (int, float)):
                _classify(out, f"{name}:{skey}", {}, skey,
                          float(sov), float(snv), threshold, analysis)
    out["verdict"] = "regression" if out["regressions"] else (
        "ok" if out["within_noise"] or out["improvements"] else "empty")
    return out


def _classify(out: dict, name: str, ent: dict, key: str,
              ov: float, nv: float, threshold: float,
              analysis: bool) -> None:
    """Delta + status for one (config, metric) pair, filed into the
    comparison dict (shared by headline and secondary-gate entries)."""
    if key in LOWER_BETTER_KEYS:
        # cost headline: sign flipped so "delta below -threshold"
        # still reads regression downstream; fractions compare by
        # absolute delta (0.0 baselines are legitimate), absolute-
        # unit keys (seconds/ms) relatively when the baseline allows
        if key in LOWER_BETTER_RELATIVE_KEYS and ov > 0:
            delta = -(nv - ov) / ov
        else:
            delta = -(nv - ov)
    else:
        delta = (nv - ov) / ov
    ent.update({"metric": key, "old": ov, "new": nv,
                "delta": round(delta, 4)})
    if key in LOWER_BETTER_KEYS:
        ent["lower_better"] = True
        ent["delta_abs"] = round(nv - ov, 4)
    if analysis:
        ent["analysis"] = True
    if delta < -threshold:
        ent["status"] = "regression"
    elif delta > threshold:
        ent["status"] = "improvement"
    else:
        ent["status"] = "within_noise"
    # analysis entries inform, never gate
    if analysis and ent["status"] == "regression":
        ent["status"] = "regression_analysis_only"
        out["within_noise"].append(name)
    else:
        out[{"regression": "regressions",
             "improvement": "improvements",
             "within_noise": "within_noise"}[ent["status"]]
            ].append(name)
    out["configs"][name] = ent


def render_text(cmp: dict) -> str:
    lines = [f"bench compare (threshold ±{cmp['threshold'] * 100:.0f}%): "
             f"verdict={cmp['verdict']}"]
    order = {"regression": 0, "regression_analysis_only": 1,
             "improvement": 2, "within_noise": 3, "incomparable": 4}
    items = sorted(cmp["configs"].items(),
                   key=lambda kv: (order.get(kv[1].get("status"), 9),
                                   kv[0]))
    for name, ent in items:
        if ent.get("status") == "incomparable":
            lines.append(f"  {name}: incomparable ({ent['reason']})")
            continue
        tag = " [analysis]" if ent.get("analysis") else ""
        lines.append(
            f"  {name}: {ent['status']}{tag}  {ent['metric']} "
            f"{ent['old']:g} -> {ent['new']:g} "
            f"({ent['delta'] * 100:+.1f}%)")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="compare two bench rounds; exit 1 on regressions "
                    "beyond the noise band")
    ap.add_argument("old", nargs="?", help="baseline round JSON")
    ap.add_argument("new", nargs="?", help="new round JSON")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="relative noise band (default 0.10 = ±10%%)")
    ap.add_argument("--json", action="store_true",
                    help="emit the comparison as JSON")
    ap.add_argument("--find-baseline", metavar="DIR",
                    help="print the newest measured BENCH_r*.json under "
                         "DIR and exit (what the orchestrator "
                         "auto-compares against)")
    args = ap.parse_args(argv)

    if args.find_baseline:
        path = find_baseline(args.find_baseline)
        if not path:
            print("no measured round found", file=sys.stderr)
            return 2
        print(path)
        return 0
    if not args.old or not args.new:
        ap.error("OLD and NEW round paths are required")
    try:
        old = load_round(args.old)
        new = load_round(args.new)
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    cmp = compare(old, new, threshold=args.threshold)
    sys.stdout.write(json.dumps(cmp, indent=2) + "\n" if args.json
                     else render_text(cmp))
    return 1 if cmp["verdict"] == "regression" else 0


if __name__ == "__main__":
    sys.exit(main())
