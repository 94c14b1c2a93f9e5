"""Bench-regression gate: the perf trajectory finally enforces something.

Compares the freshly-emitted ``BENCH_*.json`` artifacts of a CI bench run
against committed baselines and fails (exit 1) when any tracked metric
regresses by more than the threshold (default 25%, overridable with
``--threshold`` or the ``REPRO_BENCH_TOLERANCE`` env var, e.g. "0.4").
Also refuses a ``bench_summary.json`` containing failed benchmarks.

  python -m benchmarks.check_regression --baseline-dir .bench-baseline
  python -m benchmarks.check_regression --baseline-git HEAD   # via git show

Tracked metrics per artifact (direction-aware):

  BENCH_round_loop.json  session_us_per_round               (lower better)
  BENCH_scenarios.json   us_per_round per scenario          (lower better)
  BENCH_serving.json     tok_s per (n_slots, mode, n_adapters) (higher)
                         + Poisson-traffic tok_s / max_streams (higher)
                         and latency p50/p99 ms                (lower)
  BENCH_multihost.json   rounds_per_s per (mix_comm, grid size) and the
                         within-mode scale_vs_1p at N>1       (higher)
  BENCH_figs.json        absolute per-(p, method) accuracies of the
                         fig2/3/4 pass on the streaming data layer and
                         fig4's per-p LoRA/TAD-best accs      (higher)
  BENCH_control.json     FMMC spectral gap per graph family   (higher)
                         + closed-loop final loss per regime  (lower)

Baselines missing on either side are reported but never fail the gate
(a NEW artifact has no baseline yet; deleting one is caught by review).
Imports nothing heavy — the gate must run in milliseconds at the end of a
CI job.

Caveat the threshold encodes: tracked metrics are wall-clock, and the
committed baselines were measured on whatever box last regenerated them —
a runner-class machine differing from it by more than the band will fail
honestly-unchanged code. When that happens, regenerate the baselines from
a CI artifact of a known-good run (or widen ``REPRO_BENCH_TOLERANCE`` for
that runner class) rather than deleting the gate.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import Callable, Dict, Tuple

# metric value + direction: "lower" = regression when current > baseline,
# "higher" = regression when current < baseline
Metrics = Dict[str, Tuple[float, str]]


def _round_loop(doc) -> Metrics:
    return {"round_loop_session_us": (float(doc["session_us_per_round"]),
                                      "lower")}


def _scenarios(doc) -> Metrics:
    return {f"scenario_{row['scenario']}_us": (float(row["us_per_round"]),
                                               "lower")
            for row in doc.get("scenarios", [])}


def _serving(doc) -> Metrics:
    out: Metrics = {}
    for row in doc.get("rows", []):
        key = (f"serving_s{row['n_slots']}_{row['mode']}"
               f"{row['n_adapters']}_tok_s")
        out[key] = (float(row["tok_s"]), "higher")
    tr = doc.get("traffic")
    if tr:
        out["serving_traffic_tok_s"] = (float(tr["tok_s"]), "higher")
        out["serving_traffic_p50_ms"] = (float(tr["latency_p50_ms"]),
                                         "lower")
        out["serving_traffic_p99_ms"] = (float(tr["latency_p99_ms"]),
                                         "lower")
        out["serving_traffic_max_streams"] = (float(tr["max_streams"]),
                                              "higher")
    return out


def _multihost(doc) -> Metrics:
    out: Metrics = {}
    for row in doc.get("rows", []):
        n = row["n_processes"]
        mode = row.get("mix_comm")
        if mode is None:           # pre-mix_comm artifact (legacy baseline)
            out[f"multihost_{n}p_rounds_per_s"] = (
                float(row["rounds_per_s"]), "higher")
            continue
        quant = row.get("mix_quant", "off")
        if quant != "off":         # quantized rows track separately
            mode = f"{mode}_{quant}"
        out[f"multihost_{mode}_{n}p_rounds_per_s"] = (
            float(row["rounds_per_s"]), "higher")
        if n > 1 and "scale_vs_1p" in row:
            # within-mode scaling efficiency: losing it means the sparse
            # comm path stopped paying for itself, even if absolute
            # rounds/s moved for unrelated reasons
            out[f"multihost_{mode}_{n}p_scale_vs_1p"] = (
                float(row["scale_vs_1p"]), "higher")
    return out


def _figs(doc) -> Metrics:
    out: Metrics = {}
    for row in doc.get("fig2_rows", []):
        p = row["p"]
        for method, acc in row.items():
            if method == "p":
                continue
            out[f"figs_fig2_p{p}_{method}_acc"] = (float(acc), "higher")
    # the fig3 monotone-trend bit stays in the artifact for inspection but
    # is NOT a gated metric: it can legitimately be 0/False on the reduced
    # quick grid, and a zero can't anchor a ratio-based check
    for p, accs in doc.get("fig4_absolute", {}).items():
        out[f"figs_fig4_p{p}_lora_acc"] = (float(accs["lora_acc"]),
                                           "higher")
        out[f"figs_fig4_p{p}_tad_best_acc"] = (float(accs["tad_best_acc"]),
                                               "higher")
    return out


def _control(doc) -> Metrics:
    out: Metrics = {}
    for row in doc.get("families", []):
        out[f"control_fmmc_gap_{row['family']}"] = (float(row["fmmc_gap"]),
                                                    "higher")
    for row in doc.get("closed_loop", []):
        out[f"control_{row['regime']}_closed_loss"] = (
            float(row["closed_final_loss"]), "lower")
        out[f"control_{row['regime']}_oracle_loss"] = (
            float(row["oracle_final_loss"]), "lower")
    return out


TRACKED: Dict[str, Callable] = {
    "BENCH_round_loop.json": _round_loop,
    "BENCH_scenarios.json": _scenarios,
    "BENCH_serving.json": _serving,
    "BENCH_multihost.json": _multihost,
    "BENCH_figs.json": _figs,
    "BENCH_control.json": _control,
}


def compare(baseline: Metrics, current: Metrics,
            threshold: float) -> Tuple[list, list]:
    """-> (regressions, notes). A regression is a tracked metric moving
    past ``threshold`` in its bad direction; metrics present on only one
    side become notes."""
    regressions, notes = [], []
    for name, (base, direction) in sorted(baseline.items()):
        if name not in current:
            notes.append(f"metric {name} missing from current run")
            continue
        cur = current[name][0]
        if base <= 0:
            notes.append(f"metric {name} has non-positive baseline {base}")
            continue
        ratio = cur / base
        bad = ratio > 1.0 + threshold if direction == "lower" \
            else ratio < 1.0 - threshold
        if bad:
            regressions.append(
                f"{name}: {base:g} -> {cur:g} "
                f"({(ratio - 1.0) * 100.0:+.1f}%, allowed ±{threshold:.0%},"
                f" {direction} is better)")
    for name in sorted(set(current) - set(baseline)):
        notes.append(f"new metric {name} (no baseline yet)")
    return regressions, notes


def _load_json(path: str):
    with open(path) as f:
        return json.load(f)


def _load_baseline(name: str, baseline_dir: str, git_ref: str):
    if baseline_dir:
        path = os.path.join(baseline_dir, name)
        return _load_json(path) if os.path.exists(path) else None
    try:
        blob = subprocess.run(["git", "show", f"{git_ref}:{name}"],
                              capture_output=True, text=True, check=True)
        return json.loads(blob.stdout)
    except (subprocess.CalledProcessError, json.JSONDecodeError):
        return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline-dir", default="",
                    help="directory holding baseline BENCH_*.json (CI "
                         "snapshots the checkout before the bench run)")
    ap.add_argument("--baseline-git", default="HEAD",
                    help="git ref to read baselines from when no "
                         "--baseline-dir is given")
    ap.add_argument("--current-dir", default=".",
                    help="directory holding the fresh BENCH_*.json")
    ap.add_argument("--summary", default="",
                    help="bench_summary.json to refuse on failed entries")
    ap.add_argument("--artifacts", default="",
                    help="comma-separated BENCH_*.json names this job "
                         "actually regenerated; others are ignored (an "
                         "unscoped gate would 'verify' stale committed "
                         "artifacts against themselves)")
    ap.add_argument("--threshold",
                    type=float,
                    default=float(os.environ.get("REPRO_BENCH_TOLERANCE",
                                                 "0.25")),
                    help="allowed fractional slowdown (default 0.25)")
    args = ap.parse_args(argv)

    failures = []
    if args.summary and os.path.exists(args.summary):
        bad = [row["name"] for row in _load_json(args.summary)
               if row.get("failed")]
        if bad:
            failures.append(f"bench_summary has failed benchmarks: {bad}")

    tracked = dict(TRACKED)
    if args.artifacts:
        names = [n.strip() for n in args.artifacts.split(",") if n.strip()]
        unknown = [n for n in names if n not in TRACKED]
        if unknown:
            print(f"[gate] unknown artifact(s) {unknown}; "
                  f"tracked: {sorted(TRACKED)}", file=sys.stderr)
            return 2
        tracked = {n: TRACKED[n] for n in names}

    checked = 0
    for name, extract in tracked.items():
        cur_path = os.path.join(args.current_dir, name)
        if not os.path.exists(cur_path):
            print(f"[gate] {name}: not produced by this run — skipped")
            continue
        base_doc = _load_baseline(name, args.baseline_dir, args.baseline_git)
        if base_doc is None:
            print(f"[gate] {name}: no committed baseline — skipped")
            continue
        regressions, notes = compare(extract(base_doc),
                                     extract(_load_json(cur_path)),
                                     args.threshold)
        checked += 1
        for note in notes:
            print(f"[gate] {name}: {note}")
        if regressions:
            failures.append(f"{name}:\n  " + "\n  ".join(regressions))
        else:
            print(f"[gate] {name}: OK "
                  f"(within ±{args.threshold:.0%})")

    if checked == 0:
        # a gate that watched nothing must not go green: a typo'd
        # --baseline-dir or a bench step writing elsewhere would otherwise
        # pass vacuously (the --only lesson, applied here)
        failures.append(
            "0 artifacts checked — no tracked BENCH_*.json had both a "
            "current file and a baseline (check --baseline-dir / "
            "--current-dir / --artifacts)")
    if failures:
        print("\nBENCH REGRESSION GATE FAILED\n" + "\n".join(failures),
              file=sys.stderr)
        return 1
    print(f"[gate] passed ({checked} artifacts checked)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
