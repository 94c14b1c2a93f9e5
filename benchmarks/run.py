"""Benchmark harness entry point — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # quick mode
  PYTHONPATH=src python -m benchmarks.run --paper    # full sweeps
  PYTHONPATH=src python -m benchmarks.run --only fig2,theory
  PYTHONPATH=src python -m benchmarks.run --json out.json   # machine-readable

Each module prints its own table and returns a result dict; a final
``name,us_per_call,derived`` CSV line per benchmark summarizes wall time
and the headline derived quantity. ``--json`` additionally writes the
summary rows as ``[{name, us, headline, failed}]``; the "kernels" bench
also records its kernel timings to ``--mixing-json``
(BENCH_mixing.json by default).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

BENCHES = ("fig2", "table1", "fig3", "fig4", "figs", "table3", "table5",
           "theory", "adaptive", "kernels", "roofline", "round_loop",
           "scenarios", "serving", "multihost", "control")


def _headline(name: str, result) -> str:
    try:
        if name == "fig2":
            return f"tad_gain_vs_rolora_weak={result['tad_gain_vs_rolora_weak']:+.4f}"
        if name == "table1":
            return f"weak_best={result['weak_best']}"
        if name == "fig3":
            return f"tstar_monotone={result['monotone_trend']}"
        if name == "fig4":
            vals = list(result["grid"].values())
            return f"max_gain={max(vals):+.4f}"
        if name == "figs":
            return (f"tad_gain_weak={result['fig2_tad_gain_vs_rolora_weak']:+.4f},"
                    f"tstar_monotone={result['fig3_monotone_trend']}")
        if name == "table5":
            return f"tad_ring_avg={result['tad']['avg']:.4f}"
        if name == "table3":
            return f"weak_best={result['best']}"
        if name == "theory":
            return (f"cross_1/T={result['cross_decreases_with_T']},"
                    f"cross_vs_p={result['cross_grows_as_p_shrinks']}")
        if name == "adaptive":
            worst = min(v["adaptive"] - v["fixed_T1"]
                        for v in result.values())
            return f"adaptive_vs_T1_worstcase={worst:+.4f}"
        if name == "kernels":
            return f"n_kernels={len(result)}"
        if name == "roofline":
            ok = sum(1 for v in result.values() if v == "ok")
            return f"combos_ok={ok}"
        if name == "round_loop":
            return f"session_overhead={result['overhead_pct']:+.2f}%"
        if name == "scenarios":
            rps = [r["rounds_per_s"] for r in result["scenarios"]]
            return (f"n_scenarios={len(rps)},min_rps={min(rps):.0f},"
                    f"one_compile={result['one_compiled_round']}")
        if name == "serving":
            ovs = [r["overhead_vs_merged_pct"] for r in result["rows"]
                   if r["mode"] == "multi"]
            return (f"multi_vs_merged_worst={max(ovs):+.1f}%,"
                    f"one_compile={result['one_compile']}")
        if name == "multihost":
            rps = {r["n_processes"]: r["rounds_per_s"]
                   for r in result["rows"]}
            return (f"rps_1p={rps.get(1, 0):.1f},rps_2p={rps.get(2, 0):.1f},"
                    f"rps_4p={rps.get(4, 0):.1f},"
                    f"parity={result['loss_parity_across_grids']}")
        if name == "control":
            worst = min(r["fmmc_gap"] - r["metropolis_gap"]
                        for r in result["families"])
            return (f"fmmc_gain_min={worst:+.4f},"
                    f"within_5pct={result['all_within_5pct']}")
    except Exception:
        pass
    return "done"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true",
                    help="full sweeps (slower; paper-scale grids)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of: " + ",".join(BENCHES))
    ap.add_argument("--json", default="",
                    help="write per-benchmark summary rows to this path")
    ap.add_argument("--mixing-json", default="BENCH_mixing.json",
                    help="where the kernels bench records its "
                         "timings ('' disables)")
    ap.add_argument("--round-loop-json", default="BENCH_round_loop.json",
                    help="where the round_loop bench records the Session "
                         "overhead trajectory ('' disables)")
    ap.add_argument("--scenarios-json", default="BENCH_scenarios.json",
                    help="where the scenarios bench records per-scenario "
                         "throughput ('' disables)")
    ap.add_argument("--serving-json", default="BENCH_serving.json",
                    help="where the serving bench records multi-adapter "
                         "decode throughput ('' disables)")
    ap.add_argument("--multihost-json", default="BENCH_multihost.json",
                    help="where the multihost bench records process-grid "
                         "throughput ('' disables)")
    ap.add_argument("--figs-json", default="BENCH_figs.json",
                    help="where the figs bench records the fig2/3/4 "
                         "accuracy trajectory ('' disables)")
    ap.add_argument("--control-json", default="BENCH_control.json",
                    help="where the control bench records the closed-loop "
                         "and FMMC-gap trajectory ('' disables)")
    args = ap.parse_args()
    quick = not args.paper
    selected = [b.strip() for b in args.only.split(",") if b.strip()] \
        or list(BENCHES)
    # a typo'd --only must fail loudly, not pass vacuously: validate
    # BEFORE the (slow) benchmark imports so CI steps die in milliseconds
    unknown = [b for b in selected if b not in BENCHES]
    if unknown:
        print(f"unknown benchmark(s) {', '.join(map(repr, unknown))}; "
              f"known: {','.join(BENCHES)}", file=sys.stderr)
        sys.exit(2)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (adaptive_t, control, fig2_acc_vs_p, fig3_tstar,
                            fig4_heatmap, figs, kernel_micro, multihost,
                            roofline_report, round_loop, scenarios, serving,
                            table1_regimes, table3_weak_avg, table5_ring,
                            theory_crossterm)
    mods = {"fig2": fig2_acc_vs_p, "table1": table1_regimes,
            "fig3": fig3_tstar, "fig4": fig4_heatmap, "figs": figs,
            "table3": table3_weak_avg, "table5": table5_ring,
            "theory": theory_crossterm, "adaptive": adaptive_t,
            "kernels": kernel_micro, "roofline": roofline_report,
            "round_loop": round_loop, "scenarios": scenarios,
            "serving": serving, "multihost": multihost, "control": control}

    csv_rows = []
    json_rows = []
    failed = []
    for name in selected:
        print(f"\n{'='*70}\n## {name}  ({mods[name].__doc__.splitlines()[0]})"
              f"\n{'='*70}", flush=True)
        kwargs = {}
        if name == "kernels" and args.mixing_json:
            kwargs["json_path"] = args.mixing_json
        if name == "round_loop" and args.round_loop_json:
            kwargs["json_path"] = args.round_loop_json
        if name == "scenarios" and args.scenarios_json:
            kwargs["json_path"] = args.scenarios_json
        if name == "serving" and args.serving_json:
            kwargs["json_path"] = args.serving_json
        if name == "multihost" and args.multihost_json:
            kwargs["json_path"] = args.multihost_json
        if name == "figs" and args.figs_json:
            kwargs["json_path"] = args.figs_json
        if name == "control" and args.control_json:
            kwargs["json_path"] = args.control_json
        t0 = time.time()
        try:
            result = mods[name].run(quick=quick, **kwargs)
            us = (time.time() - t0) * 1e6
            headline = _headline(name, result)
            csv_rows.append(f"{name},{us:.0f},{headline}")
            json_rows.append({"name": name, "us": round(us),
                              "headline": headline, "failed": False})
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failed.append(name)
            csv_rows.append(f"{name},0,FAILED:{type(e).__name__}")
            json_rows.append({"name": name, "us": 0,
                              "headline": f"FAILED:{type(e).__name__}",
                              "failed": True})

    print(f"\n{'='*70}\n## summary (name,us_per_call,derived)\n{'='*70}")
    for row in csv_rows:
        print(row)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(json_rows, f, indent=1)
        print(f"wrote {args.json}")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
