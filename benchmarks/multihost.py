"""Multi-process DFL throughput: dense vs topology-sparse vs overlapped
vs int8-quantized overlapped gossip.

Spawns ``repro.launch.cluster --simulate N`` for N in {1, 2, 4} local CPU
processes (gloo collectives) for each ``mix_comm`` lowering on one shared
`DFLConfig` (m = 8 clients on a static ring — the shape where sparse
gossip matters). Per (mode, grid) row: steady-state rounds/s (compile and
the first rounds excluded via ``--warmup``), the measured per-round
collective payload (`comm_bytes_per_round`, with the dense and sparse
figures side by side), and the final loss. ``scale_vs_1p`` is WITHIN-mode:
rounds/s of the N-process grid over the same mode's 1-process grid, so it
isolates the cost of running the real cross-process collective path
against identical arithmetic.

On a single CPU box the grids share the same silicon, so scale_vs_1p ≤ 1
by construction; the gap to 1.0 is pure multi-process overhead (gloo
exchange + per-process dispatch + cache pressure). The sparse/overlap
lowerings exist to shrink exactly that gap, and the CI regression gate
pins both rounds/s and scale_vs_1p per (mode, grid).

Parity columns: dense and sparse are bit-for-bit the SAME algorithm, so
their final losses must agree across every grid AND with each other
(`loss_parity_across_grids`); sparse_overlap is a different (one-round-
delayed) algorithm whose semantics are process-count independent, so its
losses must agree across grids but not with dense
(`overlap_parity_across_grids`).
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

PROC_GRID = (1, 2, 4)
# (mix_comm, mix_quant) per benched lowering; int8 rides the overlap
# halo — the bandwidth-bound configuration compression exists for
MODES = (("dense", "off"), ("sparse", "off"), ("sparse_overlap", "off"),
         ("sparse_overlap", "int8"))
M = 8
WARMUP = 2

# Heavy enough that a round's arithmetic dominates per-round dispatch
# (local_steps=1 folds the whole local batch into one scan step — many
# small steps quadruple the per-step dispatch cost at 4 processes).
CONFIG = dict(
    model="encoder", task="sst2",
    model_kw={"n_layers": 2, "d_model": 128, "n_heads": 4, "d_ff": 256,
              "vocab_size": 256},
    n_clients=M, topology="ring", scenario="static",
    local_steps=1, batch_size=64, p=0.5, T=2, lr=1e-3, seed=0,
)


def _run_grid(n: int, mode: str, quant: str, rounds: int, tmp: str) -> dict:
    from repro.launch.cluster import failed_ranks, spawn_simulated

    cfg_path = os.path.join(tmp, f"cfg_{mode}_{quant}_{n}.json")
    out_path = os.path.join(tmp, f"grid_{mode}_{quant}_{n}.json")
    with open(cfg_path, "w") as f:
        json.dump(dict(CONFIG, rounds=rounds, mix_comm=mode,
                       mix_quant=quant), f)
    results = spawn_simulated(n, [
        "--config", cfg_path, "--warmup", str(WARMUP),
        "--json", out_path, "--quiet"])
    failed = failed_ranks(results)
    if failed:
        raise RuntimeError(
            f"{mode} {n}-process grid failed:\n" +
            "\n".join(report for _, report in failed))
    with open(out_path) as f:
        return json.load(f)


def run(quick: bool = True, json_path: str | None = None) -> dict:
    rounds = 8 if quick else 24
    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        for mode, quant in MODES:
            for n in PROC_GRID:
                payload = _run_grid(n, mode, quant, rounds, tmp)
                rows.append({
                    "n_processes": n,
                    "mix_comm": mode,
                    "mix_quant": quant,
                    "clients_per_process": payload["clients_per_process"],
                    "rounds_per_s": payload["rounds_per_s"],
                    "us_per_round": round(1e6 / payload["rounds_per_s"], 1),
                    "comm_bytes_per_round":
                        payload["comm_bytes_per_round"],
                    "dense_comm_bytes_per_round":
                        payload["dense_comm_bytes_per_round"],
                    "sparse_comm_bytes_per_round":
                        payload["sparse_comm_bytes_per_round"],
                    "sparse_quant_comm_bytes_per_round":
                        payload["sparse_quant_comm_bytes_per_round"],
                    "final_loss": payload["final_loss"],
                })

    # within-mode scaling: N-process rounds/s over the SAME lowering at 1p
    base = {(row["mix_comm"], row["mix_quant"]): row["rounds_per_s"]
            for row in rows if row["n_processes"] == 1}
    for row in rows:
        row["scale_vs_1p"] = round(
            row["rounds_per_s"] / base[row["mix_comm"], row["mix_quant"]], 3)

    # dense == sparse is an algorithm identity: one loss across both modes
    # and every grid. sparse_overlap is delayed gossip: grid-invariant but
    # legitimately different from dense. Quantized overlap is yet another
    # algorithm (EF residual), also grid-invariant by per-row quantization.
    exact = {row["final_loss"] for row in rows
             if row["mix_comm"] in ("dense", "sparse")}
    overlap = {row["final_loss"] for row in rows
               if row["mix_comm"] == "sparse_overlap"
               and row["mix_quant"] == "off"}
    quant_losses = {row["final_loss"] for row in rows
                    if row["mix_quant"] != "off"}
    parity = len(exact) == 1
    overlap_parity = len(overlap) == 1
    quant_parity = len(quant_losses) == 1

    # compression headline at the multi-process grids: quantized halo
    # bytes over the fp32 sparse halo (1B payload + 4B row scale vs 4B/el)
    quant_rows = [r for r in rows
                  if r["mix_quant"] != "off" and r["n_processes"] > 1]
    quant_bytes_ratio = max(
        (r["comm_bytes_per_round"] / r["sparse_comm_bytes_per_round"]
         for r in quant_rows), default=0.0)
    scale_4p = {(r["mix_comm"], r["mix_quant"]): r["scale_vs_1p"]
                for r in rows if r["n_processes"] == PROC_GRID[-1]}
    quant_scale_ratio_4p = round(
        scale_4p.get(("sparse_overlap", "int8"), 0.0)
        / max(scale_4p.get(("sparse_overlap", "off"), 1.0), 1e-9), 3)

    result = {
        "backend": "cpu",
        "m": M,
        "rounds": rounds,
        "warmup": WARMUP,
        "topology": CONFIG["topology"],
        "scenario": CONFIG["scenario"],
        "config": dict(CONFIG, rounds=rounds),
        "loss_parity_across_grids": parity,
        "overlap_parity_across_grids": overlap_parity,
        "quant_parity_across_grids": quant_parity,
        "quant_bytes_ratio": round(quant_bytes_ratio, 4),
        "quant_scale_ratio_4p": quant_scale_ratio_4p,
        "rows": rows,
    }
    print("\n=== multi-process grids (simulated, gloo; static ring) ===")
    print("mode,quant,n_proc,rounds_per_s,scale_vs_1p,comm_B/round,"
          "dense_B/round")
    for row in rows:
        print(f"{row['mix_comm']},{row['mix_quant']},{row['n_processes']},"
              f"{row['rounds_per_s']},{row['scale_vs_1p']},"
              f"{row['comm_bytes_per_round']},"
              f"{row['dense_comm_bytes_per_round']}")
    print(f"loss parity (dense==sparse, all grids): {parity}; "
          f"overlap parity (grids only): {overlap_parity}; "
          f"quant parity (grids only): {quant_parity}")
    print(f"int8 halo bytes / fp32 sparse halo bytes: "
          f"{result['quant_bytes_ratio']}; quant 4p scale_vs_1p over "
          f"uncompressed overlap: {quant_scale_ratio_4p}")
    if json_path:
        # written BEFORE the parity check fails: on divergence the CI
        # artifact must carry the diverging run's rows, not a stale file
        with open(json_path, "w") as f:
            json.dump(result, f, indent=1)
        print(f"wrote {json_path}")
    if not parity:
        raise RuntimeError(
            f"dense/sparse grids diverged: losses {sorted(exact)}")
    if not overlap_parity:
        raise RuntimeError(
            f"sparse_overlap grids diverged: losses {sorted(overlap)}")
    if not quant_parity:
        raise RuntimeError(
            f"quantized grids diverged: losses {sorted(quant_losses)}")
    if quant_bytes_ratio > 0.3:
        # byte accounting is deterministic — a breach means the quant
        # payload stopped being 1B/element + one row scale
        raise RuntimeError(
            f"quantized halo bytes ratio {quant_bytes_ratio:.3f} > 0.3")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true", help="more rounds")
    ap.add_argument("--json", default="BENCH_multihost.json")
    args = ap.parse_args()
    run(quick=not args.paper, json_path=args.json or None)


if __name__ == "__main__":
    main()
