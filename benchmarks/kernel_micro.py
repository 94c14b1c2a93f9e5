"""Kernel microbenchmarks: wall-time of the dispatch path on this backend
(CPU -> jnp reference; interpret-mode checked for correctness only — Pallas
timing is meaningless off-TPU) + analytic kernel roofline on v5e, plus the
mixing-lowering comparison (per-leaf oracle vs MixPlan fused path) that
feeds BENCH_mixing.json — the start of the repo's recorded perf trajectory.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mixing
from repro.kernels import ops
from repro.roofline.analysis import HW


def _time(fn, *args, iters=5):
    """Mean wall us/call. Readies the warmup AND every timed result (a
    single block on the last iteration lets earlier dispatches overlap the
    timer and under-report)."""
    jax.block_until_ready(fn(*args))  # compile + warmup
    t0 = time.perf_counter()
    results = [fn(*args) for _ in range(iters)]
    jax.block_until_ready(results)
    return (time.perf_counter() - t0) / iters * 1e6   # us


def _synthetic_lora_tree(key, m: int, P: int, d: int = 512, r: int = 8):
    """Many-leaved client-stacked LoRA tree with ~P columns per client —
    the shape regime where per-leaf dispatch overhead dominates. Mirrors
    the real layout: plain (m, d, r) a/b pairs plus one group-stacked
    (G, m, d, r) pair."""
    pair_cols = 2 * d * r
    n_pairs = max(1, P // pair_cols)
    g_pairs = max(1, n_pairs // 8)        # 1/8 of pairs in one (G, ...) leaf
    n_plain = max(1, n_pairs - g_pairs)
    layers = []
    for i in range(n_plain):
        k = jax.random.fold_in(key, i)
        layers.append({"wq": {
            "a": jax.random.normal(jax.random.fold_in(k, 0), (m, d, r)),
            "b": jax.random.normal(jax.random.fold_in(k, 1), (m, r, d)),
        }})
    kg = jax.random.fold_in(key, 10_000)
    stacked = {"wv": {
        "a": jax.random.normal(jax.random.fold_in(kg, 0), (g_pairs, m, d, r)),
        "b": jax.random.normal(jax.random.fold_in(kg, 1), (g_pairs, m, r, d)),
    }}
    return {"groups": [stacked], "tail": layers}


def mixing_bench(quick: bool = True):
    """per-leaf vs planned-fused mixing wall time over (m, P) grid.

    Masks are passed as traced scalars — exactly how the compiled DFL
    round feeds them (method/phase may not trigger recompilation), so
    per_leaf pays its real per-leaf blend rather than letting XLA
    constant-fold literal 1.0 masks away."""
    rows = []
    log_ps = (18, 20) if quick else (18, 20, 22)
    one = jnp.float32(1.0)
    # which lowering mix_tree_planned picks on this backend (flat kernel
    # under mesh/TPU vs cache-local per-slot dots) — recorded per row so
    # the perf trajectory stays comparable across backends
    lowering = "flat" if mixing.use_flat_lowering() else "per_slot"
    for m in (10, 64):
        for log_p in log_ps:
            P = 1 << log_p
            key = jax.random.fold_in(jax.random.key(7), m * 100 + log_p)
            tree = _synthetic_lora_tree(key, m, P)
            n_leaves = len(jax.tree.leaves(tree))
            W = jnp.full((m, m), 1.0 / m, jnp.float32)
            per_leaf = jax.jit(
                lambda W, t, a, b: mixing.mix_tree(W, t, a, b))
            planned = jax.jit(
                lambda W, t, a, b: mixing.mix_tree_planned(W, t, a, b))
            us_pl = _time(per_leaf, W, tree, one, one, iters=3)
            us_fu = _time(planned, W, tree, one, one, iters=3)
            rows.append({"m": m, "log2_P": log_p, "n_leaves": n_leaves,
                         "lowering": lowering,
                         "per_leaf_us": round(us_pl, 1),
                         "fused_us": round(us_fu, 1),
                         "speedup": round(us_pl / us_fu, 3)})
    return rows


def run(quick: bool = True, json_path: str | None = None):
    hw = HW()
    key = jax.random.key(0)
    rows = []

    def k(i):
        return jax.random.fold_in(key, i)

    # lora_matmul: M=K=N=1024, r=8
    M = K = N = 512 if quick else 1024
    x = jax.random.normal(k(1), (M, K), jnp.float32)
    w = jax.random.normal(k(2), (K, N), jnp.float32)
    a = jax.random.normal(k(3), (K, 8)) * 0.1
    b = jax.random.normal(k(4), (8, N)) * 0.1
    us = _time(lambda *t: ops.lora_matmul(*t, 2.0), x, w, a, b)
    flops = 2 * M * K * N + 2 * M * K * 8 + 2 * M * 8 * N
    rows.append(("lora_matmul", us, f"v5e_roofline_us={flops/hw.peak_flops*1e6:.1f}"))

    # flash_attention
    S = 512 if quick else 1024
    q = jax.random.normal(k(5), (1, S, 4, 64), jnp.float32)
    kk = jax.random.normal(k(6), (1, S, 4, 64), jnp.float32)
    v = jax.random.normal(k(7), (1, S, 4, 64), jnp.float32)
    us = _time(lambda *t: ops.flash_attention(*t, causal=True), q, kk, v)
    flops = 2 * 2 * 4 * S * S * 64
    rows.append(("flash_attention", us,
                 f"v5e_roofline_us={flops/hw.peak_flops*1e6:.1f}"))

    # gossip_mix: m=10 clients, P = 1M params
    P = 1 << (18 if quick else 20)
    W = jnp.ones((10, 10)) / 10
    xs = jax.random.normal(k(8), (10, P), jnp.float32)
    us = _time(lambda *t: ops.gossip_mix_flat(*t, 1.0), W, xs)
    byts = 10 * P * 4 * 2
    rows.append(("gossip_mix", us,
                 f"v5e_hbm_us={byts/hw.hbm_bw*1e6:.1f}"))

    # rglru_scan
    T, Wd = (512, 256) if quick else (2048, 512)
    aa = jax.nn.sigmoid(jax.random.normal(k(9), (4, T, Wd)))
    uu = jax.random.normal(k(10), (4, T, Wd)) * 0.1
    us = _time(ops.rglru_scan, aa, uu)
    byts = 4 * T * Wd * 4 * 3
    rows.append(("rglru_scan", us, f"v5e_hbm_us={byts/hw.hbm_bw*1e6:.1f}"))

    print("\n=== kernel microbench (CPU dispatch path) ===")
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    mix_rows = mixing_bench(quick=quick)
    print("\n=== mixing lowering (per-leaf oracle vs MixPlan fused) ===")
    print("m,log2_P,n_leaves,per_leaf_us,fused_us,speedup")
    for r in mix_rows:
        print(f"{r['m']},{r['log2_P']},{r['n_leaves']},"
              f"{r['per_leaf_us']:.1f},{r['fused_us']:.1f},{r['speedup']}")

    result = {n: {"us": u, "derived": d} for n, u, d in rows}
    result["mixing"] = mix_rows
    if json_path:
        payload = {
            "backend": jax.default_backend(),
            "quick": quick,
            "kernels": {n: {"us": u, "derived": d} for n, u, d in rows},
            "mixing": mix_rows,
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"\nwrote {json_path}")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true",
                    help="full grids (adds the P=2^22 mixing column)")
    ap.add_argument("--json", default="",
                    help="write BENCH_mixing.json-style payload here")
    args = ap.parse_args()
    run(quick=not args.paper, json_path=args.json or None)


if __name__ == "__main__":
    main()
