"""Kernel microbenchmarks: wall-time of the dispatch path on this backend
(CPU -> jnp reference; interpret-mode checked for correctness only — Pallas
timing is meaningless off-TPU) + analytic kernel roofline on v5e, written
to BENCH_mixing.json.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

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

    result = {n: {"us": u, "derived": d} for n, u, d in rows}
    if json_path:
        payload = {
            "backend": jax.default_backend(),
            "quick": quick,
            "kernels": result,
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"\nwrote {json_path}")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true",
                    help="full-size kernel shapes")
    ap.add_argument("--json", default="",
                    help="write BENCH_mixing.json-style payload here")
    args = ap.parse_args()
    run(quick=not args.paper, json_path=args.json or None)


if __name__ == "__main__":
    main()
