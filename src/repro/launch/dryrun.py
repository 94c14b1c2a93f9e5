import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) on the production
meshes, extract memory/cost/collective analyses, emit roofline terms.

  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b \
      --shape train_4k --mesh single --out results/dryrun.json

  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Results are cached per-combo in the output JSON; finished combos are
skipped, so the sweep is resumable. The device-count override above MUST
precede any jax import (jax locks the backend on first init) — that is why
these are the first two lines of the file.
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.dist import sharding as shd
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_production_mesh
from repro.roofline.analysis import (HW, collective_bytes_from_hlo,
                                     jaxpr_cost, model_flops,
                                     roofline_report)


def run_combo(arch: str, shape_name: str, multi_pod: bool, *,
              local_steps: int = 1, axis_map=None,
              moe_dispatch: str = "dense",
              seq_parallel: bool = False,
              client_parallel: bool = False) -> dict:
    from repro.models import moe as moe_mod
    moe_mod.set_dispatch(moe_dispatch)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    amap = axis_map or (shd.MULTIPOD_AXIS_MAP if multi_pod
                        else shd.DEFAULT_AXIS_MAP)
    if seq_parallel:
        amap = {**amap, "seq_act": ("model",)}
    if client_parallel:
        # small-model mode: one client per (data, model) chip pair; no
        # tensor parallelism (weights replicated), collectives = gossip
        # only. On the multi-pod mesh the "pod" axis replicates (client
        # count is bounded by the global batch of 256).
        amap = {"clients": ("data", "model"), "batch": ("data", "model"),
                "fsdp": (), "model": (), "seq": ()}
    shd.set_mesh(mesh, amap)
    n_chips = mesh.devices.size
    rec: dict = {"arch": arch, "shape": shape_name,
                 "mesh": "multi" if multi_pod else "single",
                 "chips": n_chips, "local_steps": local_steps}
    try:
        step, specs, n_tokens, training = steps_mod.build(
            cfg, shape, mesh, local_steps=local_steps, axis_map=amap)

        t0 = time.time()
        lowered = jax.jit(step).lower(*specs)
        rec["lower_s"] = round(time.time() - t0, 2)

        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 2)

        # --- memory analysis (proves it fits) ---
        ma = compiled.memory_analysis()
        if ma is not None:
            rec["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "code_bytes": int(ma.generated_code_size_in_bytes),
            }

        # --- XLA cost analysis (reference; scan bodies counted once) ---
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0] if ca else {}
        rec["xla_cost"] = {"flops": float(ca.get("flops", 0.0)),
                           "bytes": float(ca.get("bytes accessed", 0.0))}

        # --- jaxpr cost (scan-aware, global) ---
        jxp = jax.make_jaxpr(step)(*specs)
        jc = jaxpr_cost(jxp)
        rec["jaxpr_cost"] = jc

        # --- collectives from partitioned HLO (per-device) ---
        coll = collective_bytes_from_hlo(compiled.as_text())
        rec["collectives"] = coll

        mf = model_flops(cfg, n_tokens, training=training)
        rec["roofline"] = roofline_report(
            flops=jc["flops"], hbm_bytes=jc["bytes"],
            coll_bytes_per_device=coll["total"], n_chips=n_chips,
            model_fl=mf)
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — sweep must survive one failure
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
    finally:
        shd.clear_mesh()
    return rec


def _combo_key(arch, shape, mesh_name, local_steps, tag=""):
    # variant flags go through --tag
    k = f"{arch}|{shape}|{mesh_name}|ls{local_steps}"
    return k + (f"|{tag}" if tag else "")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--tag", default="", help="cache-key suffix for variants")
    ap.add_argument("--moe-dispatch", default="dense",
                    choices=("dense", "fused"))
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--client-parallel", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    combos = []
    archs = ARCH_IDS if args.all else [args.arch]
    shapes = tuple(SHAPES) if args.all else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    for arch, shape, mp in combos:
        mesh_name = "multi" if mp else "single"
        key = _combo_key(arch, shape, mesh_name, args.local_steps, args.tag)
        if key in results and results[key].get("status") in ("ok", "skipped") \
                and not args.force:
            print(f"[cached] {key}: {results[key]['status']}")
            continue
        print(f"[run] {key} ...", flush=True)
        t0 = time.time()
        rec = run_combo(arch, shape, mp, local_steps=args.local_steps,
                        moe_dispatch=args.moe_dispatch,
                        seq_parallel=args.seq_parallel,
                        client_parallel=args.client_parallel)
        rec["wall_s"] = round(time.time() - t0, 1)
        results[key] = rec
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        status = rec["status"]
        extra = ""
        if status == "ok":
            r = rec["roofline"]
            extra = (f" bottleneck={r['bottleneck']} "
                     f"c={r['compute_s']:.3g}s m={r['memory_s']:.3g}s "
                     f"x={r['collective_s']:.3g}s")
        elif status == "error":
            extra = " " + rec["error"][:160]
        print(f"[done] {key}: {status}{extra} ({rec['wall_s']}s)", flush=True)


if __name__ == "__main__":
    main()
