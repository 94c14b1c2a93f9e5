"""Cluster entrypoint: multi-process DFL execution on the repro.dist grid.

Two modes in one module:

*Worker* (the default): join the process grid via the ``REPRO_*`` env
protocol (or explicit ``--coordinator/--num-processes/--process-id``),
build a `DFLConfig`, run a `repro.api.ClusterSession`, optionally save a
checkpoint / JSON result (rank 0 only). On a real cluster every node runs
this with its own ``REPRO_PROCESS_ID``.

*Parent* (``--simulate N``): spawn N local worker processes on the
portable CPU backend (gloo collectives), forward the remaining CLI args to
each, stream rank 0's output, and exit non-zero if any worker fails. It
is the CPU rehearsal of a process grid, and how CI exercises the whole
multi-process path headless. A chip belongs to one process, so on a chip
host the multi-chip path is a single worker (no ``--simulate``) whose
`ClusterSession` spans every local chip:

  PYTHONPATH=src python -m repro.launch.cluster --simulate 2 \\
      --preset classifier --rounds 6 --clients 4 --json out.json

The worker JSON records the cluster perf surface: rounds/s, the
per-round gossip payload measured from the live session's plans
(`comm_bytes_per_round` — the exact bytes each process *receives* from
the collectives the round actually issues, with the dense and sparse
figures both reported for comparison), and the final loss, so tests and
``benchmarks/multihost.py`` share one measurement path.
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time
from typing import Optional, Sequence

# NOTE: jax / repro.api imports happen inside worker_main(), AFTER
# multihost.initialize() — the grid must exist before the backend is used.

PRESETS = ("classifier", "lm")


def _preset_config(args) -> dict:
    """A DFLConfig dict from the CLI knobs (small enough for CI)."""
    if args.preset == "classifier":
        cfg = dict(model="encoder", task="sst2",
                   model_kw={"n_layers": 1, "d_model": 32, "n_heads": 2,
                             "d_ff": 64, "vocab_size": 256},
                   batch_size=args.batch or 8)
    else:
        cfg = dict(model=args.arch, task="lm", reduced=True,
                   batch_size=args.batch or 2, seq_len=args.seq)
    cfg.update(n_clients=args.clients, topology=args.topology, p=args.p,
               scenario=args.scenario, method=args.method, T=args.interval,
               rounds=args.rounds, local_steps=args.local_steps,
               lr=args.lr, seed=args.seed, mix_comm=args.mix_comm,
               mix_quant=args.mix_quant)
    if args.weight_policy != "metropolis" or args.t_policy != "fixed":
        if args.weight_policy == "fmmc" and args.scenario == "gossip":
            # the default scenario's pairwise sampler has no weight
            # matrix for FMMC to rewire; picking the policy implies a
            # weighted schedule
            cfg["scenario"] = "edge_activation"
        cfg["control"] = dict(weight_policy=args.weight_policy,
                              t_policy=args.t_policy)
    return cfg


def _comm_bytes(session) -> dict:
    """Per-round gossip payload a process RECEIVES, measured from the
    live session's plans — the MixPlan of the actual LoRA tree and the
    CommPlan of the actual exchange — i.e. the exact payloads of the
    collectives the round issues, not an analytic estimate. Reports the
    active mode's figure plus both alternatives for comparison; all 0 on
    a single-process grid."""
    import jax
    from repro.core import mixing
    from repro.dist import comm
    from repro.scenarios.schedule import schedule_support

    plan = mixing.get_mix_plan(session.lora)
    cp = session.comm_plan
    if cp is None:      # dense run: compile the plan it WOULD use
        cp = comm.build_comm_plan(
            schedule_support(session.topo_schedule),
            n_shards=jax.device_count())
    dense_b = comm.dense_recv_bytes(cp.m, cp.n_shards, plan.cols)
    sparse_b = cp.sparse_recv_bytes(plan.cols)
    quant_b = cp.sparse_recv_bytes_quant(plan.cols)
    link_b = cp.link_bytes(plan.cols)
    mode = session.config.mix_comm
    quant = session.config.mix_quant
    active = dense_b if mode == "dense" else \
        (quant_b if quant != "off" else sparse_b)
    return {
        "mix_comm": mode,
        "mix_quant": quant,
        "comm_bytes_per_round": active,
        "dense_comm_bytes_per_round": dense_b,
        "sparse_comm_bytes_per_round": sparse_b,
        "sparse_quant_comm_bytes_per_round": quant_b,
        # per-link surface: what the control plane's FMMC cost term sees
        "cross_links": cp.cross_edges,
        "max_link_bytes_per_round": float(link_b.max()),
    }


def worker_main(args) -> int:
    from repro.dist import multihost
    multihost.initialize(coordinator=args.coordinator,
                         num_processes=args.num_processes,
                         process_id=args.process_id)

    import jax
    from repro.api import ClusterSession, ConsoleLogger, DFLConfig
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.config:
        with open(args.config) as f:
            config = DFLConfig.from_dict(json.load(f))
    else:
        config = DFLConfig(**_preset_config(args))

    callbacks = []
    if multihost.is_primary() and not args.quiet:
        # loss is a fully-replicated scalar — float() is a local read, so
        # rank-gating this callback breaks no collective lockstep
        callbacks.append(ConsoleLogger(every=max(1, config.rounds // 10)))
    session = ClusterSession(config, callbacks=callbacks)

    if args.restore:
        at = session.restore(args.restore)
        if multihost.is_primary():
            print(f"restored {args.restore} at round {at}", flush=True)

    rounds = args.run_rounds or None
    if args.warmup:
        # compile + first rounds untimed: rounds_per_s then measures the
        # steady-state round, not jit/partitioner/gloo startup
        session.run(args.warmup)
        jax.block_until_ready(session.lora)
    t0 = time.perf_counter()
    result = session.run(rounds)
    wall = time.perf_counter() - t0

    if args.ckpt:
        session.save(args.ckpt)
    eval_res = None
    if args.eval:
        # a collective: every rank computes, rank 0 reports
        eval_res = session.evaluate(n=64)
    if multihost.is_primary():
        m = config.n_clients
        n_proc = jax.process_count()
        payload = {
            "n_processes": n_proc,
            "n_devices": jax.device_count(),
            "m": m,
            "clients_per_process": m // n_proc,
            "rounds": result.rounds,
            "wall_s": round(wall, 4),
            "rounds_per_s": round(result.rounds / wall, 2),
            "final_loss": result.final_loss,
            "final_round": session.t,
            **_comm_bytes(session),
        }
        if eval_res is not None:
            payload["eval_acc"] = eval_res["acc"]
        print(f"[cluster] {json.dumps(payload)}", flush=True)
        if args.json:
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=1)
    multihost.sync("cluster-exit")
    multihost.shutdown()
    return 0


# ---------------------------------------------------------------------------
# --simulate N: the local process-grid spawner (CI / laptop path)
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def spawn_simulated(n: int, worker_args: Sequence[str], *,
                    timeout: float = 900.0,
                    extra_env: Optional[dict] = None):
    """Spawn ``python -m repro.launch.cluster`` × n as a local grid.

    Returns a list of (returncode, combined_output) per rank. Workers run
    on the portable CPU backend with gloo collectives; the repro source
    tree is put on each worker's PYTHONPATH so the spawner works from a
    plain checkout.
    """
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    if extra_env:
        env.update(extra_env)
    procs = []
    for i in range(n):
        env_i = dict(env)
        env_i["REPRO_COORDINATOR"] = coord
        env_i["REPRO_NUM_PROCESSES"] = str(n)
        env_i["REPRO_PROCESS_ID"] = str(i)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "repro.launch.cluster", *worker_args],
            env=env_i, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    out = []
    deadline = time.monotonic() + timeout
    for p in procs:
        left = max(1.0, deadline - time.monotonic())
        try:
            stdout, _ = p.communicate(timeout=left)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            stdout, _ = p.communicate()
            stdout += "\n[spawner] TIMEOUT"
        out.append((p.returncode, stdout))
    return out


def failed_ranks(results) -> list:
    """[(rank, formatted report)] for every non-zero worker exit — the one
    place spawn failures are shaped for humans (bench, tests, CLI)."""
    return [(rank, f"--- rank {rank} (exit {code}) ---\n{out}")
            for rank, (code, out) in enumerate(results) if code != 0]


def _parser() -> argparse.ArgumentParser:
    # allow_abbrev=False: a prefix spelling like "--sim 2" must NOT parse
    # as --simulate while evading the worker-args filter below — workers
    # re-spawning as parents would fork-bomb the machine
    ap = argparse.ArgumentParser(
        description="multi-process DFL (worker, or --simulate N parent)",
        allow_abbrev=False)
    ap.add_argument("--simulate", type=int, default=0, metavar="N",
                    help="spawn N local worker processes on the CPU "
                         "backend and wait (parent mode): the CPU "
                         "rehearsal of a process grid. On a chip host the "
                         "multi-chip path is ONE worker process over all "
                         "local chips. 0 = run as a worker")
    # grid (worker mode; REPRO_* env is the usual source)
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    # experiment
    ap.add_argument("--config", default="",
                    help="JSON DFLConfig dict (overrides the preset knobs)")
    ap.add_argument("--preset", default="classifier", choices=PRESETS)
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--method", default="tad",
                    choices=("lora", "ffa", "rolora", "tad"))
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--batch", type=int, default=0,
                    help="per-client per-step batch (0 = preset default)")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--topology", default="complete")
    ap.add_argument("--scenario", default="gossip")
    ap.add_argument("--p", type=float, default=0.5)
    ap.add_argument("--interval", type=int, default=2,
                    help="switching interval T (static)")
    ap.add_argument("--mix-comm", default="dense",
                    choices=("dense", "sparse", "sparse_overlap"),
                    help="gossip comm lowering (DFLConfig.mix_comm)")
    ap.add_argument("--mix-quant", default="off",
                    choices=("off", "int8", "fp8"),
                    help="compressed gossip: quantize the sparse halo "
                         "exchange (DFLConfig.mix_quant)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--weight-policy", default="metropolis",
                    choices=("metropolis", "fmmc"),
                    help="closed-loop mixing weights "
                         "(ControlConfig.weight_policy)")
    ap.add_argument("--t-policy", default="fixed",
                    choices=("fixed", "adaptive"),
                    help="closed-loop T retuning (ControlConfig.t_policy)")
    # run control / artifacts
    ap.add_argument("--run-rounds", type=int, default=0,
                    help="rounds to run now (0 = config.rounds)")
    ap.add_argument("--warmup", type=int, default=0,
                    help="untimed leading rounds (compile excluded from "
                         "rounds_per_s; they still advance the session)")
    ap.add_argument("--restore", default="")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--eval", action="store_true",
                    help="session.evaluate() after training (classifier "
                         "presets; reported in the result JSON)")
    ap.add_argument("--json", default="",
                    help="rank-0 result JSON (rounds/s, collective bytes)")
    ap.add_argument("--quiet", action="store_true")
    return ap


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    args = _parser().parse_args(argv)
    if args.simulate:
        worker_args, skip = [], False
        for a in argv:
            if skip:
                skip = False
            elif a == "--simulate":
                skip = True
            elif not a.startswith("--simulate="):
                worker_args.append(a)
        results = spawn_simulated(args.simulate, worker_args)
        failed = failed_ranks(results)
        bad = {rank for rank, _ in failed}
        for rank, (code, outp) in enumerate(results):
            if rank == 0 and rank not in bad:
                sys.stdout.write(f"--- rank 0 (exit {code}) ---\n{outp}\n")
        for _, report in failed:
            sys.stdout.write(report + "\n")
        if failed:
            print(f"[simulate] FAILED ranks: {sorted(bad)}", file=sys.stderr)
            return 1
        return 0
    return worker_main(args)


if __name__ == "__main__":
    sys.exit(main())
