"""End-to-end DFL LoRA fine-tuning driver.

Arg-parsing + `repro.api.Session`: builds a `DFLConfig` from the CLI,
runs the paper's Algorithm 1 against any assigned architecture (reduced
or full) on whatever devices exist. On CPU this trains a reduced config
for real; on a pod, pass --full to train the full config across the
production mesh (the Session's round is mesh-aware via repro.dist).

  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b \
      --method tad --rounds 40 --interval 3 --p 0.1 --topology complete
"""
from __future__ import annotations

import argparse
import json
import os

from repro.api import ConsoleLogger, DFLConfig, HistoryRecorder, Session
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--method", default="tad",
                    choices=("lora", "ffa", "rolora", "tad"))
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--interval", type=int, default=0,
                    help="switching interval T; 0 = topology-aware T*(rho)")
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--p", type=float, default=0.2,
                    help="edge activation probability")
    ap.add_argument("--topology", default="complete",
                    choices=("complete", "ring", "erdos_renyi"))
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--adaptive-t", action="store_true",
                    help="online T via the control plane's spectral "
                         "estimator (ControlConfig t_policy='adaptive')")
    ap.add_argument("--full", action="store_true",
                    help="full (non-reduced) architecture config")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log", default="")
    args = ap.parse_args()
    enable_compile_cache()

    config = DFLConfig(
        model=args.arch, task="lm", reduced=not args.full,
        n_clients=args.clients, topology=args.topology, p=args.p,
        method=args.method, T=args.interval,
        control={"t_policy": "adaptive"} if args.adaptive_t else None,
        rounds=args.rounds, local_steps=args.local_steps,
        batch_size=args.batch, seq_len=args.seq, lr=args.lr,
        seed=args.seed,
        # the Session loop rebinds lora/opt_state every round, so the
        # round updates them in place (no per-round copy of client state)
        donate=True,
    )
    history = HistoryRecorder(every=5, consensus=True)
    # consensus on the console too: the RoundEvent memoizes the stats, so
    # the two callbacks share one computation per due round
    console = ConsoleLogger(every=5, consensus=True)
    session = Session(config, callbacks=[history, console])

    if args.adaptive_t:
        t_desc = f"T=adaptive (from T*={session.T})"
    else:
        t_desc = f"T={session.T}{'' if args.interval else ' (T*-selected)'}"
    print(f"arch={session.model_cfg.name} method={args.method} "
          f"m={args.clients} p={args.p} rho≈{session.rho:.4f} {t_desc}")

    result = session.run()
    print(f"trained {result.rounds} rounds in {result.wall_s:.1f}s "
          f"({result.wall_s / result.rounds:.2f}s/round)")

    if args.ckpt:
        session.save(args.ckpt)
        print(f"saved LoRA checkpoint -> {args.ckpt}")
    if args.log:
        os.makedirs(os.path.dirname(os.path.abspath(args.log)), exist_ok=True)
        with open(args.log, "w") as f:
            # result.T is the interval in force at run end (moves under
            # --adaptive-t); T_initial is the pre-run static selection
            json.dump({"config": vars(args), "dfl_config": config.to_dict(),
                       "rho": session.rho, "T": result.T,
                       "T_initial": session.T,
                       "history": history.history}, f, indent=1)


if __name__ == "__main__":
    main()
