"""Serving CLI on `repro.api.ServingSession`: continuous-batching decode
with per-request TAD-LoRA adapters from a training checkpoint.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b \
      --batch 4 --prompt-len 32 --gen 16 \
      [--lora run.npz] [--merge] [--adapter consensus]

Default with ``--lora``: every per-client adapter the checkpoint holds
(plus their consensus mean) is served side-by-side from ONE compiled decode
step — request i decodes under adapter i mod n_adapters. ``--merge`` folds
the consensus adapter into the base weights instead (the pre-multi-adapter
behavior); ``--adapter NAME`` pins every request to one adapter.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.api.serving import AdapterPool, ServingSession
from repro.checkpoint import load_pytree
from repro.core.lora import client_mean, merge_lora
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tf


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests (= decode slots)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--lora", default="",
                    help="Session checkpoint with per-client LoRA adapters")
    ap.add_argument("--merge", action="store_true",
                    help="fold the consensus adapter into the base weights "
                         "instead of multi-adapter serving")
    ap.add_argument("--adapter", default="",
                    help="serve every request with this one adapter")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    key = jax.random.key(args.seed)
    pool = None
    params = None
    if args.lora and args.merge:
        # legacy path: one merged model, no adapter pool
        from repro.configs import get_config
        cfg = get_config(args.arch)
        if not args.full:
            cfg = cfg.reduced()
        params = tf.init_params(key, cfg)
        lora_tree = jax.tree.map(jax.numpy.asarray,
                                 load_pytree(args.lora)["lora"])
        params = merge_lora(params, client_mean(lora_tree), cfg)
        print(f"merged consensus LoRA from {args.lora}")
        serving = ServingSession(args.arch, reduced=not args.full,
                                 params=params, n_slots=args.batch,
                                 max_len=args.prompt_len + args.gen + 8,
                                 init_seed=args.seed)
    else:
        if args.lora:
            pool = AdapterPool.from_checkpoint(args.lora)
            print(f"serving adapters from {args.lora}: {pool.ids}")
        serving = ServingSession(args.arch, reduced=not args.full,
                                 adapters=pool, n_slots=args.batch,
                                 max_len=args.prompt_len + args.gen + 8,
                                 init_seed=args.seed)
    cfg = serving.model_cfg

    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           size=(args.batch, args.prompt_len)).astype(np.int32)
    if cfg.n_frontend_tokens:
        frontend = jax.random.normal(
            key, (args.batch, cfg.n_frontend_tokens, cfg.d_model)) * 0.02
        serving.engine.set_frontend(frontend)

    # round-robin over the trained adapters + consensus ("base" excluded —
    # it is the reserved zero row, not one of the run's models)
    names = ([n for n in serving.adapters if n != "base"]
             if (args.lora and not args.merge) else [None])
    if args.adapter:
        names = [args.adapter]
    rids = [serving.submit(prompts[i], adapter=names[i % len(names)],
                           max_new=args.gen)
            for i in range(args.batch)]

    t0 = time.time()
    serving.run()
    dt = time.time() - t0
    total = args.batch * (args.prompt_len + args.gen)
    print(f"decoded {args.gen} tokens x{args.batch} in {dt:.2f}s "
          f"({total / dt:.1f} tok/s incl. prefill-by-decode, "
          f"{serving.compile_count} compile)")
    for rid in rids[:2]:
        req = serving.engine.requests[rid]
        tag = req.adapter if req.adapter is not None else "base"
        print(f"sample [{tag}]:", serving.result(rid)[:12])


if __name__ == "__main__":
    main()
