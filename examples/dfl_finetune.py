"""End-to-end driver: train a ~100M-param LM for a few hundred DFL rounds.

Builds a ~100M decoder (gemma3-family geometry scaled down), fine-tunes it
with TAD-LoRA over a 8-client gossip graph for 200 rounds (LM objective on
synthetic non-IID token streams) through a `repro.api.Session`, checkpoints
the LoRA state, then merges the consensus adapters and compares held-out
perplexity before/after.

  PYTHONPATH=src python examples/dfl_finetune.py [--rounds 200]
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import ConsoleLogger, DFLConfig, Session
from repro.configs import get_config
from repro.configs.base import LayerSpec, ATTN, DENSE
from repro.core import client_mean, merge_lora
from repro.data.synthetic import lm_token_stream
from repro.models import transformer as tf


def model_100m():
    """~100M-param decoder (8L, d=768, 12H, ff=2048, vocab 32k)."""
    return dataclasses.replace(
        get_config("gemma3-1b"),
        name="gemma-ish-100m", n_layers=8, d_model=768, n_heads=12,
        n_kv_heads=4, head_dim=64, d_ff=2048, vocab_size=32000,
        pattern=(LayerSpec(kind=ATTN, window=256, ffn=DENSE),
                 LayerSpec(kind=ATTN, window=None, ffn=DENSE)),
    )


def perplexity(base, cfg, lora, batches):
    tot, n = 0.0, 0
    for b in batches:
        loss, (ce, *_) = tf.lm_loss(base, cfg, jnp.asarray(b["tokens"]),
                                    jnp.asarray(b["targets"]), lora=lora,
                                    remat=False)
        tot += float(ce)
        n += 1
    return float(np.exp(tot / n))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--p", type=float, default=0.2)
    ap.add_argument("--small", action="store_true",
                    help="reduced model + fewer rounds (CI-speed)")
    args = ap.parse_args()

    config = DFLConfig(
        model="gemma3-1b", task="lm", reduced=args.small,
        n_clients=args.clients, p=args.p, method="tad", T=0,
        rounds=10 if args.small else args.rounds,
        local_steps=args.local_steps, batch_size=args.batch,
        seq_len=args.seq, lr=2e-3, seed=0,
    )
    session = Session(config,
                      model_cfg=None if args.small else model_100m(),
                      callbacks=[ConsoleLogger(every=20)])
    cfg, base = session.model_cfg, session.base

    n_params = cfg.param_count()
    print(f"model {cfg.name}: {n_params/1e6:.0f}M params, "
          f"{config.rounds} rounds x {config.local_steps} local steps, "
          f"m={config.n_clients}")
    n_lora = sum(x.size for x in jax.tree.leaves(session.lora)) \
        // config.n_clients
    print(f"LoRA params per client: {n_lora/1e3:.1f}K "
          f"({100*n_lora/n_params:.3f}% of base)")
    print(f"T*={session.T}")

    # held-out eval stream (same non-IID mixture, new draws)
    eval_stream = lm_token_stream(cfg.vocab_size, 8, args.seq, seed=777)
    eval_batches = [next(eval_stream) for _ in range(4)]
    ppl0 = perplexity(base, cfg, None, eval_batches)
    print(f"held-out perplexity before training: {ppl0:.1f}")

    result = session.run()
    print(f"trained {result.rounds} rounds in {result.wall_s:.1f}s "
          f"({result.wall_s / result.rounds:.2f}s/round)")

    session.save("results/dfl_finetune_lora.npz")
    print("checkpoint -> results/dfl_finetune_lora.npz")

    consensus = client_mean(session.lora)
    merged = merge_lora(base, consensus, cfg)
    ppl1 = perplexity(merged, cfg, None, eval_batches)
    print(f"held-out perplexity after merge: {ppl1:.1f} "
          f"(improvement {ppl0/ppl1:.2f}x)")


if __name__ == "__main__":
    main()
