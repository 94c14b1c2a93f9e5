"""Plain reference of the first DFL rounds of a training cell.

It is fed what the program is fed (bench/seeded.py: the base weights,
the LoRA start, the token stream, the gossip mixing matrices and T*) and
runs the rounds in straight `jax.numpy`: per-client forward and backward
of the frozen base with LoRA on the targets, AdamW with the TAD update
masks, then the gossip mix of every factor. Matrix products run at
``jax.default_matmul_precision("highest")``; ``dtype=bfloat16`` gives
the control, the same rounds computed in bfloat16 throughout: the model,
the LoRA factors, the optimizer's moments and the mix.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import seeded
from bench.reference import model as M


# ---------------------------------------------------------------------------
# what the round is fed
# ---------------------------------------------------------------------------

def tad_masks(t: int, T: int) -> np.ndarray:
    """(update_a, update_b, mix_a, mix_b): B trains while floor(t/T) is
    even, A otherwise; both factors are mixed every round."""
    a_phase = (t // T) % 2 == 1
    return np.array([float(a_phase), 1.0 - a_phase, 1.0, 1.0], np.float32)


# ---------------------------------------------------------------------------
# one local step and the mix
# ---------------------------------------------------------------------------

def _loss(lora, seed, embed, unembed, tokens, targets, s: M.Spec, dtype):
    """Mean cross-entropy over every client's tokens plus the balance
    loss; also each client's mean cross-entropy."""
    x, aux = M.hidden(seed, embed, tokens, lora, s, dtype)

    @jax.checkpoint
    def client_ce(_, xt):
        xi, ti = xt
        lg = M.logits(xi, unembed, s)
        ce = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, ti[..., None], -1)[..., 0]
        return None, jnp.sum(ce)

    _, ce_sum = jax.lax.scan(client_ce, None, (x, targets))
    per_client = ce_sum / (targets.size // targets.shape[0])
    return jnp.sum(ce_sum) / targets.size + aux, per_client


@partial(jax.jit, static_argnames=("s", "dtype", "lr"))
def local_step(lora, opt, seed, embed, unembed, tokens, targets, masks, *,
               s, dtype, lr):
    (_, per), g = jax.value_and_grad(_loss, has_aux=True)(
        lora, seed, embed, unembed, tokens, targets, s, dtype)
    step, mu, nu = opt
    step = step + 1
    b1, b2, eps, wd = 0.9, 0.999, 1e-8, 0.01
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(path, p, gr, m1, m2):
        on = masks[0] if path[-1].key == "a" else masks[1]
        m1n = b1 * m1 + (1 - b1) * gr
        m2n = b2 * m2 + (1 - b2) * gr * gr
        u = (m1n / bc1) / (jnp.sqrt(m2n / bc2) + eps) + wd * p
        dt = p.dtype
        return ((p - lr * on * u).astype(dt),
                (on * m1n + (1 - on) * m1).astype(dt),
                (on * m2n + (1 - on) * m2).astype(dt))

    out = jax.tree_util.tree_map_with_path(upd, lora, g, mu, nu)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), (step, pick(1), pick(2)), per


@jax.jit
def mix(lora, W, masks):
    """Gossip mix over the client axis; a factor whose mix mask is 0
    keeps its own row."""
    eye = jnp.eye(W.shape[0])

    def one(path, x):
        on = masks[2] if path[-1].key == "a" else masks[3]
        We = (on * W + (1 - on) * eye).astype(x.dtype)
        return jnp.einsum("ij,ljdr->lidr", We, x)
    return jax.tree_util.tree_map_with_path(one, lora)


# ---------------------------------------------------------------------------
# the rounds
# ---------------------------------------------------------------------------

def leaf_norms(tree) -> dict:
    """Per (leaf, layer) Frobenius norms: {"wq.a": (L,), ...}."""
    out = {}
    for name, node in tree.items():
        for f in ("a", "b"):
            x = np.asarray(node[f], np.float64)
            out[f"{name}.{f}"] = np.sqrt(np.sum(x * x, axis=(1, 2, 3)))
    return out


def run_rounds(cfg: dict, mix_spec: dict, seeds: dict, rounds: int,
               dtype=jnp.float32) -> dict:
    """The first ``rounds`` rounds. Returns the per-client losses of
    every local step (rounds, ls, m), the first moment after round 1 as
    per-leaf norms divided by (1 - b1) (the gradient the optimizer saw),
    and per-leaf norms of the factors' change after ``rounds``."""
    s = M.spec_from_config(cfg)
    m, ls = mix_spec["n_clients"], mix_spec["local_steps"]
    b, S = mix_spec["batch"], mix_spec["seq_len"]
    adj = seeded.graph(mix_spec["topology"], m)
    T = mix_spec["T"] or seeded.switching_interval(adj, mix_spec["p"],
                                                   seeds["topology"])
    sched = seeded.GossipSchedule(adj, mix_spec["p"], seeds["topology"])
    data = seeded.token_rounds(s.vocab, m, ls, b, S, seeds["data"])

    with jax.default_matmul_precision("highest"):
        embed, unembed = M.head_weights(seeds["init"], s)
        seed = jnp.asarray(seeds["init"], jnp.uint32)
        lora0 = jax.tree.map(lambda t: t.astype(dtype),
                             M.lora_init(seeds["init"], s, m))
        lora = lora0
        zeros = jax.tree.map(jnp.zeros_like, lora)
        opt = (jnp.zeros((), jnp.float32), zeros, zeros)
        losses, mu1 = [], None
        for t in range(rounds):
            batch = next(data)
            tokens, targets = batch["tokens"], batch["targets"]
            W = sched.next_w(t)
            masks = jnp.asarray(tad_masks(t, T))
            per_step = []
            for k in range(ls):
                lora, opt, per = local_step(
                    lora, opt, seed, embed, unembed, jnp.asarray(tokens[k]),
                    jnp.asarray(targets[k]), masks, s=s, dtype=dtype,
                    lr=mix_spec["lr"])
                per_step.append(np.asarray(per))
            if t == 0:
                mu1 = {k: v / 0.1 for k, v in leaf_norms(opt[1]).items()}
            lora = mix(lora, jnp.asarray(W, jnp.float32), masks)
            jax.block_until_ready(lora)
            losses.append(np.stack(per_step))
        change = leaf_norms(jax.tree.map(lambda a, b: a - b, lora, lora0))
    return {"loss": np.stack(losses), "grad": mu1, "change": change, "T": T}
