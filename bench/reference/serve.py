"""Plain reference of multi-adapter serving: the full forward pass of a
prompt and its served tokens with the request's adapter, in straight
`jax.numpy` at ``jax.default_matmul_precision("highest")``, nothing
imported from the program. Also makes the adapters a serving cell uses
from its seed, for the program and the reference alike."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import model as M


def adapters(key, s: M.Spec, m: int) -> dict:
    """m random adapters on the LoRA targets, stacked as
    {"wq": {"a": (L, m, d, r), "b": (L, m, r, d_out)}, ...}:
    a ~ N(0, 1/d), b ~ N(0, 1/r^2), so each adapter moves its
    projection by a share comparable to the base weights."""
    dims = {"wq": s.heads * s.hd, "wv": s.kv_heads * s.hd}
    out = {}
    for i, name in enumerate(s.targets):
        o = dims[name]
        ka, kb = jax.random.fold_in(key, 2 * i), jax.random.fold_in(key,
                                                                   2 * i + 1)
        out[name] = {
            "a": jax.random.normal(ka, (s.layers, m, s.d, s.rank)) / np.sqrt(
                s.d),
            "b": jax.random.normal(kb, (s.layers, m, s.rank, o)) / s.rank}
    return out


def pick(pool: dict, row: int) -> dict:
    """One adapter of a stacked set; row -1 is the consensus (the mean
    of every factor over the clients)."""
    def one(x):
        y = jnp.mean(x, axis=1) if row < 0 else x[:, row]
        return y[:, None]
    return jax.tree.map(one, pool)


@partial(jax.jit, static_argnames=("s", "dtype"))
def logits_at(seed, embed, unembed, tokens, positions, lora, *, s, dtype):
    """Logits (float32) at ``positions`` of the padded sequence
    ``tokens``, with the model computed in ``dtype``."""
    x, _ = M.hidden(seed, embed, tokens[None, None], lora, s, dtype)
    return M.logits(x[0, 0][positions], unembed, s)


def served_gaps(cfg: dict, init_seed: int, adapter_seed: int, n_clients: int,
                requests, max_len: int, max_new: int,
                control: bool = False) -> dict:
    """Widest gap by which a served token's reference logit lies below
    the reference's best, over ``requests`` = [(prompt, served, row)].
    With ``control`` also the control's reading: the same model computed
    in bfloat16, and at each position the gap, under the reference, of
    the token bfloat16 puts first."""
    s = M.spec_from_config(cfg)
    worst = worst_ctrl = 0.0
    n_tokens = 0
    with jax.default_matmul_precision("highest"):
        embed, unembed = M.head_weights(init_seed, s)
        seed = jnp.asarray(init_seed, jnp.uint32)
        pool = adapters(jax.random.key(adapter_seed), s, n_clients)
        for prompt, served, row in requests:
            seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
            toks = np.zeros(max_len, np.int32)
            toks[:len(seq)] = seq
            n = len(served)
            pos = np.zeros(max_new, np.int32)
            pos[:n] = len(prompt) - 1 + np.arange(n)
            args = (seed, embed, unembed, jnp.asarray(toks), jnp.asarray(pos),
                    pick(pool, row))
            ref = np.asarray(logits_at(*args, s=s, dtype=jnp.float32))[:n]
            best = ref.max(-1)
            gap = best - ref[np.arange(n), np.asarray(served)]
            worst = max(worst, float(gap.max()))
            if control:
                low = np.asarray(logits_at(*args, s=s,
                                           dtype=jnp.bfloat16))[:n]
                g = best - ref[np.arange(n), low.argmax(-1)]
                worst_ctrl = max(worst_ctrl, float(g.max()))
            n_tokens += n
    out = {"gap": worst, "tokens": n_tokens}
    if control:
        out["control_gap"] = worst_ctrl
    return out
