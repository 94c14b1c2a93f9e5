"""What a cell makes from its seed and hands to both sides: the frozen
base weights, the LoRA start, the synthetic token stream, the gossip
mixing matrices W_t and the switching interval T*.

The program gets them through its inputs (`Session.base`, `Session.lora`,
the round's batch feed, a ``topology_schedule`` and the config's ``T``;
``ServingSession(params=...)``); the plain reference draws the same
values layer by layer. Neither side copies the other's generators, so a
change to the program's own initialiser, data pipeline or topology
sampler leaves the comparison as it was.

Weights: every leaf is drawn from its own key, made from the seed and
the leaf's path (``groups/0/attn/wq``). A leaf stacked over layers draws
layer g from ``fold_in(key, g)``; the vocabulary axis of ``embed`` and
``unembed`` draws token v from ``fold_in(key, v)``, so padding the
vocabulary changes no real row. Scales: embeddings N(0, 0.02^2), norm
gains zero (weight one), q/k/v biases N(0, 0.02^2), every other matrix
N(0, 1/fan_in) with fan_in its second-to-last axis.
"""
from __future__ import annotations

import math
import zlib

import numpy as np

BIASES = ("bq", "bk", "bv")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _key(seed, path: str):
    import jax

    return jax.random.fold_in(jax.random.key(seed),
                              zlib.crc32(path.encode()) & 0x7FFFFFFF)


def scale(path: str, shape) -> float:
    """Standard deviation of a leaf's entries (``shape``: one layer's)."""
    name = path.rsplit("/", 1)[-1]
    if name == "embed" or name in BIASES:
        return 0.02
    if name.startswith("norm") or name == "final_norm":
        return 0.0
    return 1.0 / math.sqrt(shape[-2])


def _draw(key, shape, std: float):
    import jax
    import jax.numpy as jnp

    if std == 0.0:
        return jnp.zeros(shape, jnp.float32)
    return jax.random.normal(key, shape, jnp.float32) * std


def vocab_rows(seed, path: str, n: int, width: int, std: float):
    """(n, width): token v's row drawn from fold_in(key, v)."""
    import jax
    import jax.numpy as jnp

    k = _key(seed, path)
    return jax.vmap(lambda v: _draw(jax.random.fold_in(k, v), (width,),
                                    std))(jnp.arange(n))


def layer_leaf(seed, path: str, g, shape):
    """Layer ``g``'s slice of a stacked leaf (``g`` may be traced)."""
    import jax

    return _draw(jax.random.fold_in(_key(seed, path), g), tuple(shape),
                 scale(path, shape))


def _path(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in kp)


def _leaf(seed, path: str, shape):
    import jax
    import jax.numpy as jnp

    if path == "embed":
        return vocab_rows(seed, path, shape[0], shape[1], 0.02)
    if path == "unembed":
        return vocab_rows(seed, path, shape[1], shape[0],
                          1.0 / math.sqrt(shape[0])).T
    if path.startswith("groups/"):
        return jax.vmap(lambda g: layer_leaf(seed, path, g, shape[1:]))(
            jnp.arange(shape[0]))
    return _draw(_key(seed, path), tuple(shape), scale(path, shape))


def _made_like(like, make_leaf):
    """``make_leaf(path, shape_dtype)`` for every leaf of ``like`` (a tree
    of arrays or ShapeDtypeStructs), in one jitted call that places each
    leaf where ``like``'s is."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten_with_path(like)
    shardings = [getattr(x, "sharding", None) for _, x in leaves]
    out_sh = shardings if all(s is not None for s in shardings) else None
    made = jax.jit(lambda: [make_leaf(_path(kp), x).astype(x.dtype)
                            for kp, x in leaves], out_shardings=out_sh)()
    return jax.tree_util.tree_unflatten(tree, made)


def base_params(seed: int, like):
    """The frozen base, in the shapes, types and placement of ``like``,
    made on the device in one jitted call."""
    return _made_like(like, lambda path, x: _leaf(seed, path, x.shape))


def lora_a(seed, path: str, g, d_in: int, rank: int):
    """Layer g's LoRA ``a`` (path ``groups/0/attn/wq/a``), shared by
    every client: N(0, 1/d_in)."""
    return layer_leaf(seed, path, g, (d_in, rank))


def lora_start(seed: int, like):
    """Training's LoRA start in the shapes of ``like``: each ``a`` leaf
    (L, m, d_in, r) drawn per layer and shared by the m clients, each
    ``b`` zero."""
    import jax
    import jax.numpy as jnp

    def make(path, x):
        if not path.endswith("/a"):
            return jnp.zeros(x.shape)
        L, m, d_in, r = x.shape
        a = jax.vmap(lambda g: lora_a(seed, path, g, d_in, r))(
            jnp.arange(L))
        return jnp.broadcast_to(a[:, None], x.shape)
    return _made_like(like, make)


# ---------------------------------------------------------------------------
# tokens
# ---------------------------------------------------------------------------

def token_rounds(vocab: int, m: int, ls: int, b: int, S: int, seed: int):
    """Yields one round's batch {"tokens", "targets"}, each (ls, m, b, S):
    a Markov chain per client, next = (cur * 31 + bias_client + U{0..6})
    mod vocab, so every client's data is learnable and its own."""
    rng = np.random.default_rng(seed)
    shape = (m, b * ls)
    biases = rng.integers(0, vocab, size=m)
    while True:
        cur = rng.integers(0, vocab, size=shape)
        toks = [cur]
        for _ in range(S):
            cur = (cur * 31 + biases[:, None]
                   + rng.integers(0, 7, size=shape)) % vocab
            toks.append(cur)
        arr = np.stack(toks, -1).astype(np.int32)

        def lay(x):
            return x.reshape(m, ls, b, S).swapaxes(0, 1)
        yield {"tokens": lay(arr[..., :-1]), "targets": lay(arr[..., 1:])}


class Feed:
    """An iterator over batches that counts what it handed out, so a run
    can tell that the program read its batches from it."""

    def __init__(self, it):
        self.it, self.drawn = it, 0

    def __iter__(self):
        return self

    def __next__(self):
        self.drawn += 1
        return next(self.it)


# ---------------------------------------------------------------------------
# the communication graph
# ---------------------------------------------------------------------------

def ring_adjacency(m: int) -> np.ndarray:
    adj = np.zeros((m, m))
    for i in range(m):
        adj[i, (i + 1) % m] = adj[(i + 1) % m, i] = 1.0
    return adj


def gossip_w(adj: np.ndarray, p: float,
             rng: np.random.Generator) -> np.ndarray:
    """Each edge fires with probability p; fired edges average their two
    ends, one after another in a random order."""
    m = adj.shape[0]
    iu = np.triu_indices(m, k=1)
    keep = adj[iu] > 0
    edges = np.stack([iu[0][keep], iu[1][keep]], axis=1)
    W = np.eye(m)
    fired = edges[rng.random(len(edges)) < p]
    for idx in rng.permutation(len(fired)):
        i, j = fired[idx]
        E = np.eye(m)
        E[i, i] = E[j, j] = E[i, j] = E[j, i] = 0.5
        W = E @ W
    return W


class GossipSchedule:
    """Randomized pairwise gossip over a fixed graph: the W_t stream of a
    cell, drawn from its topology seed (a `TopologySchedule`)."""

    symmetric = False               # a product of pairwise averages

    def __init__(self, adj: np.ndarray, p: float, seed: int):
        self.adj, self.p = adj, p
        self.m = adj.shape[0]
        self.rng = np.random.default_rng(seed)

    def next_w(self, t: int) -> np.ndarray:
        return gossip_w(self.adj, self.p, self.rng)

    def support_adjacency(self) -> np.ndarray:
        return self.adj.copy()


def switching_interval(adj: np.ndarray, p: float, seed: int) -> int:
    """T* = round(1 / sqrt(1 - rho)), clipped to [1, 64], with rho the
    root mean square of ||W - J||_2 over 100 draws of the stream's law
    (from their own generator, not the stream's)."""
    m = adj.shape[0]
    rng = np.random.default_rng(seed + 12345)
    J = np.ones((m, m)) / m
    vals = [np.linalg.norm(gossip_w(adj, p, rng) - J, ord=2) ** 2
            for _ in range(100)]
    rho = float(np.sqrt(np.mean(vals)))
    return int(np.clip(int(round(1.0 / np.sqrt(max(1.0 - rho, 1e-6)))),
                       1, 64))


def graph(topology: str, m: int) -> np.ndarray:
    if topology != "ring":
        from bench.core import HarnessError
        raise HarnessError(f"no generator for topology {topology!r}")
    return ring_adjacency(m)
