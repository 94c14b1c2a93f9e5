"""Communication topologies and time-varying mixing matrices W_t.

Paper §IV-A / Appendix A-J: clients gossip through doubly-stochastic W_t
with mean-square contraction E||W_t − (1/m)11ᵀ||² ≤ ρ². The experimental
topology is Erdős–Rényi *edge activation*: each edge of an underlying graph
fires independently with probability p each round, and every activated edge
performs pairwise averaging (Lemma A.10) — giving 1−ρ ≥ c_mix·p·λ2(L).

Implemented here:
  * underlying graphs: complete (paper's main setting), ring (Table V),
    static Erdős–Rényi, exponential/hypercube, 2-D torus, Watts–Strogatz
    small-world — the families DeCAF / decentralized-LoRA evaluate on —
    plus arbitrary adjacency;
  * per-round W_t sampling via sequential pairwise averaging in random order
    (exactly Lemma A.10's model, so W_t is doubly stochastic by
    construction), Metropolis–Hastings weights (symmetric doubly
    stochastic, the scenario library's constructor), and fastest-mixing
    (FMMC) weights by projected subgradient — the control plane's
    bandwidth-aware alternative;
  * spectral diagnostics: λ2(L), ρ estimation (both the ||E[WᵀW] − J||₂
    gram route and per-sample Monte-Carlo), effective spectral gap, and
    the Lemma A.10 contraction lower bound 1−ρ ≥ c_mix·p·λ2(L).

W_t is *data*, not code — the compiled DFL round consumes it as an input
array, so dynamic graphs never trigger recompilation.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, Optional

import numpy as np


# ---------------------------------------------------------------------------
# Underlying graphs
# ---------------------------------------------------------------------------

def complete_graph(m: int) -> np.ndarray:
    a = np.ones((m, m)) - np.eye(m)
    return a


def ring_graph(m: int) -> np.ndarray:
    a = np.zeros((m, m))
    for i in range(m):
        a[i, (i + 1) % m] = a[(i + 1) % m, i] = 1.0
    return a


def erdos_renyi_graph(m: int, q: float, rng: np.random.Generator) -> np.ndarray:
    """Static ER graph with edge prob q (used as an underlying graph)."""
    u = rng.random((m, m))
    a = np.triu((u < q).astype(float), k=1)
    return a + a.T


def exponential_graph(m: int) -> np.ndarray:
    """Exponential graph: node i links to (i ± 2^k) mod m for all 2^k < m.
    For m = 2^d this is the d-dimensional hypercube's standard surrogate in
    decentralized SGD — O(log m) degree with λ2(L) = Θ(degree)."""
    a = np.zeros((m, m))
    k = 1
    while k < m:
        for i in range(m):
            j = (i + k) % m
            if j != i:
                a[i, j] = a[j, i] = 1.0
        k *= 2
    return a


def torus_dims(m: int) -> tuple[int, int]:
    """Most-square (rows, cols) factorization of m, rows <= cols."""
    r = int(np.sqrt(m))
    while m % r:
        r -= 1
    return r, m // r


def torus_graph(m: int, rows: int = 0, cols: int = 0) -> np.ndarray:
    """2-D torus C_rows x C_cols (rows*cols = m). Defaults to the
    most-square factorization; a 1 x m torus degenerates to the ring."""
    if not rows or not cols:
        rows, cols = torus_dims(m)
    if rows * cols != m:
        raise ValueError(f"torus {rows}x{cols} != m={m}")
    a = np.zeros((m, m))
    for r in range(rows):
        for c in range(cols):
            i = r * cols + c
            for j in (((r + 1) % rows) * cols + c,
                      r * cols + (c + 1) % cols):
                if j != i:
                    a[i, j] = a[j, i] = 1.0
    return a


def watts_strogatz_graph(m: int, k: int = 4, beta: float = 0.2,
                         rng: Optional[np.random.Generator] = None,
                         ) -> np.ndarray:
    """Watts–Strogatz small world: ring lattice with k neighbors per node
    (k/2 each side), each lattice edge rewired w.p. beta to a uniformly
    random non-neighbor. Resamples (up to 32 draws, advancing the rng) in
    the rare event rewiring disconnects the graph."""
    if rng is None:
        rng = np.random.default_rng(0)
    k = min(k, m - 1)
    half = max(k // 2, 1)
    for _ in range(32):
        a = np.zeros((m, m))
        for i in range(m):
            for d in range(1, half + 1):
                a[i, (i + d) % m] = a[(i + d) % m, i] = 1.0
        for i in range(m):
            for d in range(1, half + 1):
                j = (i + d) % m
                if a[i, j] and rng.random() < beta:
                    free = np.flatnonzero(a[i] == 0)
                    free = free[free != i]
                    if len(free):
                        a[i, j] = a[j, i] = 0.0
                        jn = int(rng.choice(free))
                        a[i, jn] = a[jn, i] = 1.0
        if lambda2(a) > 1e-9:            # connected
            return a
    return a                              # last draw (k>=2 is near-surely ok)


def hierarchical_graph(m: int, n_silos: int = 0, intra: str = "complete",
                       inter: str = "ring", seed: int = 0) -> np.ndarray:
    """Two-tier cross-silo topology: m clients split into `n_silos`
    near-equal contiguous silos, each silo internally wired by the
    `intra` family (dense by default), and silo *gateways* (the first
    node of each silo) wired by the `inter` family over silos (sparse by
    default) — the hierarchical intra-silo-dense / inter-silo-sparse
    setting of cross-silo FL, composed from the existing graph families.

    `n_silos=0` picks ~sqrt(m) silos. Both tier families accept any
    non-hierarchical `GRAPH_FAMILIES` member."""
    if n_silos <= 0:
        n_silos = max(2, int(np.sqrt(m)))
    if not 2 <= n_silos <= m:
        raise ValueError(f"n_silos={n_silos} must be in [2, m={m}]")
    if "hierarchical" in (intra, inter):
        raise ValueError("hierarchical tiers cannot nest")
    groups = np.array_split(np.arange(m), n_silos)
    a = np.zeros((m, m))
    for g in groups:
        if len(g) > 1:
            a[np.ix_(g, g)] = underlying_graph(intra, len(g), seed)
    gateways = [int(g[0]) for g in groups]
    top = underlying_graph(inter, n_silos, seed + 1)
    for s in range(n_silos):
        for s2 in range(s + 1, n_silos):
            if top[s, s2]:
                i, j = gateways[s], gateways[s2]
                a[i, j] = a[j, i] = 1.0
    return a


def laplacian(adj: np.ndarray) -> np.ndarray:
    return np.diag(adj.sum(1)) - adj


def lambda2(adj: np.ndarray) -> float:
    """Algebraic connectivity λ2(L)."""
    ev = np.linalg.eigvalsh(laplacian(adj))
    return float(ev[1]) if len(ev) > 1 else 0.0


def _check_adjacency(a: np.ndarray, who: str) -> np.ndarray:
    """Validate a weight-construction adjacency: square, finite, symmetric
    support. Returns the 0/1 support with an empty diagonal."""
    a = np.asarray(a, dtype=float)
    if a.ndim != 2 or a.shape[0] != a.shape[1]:
        raise ValueError(f"{who}: adjacency must be a square matrix, "
                         f"got shape {a.shape}")
    if not np.isfinite(a).all():
        raise ValueError(f"{who}: adjacency must be finite")
    s = (a > 0).astype(float)
    np.fill_diagonal(s, 0.0)
    if not np.array_equal(s, s.T):
        raise ValueError(f"{who}: adjacency support must be symmetric "
                         f"(gossip edges are undirected)")
    return s


def metropolis_weights(adj: np.ndarray) -> np.ndarray:
    """Metropolis–Hastings mixing matrix of a graph: W[i,j] =
    1/(1+max(d_i,d_j)) on edges, diagonal = 1 − row sum. Symmetric, doubly
    stochastic, non-negative for any validated adjacency — including graphs
    with isolated nodes, whose rows degenerate to e_i (the identity row/col
    "repair" the churn/straggler scenarios rely on), and the all-zero
    adjacency, which yields the identity. Raises ValueError on non-square,
    non-finite, or asymmetric-support input instead of silently producing a
    non-stochastic W."""
    a = _check_adjacency(adj, "metropolis_weights")
    deg = a.sum(1)
    inv = 1.0 / (1.0 + np.maximum(deg[:, None], deg[None, :]))
    W = a * inv
    np.fill_diagonal(W, 1.0 - W.sum(1))
    return W


def fastest_mixing_weights(adj: np.ndarray,
                           link_cost: Optional[np.ndarray] = None, *,
                           iters: int = 120, step: float = 0.4,
                           cost_weight: float = 0.0) -> np.ndarray:
    """Fastest-mixing symmetric weights (Boyd–Diaconis–Xiao FMMC) by
    projected subgradient — no solver dependency.

    Minimizes μ(W) = ||W − J||₂ over W = I − Σ_e w_e (e_i−e_j)(e_i−e_j)ᵀ
    with w ≥ 0 and per-node Σ_{e∋i} w_e ≤ 1 (so W stays elementwise
    non-negative: a subfamily of the FMMC feasible set that every gossip
    predicate in this repo assumes). The subgradient of μ at the active
    eigenvector u is ∂μ/∂w_e = ∓(u_i − u_j)²; projection is a clip plus a
    per-node edge-sum repair. Deterministic: initialized at
    `metropolis_weights` and tracking the best iterate, so the returned
    spectral gap is never worse than Metropolis (when cost_weight = 0).

    `link_cost` is an optional (m, m) per-link cost (e.g. bytes moved per
    round from `CommPlan.link_bytes`); with cost_weight > 0 the objective
    gains `cost_weight · Σ_e c_e w_e` (costs normalized to mean 1 over
    edges), trading spectral gap against traffic on expensive links.
    """
    a = _check_adjacency(adj, "fastest_mixing_weights")
    m = a.shape[0]
    ii, jj = np.triu_indices(m, k=1)
    on = a[ii, jj] > 0
    ii, jj = ii[on], jj[on]
    if len(ii) == 0:
        return np.eye(m)
    if link_cost is not None:
        c = np.asarray(link_cost, dtype=float)
        if c.shape != (m, m):
            raise ValueError(f"fastest_mixing_weights: link_cost shape "
                             f"{c.shape} != adjacency shape {(m, m)}")
        c = np.maximum(c[ii, jj], 0.0)
        c = c / c.mean() if c.mean() > 0 else np.zeros_like(c)
    else:
        c = np.zeros(len(ii))
    J = np.ones((m, m)) / m

    def build(w: np.ndarray) -> np.ndarray:
        W = np.zeros((m, m))
        W[ii, jj] = w
        W = W + W.T
        np.fill_diagonal(W, 1.0 - W.sum(1))
        return W

    def objective(w: np.ndarray) -> float:
        return float(np.linalg.norm(build(w) - J, ord=2)
                     + cost_weight * (c @ w))

    w = metropolis_weights(a)[ii, jj].copy()
    best_w, best_obj = w.copy(), objective(w)
    for k in range(max(int(iters), 0)):
        evals, evecs = np.linalg.eigh(build(w) - J)
        if evals[-1] >= -evals[0]:          # μ attained at λ_max(W − J)
            u = evecs[:, -1]
            g = -((u[ii] - u[jj]) ** 2)
        else:                               # μ attained at −λ_min(W − J)
            u = evecs[:, 0]
            g = (u[ii] - u[jj]) ** 2
        w = np.clip(w - (step / np.sqrt(k + 1.0)) * (g + cost_weight * c),
                    0.0, None)
        for _ in range(8):                  # per-node edge-sum ≤ 1 repair
            s = np.zeros(m)
            np.add.at(s, ii, w)
            np.add.at(s, jj, w)
            over = s > 1.0
            if not over.any():
                break
            f = np.where(over, 1.0 / np.maximum(s, 1e-12), 1.0)
            w = w * np.minimum(f[ii], f[jj])
        obj = objective(w)
        if obj < best_obj - 1e-12:
            best_obj, best_w = obj, w.copy()
    return build(best_w)


def rho_sq_from_samples(Ws) -> float:
    """Mean-square contraction from W samples via the gram route:
    ρ² = ||E[WᵀW] − J||₂ (tight for the Appendix A-A assumption
    E||Wx − x̄||² ≤ ρ²||x − x̄||², unlike averaging per-sample norms)."""
    Ws = list(Ws)
    m = Ws[0].shape[0]
    G = np.zeros((m, m))
    for W in Ws:
        G += W.T @ W
    G /= len(Ws)
    return float(np.linalg.norm(G - np.ones((m, m)) / m, ord=2))


def lemma_a10_gap_bound(adj: np.ndarray, p: float,
                        c_mix: float = 0.5) -> float:
    """Lemma A.10's spectral-gap lower bound 1−ρ ≥ c_mix·p·λ2(L) for
    edge-activation gossip on `adj`, in [0, 1]: the gap cannot exceed 1,
    and λ2 of a Laplacian is ≥ 0, so its rounding below 0 on a
    disconnected graph is clamped. Conformance tests check measured gaps
    against this with a conservative empirical c_mix."""
    return float(min(c_mix * p * max(0.0, lambda2(adj)), 1.0))


# ---------------------------------------------------------------------------
# Edge-activation gossip (Lemma A.10)
# ---------------------------------------------------------------------------

def _edges(adj: np.ndarray) -> np.ndarray:
    iu = np.triu_indices(adj.shape[0], k=1)
    mask = adj[iu] > 0
    return np.stack([iu[0][mask], iu[1][mask]], axis=1)


def sample_mixing_matrix(adj: np.ndarray, p: float,
                         rng: np.random.Generator) -> np.ndarray:
    """One round's W_t: every edge activates w.p. p; activated edges apply
    pairwise averaging in uniformly-random order (Lemma A.10). The product
    of symmetric doubly-stochastic pairwise averagers is doubly stochastic."""
    m = adj.shape[0]
    W = np.eye(m)
    edges = _edges(adj)
    if len(edges) == 0:
        return W
    fired = edges[rng.random(len(edges)) < p]
    if len(fired) == 0:
        return W
    order = rng.permutation(len(fired))
    for idx in order:
        i, j = fired[idx]
        We = np.eye(m)
        We[i, i] = We[j, j] = 0.5
        We[i, j] = We[j, i] = 0.5
        W = We @ W
    return W


@dataclass
class Topology:
    """A sampled-communication environment for one DFL run."""
    adj: np.ndarray
    p: float
    seed: int = 0

    def __post_init__(self):
        self.m = self.adj.shape[0]
        self._rng = np.random.default_rng(self.seed)

    def sample(self) -> np.ndarray:
        return sample_mixing_matrix(self.adj, self.p, self._rng)

    def matrices(self, rounds: int) -> Iterator[np.ndarray]:
        for _ in range(rounds):
            yield self.sample()

    # ---- spectral diagnostics -------------------------------------------
    def lambda2(self) -> float:
        return lambda2(self.adj)

    def rho_estimate(self, n_samples: int = 200) -> float:
        """Monte-Carlo estimate of ρ with E||W − J||₂² ≤ ρ²: uses the
        top singular value of (W − J) per sample and averages the square
        (the assumption is mean-square, Appendix A-A)."""
        m = self.m
        J = np.ones((m, m)) / m
        rng = np.random.default_rng(self.seed + 12345)
        vals = []
        for _ in range(n_samples):
            W = sample_mixing_matrix(self.adj, self.p, rng)
            s = np.linalg.norm(W - J, ord=2)
            vals.append(s * s)
        return float(np.sqrt(np.mean(vals)))

    def spectral_gap(self, n_samples: int = 200) -> float:
        return 1.0 - self.rho_estimate(n_samples)


GRAPH_FAMILIES = ("complete", "ring", "erdos_renyi", "exponential",
                  "torus", "small_world", "hierarchical")


def underlying_graph(kind: str, m: int, seed: int = 0, *, er_q: float = 0.5,
                     torus_rows: int = 0, torus_cols: int = 0,
                     ws_k: int = 4, ws_beta: float = 0.2,
                     hier_silos: int = 0, hier_intra: str = "complete",
                     hier_inter: str = "ring") -> np.ndarray:
    """Adjacency of a named graph family (the scenario library's graph
    constructor; graph randomness derives from `seed`, not a shared rng)."""
    if kind == "complete":
        return complete_graph(m)
    if kind == "ring":
        return ring_graph(m)
    if kind == "erdos_renyi":
        return erdos_renyi_graph(m, er_q, np.random.default_rng(seed + 777))
    if kind == "exponential":
        return exponential_graph(m)
    if kind == "torus":
        return torus_graph(m, torus_rows, torus_cols)
    if kind == "small_world":
        return watts_strogatz_graph(m, ws_k, ws_beta,
                                    np.random.default_rng(seed + 777))
    if kind == "hierarchical":
        return hierarchical_graph(m, hier_silos, hier_intra, hier_inter,
                                  seed)
    raise ValueError(f"unknown graph family {kind!r}; "
                     f"known: {GRAPH_FAMILIES}")


def make_topology(kind: str, m: int, p: float, seed: int = 0,
                  er_q: float = 0.5, **graph_kw) -> Topology:
    adj = underlying_graph(kind, m, seed, er_q=er_q, **graph_kw)
    return Topology(adj=adj, p=p, seed=seed)


# ---------------------------------------------------------------------------
# Topology-aware switching interval (the paper's headline formula)
# ---------------------------------------------------------------------------

def optimal_switching_interval(rho: float, *, c: float = 1.0,
                               t_min: int = 1, t_max: int = 64) -> int:
    """T*(ρ) ≍ c/√(1−ρ)  (Theorem V.3 / Corollary A.9)."""
    gap = max(1.0 - rho, 1e-6)
    t = int(round(c / np.sqrt(gap)))
    return int(np.clip(t, t_min, t_max))


def optimal_switching_interval_edge_activation(
        p: float, lam2: float, *, c: float = 1.0, c_mix: float = 0.5,
        t_min: int = 1, t_max: int = 64) -> int:
    """T*(p, L) ≍ c/√(p·λ2(L))  (Corollary A.11): 1−ρ ≥ c_mix·p·λ2(L)."""
    gap = max(c_mix * p * lam2, 1e-6)
    t = int(round(c / np.sqrt(gap)))
    return int(np.clip(t, t_min, t_max))
