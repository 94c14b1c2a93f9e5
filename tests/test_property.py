"""Property-based tests (hypothesis) on the system's invariants."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

if os.environ.get("REPRO_REQUIRE_HYPOTHESIS"):
    # CI sets this so a broken hypothesis install FAILS the suite instead
    # of silently skipping the whole property tier
    import hypothesis
else:
    hypothesis = pytest.importorskip(
        "hypothesis", reason="property tests need hypothesis (optional dep)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import mix_tree, mix_tree_planned, sample_mixing_matrix
from repro.core.diagnostics import consensus_stats
from repro.core.topology import (complete_graph, lambda2, make_topology,
                                 ring_graph)

SETTINGS = dict(max_examples=25, deadline=None)


@given(m=st.integers(3, 12), p=st.floats(0.05, 1.0), seed=st.integers(0, 99))
@settings(**SETTINGS)
def test_mixing_matrix_doubly_stochastic(m, p, seed):
    """Lemma A.10: edge-activation pairwise averaging gives doubly-stochastic
    W_t for every sample."""
    rng = np.random.default_rng(seed)
    W = sample_mixing_matrix(complete_graph(m), p, rng)
    np.testing.assert_allclose(W.sum(0), 1.0, atol=1e-9)
    np.testing.assert_allclose(W.sum(1), 1.0, atol=1e-9)
    assert (W >= -1e-12).all()


@given(m=st.integers(3, 10), p=st.floats(0.05, 1.0), seed=st.integers(0, 99))
@settings(**SETTINGS)
def test_gossip_preserves_mean(m, p, seed):
    """Doubly-stochastic mixing preserves the client average of every leaf
    (the conserved quantity behind the paper's consensus analysis)."""
    rng = np.random.default_rng(seed)
    W = jnp.asarray(sample_mixing_matrix(complete_graph(m), p, rng))
    x = jnp.asarray(rng.normal(size=(m, 4, 3)))
    tree = {"mod": {"a": x, "b": jnp.asarray(rng.normal(size=(m, 3, 5)))}}
    mixed = mix_tree(W, tree, 1.0, 1.0)
    for k in ("a", "b"):
        np.testing.assert_allclose(
            np.asarray(jnp.mean(mixed["mod"][k], 0)),
            np.asarray(jnp.mean(tree["mod"][k], 0)), atol=1e-6)


@given(m=st.integers(3, 8), seed=st.integers(0, 50))
@settings(**SETTINGS)
def test_mix_planned_equals_per_leaf(m, seed):
    """The fused single-buffer planned mix is numerically identical to
    per-leaf mixing."""
    rng = np.random.default_rng(seed)
    W = jnp.asarray(sample_mixing_matrix(complete_graph(m), 0.5, rng),
                    jnp.float32)
    tree = {"x": {"a": jnp.asarray(rng.normal(size=(m, 6, 2)), jnp.float32),
                  "b": jnp.asarray(rng.normal(size=(m, 2, 7)), jnp.float32)},
            "y": {"a": jnp.asarray(rng.normal(size=(3, m, 4, 2)),
                                   jnp.float32),
                  "b": jnp.asarray(rng.normal(size=(3, m, 2, 4)),
                                   jnp.float32)}}
    m1 = mix_tree(W, tree, 1.0, 0.3)
    m2 = mix_tree_planned(W, tree, 1.0, 0.3)
    for l1, l2 in zip(jax.tree.leaves(m1), jax.tree.leaves(m2)):
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=1e-5, atol=1e-5)


@given(m=st.integers(3, 8), seed=st.integers(0, 50))
@settings(**SETTINGS)
def test_cross_term_cauchy_schwarz(m, seed):
    """Appendix A-D: ||C|| <= ||Δ_A||·||Δ_B|| for any client states."""
    rng = np.random.default_rng(seed)
    tree = {"mod": {"a": jnp.asarray(rng.normal(size=(m, 8, 3))),
                    "b": jnp.asarray(rng.normal(size=(m, 3, 8)))}}
    s = consensus_stats(tree)
    assert float(s["cross_norm"]) <= float(s["cs_bound"]) + 1e-6


@given(m=st.integers(4, 12))
@settings(**SETTINGS)
def test_ring_worse_connected_than_complete(m):
    """λ2(ring) < λ2(complete) — the spectral ordering the paper's Table V
    stress test relies on."""
    assert lambda2(ring_graph(m)) < lambda2(complete_graph(m))


@given(seed=st.integers(0, 20))
@settings(max_examples=10, deadline=None)
def test_rho_decreases_with_p(seed):
    """Higher activation probability -> smaller ρ (Lemma A.10 scaling)."""
    t_lo = make_topology("complete", 8, p=0.05, seed=seed)
    t_hi = make_topology("complete", 8, p=0.8, seed=seed)
    assert t_hi.rho_estimate(60) < t_lo.rho_estimate(60)


@given(m=st.integers(3, 10), q=st.floats(0.1, 1.0), seed=st.integers(0, 99))
@settings(**SETTINGS)
def test_metropolis_doubly_stochastic_on_random_adjacency(m, q, seed):
    """Metropolis weights are symmetric doubly stochastic for ANY adjacency
    — including disconnected draws and isolated nodes (identity rows)."""
    from repro.core.topology import erdos_renyi_graph, metropolis_weights
    adj = erdos_renyi_graph(m, q, np.random.default_rng(seed))
    W = metropolis_weights(adj)
    np.testing.assert_allclose(W.sum(0), 1.0, atol=1e-12)
    np.testing.assert_allclose(W.sum(1), 1.0, atol=1e-12)
    np.testing.assert_allclose(W, W.T, atol=1e-12)
    assert (W >= 0).all()


@given(m=st.integers(3, 10), q=st.floats(0.2, 1.0), p=st.floats(0.05, 1.0),
       kind=st.sampled_from(["edge_activation", "churn", "straggler"]),
       seed=st.integers(0, 99))
@settings(**SETTINGS)
def test_scenario_schedules_doubly_stochastic_on_random_adjacency(
        m, q, p, kind, seed):
    """Every W_t a scenario schedule emits over a random underlying graph
    is doubly stochastic — the invariant the convergence theory needs, and
    what the churn/straggler identity-row repair must preserve."""
    from repro.core.topology import erdos_renyi_graph
    from repro.scenarios import ClientChurn, EdgeActivation, StragglerDropout
    adj = erdos_renyi_graph(m, q, np.random.default_rng(seed))
    sched = {"edge_activation": lambda: EdgeActivation(adj, p, seed),
             "churn": lambda: ClientChurn(adj, p, seed, leave=0.3,
                                          rejoin=0.4),
             "straggler": lambda: StragglerDropout(adj, p, seed, drop=0.3),
             }[kind]()
    for t in range(5):
        W = sched.next_w(t)
        np.testing.assert_allclose(W.sum(0), 1.0, atol=1e-12)
        np.testing.assert_allclose(W.sum(1), 1.0, atol=1e-12)
        assert (W >= 0).all()
        np.testing.assert_allclose(W, W.T, atol=1e-12)


@given(m=st.integers(3, 10), q=st.floats(0.2, 1.0), p=st.floats(0.05, 1.0),
       seed=st.integers(0, 99))
@settings(**SETTINGS)
def test_mixing_never_expands_consensus_distance(m, q, p, seed):
    """Doubly-stochastic mixing is non-expansive in the consensus seminorm
    Σ_i||x_i − x̄||² — the one-step form of Lemma A.4, for any graph."""
    from repro.core.topology import erdos_renyi_graph
    from repro.scenarios import EdgeActivation
    rng = np.random.default_rng(seed)
    adj = erdos_renyi_graph(m, q, rng)
    sched = EdgeActivation(adj, p, seed)
    x = rng.normal(size=(m, 7))
    d = float(np.sum((x - x.mean(0)) ** 2))
    for t in range(4):
        x = sched.next_w(t) @ x
        dn = float(np.sum((x - x.mean(0)) ** 2))
        # the 1e-24 floor absorbs float noise once consensus is numerically
        # exact (d ~ 1e-32 after a complete-graph round)
        assert dn <= d * (1 + 1e-9) + 1e-24
        d = dn


@given(m=st.integers(3, 10), q=st.floats(0.1, 1.0), p=st.floats(0.0, 1.0),
       seed=st.integers(0, 99))
@settings(**SETTINGS)
def test_lemma_a10_bound_in_unit_interval(m, q, p, seed):
    from repro.core.topology import erdos_renyi_graph, lemma_a10_gap_bound
    adj = erdos_renyi_graph(m, q, np.random.default_rng(seed))
    b = lemma_a10_gap_bound(adj, p)
    assert 0.0 <= b <= 1.0


# ---------------------------------------------------------------------------
# data-layer partitioners (repro.data.partition)
# ---------------------------------------------------------------------------

_PARTITIONER_NAMES = ("iid", "dirichlet", "quantity", "domain", "paper")


def _labels_and_domains(n, n_classes, n_domains, seed):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, n_classes, size=n)
    # contiguous domain blocks (the layout shard writers produce)
    domains = np.sort(rng.integers(0, n_domains, size=n))
    return labels, domains


@given(name=st.sampled_from(_PARTITIONER_NAMES),
       n=st.integers(40, 400), n_classes=st.integers(2, 5),
       n_clients=st.integers(2, 10), seed=st.integers(0, 99))
@settings(**SETTINGS)
def test_partitioners_valid_partition(name, n, n_classes, n_clients, seed):
    """Every partitioner yields disjoint in-range index sets with every
    client owning >= 1 sample, and client label distributions are valid
    probability rows."""
    from repro.data import client_label_distributions, make_partition
    labels, domains = _labels_and_domains(n, n_classes,
                                          max(n_clients, 3), seed)
    parts = make_partition(name, labels, n_clients, seed=seed,
                           domains=domains)
    assert len(parts) == n_clients
    allidx = np.concatenate(parts)
    assert len(np.unique(allidx)) == len(allidx)          # disjoint
    assert allidx.min() >= 0 and allidx.max() < n          # in range
    assert all(len(p) >= 1 for p in parts)                 # nobody empty
    dist = client_label_distributions(parts, labels, n_classes)
    assert (dist >= 0).all()
    np.testing.assert_allclose(dist.sum(1), 1.0, atol=1e-9)


@given(name=st.sampled_from(_PARTITIONER_NAMES),
       n=st.integers(50, 300), n_classes=st.integers(2, 4),
       n_clients=st.integers(2, 8), seed=st.integers(0, 99))
@settings(**SETTINGS)
def test_partitioners_deterministic_per_seed(name, n, n_classes, n_clients,
                                             seed):
    """Same (inputs, seed) -> bitwise identical partition; a different
    seed moves it (except the seed-free paper realization's class pools,
    which may coincide on tiny inputs — only sameness is asserted)."""
    from repro.data import make_partition
    labels, domains = _labels_and_domains(n, n_classes, 4, seed)
    a = make_partition(name, labels, n_clients, seed=seed, domains=domains)
    b = make_partition(name, labels, n_clients, seed=seed, domains=domains)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


@given(alpha=st.floats(0.05, 0.3), seed=st.integers(0, 99))
@settings(**SETTINGS)
def test_dirichlet_concentration_controls_skew(alpha, seed):
    """Dirichlet label skew is monotone in concentration: a small alpha
    partition is measurably more skewed than the same draw at 100x the
    concentration (which approaches IID)."""
    from repro.data import label_skew, make_partition
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 3, size=600)
    lo = make_partition("dirichlet", labels, 8, seed=seed, alpha=alpha)
    hi = make_partition("dirichlet", labels, 8, seed=seed,
                        alpha=alpha * 100.0)
    assert label_skew(lo, labels, 3) > label_skew(hi, labels, 3)


@given(m=st.integers(2, 6), seed=st.integers(0, 30))
@settings(**SETTINGS)
def test_lora_merge_equals_adapter_forward(m, seed):
    """merge_lora(base, lora) forward == base forward with LoRA adapters
    (classifier substrate)."""
    from repro.core import build_lora_tree, client_slice, merge_lora
    from repro.models.classifier import (classifier_forward, encoder_config,
                                         init_classifier)
    cfg = encoder_config(n_layers=1, d_model=32, n_heads=2, d_ff=32,
                         vocab_size=64)
    key = jax.random.key(seed)
    base = init_classifier(key, cfg, n_classes=2)
    lora = build_lora_tree(jax.random.fold_in(key, 1), base, cfg,
                           n_clients=m)
    # give b random values (zero-init would make the test vacuous)
    lora = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.fold_in(
            key, x.size % 97), x.shape), lora)
    toks = jax.random.randint(key, (3, 8), 0, cfg.vocab_size)
    li = client_slice(lora, 0)
    merged = merge_lora(base, li, cfg)
    y_adapter = classifier_forward(base, cfg, toks, lora=li)
    y_merged = classifier_forward(merged, cfg, toks)
    np.testing.assert_allclose(np.asarray(y_adapter), np.asarray(y_merged),
                               rtol=2e-4, atol=2e-4)
