"""Tier-1 units for the topology-sparse gossip comm layer.

Covers the pure-data side in-process: `repro.dist.comm.CommPlan`
compilation (peer sets, export tables, byte accounting),
`repro.scenarios.schedule.schedule_support` union supports, the
single-process degenerate numerics of `mix_tree_sparse` (bitwise equal to
the dense planned path; overlap mode well-defined and genuinely delayed),
and the `mix_comm` config surface (validation, cache keys, session
threading). The REAL process grids live in `-m multihost`
(tests/test_multihost.py); the staleness bound in `-m conformance`.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import DFLConfig, Session
from repro.core import mixing
from repro.core.topology import metropolis_weights, ring_graph, torus_graph
from repro.dist import comm
from repro.scenarios import get_scenario
from repro.scenarios.schedule import schedule_support

ENC_KW = dict(n_layers=1, d_model=32, n_heads=2, d_ff=64, vocab_size=256)


def _cfg(**kw):
    base = dict(model="encoder", task="sst2", model_kw=ENC_KW, n_clients=8,
                rounds=3, local_steps=2, batch_size=8, topology="ring",
                scenario="static", p=0.5, T=2, lr=1e-3, seed=0)
    base.update(kw)
    return DFLConfig(**base)


def _tree(key, m=8, d=16, r=4):
    ks = jax.random.split(key, 4)
    return {"q": {"a": jax.random.normal(ks[0], (m, d, r)),
                  "b": jax.random.normal(ks[1], (m, r, d))},
            "v": {"a": jax.random.normal(ks[2], (m, d, r)),
                  "b": jax.random.normal(ks[3], (m, r, d))}}


# ---------------------------------------------------------------------------
# CommPlan compilation: structure, peers, padding, bytes
# ---------------------------------------------------------------------------

def test_comm_plan_ring_structure():
    """Ring, 8 clients over 4 shards: each shard owns 2 clients and only
    its two ring neighbours' border rows cross shard boundaries."""
    cp = comm.build_comm_plan(ring_graph(8), n_shards=4)
    assert (cp.m, cp.n_shards, cp.m_loc) == (8, 4, 2)
    # every owned row is a border row on a 2-client shard -> k = 2
    assert cp.k == 2
    # shard p talks exactly to its ring neighbours (p-1, p+1) mod 4
    for p in range(4):
        assert cp.recv_peers[p] == tuple(sorted({(p - 1) % 4, (p + 1) % 4}))
        assert cp.send_peers[p] == cp.recv_peers[p]
    # export tables address real local rows and agree with global ids
    assert cp.export_local.shape == (4, 2)
    assert ((0 <= cp.export_local) & (cp.export_local < 2)).all()
    np.testing.assert_array_equal(
        cp.export_global.reshape(4, 2),
        cp.export_local + (np.arange(4) * 2)[:, None])


def test_comm_plan_two_shards_vs_dense():
    """On 2 shards of a ring each side needs both of the other side's
    border rows; a complete graph needs ALL remote rows — sparse bytes
    then equal the dense all-gather exactly (no double counting)."""
    ring = comm.build_comm_plan(ring_graph(8), n_shards=2)
    assert ring.k == 2 and ring.cross_edges == 4
    full = comm.build_comm_plan(np.ones((8, 8), bool), n_shards=2)
    assert full.k == 4
    cols = 96
    assert full.sparse_recv_bytes(cols) == comm.dense_recv_bytes(8, 2, cols)
    assert ring.sparse_recv_bytes(cols) < comm.dense_recv_bytes(8, 2, cols)


def test_comm_plan_torus_asymmetric_exports_pad():
    """2x4 torus over 4 shards: column-pair shards export BOTH rows, so
    uneven needs still compile to one rectangular (n, k) table whose pad
    slots are real local rows (value-identical duplicate scatters)."""
    cp = comm.build_comm_plan(torus_graph(8, 2, 4), n_shards=4)
    assert cp.k >= 1
    for p in range(4):
        # padded entries remain valid local indices
        assert ((0 <= cp.export_local[p]) & (cp.export_local[p] < cp.m_loc)).all()
    owner = np.arange(8) // 2
    # every support edge crossing shards is covered by an export
    exported = set(cp.export_global.tolist())
    for i in range(8):
        for j in range(8):
            if cp.support[i, j] and owner[i] != owner[j]:
                assert j in exported, f"row {j} needed by {i} not exported"


def test_comm_plan_single_shard_degenerate():
    cp = comm.build_comm_plan(ring_graph(8), n_shards=1)
    assert cp.k == 0 and cp.cross_edges == 0
    assert cp.sparse_recv_bytes(100) == 0
    assert comm.dense_recv_bytes(8, 1, 100) == 0
    assert cp.recv_peers == ((),) and cp.send_peers == ((),)


def test_comm_plan_validation_errors():
    with pytest.raises(ValueError):
        comm.build_comm_plan(np.ones((3, 4)), n_shards=2)      # not square
    with pytest.raises(ValueError):
        comm.build_comm_plan(ring_graph(8), n_shards=3)        # 8 % 3 != 0


def test_comm_plan_signature_distinguishes():
    a = comm.build_comm_plan(ring_graph(8), n_shards=4)
    b = comm.build_comm_plan(ring_graph(8), n_shards=2)
    c = comm.build_comm_plan(torus_graph(8, 2, 4), n_shards=4)
    assert len({a.signature(), b.signature(), c.signature()}) == 3
    # deterministic: same inputs, same id
    assert a.signature() == comm.build_comm_plan(ring_graph(8),
                                                 n_shards=4).signature()


# ---------------------------------------------------------------------------
# schedule_support: union supports of the scenario schedules
# ---------------------------------------------------------------------------

def test_schedule_support_static_is_graph():
    sched = get_scenario("complete-static").build(8, seed=0)
    sup = schedule_support(sched)
    assert sup.dtype == bool and sup.all()


def test_schedule_support_gossip_transitive_closure():
    """A gossip round applies a PRODUCT of pair averagings, so one round
    can couple clients beyond graph edges — the support must be the
    transitive closure (complete on a connected graph), not the edge set."""
    sched = get_scenario("complete-gossip").build(8, seed=0)
    assert schedule_support(sched).all()


def test_schedule_support_edge_activation_is_edges():
    """Edge activation masks single edges of the underlying graph: the
    union support is exactly graph ∪ diagonal, never more."""
    sched = get_scenario("ring-edge").build(8, seed=0)
    sup = schedule_support(sched)
    expect = ring_graph(8).astype(bool) | np.eye(8, dtype=bool)
    np.testing.assert_array_equal(sup, expect)
    # and a long W_t sample stream stays inside the declared support
    for t in range(50):
        W = sched.next_w(t)
        assert (np.abs(W[~sup]) == 0).all(), f"round {t} left the support"


# ---------------------------------------------------------------------------
# mix_tree_sparse numerics (single-process degenerate path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ma,mb", [(1.0, 1.0), (1.0, 0.0), (0.0, 1.0),
                                   (0.3, 0.8)])
def test_sparse_mix_bitwise_equals_dense(ma, mb):
    """The sparse contraction is the SAME arithmetic as the planned dense
    path at the same operand layout: bitwise at the BINARY masks every
    paper method actually passes (RoundMasks are 0/1 scalars) and at
    fractional (damped-variant) masks, with and without a (1-shard)
    CommPlan attached."""
    W = jnp.asarray(metropolis_weights(ring_graph(8)), jnp.float32)
    lora = _tree(jax.random.PRNGKey(0))
    cp = comm.build_comm_plan(ring_graph(8), n_shards=1)
    dense = mixing.mix_tree_planned(W, lora, ma, mb)
    for plan in (None, cp):
        sparse = mixing.mix_tree_sparse(W, lora, ma, mb, comm_plan=plan)
        for x, y in zip(jax.tree.leaves(dense), jax.tree.leaves(sparse)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_sparse_overlap_delayed_semantics():
    """Overlap mode must equal the hand-computed delayed-gossip identity
    y = W_diag·x_post + W_offdiag·x_pre (per column segment blend), and
    reduce to plain sparse when pre == post."""
    W = jnp.asarray(metropolis_weights(ring_graph(8)), jnp.float32)
    post = _tree(jax.random.PRNGKey(1))
    pre = _tree(jax.random.PRNGKey(2))

    # pre == post collapses to fresh mixing ARITHMETICALLY (the split-out
    # diagonal term changes summation order, so equality is to float
    # tolerance, not bitwise — bitwise is dense-vs-sparse's contract)
    same = mixing.mix_tree_sparse(W, post, 1.0, 1.0, comm_plan=None,
                                  lora_prev=post)
    plain = mixing.mix_tree_sparse(W, post, 1.0, 1.0, comm_plan=None)
    for x, y in zip(jax.tree.leaves(same), jax.tree.leaves(plain)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)

    got = mixing.mix_tree_sparse(W, post, 1.0, 1.0, comm_plan=None,
                                 lora_prev=pre)
    Wd = np.diag(np.diag(np.asarray(W)))
    Wo = np.asarray(W) - Wd
    for g, xp, xq in zip(jax.tree.leaves(got), jax.tree.leaves(post),
                         jax.tree.leaves(pre)):
        expect = (np.einsum("ij,jdr->idr", Wd, np.asarray(xp)) +
                  np.einsum("ij,jdr->idr", Wo, np.asarray(xq)))
        np.testing.assert_allclose(np.asarray(g), expect,
                                   rtol=1e-5, atol=1e-6)
    # and it genuinely differs from fresh mixing when pre != post
    fresh = jax.tree.leaves(plain)
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(got), fresh))


class _FakeMesh:
    def __init__(self, size, axis_names):
        self.size = size
        self.axis_names = axis_names


def test_sparse_mesh_plan_mismatch_raises(monkeypatch):
    """A bound mesh whose shape cannot host the CommPlan used to fall
    through to the degenerate local contraction — parity held but the
    sparse savings silently vanished. It must refuse instead; the
    plan-less call (conformance probes, rate measurements) keeps the
    degenerate path."""
    from repro.dist import sharding
    W = jnp.asarray(metropolis_weights(ring_graph(8)), jnp.float32)
    lora = _tree(jax.random.PRNGKey(4))
    cp = comm.build_comm_plan(ring_graph(8), n_shards=2)

    monkeypatch.setattr(sharding, "current_mesh",
                        lambda: _FakeMesh(4, ("x",)))
    with pytest.raises(ValueError, match="4 devices"):
        mixing.mix_tree_sparse(W, lora, 1.0, 1.0, comm_plan=cp)

    monkeypatch.setattr(sharding, "current_mesh",
                        lambda: _FakeMesh(4, ("x", "y")))
    with pytest.raises(ValueError, match="1-D mesh"):
        mixing.mix_tree_sparse(W, lora, 1.0, 1.0, comm_plan=cp)

    # comm_plan=None under a multi-device mesh stays degenerate (the
    # conformance tier's rate probes depend on it)
    out = mixing.mix_tree_sparse(W, lora, 1.0, 1.0, comm_plan=None)
    assert jax.tree.structure(out) == jax.tree.structure(lora)


# ---------------------------------------------------------------------------
# config / session surface
# ---------------------------------------------------------------------------

def test_mix_comm_validation_and_cache_key():
    with pytest.raises(ValueError):
        _cfg(mix_comm="pairwise")
    keys = {_cfg(mix_comm=m).cache_key() for m in
            ("dense", "sparse", "sparse_overlap")}
    assert len(keys) == 3, "mix_comm must enter the build cache key"
    assert _cfg().mix_comm == "dense"


def test_session_sparse_bitwise_equals_dense_run():
    """End-to-end degenerate check: a full single-process training run
    under mix_comm='sparse' reproduces the dense run bit-for-bit (static
    graph), and the session carries a CommPlan for the active support."""
    dense = Session(_cfg(mix_comm="dense"))
    sparse = Session(_cfg(mix_comm="sparse"))
    dense.run()
    sparse.run()
    for x, y in zip(jax.tree.leaves(dense.lora), jax.tree.leaves(sparse.lora)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert sparse.comm_plan is not None
    assert sparse.comm_plan.n_shards == 1
    assert dense.comm_plan is None


def test_session_sparse_overlap_runs_and_differs():
    """Overlap is a different algorithm: it must run cleanly to a finite
    loss on the same config but NOT match dense on a ring (the delayed
    off-diagonal terms lag one round)."""
    dense = Session(_cfg(mix_comm="dense", rounds=4))
    overlap = Session(_cfg(mix_comm="sparse_overlap", rounds=4))
    dense.run()
    res = overlap.run()
    assert np.isfinite(res.final_loss)
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(dense.lora),
                               jax.tree.leaves(overlap.lora)))
