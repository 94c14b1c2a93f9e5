"""Multi-process execution tests.

Two groups:
  * tier-1 units — in-process, single-process-degenerate behaviour of the
    multihost helpers, `BroadcastSchedule`, the `mix_gather` lowering, and
    `ClusterSession` (which must be an exact `Session` on one process).
  * `-m multihost` — tests that spawn a REAL simulated process grid via
    `repro.launch.cluster` (CPU backend, gloo collectives) and assert the
    acceptance bar: a 2-process `ClusterSession` reproduces the
    single-process `Session` bit-for-bit, and checkpoints round-trip
    across process counts with exact RNG replay. These run in the
    dedicated `multihost` CI job.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import ClusterSession, DFLConfig, HistoryRecorder, Session
from repro.api.rounds import build_round
from repro.checkpoint import load_pytree
from repro.core import build_lora_tree, round_masks
from repro.core.topology import make_topology
from repro.data import federated_batches, label_skew_partitions, make_task
from repro.dist import multihost, sharding
from repro.launch.cluster import failed_ranks, spawn_simulated
from repro.models.classifier import (classifier_loss, encoder_config,
                                     init_classifier)
from repro.optim import AdamW
from repro.scenarios.schedule import BroadcastSchedule, GossipSchedule

ENC_KW = dict(n_layers=1, d_model=32, n_heads=2, d_ff=64, vocab_size=256)


def _clf_config(**kw):
    base = dict(model="encoder", task="sst2", model_kw=ENC_KW, n_clients=4,
                rounds=6, local_steps=2, batch_size=8, p=0.5, T=2,
                lr=1e-3, seed=0)
    base.update(kw)
    return DFLConfig(**base)


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# tier-1: helpers + degenerate single-process behaviour
# ---------------------------------------------------------------------------

def test_multihost_helpers_single_process():
    assert not multihost.is_distributed()
    assert multihost.is_primary()
    assert multihost.process_count() == 1
    mesh = multihost.cluster_mesh()
    assert mesh.axis_names == ("data",)
    slc = multihost.local_client_slice(8, mesh)
    assert (slc.start, slc.stop) == (0, 8)

    class _Grid9:                       # mesh stub: 9 devices
        size = 9
    with pytest.raises(ValueError):
        multihost.local_client_slice(4, _Grid9())
    # replicate / shard / gather round-trip exactly
    x = np.arange(24, dtype=np.float32).reshape(4, 3, 2)
    g = multihost.shard_clients(mesh, x[multihost.local_client_slice(4, mesh)],
                                x.shape, axis=0)
    np.testing.assert_array_equal(np.asarray(g), x)
    r = multihost.replicate(mesh, x)
    np.testing.assert_array_equal(np.asarray(r), x)
    back = multihost.to_host({"x": g}, mesh)
    np.testing.assert_array_equal(back["x"], x)
    multihost.sync("noop")  # single-process barrier is a no-op


def test_broadcast_schedule_passthrough_single_process():
    """On one process the wrapper must not perturb the inner schedule's
    stream — same matrices, same dtype, same RNG advancement."""
    topo_a = make_topology("complete", 4, 0.5, seed=3)
    topo_b = make_topology("complete", 4, 0.5, seed=3)
    inner, wrapped = GossipSchedule(topo_a), \
        BroadcastSchedule(GossipSchedule(topo_b))
    assert wrapped.m == 4 and wrapped.symmetric is False
    for t in range(5):
        np.testing.assert_array_equal(inner.next_w(t), wrapped.next_w(t))


def test_session_mix_gather_follows_process_count(monkeypatch):
    """`Session` builds its round with the pre-mix all-gather on exactly
    when the run spans processes."""
    from repro.api import session as session_mod
    seen = []
    real = session_mod.build_round

    def recording(*args, **kw):
        seen.append(kw)
        return real(*args, **kw)

    monkeypatch.setattr(session_mod, "build_round", recording)
    session_mod.clear_build_cache()
    Session(_clf_config(rounds=1))
    assert [kw["mix_gather"] for kw in seen] == [jax.process_count() > 1]


def test_mix_gather_bitwise_noop_single_process():
    """mix_gather pins the communication lowering; it must not change a
    single bit of the single-process numerics."""
    cfg = encoder_config(**ENC_KW)
    base = init_classifier(jax.random.key(0), cfg, n_classes=2)
    lora0 = build_lora_tree(jax.random.key(1), base, cfg, n_clients=4)
    opt = AdamW(lr=1e-3)

    def loss_fn(bp, lo, micro):
        return classifier_loss(bp, cfg, micro["tokens"], micro["labels"],
                               lora=lo)

    topo = make_topology("complete", 4, p=0.5, seed=0)
    feed = [(jax.tree.map(jnp.asarray, batch),
             jnp.asarray(topo.sample(), jnp.float32),
             round_masks("tad", t, 2).as_array())
            for t, batch in enumerate(federated_batches(
                make_task("sst2", vocab_size=256),
                label_skew_partitions(2, 4), 8, 2, 3, seed=0))]
    out = []
    for gather in (False, True):
        round_fn = jax.jit(build_round(loss_fn, opt, local_steps=2,
                                       mix_gather=gather))
        lora, opt_state = lora0, opt.init(lora0)
        for batch, W, masks in feed:
            lora, opt_state, _ = round_fn(base, lora, opt_state, batch, W,
                                          masks)
        out.append(lora)
    _assert_trees_equal(*out)


def test_cluster_session_degenerate_matches_session():
    """A 1-process ClusterSession is an exact Session: same losses, same
    final state, and no leaked mesh binding afterwards."""
    assert sharding.current_mesh() is None
    rec_c, rec_s = HistoryRecorder(), HistoryRecorder()
    cs = ClusterSession(_clf_config(), callbacks=[rec_c])
    cs.run()
    assert sharding.current_mesh() is None      # _bound() restored state
    ss = Session(_clf_config(), callbacks=[rec_s])
    ss.run()
    assert [h["loss"] for h in rec_c.history] == \
        [h["loss"] for h in rec_s.history]
    _assert_trees_equal(cs.lora, ss.lora)


def test_cluster_checkpoint_interop_single_process(tmp_path):
    """ClusterSession.save writes Session's exact checkpoint format."""
    path = os.path.join(tmp_path, "cs.npz")
    cs = ClusterSession(_clf_config())
    cs.run(3)
    cs.save(path)
    cs.run(3)
    resumed = Session(_clf_config())
    assert resumed.restore(path) == 3
    resumed.run(3)
    _assert_trees_equal(cs.lora, resumed.lora)


# ---------------------------------------------------------------------------
# -m multihost: real simulated process grids (dedicated CI job)
# ---------------------------------------------------------------------------

def _spawn_ok(n, args, timeout=600.0):
    results = spawn_simulated(n, args, timeout=timeout)
    bad = failed_ranks(results)
    assert not bad, "\n".join(report for _, report in bad)
    return results


@pytest.mark.multihost
def test_two_process_parity_bitwise(tmp_path):
    """THE acceptance bar: a 2-process simulated ClusterSession reproduces
    the single-process Session's params bit-for-bit for the same
    DFLConfig/seed — local training shard-local, W_t broadcast from rank
    0, gossip mix through the cross-process all-gather."""
    config = _clf_config()
    cfg_path = os.path.join(tmp_path, "cfg.json")
    ckpt = os.path.join(tmp_path, "cluster2.npz")
    out_json = os.path.join(tmp_path, "cluster2.json")
    with open(cfg_path, "w") as f:
        json.dump(config.to_dict(), f)
    _spawn_ok(2, ["--config", cfg_path, "--ckpt", ckpt,
                  "--json", out_json, "--eval", "--quiet"])

    rec = HistoryRecorder()
    single = Session(config, callbacks=[rec])
    single.run()

    tree = load_pytree(ckpt)
    _assert_trees_equal(tree["lora"], single.lora)
    _assert_trees_equal(tree["opt"]["mu"], single.opt_state.mu)
    payload = json.load(open(out_json))
    assert payload["n_processes"] == 2
    assert payload["final_loss"] == rec.history[-1]["loss"]
    # dense run: the measured collective payload is the dense all-gather,
    # with the sparse alternative reported alongside for comparison
    assert payload["mix_comm"] == "dense"
    assert payload["comm_bytes_per_round"] > 0
    assert payload["comm_bytes_per_round"] == \
        payload["dense_comm_bytes_per_round"]
    # complete graph at 4 clients / 2 shards: every row is a border row,
    # so the sparse halo carries exactly the dense byte count (strict
    # reduction on sparser graphs is pinned in tests/test_comm.py)
    assert 0 < payload["sparse_comm_bytes_per_round"] <= \
        payload["dense_comm_bytes_per_round"]
    # evaluate() works on the grid (global eval batch + sharded lora
    # slices) and scores identically to the single-process run
    assert payload["eval_acc"] == single.evaluate(n=64)["acc"]


@pytest.mark.multihost
def test_two_process_parity_adaptive_T(tmp_path):
    """Adaptive-T parity: the online controller consumes the RAW W_t at
    full precision, so the broadcast must be bit-exact (not a float32
    shadow) or the two sides can pick different T at a decision boundary.
    Guards the float64 byte-transport in `BroadcastSchedule`."""
    config = _clf_config(adaptive_T=True, rounds=6)
    cfg_path = os.path.join(tmp_path, "cfg.json")
    ckpt = os.path.join(tmp_path, "adaptive2.npz")
    with open(cfg_path, "w") as f:
        json.dump(config.to_dict(), f)
    _spawn_ok(2, ["--config", cfg_path, "--ckpt", ckpt, "--quiet"])

    single = Session(config)
    single.run()
    _assert_trees_equal(load_pytree(ckpt)["lora"], single.lora)


@pytest.mark.multihost
def test_checkpoint_across_process_counts(tmp_path):
    """Save under a 2-process ClusterSession, restore single-process:
    params AND the replayed RNG streams must line up exactly — the
    restored run continues bit-for-bit into the same final state as an
    uninterrupted single-process run."""
    config = _clf_config(rounds=6)
    cfg_path = os.path.join(tmp_path, "cfg.json")
    ckpt = os.path.join(tmp_path, "half.npz")
    with open(cfg_path, "w") as f:
        json.dump(config.to_dict(), f)
    # 2-process grid runs the FIRST 3 rounds and checkpoints
    _spawn_ok(2, ["--config", cfg_path, "--run-rounds", "3",
                  "--ckpt", ckpt, "--quiet"])

    # single-process restore: replays data/topology/schedule RNGs 0..2,
    # then runs rounds 3..5
    resumed = Session(config)
    assert resumed.restore(ckpt) == 3
    resumed.run(3)

    # reference: uninterrupted single-process run of all 6 rounds
    full = Session(config)
    full.run()

    assert resumed.t == full.t == 6
    _assert_trees_equal(resumed.lora, full.lora)
    _assert_trees_equal(resumed.opt_state.mu, full.opt_state.mu)
    _assert_trees_equal(resumed.opt_state.nu, full.opt_state.nu)


@pytest.mark.multihost
def test_restore_into_two_process_grid(tmp_path):
    """The reverse direction: a single-process checkpoint restores into a
    2-process grid and continues to the same final state."""
    config = _clf_config(rounds=6)
    cfg_path = os.path.join(tmp_path, "cfg.json")
    half = os.path.join(tmp_path, "half1p.npz")
    done = os.path.join(tmp_path, "done2p.npz")
    with open(cfg_path, "w") as f:
        json.dump(config.to_dict(), f)

    first = Session(config)
    first.run(3)
    first.save(half)

    _spawn_ok(2, ["--config", cfg_path, "--restore", half,
                  "--run-rounds", "3", "--ckpt", done, "--quiet"])

    full = Session(config)
    full.run()
    _assert_trees_equal(load_pytree(done)["lora"], full.lora)


# ---------------------------------------------------------------------------
# -m multihost: topology-sparse gossip (mix_comm) on real grids
# ---------------------------------------------------------------------------

SPARSE_FAMILIES = ("ring", "torus", "exponential", "small_world",
                   "erdos_renyi", "complete")


def _sparse_cfg(**kw):
    base = dict(model="encoder", task="sst2", model_kw=ENC_KW, n_clients=8,
                rounds=3, local_steps=2, batch_size=8, scenario="static",
                topology="ring", p=0.5, T=2, lr=1e-3, seed=0,
                mix_comm="sparse")
    base.update(kw)
    return DFLConfig(**base)


def _spawn_ckpt(n, config, tmp_path, tag, extra=()):
    cfg_path = os.path.join(tmp_path, f"{tag}.json")
    ckpt = os.path.join(tmp_path, f"{tag}.npz")
    with open(cfg_path, "w") as f:
        json.dump(config.to_dict(), f)
    _spawn_ok(n, ["--config", cfg_path, "--ckpt", ckpt, "--quiet", *extra])
    return load_pytree(ckpt)


@pytest.mark.multihost
@pytest.mark.parametrize("topology", SPARSE_FAMILIES)
def test_sparse_parity_bitwise_across_grids(topology, tmp_path):
    """mix_comm='sparse' on a static graph is the dense algorithm with a
    smaller exchange: a 2-process grid must reproduce the single-process
    run bit-for-bit for EVERY library graph family (each exercises a
    different CommPlan shape — border rows only, asymmetric exports,
    all-rows-remote on complete)."""
    config = _sparse_cfg(topology=topology)
    tree = _spawn_ckpt(2, config, tmp_path, f"sparse2_{topology}")
    single = Session(config)
    single.run()
    _assert_trees_equal(tree["lora"], single.lora)
    if topology == "ring":
        # and the sparse lowering IS dense end-to-end (same grid count)
        dense = Session(_sparse_cfg(topology=topology, mix_comm="dense"))
        dense.run()
        _assert_trees_equal(tree["lora"], dense.lora)


@pytest.mark.multihost
def test_sparse_four_process_parity_and_comm_bytes(tmp_path):
    """4 shards of a ring: parity still bitwise, and the reported
    collective payload is the SPARSE halo figure. At 8 clients / 4
    shards every ring row is a border row, so the halo carries exactly
    the dense byte count (the win at this ratio is fewer/smaller
    collectives, not bytes — strict byte reduction is asserted at 2
    shards, where interior rows exist)."""
    config = _sparse_cfg()
    out_json = os.path.join(tmp_path, "sparse4.json")
    tree = _spawn_ckpt(4, config, tmp_path, "sparse4",
                       extra=["--json", out_json])
    single = Session(config)
    single.run()
    _assert_trees_equal(tree["lora"], single.lora)
    payload = json.load(open(out_json))
    assert payload["mix_comm"] == "sparse"
    assert payload["comm_bytes_per_round"] == \
        payload["sparse_comm_bytes_per_round"] > 0
    assert payload["sparse_comm_bytes_per_round"] == \
        payload["dense_comm_bytes_per_round"]


@pytest.mark.multihost
def test_sparse_overlap_parity_across_grids(tmp_path):
    """Overlapped (one-round-delayed) gossip is a DIFFERENT algorithm
    from dense, but its semantics must not depend on the process count:
    1-, 2- and 4-process grids land on identical states."""
    config = _sparse_cfg(mix_comm="sparse_overlap", rounds=4)
    tree2 = _spawn_ckpt(2, config, tmp_path, "overlap2")
    tree4 = _spawn_ckpt(4, config, tmp_path, "overlap4")
    single = Session(config)
    single.run()
    _assert_trees_equal(tree2["lora"], single.lora)
    _assert_trees_equal(tree4["lora"], single.lora)
    _assert_trees_equal(tree2["opt"]["mu"], single.opt_state.mu)
    # and it genuinely differs from the dense algorithm on a ring
    dense = Session(_sparse_cfg(mix_comm="dense", rounds=4))
    dense.run()
    import jax as _jax
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(_jax.tree.leaves(dense.lora),
                        _jax.tree.leaves(single.lora)))


# ---------------------------------------------------------------------------
# shards data source on process grids (streaming data layer)
# ---------------------------------------------------------------------------

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "shards",
                       "mnli_tiny")


def _shards_cfg(**kw):
    base = dict(model="encoder", task="mnli", model_kw=ENC_KW, n_clients=8,
                rounds=4, local_steps=2, batch_size=4, p=0.6, T=2,
                lr=1e-3, seed=0, data_source="shards", data_path=FIXTURE,
                partitioner="domain")
    base.update(kw)
    return DFLConfig(**base)


def test_cluster_degenerate_on_shards():
    """Tier-1: a 1-process ClusterSession on the shards data source is an
    exact Session (the stream is drawn globally; _to_device slices the
    local client block, which on one process is everything)."""
    cs = ClusterSession(_shards_cfg(rounds=3))
    cs.run()
    ss = Session(_shards_cfg(rounds=3))
    ss.run()
    _assert_trees_equal(cs.lora, ss.lora)


@pytest.mark.multihost
def test_shards_batch_order_invariant_across_grids(tmp_path):
    """1-, 2- and 4-process grids see the identical global batch order:
    `FederatedStream.round_batch(t)` is a pure function of the round
    index drawn identically on every process, so sharding the client
    axis cannot perturb a single sample — final params are bitwise equal
    across process counts."""
    config = _shards_cfg()
    tree2 = _spawn_ckpt(2, config, tmp_path, "shards2")
    tree4 = _spawn_ckpt(4, config, tmp_path, "shards4")
    single = Session(config)
    single.run()
    _assert_trees_equal(tree2["lora"], single.lora)
    _assert_trees_equal(tree4["lora"], single.lora)
    _assert_trees_equal(tree2["opt"]["mu"], single.opt_state.mu)
    _assert_trees_equal(tree4["opt"]["nu"], single.opt_state.nu)


@pytest.mark.multihost
def test_shards_midepoch_ckpt_across_process_counts(tmp_path):
    """A 2-process grid checkpoints MID-EPOCH (round 3 of a 6-round client
    epoch on the fixture); a single-process restore seeks the stream to
    the saved round and continues bit-for-bit into the same final state
    as an uninterrupted run."""
    config = _shards_cfg(rounds=6)
    cfg_path = os.path.join(tmp_path, "cfg.json")
    ckpt = os.path.join(tmp_path, "shards_half.npz")
    with open(cfg_path, "w") as f:
        json.dump(config.to_dict(), f)
    _spawn_ok(2, ["--config", cfg_path, "--run-rounds", "3",
                  "--ckpt", ckpt, "--quiet"])

    resumed = Session(config)
    assert resumed.restore(ckpt) == 3
    resumed.run(3)
    full = Session(config)
    full.run()
    _assert_trees_equal(resumed.lora, full.lora)
    _assert_trees_equal(resumed.opt_state.mu, full.opt_state.mu)


@pytest.mark.multihost
def test_cold_join_warm_start_parity_on_grid(tmp_path):
    """Cold-join adapter warm start on a grid: the joiner repair is a
    host-side client-axis matrix applied to the GLOBAL state (gathered,
    repaired, re-sharded), so a 2-process hierarchical cold-join run must
    land bitwise on the single-process result."""
    config = _shards_cfg(rounds=5, scenario="cold_join",
                         topology="hierarchical",
                         topology_kw=dict(hier_silos=3),
                         scenario_kw=dict(joiners=2, join_round=2))
    tree2 = _spawn_ckpt(2, config, tmp_path, "coldjoin2")
    single = Session(config)
    single.run()
    _assert_trees_equal(tree2["lora"], single.lora)
    _assert_trees_equal(tree2["opt"]["mu"], single.opt_state.mu)


# ---------------------------------------------------------------------------
# -m multihost: compressed gossip (mix_quant) on real grids
# ---------------------------------------------------------------------------

@pytest.mark.multihost
def test_quant_parity_across_grids_and_bytes(tmp_path):
    """int8 compressed gossip is grid-invariant: per-row quantization of a
    shard's block equals the global quantization of those rows, so 1-, 2-
    and 4-process grids land on identical states AND identical EF
    buffers. The reported wire payload is the compressed figure, at most
    0.3x the fp32 sparse bytes (the acceptance ratio)."""
    config = _sparse_cfg(mix_comm="sparse_overlap", mix_quant="int8",
                         rounds=4)
    out_json = os.path.join(tmp_path, "quant4.json")
    tree2 = _spawn_ckpt(2, config, tmp_path, "quant2")
    tree4 = _spawn_ckpt(4, config, tmp_path, "quant4",
                        extra=["--json", out_json])
    single = Session(config)
    single.run()
    _assert_trees_equal(tree2["lora"], single.lora)
    _assert_trees_equal(tree4["lora"], single.lora)
    assert single.ef is not None
    np.testing.assert_array_equal(np.asarray(tree2["ef"]),
                                  np.asarray(single.ef))
    np.testing.assert_array_equal(np.asarray(tree4["ef"]),
                                  np.asarray(single.ef))
    payload = json.load(open(out_json))
    assert payload["mix_quant"] == "int8"
    quant_b = payload["sparse_quant_comm_bytes_per_round"]
    assert payload["comm_bytes_per_round"] == quant_b > 0
    assert quant_b <= 0.3 * payload["sparse_comm_bytes_per_round"]


@pytest.mark.multihost
def test_quant_ckpt_restores_into_two_process_grid(tmp_path):
    """A single-process quant checkpoint restores into a 2-process grid
    and continues to the same final state as the uninterrupted run (the
    EF buffer re-globalizes onto the grid)."""
    config = _sparse_cfg(mix_comm="sparse", mix_quant="int8", rounds=4)
    half = Session(config)
    half.run(2)
    ckpt = os.path.join(tmp_path, "quant_half.npz")
    half.save(ckpt)
    full = Session(config)
    full.run()
    cfg_path = os.path.join(tmp_path, "quant_restore.json")
    out = os.path.join(tmp_path, "quant_restored.npz")
    with open(cfg_path, "w") as f:
        json.dump(config.to_dict(), f)
    _spawn_ok(2, ["--config", cfg_path, "--restore", ckpt,
                  "--run-rounds", "2", "--ckpt", out, "--quiet"])
    tree = load_pytree(out)
    _assert_trees_equal(tree["lora"], full.lora)
    np.testing.assert_array_equal(np.asarray(tree["ef"]),
                                  np.asarray(full.ef))
