"""Integration tests of the DFL engine: method semantics, phase behaviour,
consensus dynamics — the paper's mechanics at CPU scale."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (build_lora_tree, consensus_stats, make_dfl_round,
                        make_topology, mix_tree, round_masks)
from repro.core.alternating import phase_is_a
from repro.data import federated_batches, label_skew_partitions, make_task
from repro.models.classifier import (classifier_loss, encoder_config,
                                     init_classifier)
from repro.optim import AdamW

M = 6


@pytest.fixture(scope="module")
def setup():
    cfg = encoder_config(n_layers=1, d_model=32, n_heads=2, d_ff=64,
                         vocab_size=256)
    key = jax.random.key(0)
    base = init_classifier(key, cfg, n_classes=2)
    lora = build_lora_tree(jax.random.key(1), base, cfg, n_clients=M)
    opt = AdamW(lr=1e-3)

    def loss_fn(bp, lo, micro):
        return classifier_loss(bp, cfg, micro["tokens"], micro["labels"],
                               lora=lo)

    round_fn = jax.jit(make_dfl_round(loss_fn, opt, local_steps=2))
    task = make_task("sst2", vocab_size=256)
    parts = label_skew_partitions(2, M)
    return cfg, base, lora, opt, round_fn, task, parts


def _run(setup, method, rounds=6, T=2, p=1.0, seed=0):
    cfg, base, lora, opt, round_fn, task, parts = setup
    topo = make_topology("complete", M, p=p, seed=seed)
    opt_state = opt.init(lora)
    for t, batch in enumerate(federated_batches(task, parts, 8, 2, rounds,
                                                seed=seed)):
        W = jnp.asarray(topo.sample(), jnp.float32)
        masks = round_masks(method, t, T).as_array()
        lora, opt_state, metrics = round_fn(base, lora, opt_state,
                                            jax.tree.map(jnp.asarray, batch),
                                            W, masks)
    return lora, metrics


def test_phase_schedule():
    # B-phase when floor(t/T) even (paper Algorithm 1)
    assert not phase_is_a(0, 3) and not phase_is_a(2, 3)
    assert phase_is_a(3, 3) and phase_is_a(5, 3)
    assert not phase_is_a(6, 3)


def test_ffa_freezes_a(setup):
    _, _, lora0, *_ = setup
    lora, _ = _run(setup, "ffa", rounds=4)
    for (p1, l1), (_, l0) in zip(
            jax.tree_util.tree_flatten_with_path(lora)[0],
            jax.tree_util.tree_flatten_with_path(lora0)[0]):
        name = p1[-1].key
        if name == "a":
            np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                                       atol=1e-7)
        else:
            assert float(jnp.max(jnp.abs(l1 - l0))) > 0


def test_alternating_updates_one_block_per_phase(setup):
    cfg, base, lora0, opt, round_fn, task, parts = setup
    opt_state = opt.init(lora0)
    batch = next(iter(federated_batches(task, parts, 8, 2, 1)))
    W = jnp.eye(M, dtype=jnp.float32)
    # round 0 with T=1 -> B-phase: a must stay (identity mixing)
    masks = round_masks("tad", 0, 1).as_array()
    lora1, _, _ = round_fn(base, lora0, opt_state,
                           jax.tree.map(jnp.asarray, batch), W, masks)
    for (p, l1), (_, l0) in zip(
            jax.tree_util.tree_flatten_with_path(lora1)[0],
            jax.tree_util.tree_flatten_with_path(lora0)[0]):
        if p[-1].key == "a":
            np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                                       atol=1e-7)
    # round 1 with T=1 -> A-phase: b must stay
    masks = round_masks("tad", 1, 1).as_array()
    lora2, _, _ = round_fn(base, lora1, opt_state,
                           jax.tree.map(jnp.asarray, batch), W, masks)
    for (p, l2), (_, l1) in zip(
            jax.tree_util.tree_flatten_with_path(lora2)[0],
            jax.tree_util.tree_flatten_with_path(lora1)[0]):
        if p[-1].key == "b":
            np.testing.assert_allclose(np.asarray(l2), np.asarray(l1),
                                       atol=1e-7)


def test_tad_joint_mixing_cuts_frozen_drift(setup):
    """TAD (joint mixing) must keep smaller frozen-block disagreement than
    RoLoRA (active-only mixing) under sparse communication — the paper's
    central mechanism (Fig. 2 rationale)."""
    lora_tad, _ = _run(setup, "tad", rounds=8, T=2, p=0.3, seed=3)
    lora_rol, _ = _run(setup, "rolora", rounds=8, T=2, p=0.3, seed=3)
    s_tad = consensus_stats(lora_tad)
    s_rol = consensus_stats(lora_rol)
    tot_tad = float(s_tad["delta_a_sq"] + s_tad["delta_b_sq"])
    tot_rol = float(s_rol["delta_a_sq"] + s_rol["delta_b_sq"])
    assert tot_tad < tot_rol


def test_loss_decreases(setup):
    """Held-out loss on a FIXED batch must improve after training
    (per-round losses are on heterogeneous fresh batches — too noisy)."""
    from repro.data.synthetic import eval_batch
    from repro.models.classifier import classifier_loss
    cfg, base, lora, opt, round_fn, task, parts = setup
    topo = make_topology("complete", M, p=1.0, seed=0)
    opt_state = opt.init(lora)
    ev = eval_batch(task, 128, seed=5)
    toks, labs = jnp.asarray(ev["tokens"]), jnp.asarray(ev["labels"])

    def held_out(lo):
        li = jax.tree.map(lambda x: x[..., 0, :, :], lo)
        return float(classifier_loss(base, cfg, toks, labs, lora=li))

    before = held_out(lora)
    for t, batch in enumerate(federated_batches(task, parts, 16, 2, 15,
                                                seed=1)):
        W = jnp.asarray(topo.sample(), jnp.float32)
        masks = round_masks("tad", t, 2).as_array()
        lora, opt_state, metrics = round_fn(base, lora, opt_state,
                                            jax.tree.map(jnp.asarray, batch),
                                            W, masks)
    after = held_out(lora)
    assert after < before, (before, after)


def test_identity_mixing_is_noop(setup):
    _, _, lora, *_ = setup
    W = jnp.eye(M, dtype=jnp.float32)
    mixed = mix_tree(W, lora, 1.0, 1.0)
    for l1, l0 in zip(jax.tree.leaves(mixed), jax.tree.leaves(lora)):
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l0), atol=1e-7)


def test_planned_round_matches_per_leaf_oracle(setup):
    """The round's planned fused mix must match the per-leaf oracle: the
    same round at W = I (the local steps alone) followed by
    `mix_tree(W, ...)` — same batch, same W, same state in."""
    _, base, lora0, opt, round_fn, task, parts = setup
    batch = jax.tree.map(jnp.asarray,
                         next(iter(federated_batches(task, parts, 8, 2, 1))))
    topo = make_topology("complete", M, p=0.5, seed=7)
    W = jnp.asarray(topo.sample(), jnp.float32)
    masks = round_masks("lora", 0, 1).as_array()    # equal mix masks
    l1, o1, m1 = round_fn(base, lora0, opt.init(lora0), batch, W, masks)
    l0, o2, m2 = round_fn(base, lora0, opt.init(lora0), batch,
                          jnp.eye(M, dtype=jnp.float32), masks)
    l2 = mix_tree(W, l0, masks[2], masks[3])
    for a, b in zip(jax.tree.leaves(l1), jax.tree.leaves(l2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(o1), jax.tree.leaves(o2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(m1["loss"]),
                                  np.asarray(m2["loss"]))


def test_round_mixes_with_one_gossip_kernel_call(setup, monkeypatch):
    """One traced round calls `ops.gossip_mix_seg` exactly once, with the
    masks as traced inputs, so every method and phase runs the one flat
    lowering the chip runs."""
    from repro.kernels import ops
    cfg, base, lora0, opt, _, task, parts = setup
    calls = []
    real = ops.gossip_mix_seg

    def counting(*args):
        calls.append(args[1].shape)
        return real(*args)

    monkeypatch.setattr(ops, "gossip_mix_seg", counting)

    def loss_fn(bp, lo, micro):
        return classifier_loss(bp, cfg, micro["tokens"], micro["labels"],
                               lora=lo)

    batch = jax.tree.map(jnp.asarray,
                         next(iter(federated_batches(task, parts, 8, 2, 1))))
    jax.make_jaxpr(make_dfl_round(loss_fn, opt, local_steps=2))(
        base, lora0, opt.init(lora0), batch,
        jnp.eye(M, dtype=jnp.float32), jnp.zeros((4,), jnp.float32))
    assert len(calls) == 1
    assert calls[0][0] == M and calls[0][1] % 512 == 0
