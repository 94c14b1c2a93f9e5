"""Mixing equivalence: the plan-cached mix_tree_planned against the
per-leaf mix_tree oracle, across mask regimes and leaf layouts, plus the
MixPlan cache contract (built once per tree signature)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mixing

M = 6


def _tree(key, m=M, dtype=jnp.float32):
    """Plain (m, d, r) and group-stacked (G, m, d, r) a/b leaves."""
    def n(i, shape):
        return jax.random.normal(jax.random.fold_in(key, i),
                                 shape).astype(dtype)
    return {
        "groups": [{"attn": {"wq": {"a": n(1, (3, m, 16, 4)),
                                    "b": n(2, (3, m, 4, 16))}}}],
        "tail": [{"ffn": {"a": n(3, (m, 10, 4)),
                          "b": n(4, (m, 4, 10))}},
                 {"attn": {"wv": {"a": n(5, (m, 24, 4)),
                                  "b": n(6, (m, 4, 24))}}}],
    }


def _w(key, m=M):
    W = jax.random.uniform(key, (m, m))
    W = W / W.sum(1, keepdims=True)
    W = 0.5 * (W + W.T)
    return W / W.sum(1, keepdims=True)


@pytest.mark.parametrize("mask_a,mask_b", [
    (1.0, 1.0),            # joint mixing (TAD)
    (1.0, 0.0),            # active-only / frozen-block no-mix (RoLoRA)
    (0.0, 1.0),
    (0.3, 0.7),            # fractional (damped-mixing variant)
])
def test_lowerings_agree(key, mask_a, mask_b):
    tree = _tree(key)
    W = _w(jax.random.fold_in(key, 99))
    oracle = mixing.mix_tree(W, tree, mask_a, mask_b)
    planned = mixing.mix_tree_planned(W, tree, mask_a, mask_b)
    for lo, lp in zip(jax.tree.leaves(oracle), jax.tree.leaves(planned)):
        np.testing.assert_allclose(np.asarray(lo), np.asarray(lp),
                                   rtol=2e-5, atol=1e-6)


def test_planned_identity_W_noop(key):
    tree = _tree(key)
    out = mixing.mix_tree_planned(jnp.eye(M, dtype=jnp.float32), tree,
                                  1.0, 1.0)
    for l1, l0 in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                                   atol=1e-6)


def test_plan_built_once_per_treedef(key):
    """The MixPlan is cached on the tree's static signature: repeated
    (jitted) mixing calls on same-structured trees never re-walk the tree
    in Python."""
    tree = _tree(key)
    W = _w(jax.random.fold_in(key, 97))
    fn = jax.jit(lambda W, t, a, b: mixing.mix_tree_planned(W, t, a, b))
    before = mixing.plan_builds()
    fn(W, tree, jnp.float32(1.0), jnp.float32(1.0))
    after_first = mixing.plan_builds()
    assert after_first <= before + 1
    tree2 = _tree(jax.random.fold_in(key, 5))      # same structure, new data
    fn(W, tree2, jnp.float32(1.0), jnp.float32(0.0))
    fn(W, tree, jnp.float32(0.3), jnp.float32(0.7))
    assert mixing.plan_builds() == after_first     # no rebuilds

    # a different structure (extra leaf) builds exactly one more plan
    tree3 = {**tree, "extra": {"a": jnp.ones((M, 8, 4)),
                               "b": jnp.zeros((M, 4, 8))}}
    mixing.mix_tree_planned(W, tree3, 1.0, 1.0)
    assert mixing.plan_builds() == after_first + 1


def test_plan_layout_matches_tree(key):
    tree = _tree(key)
    plan = mixing.get_mix_plan(tree)
    leaves = jax.tree.leaves(tree)
    assert plan.m == M
    assert plan.cols == sum(x.size for x in leaves) // M
    assert plan.padded % plan.bp == 0 and plan.padded >= plan.cols
    assert plan.a_indicator.shape == (1, plan.padded)
    # offsets are contiguous and in flatten order
    off = 0
    for slot, leaf in zip(plan.slots, leaves):
        assert slot.offset == off
        assert slot.cols == leaf.size // M
        off += slot.cols
    # segment indicator marks exactly the "a" columns
    n_a_cols = sum(s.cols for s in plan.slots if s.is_a)
    assert float(plan.a_indicator.sum()) == n_a_cols


def test_unknown_leaf_name_raises(key):
    """A LoRA tree with a leaf named neither 'a' nor 'b' is malformed —
    the oracle and the plan must refuse instead of silently mixing it
    as a 'b' leaf (the historical fallback)."""
    bad = {"attn": {"a": jnp.ones((M, 8, 4)), "c": jnp.zeros((M, 4, 8))}}
    W = _w(key)
    with pytest.raises(ValueError, match="'c'"):
        mixing.mix_tree(W, bad, 1.0, 1.0)
    with pytest.raises(ValueError, match="'c'"):
        mixing.build_mix_plan(bad)


def test_plan_cache_lru_bounded(key, monkeypatch):
    """The plan cache is LRU-bounded: churning tree signatures past the
    cap evicts the oldest entries instead of growing forever, recently
    used plans survive, and clear_mix_plans() empties it."""
    monkeypatch.setattr(mixing, "_PLAN_CACHE_MAX", 4)
    mixing.clear_mix_plans()

    def tree_of(cols):
        return {"a": jnp.ones((M, cols, 4)), "b": jnp.ones((M, 4, cols))}

    first = tree_of(3)
    mixing.get_mix_plan(first)
    for c in range(4, 10):
        mixing.get_mix_plan(tree_of(c))
        mixing.get_mix_plan(first)          # keep `first` recently used
        assert len(mixing._PLAN_CACHE) <= 4
    before = mixing.plan_builds()
    mixing.get_mix_plan(first)              # still cached: no rebuild
    assert mixing.plan_builds() == before
    mixing.get_mix_plan(tree_of(4))         # evicted: rebuilds
    assert mixing.plan_builds() == before + 1
    mixing.clear_mix_plans()
    assert len(mixing._PLAN_CACHE) == 0
    mixing.get_mix_plan(first)
    assert mixing.plan_builds() == before + 2


def test_resolve_bp_shrinks_to_divisor():
    from repro.kernels.gossip_mix import _resolve_bp
    assert _resolve_bp(1024, 512) == 512
    assert _resolve_bp(256, 512) == 256       # bp capped at P
    assert _resolve_bp(768, 512) == 256       # gcd fallback, not assert
    assert _resolve_bp(700, 512) == 4
    assert _resolve_bp(7, 512) == 7
    for P, bp in ((0, 512), (512, 0), (-8, 512)):
        with pytest.raises(ValueError):
            _resolve_bp(P, bp)


def test_gossip_mix_validation_raises_not_asserts(key):
    """Shape validation survives `python -O`: ValueError, not assert, and
    a non-multiple P runs via the divisor fallback instead of tripping."""
    from repro.kernels.gossip_mix import gossip_mix
    m = 4
    W = _w(key, m)
    x = jax.random.normal(jax.random.fold_in(key, 1), (m, 768))
    with pytest.raises(ValueError, match="w_eff"):
        gossip_mix(W[:3, :3], x, interpret=True)
    with pytest.raises(ValueError, match="seg"):
        gossip_mix(W, x, jnp.ones((1, 99)), interpret=True)
    # P=768 at the default bp=512: shrink-to-divisor keeps it running
    from repro.kernels import ref
    y = gossip_mix(W, x, interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.gossip_mix_ref(W, x)),
                               rtol=2e-5, atol=2e-5)


def test_gossip_mix_seg_kernel_interpret(key):
    """Segmented kernel (interpret) vs the jnp oracle, non-uniform seg."""
    from repro.kernels import ref
    from repro.kernels.gossip_mix import gossip_mix
    m, P = 8, 1024
    W = _w(key, m)
    x = jax.random.normal(jax.random.fold_in(key, 1), (m, P))
    seg = (jax.random.uniform(jax.random.fold_in(key, 2), (1, P)) > 0.5
           ).astype(jnp.float32) * 0.8
    y = gossip_mix(W, x, seg, interpret=True)
    yr = ref.gossip_mix_seg_ref(W, x, seg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)


def test_lora_matmul_interpret_nonsquare(key):
    """lora_matmul pallas-interpret vs ref at a non-square (M≠K≠N) shape."""
    from repro.kernels import ref
    from repro.kernels.lora_matmul import lora_matmul
    M_, K_, N_, r = 192, 320, 448, 8
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (M_, K_))
    w = jax.random.normal(ks[1], (K_, N_))
    a = jax.random.normal(ks[2], (K_, r)) * 0.1
    b = jax.random.normal(ks[3], (r, N_)) * 0.1
    y = lora_matmul(x, w, a, b, scale=1.5, bm=64, bn=64, bk=64,
                    interpret=True)
    yr = ref.lora_matmul_ref(x, w, a, b, 1.5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-4, atol=2e-3)
