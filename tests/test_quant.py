"""Compressed-gossip (mix_quant) units.

Covers the quantization core (`repro.core.mixing.quantize_rows` /
`dequantize_rows`), the fused `gossip_mix_quant` kernel against its ref
oracle, error-feedback threading through `mix_tree_sparse` (single-process
degenerate path; real grids live in `-m multihost`), and the config /
session surface: the `mix_quant` knob's validation, build-key separation,
the quant round signature, and checkpoint roundtrip of the EF buffer.
The Lemma A.10 contraction-budget predicate lives in `-m conformance`.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import DFLConfig, Session
from repro.core import mixing
from repro.core.topology import metropolis_weights, ring_graph
from repro.dist import comm
from repro.kernels import ops, ref

ENC_KW = dict(n_layers=1, d_model=32, n_heads=2, d_ff=64, vocab_size=256)


def _cfg(**kw):
    base = dict(model="encoder", task="sst2", model_kw=ENC_KW, n_clients=8,
                rounds=3, local_steps=2, batch_size=8, topology="ring",
                scenario="static", p=0.5, T=2, lr=1e-3, seed=0,
                mix_comm="sparse_overlap")
    base.update(kw)
    return DFLConfig(**base)


def _tree(key, m=8, d=16, r=4):
    ks = jax.random.split(key, 4)
    return {"q": {"a": jax.random.normal(ks[0], (m, d, r)),
                  "b": jax.random.normal(ks[1], (m, r, d))},
            "v": {"a": jax.random.normal(ks[2], (m, d, r)),
                  "b": jax.random.normal(ks[3], (m, r, d))}}


# ---------------------------------------------------------------------------
# quantization core
# ---------------------------------------------------------------------------

def test_quantize_rows_int8_roundtrip_error_bound(key):
    x = jax.random.normal(key, (6, 200)) * jnp.asarray(
        [[0.01], [1.0], [100.0], [1e-4], [3.0], [7.0]])
    q, scale = mixing.quantize_rows(x, "int8")
    assert q.dtype == jnp.int8 and scale.shape == (6, 1)
    err = np.abs(np.asarray(mixing.dequantize_rows(q, scale)) -
                 np.asarray(x, np.float32))
    # round-to-nearest: per-element error <= scale/2 for every row
    assert (err <= 0.5 * np.asarray(scale) + 1e-12).all()
    # the row max maps to the top of the range
    assert (np.abs(np.asarray(q)).max(axis=1) == 127).all()


def test_quantize_rows_fp8_roundtrip(key):
    x = jax.random.normal(key, (4, 128))
    q, scale = mixing.quantize_rows(x, "fp8")
    assert q.dtype == jnp.float8_e4m3fn
    deq = np.asarray(mixing.dequantize_rows(q, scale))
    # e4m3 keeps ~2 decimal digits: relative row error well under 10%
    np.testing.assert_allclose(deq, np.asarray(x), atol=float(
        np.abs(np.asarray(x)).max()) * 0.1)


def test_quantize_rows_zero_row_is_exact():
    x = jnp.stack([jnp.zeros(64), jnp.ones(64)])
    for mode in ("int8", "fp8"):
        q, scale = mixing.quantize_rows(x, mode)
        deq = np.asarray(mixing.dequantize_rows(q, scale))
        np.testing.assert_array_equal(deq[0], np.zeros(64))   # no 0/0
        np.testing.assert_allclose(deq[1], np.ones(64), rtol=1e-2)


def test_quantize_rows_unknown_mode_raises(key):
    with pytest.raises(ValueError):
        mixing.quantize_rows(jnp.ones((2, 8)), "int4")


# ---------------------------------------------------------------------------
# the fused quant kernel vs its oracle
# ---------------------------------------------------------------------------

def test_gossip_mix_quant_kernel_interpret_vs_ref(key):
    from repro.kernels.gossip_mix import gossip_mix_quant
    m, P = 8, 1024
    ks = jax.random.split(key, 4)
    W = jax.random.uniform(ks[0], (m, m))
    W = W / W.sum(1, keepdims=True)
    w_off = W - jnp.diag(jnp.diag(W))
    w_diag = jnp.diag(W)[:, None]
    x = jax.random.normal(ks[1], (m, P))
    q, scale = mixing.quantize_rows(
        jax.random.normal(ks[2], (m, P)), "int8")
    seg = (jax.random.uniform(ks[3], (1, P)) > 0.5).astype(jnp.float32)
    y = gossip_mix_quant(w_off, q, scale, x, w_diag, seg, interpret=True)
    yr = ref.gossip_mix_quant_ref(w_off, q, scale, x, w_diag, seg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)


def test_ops_quant_dispatch_pads_non_multiple_P(key):
    """The ops wrapper pads q/x/seg to the kernel stripe and slices back;
    zero int8 pad columns dequantize to exact zeros, so padded-and-sliced
    equals the unpadded oracle."""
    m, P = 6, 700          # not a multiple of 512
    ks = jax.random.split(key, 3)
    w_off = jax.random.uniform(ks[0], (m, m)) * (1 - jnp.eye(m))
    w_diag = jax.random.uniform(ks[1], (m, 1))
    x = jax.random.normal(ks[2], (m, P))
    q, scale = mixing.quantize_rows(x, "int8")
    seg = jnp.ones((1, P), jnp.float32)
    expect = ref.gossip_mix_quant_ref(w_off, q, scale, x, w_diag, seg)
    prev = ops._FORCE
    ops.set_backend("pallas_interpret")
    try:
        got = ops.gossip_mix_quant(w_off, q, scale, x, w_diag, seg)
    finally:
        ops.set_backend(prev)
    assert got.shape == (m, P)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# mix_tree_sparse quant semantics (degenerate single-process path)
# ---------------------------------------------------------------------------

def test_quant_mix_close_to_exact_and_updates_ef(key):
    W = jnp.asarray(metropolis_weights(ring_graph(8)), jnp.float32)
    lora = _tree(key)
    plan = mixing.get_mix_plan(lora)
    ef0 = jnp.zeros((8, plan.cols), jnp.float32)
    exact = mixing.mix_tree_sparse(W, lora, 1.0, 1.0, comm_plan=None)
    mixed, ef1 = mixing.mix_tree_sparse(
        W, lora, 1.0, 1.0, comm_plan=None, quant="int8", ef=ef0)
    # int8 off-diagonal noise stays ~1% of the signal
    for a, b in zip(jax.tree.leaves(exact), jax.tree.leaves(mixed)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=0.05)
    assert ef1.shape == (8, plan.cols)
    assert float(jnp.abs(ef1).max()) > 0          # residual captured
    # EF is exactly the quantization residual of the source rows
    flat = jnp.concatenate(
        [jnp.moveaxis(x, -3, 0).reshape(8, -1)
         for x in jax.tree.leaves(lora)], axis=1)
    q, scale = mixing.quantize_rows(flat, "int8")
    np.testing.assert_allclose(
        np.asarray(ef1),
        np.asarray(flat - mixing.dequantize_rows(q, scale)),
        rtol=1e-5, atol=1e-7)


def test_quant_overlap_reads_prev_round_sources(key):
    """Under overlap the quantized off-diagonal terms read the PREVIOUS
    state: y = diag(W)·post + offdiag(W)·deq(Q(pre + ef))."""
    W = jnp.asarray(metropolis_weights(ring_graph(8)), jnp.float32)
    post, pre = _tree(key), _tree(jax.random.fold_in(key, 1))
    plan = mixing.get_mix_plan(post)
    ef0 = jnp.zeros((8, plan.cols), jnp.float32)
    got, _ = mixing.mix_tree_sparse(W, post, 1.0, 1.0, comm_plan=None,
                                    lora_prev=pre, quant="int8", ef=ef0)
    pre_flat = np.concatenate(
        [np.moveaxis(np.asarray(x), -3, 0).reshape(8, -1)
         for x in jax.tree.leaves(pre)], axis=1)
    q, scale = mixing.quantize_rows(jnp.asarray(pre_flat), "int8")
    deq = np.asarray(mixing.dequantize_rows(q, scale))
    Wn = np.asarray(W)
    Wd, Wo = np.diag(np.diag(Wn)), Wn - np.diag(np.diag(Wn))
    post_flat = np.concatenate(
        [np.moveaxis(np.asarray(x), -3, 0).reshape(8, -1)
         for x in jax.tree.leaves(post)], axis=1)
    expect = Wd @ post_flat + Wo @ deq
    got_flat = np.concatenate(
        [np.moveaxis(np.asarray(x), -3, 0).reshape(8, -1)
         for x in jax.tree.leaves(got)], axis=1)
    np.testing.assert_allclose(got_flat, expect, rtol=1e-4, atol=1e-5)


def test_quant_requires_ef_and_known_mode(key):
    W = jnp.asarray(metropolis_weights(ring_graph(8)), jnp.float32)
    lora = _tree(key)
    with pytest.raises(ValueError, match="error-feedback"):
        mixing.mix_tree_sparse(W, lora, 1.0, 1.0, comm_plan=None,
                               quant="int8")
    with pytest.raises(ValueError, match="quant mode"):
        mixing.mix_tree_sparse(W, lora, 1.0, 1.0, comm_plan=None,
                               quant="int4")


# ---------------------------------------------------------------------------
# byte accounting
# ---------------------------------------------------------------------------

def test_sparse_recv_bytes_quant_accounting():
    cp = comm.build_comm_plan(ring_graph(8), n_shards=2)
    cols = 512
    fp32 = cp.sparse_recv_bytes(cols)
    q = cp.sparse_recv_bytes_quant(cols)
    assert q == (1 * cols + 4) * cp.k * (cp.n_shards - 1)
    # the acceptance ratio: int8+scale <= 0.3x the fp32 sparse bytes
    assert q <= 0.3 * fp32
    assert comm.build_comm_plan(ring_graph(8),
                                n_shards=1).sparse_recv_bytes_quant(cols) == 0


# ---------------------------------------------------------------------------
# config / session surface
# ---------------------------------------------------------------------------

def test_mix_quant_config_validation_and_cache_key():
    assert _cfg().mix_quant == "off" or True     # default checked below
    assert DFLConfig(model="encoder", task="sst2",
                     model_kw=ENC_KW).mix_quant == "off"
    with pytest.raises(ValueError):
        _cfg(mix_quant="int4")
    with pytest.raises(ValueError):
        _cfg(mix_comm="dense", mix_quant="int8")   # quant needs sparse
    keys = {_cfg(mix_quant=m).cache_key() for m in ("off", "int8", "fp8")}
    assert len(keys) == 3, "mix_quant must enter the cache key"


def test_quant_round_signature_and_off_unchanged():
    """mix_quant='off' keeps the exact 6-arg round; quant rounds take the
    EF buffer and return it — the 'off' path is never re-traced or
    re-shaped by the feature existing."""
    off = Session(_cfg(mix_quant="off"))
    assert off.ef is None
    q = Session(_cfg(mix_quant="int8"))
    plan = mixing.get_mix_plan(q.lora)
    assert q.ef is not None and q.ef.shape == (8, plan.cols)
    assert off.round_fn is not q.round_fn
    res = q.run()
    assert np.isfinite(res.final_loss)
    assert float(jnp.abs(q.ef).max()) > 0


def test_quant_session_checkpoint_roundtrip(tmp_path):
    """save/restore carries the EF buffer: a restored quant session
    continues bit-for-bit with the original."""
    a = Session(_cfg(mix_quant="int8", rounds=4))
    a.run(2)
    ckpt = str(tmp_path / "q.npz")
    a.save(ckpt)
    b = Session(_cfg(mix_quant="int8", rounds=4))
    assert b.restore(ckpt) == 2
    np.testing.assert_array_equal(np.asarray(a.ef), np.asarray(b.ef))
    a.run(2)
    b.run(2)
    for x, y in zip(jax.tree.leaves(a.lora), jax.tree.leaves(b.lora)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(np.asarray(a.ef), np.asarray(b.ef))


def test_fp8_session_runs():
    res = Session(_cfg(mix_comm="sparse", mix_quant="fp8")).run()
    assert np.isfinite(res.final_loss)
