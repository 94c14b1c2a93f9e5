"""Pallas kernel validation: shape/dtype sweeps, interpret=True vs ref.py
oracle (task-mandated per-kernel allclose)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gossip_mix import gossip_mix
from repro.kernels.lora_matmul import lora_matmul, slot_lora_matmul
from repro.kernels.rglru_scan import rglru_scan

TOLS = {jnp.float32: 2e-4, jnp.bfloat16: 2e-2}


def _tol(dt):
    return TOLS[jnp.bfloat16 if dt == jnp.bfloat16 else jnp.float32]


@pytest.mark.parametrize("M,K,N,r", [(128, 128, 128, 8), (256, 384, 512, 16),
                                     (128, 256, 128, 4), (512, 128, 256, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_matmul(M, K, N, r, dtype, key):
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (M, K), dtype)
    w = jax.random.normal(ks[1], (K, N), dtype)
    a = (jax.random.normal(ks[2], (K, r)) * 0.1).astype(dtype)
    b = (jax.random.normal(ks[3], (r, N)) * 0.1).astype(dtype)
    y = lora_matmul(x, w, a, b, scale=2.0, interpret=True)
    yr = ref.lora_matmul_ref(x, w, a, b, 2.0)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=_tol(dtype), atol=K * _tol(dtype) * 0.05)


@pytest.mark.parametrize("B,K,N,r,n_ad", [(4, 128, 128, 8, 4),
                                          (3, 256, 384, 16, 8),
                                          (8, 128, 256, 4, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_slot_lora_matmul(B, K, N, r, n_ad, dtype, key):
    """Per-slot adapter gather kernel (multi-adapter serving) vs oracle,
    including repeated and out-of-order slot ids."""
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (B, K), dtype)
    w = jax.random.normal(ks[1], (K, N), dtype)
    a = (jax.random.normal(ks[2], (n_ad, K, r)) * 0.1).astype(dtype)
    b = (jax.random.normal(ks[3], (n_ad, r, N)) * 0.1).astype(dtype)
    rng = np.random.default_rng(B * K)
    slots = jnp.asarray(rng.integers(0, n_ad, size=B), jnp.int32)
    y = slot_lora_matmul(x, w, a, b, slots, scale=2.0, bk=64, interpret=True)
    yr = ref.slot_lora_matmul_ref(x, w, a, b, slots, 2.0)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=_tol(dtype), atol=K * _tol(dtype) * 0.05)


def test_slot_lora_matmul_matches_single_adapter(key):
    """Slot row i with adapter s is bit-for-bit the plain single-adapter
    lora path for (x_i, a[s], b[s]) — the serving-equals-training-math
    invariant multi-adapter decode relies on."""
    from repro.kernels import ops
    ks = jax.random.split(key, 4)
    B, K, N, r, n_ad = 4, 128, 192, 8, 6
    x = jax.random.normal(ks[0], (B, K))
    w = jax.random.normal(ks[1], (K, N))
    a = jax.random.normal(ks[2], (n_ad, K, r)) * 0.1
    b = jax.random.normal(ks[3], (n_ad, r, N)) * 0.1
    slots = jnp.asarray([5, 0, 5, 2], jnp.int32)
    y = ops.slot_lora_matmul(x, w, a, b, slots, 2.0)
    for i, s in enumerate([5, 0, 5, 2]):
        yi = x[i:i + 1] @ w + ((x[i:i + 1] @ a[s]) @ b[s]) * 2.0
        np.testing.assert_array_equal(np.asarray(y[i:i + 1]),
                                      np.asarray(yi))


def _case(S, L, window, causal, heads=(3, 3), block=64, d=64, tag=""):
    return pytest.param(S, L, window, causal, heads, block, d,
                        id=f"{S}-{L}-{window}-{causal}{tag}")


def _qkv(key, B, S, L, heads, d, dtype):
    H, KV = heads
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (B, S, H, d), dtype),
            jax.random.normal(ks[1], (B, L, KV, d), dtype),
            jax.random.normal(ks[2], (B, L, KV, d), dtype))


@pytest.mark.parametrize("S,L,window,causal,heads,block,d", [
    _case(128, 128, None, True), _case(256, 256, 64, True),
    _case(128, 128, None, False), _case(256, 256, 200, True),
    # grouped-query heads (kv head h // G); blocks, and sub-tiles of the
    # 256- and 512-row blocks, smaller than S, so that the fully-masked
    # ones are skipped
    _case(512, 512, None, True, (4, 1), 256, 128, "-gqa4x1"),
    _case(256, 256, 96, True, (6, 2), 64, 64, "-gqa6x2"),
    _case(512, 512, 200, True, (2, 2), 512, 128, "-mha2"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(S, L, window, causal, heads, block, d, dtype, key):
    """The kernel with products in the inputs' dtype (float32 ones
    under "highest") against the oracle at the same precision."""
    q, k, v = _qkv(key, 2, S, L, heads, d, dtype)
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        y = flash_attention(q, k, v, causal=causal, window=window, bq=block,
                            bk=block, interpret=True)
    yr = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=_tol(dtype), atol=_tol(dtype) * 10)


@pytest.mark.parametrize("S,window,heads,bq,bk", [
    (128, None, (4, 4), 64, 64), (256, 64, (4, 2), 64, 128),
    (512, None, (4, 1), 512, 512), (512, 200, (2, 2), 256, 256),
])
@pytest.mark.parametrize("precision,tol", [("highest", 1e-4),
                                           ("default", 1e-2)])
def test_flash_attention_grad(S, window, heads, bq, bk, precision, tol, key):
    """o, dq, dk and dv of the custom VJP against the float32 oracle
    differentiated at highest precision, as relative norms of the
    difference: under "highest" the products take float32 operands and
    agree to rounding; at the default (the chip's) they take bfloat16
    ones and agree to their operand rounding."""
    q, k, v = _qkv(key, 1, S, S, heads, 128, jnp.float32)
    do = jax.random.normal(jax.random.fold_in(key, 1), q.shape)

    def value_and_vjp(f):
        o, vjp = jax.vjp(f, q, k, v)
        return (o,) + vjp(do)

    with jax.default_matmul_precision(precision):
        got = value_and_vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, bq=bq, bk=bk,
            interpret=True))
    with jax.default_matmul_precision("highest"):
        want = value_and_vjp(lambda q, k, v: ref.flash_attention_ref(
            q, k, v, causal=True, window=window))
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert g.dtype == jnp.float32, name
        gap = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert gap < tol, (name, gap)


@pytest.mark.parametrize("case,routed", [
    ("self", True), ("memory", False), ("bidirectional", False),
    ("hd64", False), ("unaligned", False)])
def test_attn_forward_routes_to_flash(case, routed, key, monkeypatch):
    """Causal self-attention whose heads fill whole lanes and whose
    lengths tile runs in the flash kernel when the kernels run (here
    interpreted); cross-attention, bidirectional layers, narrow heads and
    lengths off the 128-row tiles keep the XLA core. Either way the layer,
    and its gradient, agree with the ref route to the kernel's bfloat16
    products."""
    import dataclasses

    from repro.configs.base import ModelConfig
    from repro.kernels import ops
    from repro.models import attention as attn

    cfg = ModelConfig(name="t", family="decoder", n_layers=1, d_model=256,
                      n_heads=4, n_kv_heads=2, d_ff=512, vocab_size=256,
                      head_dim=128, qkv_bias=True)
    if case == "hd64":
        cfg = dataclasses.replace(cfg, head_dim=64)
    ks = jax.random.split(key, 3)
    params = attn.init_attn(ks[0], cfg)
    x = jax.random.normal(ks[1], (2, 96 if case == "unaligned" else 128,
                                  cfg.d_model))
    kw = {"memory": jax.random.normal(ks[2], (2, 128, cfg.d_model))} \
        if case == "memory" else {"causal": case != "bidirectional"}

    def loss(x):
        y = attn.attn_forward(params, cfg, x, **kw)
        return jnp.sum(jnp.sin(y)), y

    calls = []
    kernel = ops.flash_attention
    monkeypatch.setattr(ops, "flash_attention",
                        lambda *a, **k: calls.append(1) or kernel(*a, **k))
    try:
        ops.set_backend("pallas_interpret")
        (_, y), dx = jax.value_and_grad(loss, has_aux=True)(x)
    finally:
        ops.set_backend(None)
    assert bool(calls) == routed
    (_, y_ref), dx_ref = jax.value_and_grad(loss, has_aux=True)(x)
    for got, want in ((y, y_ref), (dx, dx_ref)):
        gap = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert gap < (1e-2 if routed else 1e-6), gap


@pytest.mark.parametrize("m,P", [(10, 512), (16, 2048), (4, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gossip_mix(m, P, dtype, key):
    rng = np.random.default_rng(0)
    # random doubly-stochastic (symmetrized sinkhorn-ish)
    W = rng.random((m, m))
    for _ in range(50):
        W /= W.sum(1, keepdims=True)
        W /= W.sum(0, keepdims=True)
    W = jnp.asarray(W, jnp.float32)
    x = jax.random.normal(key, (m, P), dtype)
    y = gossip_mix(W, x, interpret=True)
    yr = ref.gossip_mix_ref(W, x)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=_tol(dtype), atol=_tol(dtype))


@pytest.mark.parametrize("B,T,W", [(2, 256, 64), (1, 512, 96), (3, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan(B, T, W, dtype, key):
    ks = jax.random.split(key, 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, T, W))).astype(dtype)
    u = (jax.random.normal(ks[1], (B, T, W)) * 0.1).astype(dtype)
    y = rglru_scan(a, u, bt=64, interpret=True)
    yr = ref.rglru_scan_ref(a, u)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ops_dispatch_cpu_fallback(key):
    """ops.* must route to the jnp reference on CPU and stay correct."""
    from repro.kernels import ops
    x = jax.random.normal(key, (64, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (64, 64))
    a = jax.random.normal(jax.random.fold_in(key, 2), (64, 8)) * 0.1
    b = jax.random.normal(jax.random.fold_in(key, 3), (8, 64)) * 0.1
    assert jax.default_backend() == "cpu"
    y = ops.lora_matmul(x, w, a, b, 2.0)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.lora_matmul_ref(x, w, a, b, 2.0)))
    ops.set_backend("pallas_interpret")
    try:
        y2 = ops.lora_matmul(x, w, a, b, 2.0)
        np.testing.assert_allclose(np.asarray(y2), np.asarray(y), rtol=1e-4,
                                   atol=1e-4)
    finally:
        ops.set_backend(None)


def test_gossip_mix_flat_identity_mask(key):
    """mask=0 -> identity regardless of W (frozen-block no-mix)."""
    from repro.kernels import ops
    W = jnp.zeros((6, 6)) + 1.0 / 6
    x = jax.random.normal(key, (6, 100))
    y = ops.gossip_mix_flat(W, x, mask=0.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-6)
