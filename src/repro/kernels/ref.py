"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors its kernel's contract exactly; tests sweep
shapes/dtypes and assert allclose between kernel (interpret=True on CPU,
compiled on TPU) and these references.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def lora_matmul_ref(x: jax.Array, w: jax.Array, a: jax.Array, b: jax.Array,
                    scale: float) -> jax.Array:
    """y = x @ w + scale * (x @ a) @ b.
    x: (M, K), w: (K, N), a: (K, r), b: (r, N)."""
    y = x.astype(jnp.float32) @ w.astype(jnp.float32)
    y = y + scale * ((x.astype(jnp.float32) @ a.astype(jnp.float32))
                     @ b.astype(jnp.float32))
    return y.astype(x.dtype)


def slot_lora_matmul_ref(x: jax.Array, w: jax.Array, a: jax.Array,
                         b: jax.Array, slots: jax.Array,
                         scale: float) -> jax.Array:
    """y[i] = x[i] @ w + scale * (x[i] @ a[slots[i]]) @ b[slots[i]].
    x: (B, K), w: (K, N), a: (N_ad, K, r), b: (N_ad, r, N), slots: (B,).

    The per-row contractions mirror `models.layers.lora_linear`'s plain
    (x @ a) @ b order so a slot-served adapter reproduces the single-adapter
    decode path bit-for-bit at equal dtypes."""
    y = x @ w
    xa = jnp.einsum("bd,bdr->br", x, a[slots].astype(x.dtype))
    return y + jnp.einsum("br,brf->bf", xa,
                          b[slots].astype(x.dtype)) * scale


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        window: Optional[int] = None) -> jax.Array:
    """Naive attention. q: (B, S, H, d); k/v: (B, L, KV, d); query head h
    reads kv head h // (H // KV)."""
    B, S, H, d = q.shape
    L, KV = k.shape[1], k.shape[2]
    qg = q.reshape(B, S, KV, H // KV, d)
    scores = jnp.einsum("bskgd,blkd->bkgsl", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(d)
    q_pos = jnp.arange(S)[:, None]
    k_pos = jnp.arange(L)[None, :]
    mask = jnp.ones((S, L), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgsl,blkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, d).astype(q.dtype)


def paged_attn_decode_ref(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, table: jax.Array,
                          lengths: jax.Array) -> jax.Array:
    """Single-token decode attention over a paged KV cache.

    q: (B, 1, H, hd); k_pages/v_pages: (n_pages, KV, page_size, hd);
    table: (B, P) int32 logical->physical page map; lengths: (B,) valid
    context per row. Returns (B, 1, H, hd).

    Gathers each row's pages into the contiguous (B, L = P*page_size, KV,
    hd) view and then mirrors `models.attention._attend` LINE FOR LINE
    (same einsum strings, f32 casts, -1e30 masking, sqrt scale), so at
    identical cached values the paged path reproduces the contiguous
    decode path bit-for-bit — the serving-core correctness contract
    asserted by tests/test_paging.py."""
    B, Sq, H, hd = q.shape
    n_kv, ps = k_pages.shape[1], k_pages.shape[2]
    P = table.shape[1]
    L = P * ps
    k = k_pages[table].swapaxes(2, 3).reshape(B, L, n_kv, hd)
    v = v_pages[table].swapaxes(2, 3).reshape(B, L, n_kv, hd)
    mask = (jnp.arange(L)[None, :] < lengths[:, None])[:, None, None, None, :]
    G = H // n_kv
    qg = q.reshape(B, Sq, n_kv, G, hd)
    scores = jnp.einsum("bskgh,blkh->bkgsl", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgsl,blkh->bskgh", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def gossip_mix_ref(w_eff: jax.Array, x: jax.Array) -> jax.Array:
    """y = w_eff @ x. w_eff: (m, m) pre-masked mixing matrix
    (mask*W + (1-mask)*I); x: (m, P) stacked flattened client params."""
    return (w_eff.astype(jnp.float32) @ x.astype(jnp.float32)).astype(x.dtype)


def gossip_mix_seg_ref(w: jax.Array, x: jax.Array,
                       seg: jax.Array) -> jax.Array:
    """y = seg*(w@x) + (1-seg)*x — per-column-segment W_eff blend.
    w: (m, m) raw mixing matrix; x: (m, P); seg: (1, P) in [0, 1]."""
    x32 = x.astype(jnp.float32)
    y = w.astype(jnp.float32) @ x32
    s = seg.astype(jnp.float32)
    return (s * y + (1.0 - s) * x32).astype(x.dtype)


def gossip_mix_quant_ref(w_off: jax.Array, q: jax.Array, scale: jax.Array,
                         x: jax.Array, w_diag: jax.Array,
                         seg: jax.Array) -> jax.Array:
    """Compressed-gossip contraction, dequantize fused:
    y = seg·(w_diag·x + w_off @ (q·scale)) + (1−seg)·x.
    w_off: (r, m) mixing rows with the diagonal zeroed; q: (m, P) int8 or
    fp8 quantized source rows; scale: (m, 1) f32 per-row scales; x: (r, P)
    fresh full-precision rows; w_diag: (r, 1); seg: (1, P). Mirrors
    `gossip_mix._kernel_quant` operation for operation (same f32 casts,
    same contraction order) so the kernel-vs-ref check is bitwise."""
    z = q.astype(jnp.float32) * scale.astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    y = w_diag.astype(jnp.float32) * x32 + w_off.astype(jnp.float32) @ z
    s = seg.astype(jnp.float32)
    return (s * y + (1.0 - s) * x32).astype(x.dtype)


def rglru_scan_ref(a: jax.Array, u: jax.Array) -> jax.Array:
    """h_t = a_t * h_{t-1} + u_t (h_{-1}=0), along axis 1.
    a, u: (B, T, W) -> h: (B, T, W)."""
    def step(h, au):
        at, ut = au
        h = at * h + ut
        return h, h
    a32 = a.astype(jnp.float32)
    u32 = u.astype(jnp.float32)
    h0 = jnp.zeros((a.shape[0], a.shape[2]), jnp.float32)
    _, hs = jax.lax.scan(step, h0, (jnp.moveaxis(a32, 1, 0),
                                    jnp.moveaxis(u32, 1, 0)))
    return jnp.moveaxis(hs, 0, 1).astype(u.dtype)


def expert_matmul_ref(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
                      *, transpose: bool = False) -> jax.Array:
    """Rows sorted by expert, one expert at a time: row i of group e is
    x[i] @ w[e] (x[i] @ w[e].T with ``transpose``); rows past the groups
    are zero. x: (M, K), w: (E, K, N) (or (E, N, K)), group_sizes: (E,)."""
    ends = jnp.cumsum(group_sizes)
    rows = jnp.arange(x.shape[0])[:, None]
    out = 0.0
    for e in range(w.shape[0]):
        we = w[e].T if transpose else w[e]
        mine = (rows >= ends[e] - group_sizes[e]) & (rows < ends[e])
        out = out + jnp.where(mine, x @ we.astype(x.dtype), 0.0)
    return jnp.asarray(out, jnp.float32).astype(x.dtype)
