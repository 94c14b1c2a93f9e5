"""Grouped-query attention: global / sliding-window / cross, train + decode.

Causal self-attention runs in the flash kernel where it can
(`kernels.ops.flash_attention_supported`); the XLA core below serves the
rest: cross-attention, bidirectional layers, the CPU.

Memory strategy of the XLA core (dry-run-safe at 32k prefill):
 - queries are chunked with lax.scan when S >= _CHUNK_THRESHOLD;
 - chunk bodies are rematerialized (jax.checkpoint) so AD through the scan
   does not retain per-chunk score tensors;
 - scores shard over kv-heads ("model") when divisible, else over the KV
   length ("seq") — sequence-parallel softmax via GSPMD collectives;
 - sliding-window prefill restricts each q-chunk to a banded KV slice.

Decode uses a rolling cache: {"k": (B, L, KV, hd), "v": ..., "t": ()} with
write slot t % L; keys are stored post-RoPE (absolute positions at write
time), so rolling overwrite needs no re-rotation.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import axis_size, logical
from repro.models.common import apply_rope, dense_init, rmsnorm, zeros
from repro.models.layers import lora_linear, shard_act

_CHUNK_THRESHOLD = 2048
_Q_CHUNK = 1024


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attn(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, hd = cfg.d_model, cfg.hd
    q_dim, kv_dim = cfg.n_heads * hd, cfg.n_kv_heads * hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, q_dim, dtype),
        "wk": dense_init(ks[1], d, kv_dim, dtype),
        "wv": dense_init(ks[2], d, kv_dim, dtype),
        "wo": dense_init(ks[3], q_dim, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros(q_dim, dtype=dtype)
        p["bk"] = zeros(kv_dim, dtype=dtype)
        p["bv"] = zeros(kv_dim, dtype=dtype)
    return p


# ---------------------------------------------------------------------------
# Score/attend core (grouped heads, no kv materialized repeat)
# ---------------------------------------------------------------------------

def _scores_spec(n_kv: int, n_groups: int):
    """Sharding for scores (B, KV, G, Sq, L): TP over kv-heads when they
    divide the model axis, else over head-groups (MQA: KV=1, G=heads),
    else fully LOCAL (batch only). Never shard L: sequence-sharded softmax
    made GSPMD all-gather K/V slices inside the q-chunk scan (measured
    ~180 GB/step in the gemma3 dry-run — EXPERIMENTS.md §Perf iter 3)."""
    model_n = axis_size("model")
    if model_n > 1 and n_kv % model_n == 0:
        return ("batch", "model", None, None, None)
    if model_n > 1 and n_groups % model_n == 0:
        return ("batch", None, "model", None, None)
    # Non-divisible heads: leave scores unconstrained. History (§Perf):
    # forced-replicated fallback gathered probs/masks (~255 GB/step,
    # iter 5); forced q-dim sharding exploded qwen2 prefill to 2.3e3 s
    # (pair-B iter 1, REFUTED). The input-side fix (replicating q/k/v per
    # layer, pair-B iter 2) steers GSPMD instead.
    return None


def _attend(q, k, v, mask, n_kv: int):
    """q: (B, Sq, H, hd); k/v: (B, L, KV, hd); mask broadcastable to
    (B, 1, 1, Sq, L) or None. Returns (B, Sq, H, hd)."""
    B, Sq, H, hd = q.shape
    L = k.shape[1]
    G = H // n_kv
    qg = q.reshape(B, Sq, n_kv, G, hd)
    scores = jnp.einsum("bskgh,blkh->bkgsl", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    spec = _scores_spec(n_kv, G)
    if spec is not None:
        scores = logical(scores, *spec)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgsl,blkh->bskgh", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def _band_mask(q_pos, k_pos, *, causal: bool, window: Optional[int]):
    """(Sq, L) boolean mask from absolute positions."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def attn_forward(params: dict, cfg: ModelConfig, x: jax.Array, *,
                 window: Optional[int] = None, causal: bool = True,
                 lora: Optional[dict] = None, positions=None,
                 memory: Optional[jax.Array] = None,
                 return_kv: bool = False):
    """x: (..., S, d). Cross-attention when ``memory`` is given (K/V from
    memory, no RoPE, bidirectional over memory)."""
    scale = cfg.lora_alpha / cfg.lora_rank
    lq = (lora or {}).get("wq")
    lk = (lora or {}).get("wk")
    lv = (lora or {}).get("wv")
    hd = cfg.hd

    q = lora_linear(x, params["wq"], lq, scale, params.get("bq"))
    kv_src = memory if memory is not None else x
    k = lora_linear(kv_src, params["wk"], lk, scale, params.get("bk"))
    v = lora_linear(kv_src, params["wv"], lv, scale, params.get("bv"))

    lead = x.shape[:-2]          # leading dims (e.g. clients) beyond batch
    S = x.shape[-2]
    L = kv_src.shape[-2]
    q = q.reshape(*lead, S, cfg.n_heads, hd)
    k = k.reshape(*lead, L, cfg.n_kv_heads, hd)
    v = v.reshape(*lead, L, cfg.n_kv_heads, hd)

    if memory is None:  # self-attention: RoPE
        if positions is None:
            positions = jnp.arange(S)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    # collapse leading dims to one batch axis for the core
    B = math.prod(lead) if lead else 1
    qf = q.reshape(B, S, cfg.n_heads, hd)
    kf = k.reshape(B, L, cfg.n_kv_heads, hd)
    vf = v.reshape(B, L, cfg.n_kv_heads, hd)

    G = cfg.n_heads // cfg.n_kv_heads
    model_n = axis_size("model")
    if (model_n > 1 and cfg.n_kv_heads % model_n and G % model_n
            and memory is None):
        # heads don't divide the TP axis: replicate q/k/v ONCE per layer
        # (cheap: per-layer gather) so GSPMD cannot partial-sum the hd
        # contraction and all-reduce full score tensors per q-chunk
        # (measured 1.68 TB/step on qwen2 prefill — §Perf pair-B iter 2)
        rep = lambda z: logical(z, "batch", *((None,) * (z.ndim - 1)))
        qf, kf, vf = rep(qf), rep(kf), rep(vf)

    from repro.kernels import ops   # deferred: kernels import jax.pallas
    with jax.named_scope("attn_core"):
        if memory is not None:
            out = _attend(qf, kf, vf, None, cfg.n_kv_heads)
        elif causal and ops.flash_attention_supported(S, L, hd):
            out = ops.flash_attention(qf, kf, vf, window=window)
        elif S < _CHUNK_THRESHOLD:
            mask = _band_mask(jnp.arange(S), jnp.arange(L), causal=causal,
                              window=window)
            out = _attend(qf, kf, vf, mask[None, None, None], cfg.n_kv_heads)
        else:
            out = _chunked_attend(qf, kf, vf, cfg.n_kv_heads, causal=causal,
                                  window=window)

    out = out.reshape(*lead, S, cfg.n_heads * hd)
    out = lora_linear(out, params["wo"], (lora or {}).get("wo"), scale)
    out = shard_act(out)
    if return_kv:
        return out, (k, v)
    return out


def _chunked_attend(q, k, v, n_kv: int, *, causal: bool,
                    window: Optional[int]):
    """lax.scan over q chunks; banded KV slice when windowed."""
    B, S, H, hd = q.shape
    L = k.shape[1]
    C = _Q_CHUNK if L <= 8192 else _Q_CHUNK // 4   # bound live score bytes
    n_chunks = S // C
    assert S % C == 0, (S, C)

    if window is not None and causal and L == S:
        # round the band up to a multiple of C for static slicing
        band = min(L, (math.ceil(window / C) + 1) * C)
    else:
        band = None

    @jax.checkpoint
    def body(_, idx):
        q_start = idx * C
        qc = jax.lax.dynamic_slice_in_dim(q, q_start, C, axis=1)
        q_pos = q_start + jnp.arange(C)
        if band is not None:
            k_start = jnp.maximum(q_start + C - band, 0)
            kc = jax.lax.dynamic_slice_in_dim(k, k_start, band, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, k_start, band, axis=1)
            k_pos = k_start + jnp.arange(band)
        else:
            kc, vc, k_pos = k, v, jnp.arange(L)
        mask = _band_mask(q_pos, k_pos, causal=causal, window=window)
        out = _attend(qc, kc, vc, mask[None, None, None], n_kv)
        return None, out

    _, chunks = jax.lax.scan(body, None, jnp.arange(n_chunks))
    # chunks: (n_chunks, B, C, H, hd) -> (B, S, H, hd)
    return jnp.moveaxis(chunks, 0, 1).reshape(B, S, H, hd)


# ---------------------------------------------------------------------------
# Decode (one token, rolling cache)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               window: Optional[int] = None, dtype=jnp.float32) -> dict:
    """Rolling KV cache with PER-SLOT position counters "t" (B,) — each
    batch row is an independent serving slot (continuous batching:
    launch/serving.py admits/evicts requests per row)."""
    L = min(window, max_len) if window else max_len
    kv, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": zeros(batch, L, kv, hd, dtype=dtype),
        "v": zeros(batch, L, kv, hd, dtype=dtype),
        "t": jnp.zeros((batch,), dtype=jnp.int32),
    }


def cache_spec(cfg: ModelConfig, batch: int, max_len: int,
               window: Optional[int] = None, dtype=jnp.float32) -> dict:
    """ShapeDtypeStruct version of init_cache (dry-run, no allocation)."""
    L = min(window, max_len) if window else max_len
    kv, hd = cfg.n_kv_heads, cfg.hd
    f = jax.ShapeDtypeStruct
    return {
        "k": f((batch, L, kv, hd), dtype),
        "v": f((batch, L, kv, hd), dtype),
        "t": f((batch,), jnp.int32),
    }


def init_paged_cache(cfg: ModelConfig, batch: int, n_pages: int,
                     page_size: int, dtype=jnp.float32) -> dict:
    """Paged KV cache (serving core): physical pages shared by all slots,
    per-slot position counters. The logical->physical block table lives at
    the cache top level (`transformer.init_cache(paging=...)`) because one
    table serves every paged layer. Physical page 0 is the null page —
    free slots' table rows point at it and no active slot ever reads it.
    Pages are (KV, page_size, hd): heads lead so the paged kernel's
    per-head block is a whole (page_size, hd) tile."""
    kv, hd = cfg.n_kv_heads, cfg.hd
    return {
        "kp": zeros(n_pages, kv, page_size, hd, dtype=dtype),
        "vp": zeros(n_pages, kv, page_size, hd, dtype=dtype),
        "t": jnp.zeros((batch,), dtype=jnp.int32),
    }


def paged_cache_spec(cfg: ModelConfig, batch: int, n_pages: int,
                     page_size: int, dtype=jnp.float32) -> dict:
    """ShapeDtypeStruct version of init_paged_cache (dry-run)."""
    kv, hd = cfg.n_kv_heads, cfg.hd
    f = jax.ShapeDtypeStruct
    return {
        "kp": f((n_pages, kv, page_size, hd), dtype),
        "vp": f((n_pages, kv, page_size, hd), dtype),
        "t": f((batch,), jnp.int32),
    }


def attn_decode(params: dict, cfg: ModelConfig, x: jax.Array, cache: dict, *,
                window: Optional[int] = None, lora: Optional[dict] = None,
                cross_kv: Optional[tuple] = None,
                pages: Optional[dict] = None):
    """x: (B, 1, d). Returns (out, new_cache). With ``cross_kv`` (k, v) the
    layer is cross-attention (static memory KV, cache untouched). A cache
    carrying "kp"/"vp" is paged (serving core) and additionally needs
    ``pages`` = {"table": (B, P) int32}."""
    scale = cfg.lora_alpha / cfg.lora_rank
    hd = cfg.hd
    B = x.shape[0]
    q = lora_linear(x, params["wq"], (lora or {}).get("wq"), scale,
                    params.get("bq"))
    q = q.reshape(B, 1, cfg.n_heads, hd)

    if cross_kv is not None:
        k, v = cross_kv
        out = _attend(q, k, v, None, cfg.n_kv_heads)
        out = out.reshape(B, 1, cfg.n_heads * hd)
        out = lora_linear(out, params["wo"], (lora or {}).get("wo"), scale)
        return shard_act(out), cache

    t = cache["t"]                                     # (B,) per-slot pos
    k_new = lora_linear(x, params["wk"], (lora or {}).get("wk"), scale,
                        params.get("bk")).reshape(B, 1, cfg.n_kv_heads, hd)
    v_new = lora_linear(x, params["wv"], (lora or {}).get("wv"), scale,
                        params.get("bv")).reshape(B, 1, cfg.n_kv_heads, hd)
    pos = t[:, None].astype(jnp.float32)               # (B, 1)
    q = apply_rope(q, pos, cfg.rope_theta)
    k_new = apply_rope(k_new, pos, cfg.rope_theta)

    if "kp" in cache:
        return _paged_decode_core(cfg, q, k_new, v_new, cache, pages,
                                  params, lora, scale)

    L = cache["k"].shape[1]
    slot = (t % L).astype(jnp.int32)                   # (B,)
    rows = jnp.arange(B)
    k_cache = cache["k"].at[rows, slot].set(
        k_new[:, 0].astype(cache["k"].dtype))
    v_cache = cache["v"].at[rows, slot].set(
        v_new[:, 0].astype(cache["v"].dtype))
    k_cache = logical(k_cache, "batch", "seq", None, None)
    v_cache = logical(v_cache, "batch", "seq", None, None)

    valid = jnp.arange(L)[None, :] < jnp.minimum(t + 1, L)[:, None]  # (B,L)
    mask = valid[:, None, None, None, :]
    out = _attend(q, k_cache, v_cache, mask, cfg.n_kv_heads)
    out = out.reshape(B, 1, cfg.n_heads * hd)
    out = lora_linear(out, params["wo"], (lora or {}).get("wo"), scale)
    new_cache = {"k": k_cache, "v": v_cache, "t": t + 1}
    return shard_act(out), new_cache


def _paged_decode_core(cfg: ModelConfig, q, k_new, v_new, cache: dict,
                       pages: dict, params: dict, lora, scale: float):
    """Paged tail of attn_decode: scatter this token's K/V into the slot's
    current page, then attend over the block-table view. Inactive rows
    (all-zero table row) scatter onto the null page 0, which no active
    row's table references — their output is garbage the engine discards.
    At identical contexts the ref path is bitwise equal to the contiguous
    branch above: the gathered (B, L, KV, hd) view holds the same values,
    masks, and einsum shapes (tests/test_paging.py asserts this)."""
    from repro.kernels import ops   # deferred: kernels import jax.pallas

    B = q.shape[0]
    t = cache["t"]
    table = pages["table"]                             # (B, P)
    ps = cache["kp"].shape[2]
    P = table.shape[1]
    L = P * ps
    rows = jnp.arange(B)
    phys = table[rows, jnp.clip(t // ps, 0, P - 1)]    # (B,)
    off = t % ps
    kp = cache["kp"].at[phys, :, off].set(
        k_new[:, 0].astype(cache["kp"].dtype))
    vp = cache["vp"].at[phys, :, off].set(
        v_new[:, 0].astype(cache["vp"].dtype))
    lengths = jnp.minimum(t + 1, L)
    out = ops.paged_attn_decode(q, kp, vp, table, lengths)
    out = out.reshape(B, 1, cfg.n_heads * cfg.hd)
    out = lora_linear(out, params["wo"], (lora or {}).get("wo"), scale)
    new_cache = {"kp": kp, "vp": vp, "t": t + 1}
    return shard_act(out), new_cache


# ---------------------------------------------------------------------------
# Chunked prefill (serving core: one slot's prompt, C tokens per step)
# ---------------------------------------------------------------------------

def _chunk_qkv(params: dict, cfg: ModelConfig, x, pos, lora, scale):
    """Shared head of both chunk paths: projections + RoPE at absolute
    positions. x: (1, C, d); pos: (C,) int32."""
    hd = cfg.hd
    C = x.shape[1]
    lo = lora or {}
    q = lora_linear(x, params["wq"], lo.get("wq"), scale,
                    params.get("bq")).reshape(1, C, cfg.n_heads, hd)
    k_new = lora_linear(x, params["wk"], lo.get("wk"), scale,
                        params.get("bk")).reshape(1, C, cfg.n_kv_heads, hd)
    v_new = lora_linear(x, params["wv"], lo.get("wv"), scale,
                        params.get("bv")).reshape(1, C, cfg.n_kv_heads, hd)
    q = apply_rope(q, pos, cfg.rope_theta)
    k_new = apply_rope(k_new, pos, cfg.rope_theta)
    return q, k_new, v_new


def _chunk_out(params: dict, cfg: ModelConfig, out, lora, scale):
    out = out.reshape(1, -1, cfg.n_heads * cfg.hd)
    out = lora_linear(out, params["wo"], (lora or {}).get("wo"), scale)
    return shard_act(out)


def attn_chunk_paged(params: dict, cfg: ModelConfig, x, cache: dict,
                     table_row, slot, start, limit, *,
                     lora: Optional[dict] = None):
    """One prefill chunk into a PAGED layer cache. x: (1, C, d) chunk of
    one slot's prompt; table_row: (P,) the slot's block-table row; slot /
    start / limit: () int32 — batch row, absolute chunk offset, and total
    real (unpadded) prefill length. Pad positions (>= limit) write nothing
    (masked to the old value) and their outputs are garbage the caller
    drops. Returns (out (1, C, d_q), new layer cache)."""
    scale = cfg.lora_alpha / cfg.lora_rank
    C = x.shape[1]
    pos = start + jnp.arange(C)                        # (C,) absolute
    q, k_new, v_new = _chunk_qkv(params, cfg, x, pos, lora, scale)

    ps = cache["kp"].shape[2]
    P = table_row.shape[0]
    L = P * ps
    pos_c = jnp.clip(pos, 0, L - 1)                    # pads stay in range
    phys = table_row[pos_c // ps]                      # (C,)
    off = pos_c % ps
    valid_w = (pos < limit)[:, None, None]
    kw = jnp.where(valid_w, k_new[0].astype(cache["kp"].dtype),
                   cache["kp"][phys, :, off])
    vw = jnp.where(valid_w, v_new[0].astype(cache["vp"].dtype),
                   cache["vp"][phys, :, off])
    kp = cache["kp"].at[phys, :, off].set(kw)
    vp = cache["vp"].at[phys, :, off].set(vw)

    k_all = kp[table_row].swapaxes(1, 2).reshape(1, L, cfg.n_kv_heads,
                                                 cfg.hd)
    v_all = vp[table_row].swapaxes(1, 2).reshape(1, L, cfg.n_kv_heads,
                                                 cfg.hd)
    k_pos = jnp.arange(L)
    mask = (k_pos[None, :] <= pos[:, None]) & (k_pos[None, :] < limit)
    out = _attend(q, k_all, v_all, mask[None, None, None], cfg.n_kv_heads)

    t_new = cache["t"].at[slot].set(jnp.minimum(start + C, limit))
    return (_chunk_out(params, cfg, out, lora, scale),
            {"kp": kp, "vp": vp, "t": t_new})


def attn_chunk_rolling(params: dict, cfg: ModelConfig, x, cache: dict,
                       slot, start, limit, *, lora: Optional[dict] = None):
    """One prefill chunk into a ROLLING (contiguous) layer cache of length
    L = the layer's window (or max_len for global layers). The slot's
    buffer holds positions start-L..start-1 at entry (slot p%L); the chunk
    attends its banded context, then writes back its last min(C, L) real
    positions. Matches decode semantics: key position k is visible to
    query position s iff 0 <= k <= s and s - k < L."""
    scale = cfg.lora_alpha / cfg.lora_rank
    C = x.shape[1]
    L = cache["k"].shape[1]
    pos = start + jnp.arange(C)
    q, k_new, v_new = _chunk_qkv(params, cfg, x, pos, lora, scale)

    s_idx = jnp.arange(L)
    ctx_pos = start - L + ((s_idx - start) % L)        # position held at
    #                                                    buffer slot s_idx
    k_all = jnp.concatenate([cache["k"][slot][None], k_new], axis=1)
    v_all = jnp.concatenate([cache["v"][slot][None], v_new], axis=1)
    k_pos = jnp.concatenate([ctx_pos, pos])            # (L + C,)
    mask = ((k_pos[None, :] <= pos[:, None]) &
            (k_pos[None, :] >= 0) &
            (k_pos[None, :] < limit) &
            (pos[:, None] - k_pos[None, :] < L))
    out = _attend(q, k_all, v_all, mask[None, None, None], cfg.n_kv_heads)

    # write-back, one gather per buffer slot j: the LATEST real chunk
    # position p with p % L == j (pads and wrapped-over positions never
    # land; duplicate-index scatters would be order-unspecified, a gather
    # is deterministic). e = exclusive end of real positions this chunk.
    e = jnp.minimum(limit, start + C)
    last = (e - 1) - ((e - 1 - s_idx) % L)             # latest p == j (mod L)
    w_valid = (last >= start)[:, None, None]           # p inside this chunk?
    idx = jnp.clip(last - start, 0, C - 1)
    kw = jnp.where(w_valid, k_new[0, idx].astype(cache["k"].dtype),
                   cache["k"][slot])
    vw = jnp.where(w_valid, v_new[0, idx].astype(cache["v"].dtype),
                   cache["v"][slot])
    k_cache = cache["k"].at[slot].set(kw)
    v_cache = cache["v"].at[slot].set(vw)

    t_new = cache["t"].at[slot].set(jnp.minimum(start + C, limit))
    return (_chunk_out(params, cfg, out, lora, scale),
            {"k": k_cache, "v": v_cache, "t": t_new})
