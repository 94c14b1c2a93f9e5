"""Model assembly: pattern-scanned decoder, whisper enc-dec, VLM.

Layers are given by ``cfg.pattern`` repeated ``cfg.n_groups`` times (params
stacked with a leading group axis, iterated by lax.scan — keeps HLO size
independent of depth) plus an explicit ``tail`` for patterns that do not
divide n_layers (recurrentgemma 26 = 8*3 + 2, gemma3 26 = 4*6 + 2).

The LoRA tree mirrors the params tree at the adapted weight leaves
({"a": (d_in,r), "b": (r,d_out)}), optionally with a leading client axis for
stacked federated evaluation (see repro.core.lora).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, CROSS, DENSE, MLSTM, MOE, NONE, RGLRU,
                                SLSTM, LayerSpec, ModelConfig)
from repro.dist.sharding import logical
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import embed_tokens, init_mlp, mlp, rmsnorm, unembed, zeros
from repro.models.layers import shard_act


# ===========================================================================
# Init
# ===========================================================================

def _init_layer(key, cfg: ModelConfig, spec: LayerSpec, dtype,
                encdec_cross: bool) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p: dict = {"norm1": zeros(d, dtype=dtype)}
    if spec.kind in (ATTN, CROSS):
        p["attn"] = attn_mod.init_attn(ks[0], cfg, dtype)
    elif spec.kind == RGLRU:
        p["rglru"] = rglru_mod.init_rglru(ks[0], cfg, dtype)
    elif spec.kind == MLSTM:
        p["mlstm"] = xlstm_mod.init_mlstm(ks[0], cfg, dtype)
    elif spec.kind == SLSTM:
        p["slstm"] = xlstm_mod.init_slstm(ks[0], cfg, dtype)
    if encdec_cross and spec.kind == ATTN:
        p["norm_cross"] = zeros(d, dtype=dtype)
        p["cross"] = attn_mod.init_attn(ks[1], cfg, dtype)
    if spec.ffn == DENSE:
        p["norm2"] = zeros(d, dtype=dtype)
        p["ffn"] = init_mlp(ks[2], d, cfg.d_ff, dtype)
    elif spec.ffn == MOE:
        p["norm2"] = zeros(d, dtype=dtype)
        p["moe"] = moe_mod.init_moe(ks[2], cfg, dtype)
    return p


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    """Full parameter pytree for any assigned architecture."""
    d = cfg.d_model
    kE, kU, kG, kT, kenc = jax.random.split(key, 5)
    params: dict = {
        "embed": (jax.random.normal(kE, (cfg.vocab_padded, d)) *
                  0.02).astype(dtype),
        "final_norm": zeros(d, dtype=dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (jax.random.normal(kU, (d, cfg.vocab_padded)) /
                             math.sqrt(d)).astype(dtype)
    encdec = cfg.family == "encdec"

    # scanned groups: per pattern position, leaves stacked (n_groups, ...)
    # and drawn straight into the stack, layer g from fold_in(kG, j*1000+g):
    # per-layer leaves stacked afterwards held the layers twice. The key
    # is an argument, not a constant of the program, so one compiled
    # program serves every seed
    def group(j, spec):
        return jax.jit(jax.vmap(lambda k, g: _init_layer(
            jax.random.fold_in(k, j * 1000 + g), cfg, spec, dtype,
            encdec), in_axes=(None, 0)))(kG, jnp.arange(cfg.n_groups))

    params["groups"] = [group(j, spec) for j, spec in enumerate(cfg.pattern)]
    params["tail"] = [
        _init_layer(jax.random.fold_in(kT, j), cfg, spec, dtype, encdec)
        for j, spec in enumerate(cfg.tail_pattern)
    ]

    if encdec:
        enc_layers = [
            _init_layer(jax.random.fold_in(kenc, j), cfg,
                        LayerSpec(kind=ATTN, ffn=DENSE), dtype, False)
            for j in range(cfg.enc_layers)
        ]
        params["encoder"] = {"layers": enc_layers,
                             "norm": zeros(d, dtype=dtype)}
    return params


def param_specs(cfg: ModelConfig, dtype=jnp.float32):
    """ShapeDtypeStruct tree of init_params without allocating (dry-run)."""
    return jax.eval_shape(partial(init_params, cfg=cfg, dtype=dtype),
                          jax.random.key(0))


# ===========================================================================
# Forward (train / prefill)
# ===========================================================================

def _apply_layer(p: dict, cfg: ModelConfig, spec: LayerSpec, x, *,
                 memory, positions, lora: Optional[dict], encdec_cross: bool):
    """One layer: (x, balance loss, expert load (E,) or None)."""
    aux = jnp.zeros((), jnp.float32)
    load = None
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    # under sequence parallelism, re-materialize the full sequence ONCE per
    # sublayer here (Megatron-SP all-gather point); otherwise each q-chunk
    # slice gathers on its own (measured 937 GB/step on gemma3 — §Perf)
    h = logical(h, "batch", *((None,) * (h.ndim - 1)))
    lo = lora or {}
    # scopes name the sublayers in the compiled program's op names
    with jax.named_scope(spec.kind):
        if spec.kind == ATTN:
            y = attn_mod.attn_forward(p["attn"], cfg, h, window=spec.window,
                                      causal=True, lora=lo.get("attn"),
                                      positions=positions)
        elif spec.kind == CROSS:
            y = attn_mod.attn_forward(p["attn"], cfg, h, memory=memory,
                                      lora=lo.get("attn"))
        elif spec.kind == RGLRU:
            y = rglru_mod.rglru_forward(p["rglru"], cfg, h,
                                        lora=lo.get("rglru"))
        elif spec.kind == MLSTM:
            y = xlstm_mod.mlstm_forward(p["mlstm"], cfg, h,
                                        lora=lo.get("mlstm"))
        elif spec.kind == SLSTM:
            y = xlstm_mod.slstm_forward(p["slstm"], cfg, h,
                                        lora=lo.get("slstm"))
        else:
            raise ValueError(spec.kind)
    x = x + y.astype(x.dtype)
    if encdec_cross and spec.kind == ATTN:
        h = rmsnorm(x, p["norm_cross"], cfg.norm_eps)
        with jax.named_scope(CROSS):
            y = attn_mod.attn_forward(p["cross"], cfg, h, memory=memory,
                                      lora=lo.get("cross"))
        x = x + y.astype(x.dtype)
    if spec.ffn == DENSE:
        h = rmsnorm(x, p["norm2"], cfg.norm_eps)
        with jax.named_scope("ffn"):
            y = mlp(p["ffn"], h, cfg.act)
        x = x + y.astype(x.dtype)
    elif spec.ffn == MOE:
        h = rmsnorm(x, p["norm2"], cfg.norm_eps)
        with jax.named_scope("ffn"):
            y, a, load = moe_mod.moe_layer(p["moe"], cfg, h)
        x = x + y.astype(x.dtype)
        aux = aux + a
    return x, aux, load


def _encoder_forward(params: dict, cfg: ModelConfig, frontend: jax.Array,
                     lora: Optional[dict]):
    """Bidirectional encoder over stubbed frontend embeddings (whisper)."""
    x = frontend
    enc_lora = (lora or {}).get("encoder", {}) or {}
    for j, p in enumerate(params["encoder"]["layers"]):
        h = rmsnorm(x, p["norm1"], cfg.norm_eps)
        lo = enc_lora.get("layers", [None] * 99)
        lj = lo[j] if isinstance(lo, list) and j < len(lo) else None
        x = x + attn_mod.attn_forward(p["attn"], cfg, h, causal=False,
                                      lora=(lj or {}).get("attn"))
        h = rmsnorm(x, p["norm2"], cfg.norm_eps)
        x = x + mlp(p["ffn"], h, cfg.act)
    return rmsnorm(x, params["encoder"]["norm"], cfg.norm_eps)


def hidden_forward(params: dict, cfg: ModelConfig, tokens: jax.Array, *,
                   frontend: Optional[jax.Array] = None,
                   lora: Optional[dict] = None, remat: bool = True):
    """Backbone only: returns (hidden (..., S, d) post-final-norm, aux)."""
    x, aux, _ = _backbone(params, cfg, tokens, frontend=frontend, lora=lora,
                          remat=remat)
    return x, aux


def _scanned(groups: list):
    """The scanned groups' params as scan inputs, and ``layer_params(xs,
    j, i)``: pattern position j's params at scan step i. Expert weights
    stay whole outside the scanned slices and each layer's grouped
    matmuls read their blocks from the stack (`moe.Stacked`): a slice
    handed to a Mosaic call is copied whole."""
    split = [moe_mod.pull_experts(p) for p in groups]
    stacks = [s[1] for s in split]

    def layer_params(xs, j, i):
        return moe_mod.put_experts(xs[j], stacks[j], i)

    return [s[0] for s in split], layer_params


def _backbone(params: dict, cfg: ModelConfig, tokens: jax.Array, *,
              frontend: Optional[jax.Array], lora: Optional[dict],
              remat: bool):
    """(hidden, balance loss, expert load (MoE layers, E) int32 in layer
    order, or None without MoE layers)."""
    x = embed_tokens(params["embed"], tokens) * math.sqrt(cfg.d_model)
    x = shard_act(x, None)
    S = tokens.shape[-1]
    positions = jnp.arange(S)
    encdec = cfg.family == "encdec"

    memory = None
    if encdec:
        memory = _encoder_forward(params, cfg, frontend, lora)
    elif cfg.family == "vlm":
        memory = frontend

    lo = lora or {}
    lo_groups = lo.get("groups", [None] * len(cfg.pattern))
    aux_total = jnp.zeros((), jnp.float32)

    # --- scanned pattern groups ---
    group_params, layer_params = _scanned(params["groups"])

    def group_body(carry, xs):
        x, aux = carry
        loads = []
        for j, spec in enumerate(cfg.pattern):
            x, a, load = _apply_layer(
                layer_params(xs[0], j, xs[2]), cfg, spec, x, memory=memory,
                positions=positions,
                lora=xs[1][j] if xs[1] is not None else None,
                encdec_cross=encdec)
            aux = aux + a
            if load is not None:
                loads.append(load)
        return (x, aux), (jnp.stack(loads) if loads else None)

    # the layer remat recomputes all but MoE's choice of experts and its
    # routed expert rows (`moe.ROUTED_ROWS`); a model without MoE layers
    # keeps nothing
    keep = jax.checkpoint_policies.save_only_these_names(moe_mod.ROUTED_ROWS)
    body = jax.checkpoint(group_body, policy=keep) if remat else group_body
    has_lora = any(g is not None for g in lo_groups)
    xs = (group_params, lo_groups if has_lora else None,
          jnp.arange(cfg.n_groups))
    (x, aux_total), group_loads = jax.lax.scan(
        body, (x, aux_total), xs,
        length=cfg.n_groups)
    loads = [] if group_loads is None else \
        [group_loads.reshape(-1, group_loads.shape[-1])]

    # --- tail layers ---
    lo_tail = lo.get("tail", [None] * cfg.tail_len)
    for j, spec in enumerate(cfg.tail_pattern):
        x, a, load = _apply_layer(params["tail"][j], cfg, spec, x,
                                  memory=memory, positions=positions,
                                  lora=lo_tail[j], encdec_cross=encdec)
        aux_total = aux_total + a
        if load is not None:
            loads.append(load[None])

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, aux_total, (jnp.concatenate(loads) if loads else None)


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array, *,
            frontend: Optional[jax.Array] = None,
            lora: Optional[dict] = None, remat: bool = True):
    """Full forward: (logits (..., S, V_pad), aux). Materializes logits —
    use lm_loss (chunked CE) for training at scale."""
    x, aux = hidden_forward(params, cfg, tokens, frontend=frontend,
                            lora=lora, remat=remat)
    logits = unembed(x, params.get("unembed", params["embed"]),
                     tied=cfg.tie_embeddings, softcap=cfg.logit_softcap)
    return logits, aux


# ===========================================================================
# Loss — chunked fused cross-entropy
# ===========================================================================

_CE_CHUNK = 512


def _chunk_ce(x_chunk, tgt_chunk, head, cfg: ModelConfig):
    """x: (..., C, d), tgt: (..., C) -> summed CE over the chunk.
    Never materializes more than (..., C, V) logits; f32 reduction."""
    logits = unembed(x_chunk, head, tied=cfg.tie_embeddings,
                     softcap=cfg.logit_softcap).astype(jnp.float32)
    if cfg.vocab_padded != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.vocab_padded) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    # target term as a one-hot contraction: local on the vocab-sharded dim
    # (take_along_axis backward scatter-adds across shards — §Perf iter 3)
    onehot = jax.nn.one_hot(tgt_chunk, cfg.vocab_padded,
                            dtype=logits.dtype)
    tgt = jnp.einsum("...v,...v->...", logits, onehot)
    per = lse - tgt
    if per.ndim <= 1:
        return jnp.sum(per)
    # per-leading-index partial sums (clients in the DFL round) — the
    # cross-index combine happens once, replicated, in `lm_loss`, so the
    # loss scalar has one arithmetic order on every process grid
    return jnp.sum(per, axis=tuple(range(1, per.ndim)))


def lm_loss(params: dict, cfg: ModelConfig, tokens: jax.Array,
            targets: jax.Array, *, frontend=None, lora=None,
            remat: bool = True, per_client: bool = False):
    """Next-token CE over the *logical* vocab (padded ids masked out).

    The unembed + softmax-CE is computed in sequence chunks under lax.scan
    (rematerialized), so full-sequence logits over huge vocabs (gemma3:
    262k) are never resident — the fix for the 210 GB/device dry-run bomb
    (EXPERIMENTS.md §Perf notes).

    Returns (loss, (ce, aux, expert_load)): the MoE balance loss and the
    (token, expert) pairs routed to each expert of each MoE layer
    ((MoE layers, E) int32; None without MoE layers).

    CE accumulates per-leading-index (per-client) partial sums; the
    scalar is their flat combine. With ``per_client`` the return is
    ((loss, parts), per_client_mean_vec): the vector entries are
    shard-local, hence bitwise identical on every process grid — the DFL
    round reports loss from it host-side while the scalar feeds only the
    gradient (the MoE aux term keeps its plain mean; MoE archs are
    outside the multihost parity surface)."""
    x, aux, load = _backbone(params, cfg, tokens, frontend=frontend,
                             lora=lora, remat=remat)
    head = params.get("unembed", params["embed"])
    S = x.shape[-2]
    C = min(_CE_CHUNK, S)
    n_tok = targets.size
    lead = x.shape[:-2]

    with jax.named_scope("head"):
        if S % C != 0 or S <= C:
            total = _chunk_ce(x, targets, head, cfg)
        else:
            nc = S // C
            xc = jnp.moveaxis(x.reshape(*lead, nc, C, x.shape[-1]), -3, 0)
            tc = jnp.moveaxis(targets.reshape(*lead, nc, C), -2, 0)

            @jax.checkpoint
            def body(acc, inp):
                xi, ti = inp
                return acc + _chunk_ce(xi, ti, head, cfg), None

            total, _ = jax.lax.scan(
                body, jnp.zeros(lead[:1], jnp.float32), (xc, tc))
        ce = jnp.sum(total) / n_tok
    out = ce + aux, (ce, aux, load)
    if not per_client:
        return out
    vec = total / (n_tok // total.shape[0]) if total.ndim \
        else total[None] / n_tok
    return out, vec


# ===========================================================================
# Decode (one token through the whole stack)
# ===========================================================================

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.float32, *, specs_only: bool = False,
               memory: Optional[jax.Array] = None, params=None,
               paging: Optional[tuple] = None) -> dict:
    """Cache pytree. ``specs_only`` returns ShapeDtypeStructs (dry-run).
    Cross-attention KV is precomputed at prefill; here it is allocated
    (zeros / specs) with the right shape.

    ``paging`` = (n_pages, page_size) switches GLOBAL attention layers
    (window=None) to the serving core's paged storage: their K/V live in
    a shared physical page pool and the cache gains a top-level
    ``"pages": {"table": (batch, P) int32}`` block table (P = max_len /
    page_size logical pages per slot, one table shared by every paged
    layer). Windowed layers keep their rolling caches — already O(window)
    memory, and an identical code path keeps them bitwise-trivially equal
    to the non-paged engine."""
    kv, hd = cfg.n_kv_heads, cfg.hd
    f = jax.ShapeDtypeStruct
    if paging is not None:
        n_pages, page_size = paging
        if max_len % page_size != 0:
            raise ValueError(f"paged cache needs max_len % page_size == 0, "
                             f"got {max_len} % {page_size}")

    def attn_cache(window):
        if paging is not None and window is None:
            if specs_only:
                return attn_mod.paged_cache_spec(cfg, batch, n_pages,
                                                 page_size, dtype)
            return attn_mod.init_paged_cache(cfg, batch, n_pages, page_size,
                                             dtype)
        if specs_only:
            return attn_mod.cache_spec(cfg, batch, max_len, window, dtype)
        return attn_mod.init_cache(cfg, batch, max_len, window, dtype)

    def cross_cache():
        M = cfg.n_frontend_tokens
        if specs_only:
            return {"ck": f((batch, M, kv, hd), dtype),
                    "cv": f((batch, M, kv, hd), dtype)}
        return {"ck": zeros(batch, M, kv, hd, dtype=dtype),
                "cv": zeros(batch, M, kv, hd, dtype=dtype)}

    def layer_cache(spec: LayerSpec) -> dict:
        c: dict = {}
        if spec.kind == ATTN:
            c["kv"] = attn_cache(spec.window)
            if cfg.family == "encdec":
                c["cross"] = cross_cache()
        elif spec.kind == CROSS:
            c["cross"] = cross_cache()
        elif spec.kind == RGLRU:
            c["state"] = (rglru_mod.rglru_state_spec(cfg, batch, dtype)
                          if specs_only else
                          rglru_mod.init_rglru_state(cfg, batch, dtype))
        elif spec.kind == MLSTM:
            c["state"] = (xlstm_mod.mlstm_state_spec(cfg, batch, dtype)
                          if specs_only else
                          xlstm_mod.init_mlstm_state(cfg, batch, dtype))
        elif spec.kind == SLSTM:
            c["state"] = (xlstm_mod.slstm_state_spec(cfg, batch, dtype)
                          if specs_only else
                          xlstm_mod.init_slstm_state(cfg, batch, dtype))
        return c

    def stack_caches(spec: LayerSpec):
        one = layer_cache(spec)
        G = cfg.n_groups
        if specs_only:
            return jax.tree.map(
                lambda s: f((G, *s.shape), s.dtype), one,
                is_leaf=lambda s: isinstance(s, jax.ShapeDtypeStruct))
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (G, *a.shape)), one)

    cache = {
        "groups": [stack_caches(spec) for spec in cfg.pattern],
        "tail": [layer_cache(spec) for spec in cfg.tail_pattern],
    }
    if paging is not None:
        P = max_len // page_size
        cache["pages"] = {"table": (f((batch, P), jnp.int32) if specs_only
                                    else jnp.zeros((batch, P), jnp.int32))}
    return cache


def _decode_layer(p: dict, cfg: ModelConfig, spec: LayerSpec, x, cache, *,
                  lora: Optional[dict], encdec_cross: bool,
                  pages: Optional[dict] = None):
    lo = lora or {}
    new_cache = dict(cache)
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if spec.kind == ATTN:
        y, new_kv = attn_mod.attn_decode(p["attn"], cfg, h, cache["kv"],
                                         window=spec.window,
                                         lora=lo.get("attn"), pages=pages)
        new_cache["kv"] = new_kv
    elif spec.kind == CROSS:
        y, _ = attn_mod.attn_decode(p["attn"], cfg, h, {},
                                    cross_kv=(cache["cross"]["ck"],
                                              cache["cross"]["cv"]),
                                    lora=lo.get("attn"))
    elif spec.kind == RGLRU:
        y, st = rglru_mod.rglru_decode(p["rglru"], cfg, h, cache["state"],
                                       lora=lo.get("rglru"))
        new_cache["state"] = st
    elif spec.kind == MLSTM:
        y, st = xlstm_mod.mlstm_decode(p["mlstm"], cfg, h, cache["state"],
                                       lora=lo.get("mlstm"))
        new_cache["state"] = st
    elif spec.kind == SLSTM:
        y, st = xlstm_mod.slstm_decode(p["slstm"], cfg, h, cache["state"],
                                       lora=lo.get("slstm"))
        new_cache["state"] = st
    else:
        raise ValueError(spec.kind)
    x = x + y.astype(x.dtype)
    if encdec_cross and spec.kind == ATTN:
        h = rmsnorm(x, p["norm_cross"], cfg.norm_eps)
        y, _ = attn_mod.attn_decode(p["cross"], cfg, h, {},
                                    cross_kv=(cache["cross"]["ck"],
                                              cache["cross"]["cv"]),
                                    lora=lo.get("cross"))
        x = x + y.astype(x.dtype)
    if spec.ffn == DENSE:
        h = rmsnorm(x, p["norm2"], cfg.norm_eps)
        x = x + mlp(p["ffn"], h, cfg.act).astype(x.dtype)
    elif spec.ffn == MOE:
        h = rmsnorm(x, p["norm2"], cfg.norm_eps)
        y, _ = moe_mod.moe_ffn(p["moe"], cfg, h)
        x = x + y.astype(x.dtype)
    return x, new_cache


def decode_step(params: dict, cfg: ModelConfig, tokens: jax.Array,
                cache: dict, *, lora: Optional[dict] = None):
    """tokens: (B, 1) -> (logits (B, 1, V_pad), new_cache). A cache built
    with ``paging`` carries its block table in ``cache["pages"]``; the
    table is data threaded through unchanged (decode never re-maps
    pages), so occupancy changes stay inside the one compiled step."""
    x = embed_tokens(params["embed"], tokens) * math.sqrt(cfg.d_model)
    encdec = cfg.family == "encdec"
    pages = cache.get("pages")
    lo = lora or {}
    lo_groups = lo.get("groups", [None] * len(cfg.pattern))
    has_lora = any(g is not None for g in lo_groups)
    group_params, layer_params = _scanned(params["groups"])

    def body(x, xs):
        gp, gc, gl, i = xs
        new_gc = []
        for j, spec in enumerate(cfg.pattern):
            x, nc = _decode_layer(layer_params(gp, j, i), cfg, spec, x,
                                  gc[j],
                                  lora=gl[j] if gl is not None else None,
                                  encdec_cross=encdec, pages=pages)
            new_gc.append(nc)
        return x, new_gc

    xs = (group_params, cache["groups"],
          lo_groups if has_lora else None, jnp.arange(cfg.n_groups))
    x, new_group_caches = jax.lax.scan(body, x, xs, length=cfg.n_groups)

    lo_tail = lo.get("tail", [None] * cfg.tail_len)
    new_tail = []
    for j, spec in enumerate(cfg.tail_pattern):
        x, nc = _decode_layer(params["tail"][j], cfg, spec, x,
                              cache["tail"][j], lora=lo_tail[j],
                              encdec_cross=encdec, pages=pages)
        new_tail.append(nc)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x, params.get("unembed", params["embed"]),
                     tied=cfg.tie_embeddings, softcap=cfg.logit_softcap)
    new_cache = {"groups": new_group_caches, "tail": new_tail}
    if pages is not None:
        new_cache["pages"] = pages
    return logits, new_cache


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Chunked prefill covers pure-attention decoders (the serving-core
    archs). Recurrent kinds would need sequential state threading per
    chunk and enc-dec/VLM need memory plumbing — both fall back to the
    engine's teacher-forced prefill-by-decode."""
    specs = list(cfg.pattern) + list(cfg.tail_pattern)
    return (cfg.family not in ("encdec", "vlm") and
            all(s.kind == ATTN for s in specs))


def _chunk_prefill_layer(p: dict, cfg: ModelConfig, spec: LayerSpec, x,
                         cache, slot, start, limit, *,
                         lora: Optional[dict], pages: Optional[dict]):
    lo = lora or {}
    new_cache = dict(cache)
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if spec.kind != ATTN:
        raise NotImplementedError(
            f"chunked prefill supports attention-only decoders, got layer "
            f"kind {spec.kind!r} (see supports_chunked_prefill)")
    kv = cache["kv"]
    if "kp" in kv:
        y, new_kv = attn_mod.attn_chunk_paged(
            p["attn"], cfg, h, kv, pages["table"][slot], slot, start, limit,
            lora=lo.get("attn"))
    else:
        y, new_kv = attn_mod.attn_chunk_rolling(
            p["attn"], cfg, h, kv, slot, start, limit, lora=lo.get("attn"))
    new_cache["kv"] = new_kv
    x = x + y.astype(x.dtype)
    if spec.ffn == DENSE:
        h = rmsnorm(x, p["norm2"], cfg.norm_eps)
        x = x + mlp(p["ffn"], h, cfg.act).astype(x.dtype)
    elif spec.ffn == MOE:
        h = rmsnorm(x, p["norm2"], cfg.norm_eps)
        y, _ = moe_mod.moe_ffn(p["moe"], cfg, h)
        x = x + y.astype(x.dtype)
    return x, new_cache


def chunk_prefill_step(params: dict, cfg: ModelConfig, tokens: jax.Array,
                       cache: dict, slot, start, limit, *,
                       lora: Optional[dict] = None) -> dict:
    """Stream one slot's prompt chunk into the serving cache.

    tokens: (1, C) — C is the engine's fixed chunk size (pad the final
    chunk; pads past ``limit`` neither write KV nor produce used output).
    slot / start / limit: () int32 — the batch row being prefilled, the
    chunk's absolute position offset, and the total real prefill length.
    Returns the new cache only (the engine teacher-forces the final
    prompt token through decode_step, which emits the first logits), so
    one compiled chunk trace serves every prompt length."""
    if not supports_chunked_prefill(cfg):
        raise NotImplementedError(
            f"chunked prefill unsupported for {cfg.name} "
            f"(attention-only decoders; see supports_chunked_prefill)")
    x = embed_tokens(params["embed"], tokens) * math.sqrt(cfg.d_model)
    pages = cache.get("pages")
    lo = lora or {}
    lo_groups = lo.get("groups", [None] * len(cfg.pattern))
    has_lora = any(g is not None for g in lo_groups)
    group_params, layer_params = _scanned(params["groups"])

    def body(x, xs):
        gp, gc, gl, i = xs
        new_gc = []
        for j, spec in enumerate(cfg.pattern):
            x, nc = _chunk_prefill_layer(
                layer_params(gp, j, i), cfg, spec, x, gc[j], slot, start,
                limit, lora=gl[j] if gl is not None else None, pages=pages)
            new_gc.append(nc)
        return x, new_gc

    xs = (group_params, cache["groups"],
          lo_groups if has_lora else None, jnp.arange(cfg.n_groups))
    x, new_group_caches = jax.lax.scan(body, x, xs, length=cfg.n_groups)

    lo_tail = lo.get("tail", [None] * cfg.tail_len)
    new_tail = []
    for j, spec in enumerate(cfg.tail_pattern):
        x, nc = _chunk_prefill_layer(params["tail"][j], cfg, spec, x,
                                     cache["tail"][j], slot, start, limit,
                                     lora=lo_tail[j], pages=pages)
        new_tail.append(nc)

    new_cache = {"groups": new_group_caches, "tail": new_tail}
    if pages is not None:
        new_cache["pages"] = pages
    return new_cache


def prefill(params: dict, cfg: ModelConfig, tokens: jax.Array, *,
            frontend: Optional[jax.Array] = None,
            lora: Optional[dict] = None):
    """Forward over the prompt; returns last-position logits only (serving).
    Unembeds ONLY the final hidden state — (B, S, V) logits are never
    materialized. (Cache build from prefill activations is exercised in
    serve.py at small scale; the 32k dry-run lowers this step.)"""
    x, _ = hidden_forward(params, cfg, tokens, frontend=frontend, lora=lora,
                          remat=False)
    return unembed(x[..., -1, :], params.get("unembed", params["embed"]),
                   tied=cfg.tie_embeddings, softcap=cfg.logit_softcap)
