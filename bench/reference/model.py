"""Plain float32 reference of the decoders the benchmark runs.

Straight `jax.numpy`, no kernels, no cache, no batching tricks, and
nothing imported from the program. It follows what the program computes
(each departure from the published model is listed in the configuration
file). Its weights are the ones bench/seeded.py hands the program, drawn
here one layer at a time inside the layer scan, so the whole model never
has to be held at once.

Shapes: x is (m, b, S, d) — m adapter rows (clients, or one request),
b sequences each. A LoRA leaf is {"a": (m, d_in, r), "b": (m, r, d_out)}.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from bench import seeded


@dataclass(frozen=True)
class Spec:
    d: int
    heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    layers: int
    rope_theta: float
    eps: float
    experts: int = 0
    shared: int = 0
    top_k: int = 0
    e_ff: int = 0
    aux_coef: float = 0.0
    qkv_bias: bool = False
    rank: int = 8
    alpha: float = 16.0
    targets: tuple = ("wq", "wv")

    @property
    def hd(self) -> int:
        return self.d // self.heads

    @property
    def lora_scale(self) -> float:
        return self.alpha / self.rank


def spec_from_config(cfg: dict) -> Spec:
    """The reference's sizes, read from a bench/configs/<name>.json."""
    moe = "n_routed_experts" in cfg
    return Spec(d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"],
                rope_theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]),
                experts=cfg.get("n_routed_experts", 0),
                shared=cfg.get("n_shared_experts", 0),
                top_k=cfg.get("num_experts_per_tok", 0),
                e_ff=cfg.get("moe_intermediate_size", 0),
                aux_coef=float(cfg.get("aux_loss_alpha", 0.0)) if moe else 0.0,
                qkv_bias=bool(cfg.get("attention_bias", False)),
                rank=cfg["lora"]["rank"], alpha=float(cfg["lora"]["alpha"]),
                targets=tuple(cfg["lora"]["targets"]))


# ---------------------------------------------------------------------------
# weights from the seed (bench/seeded.py, under the program's leaf paths)
# ---------------------------------------------------------------------------

def head_weights(seed: int, s: Spec):
    """(embed (V, d), unembed (d, V)) over the real vocabulary."""
    embed = seeded.vocab_rows(seed, "embed", s.vocab, s.d, 0.02)
    unembed = seeded.vocab_rows(seed, "unembed", s.vocab, s.d,
                                1.0 / math.sqrt(s.d)).T
    return embed, unembed


def layer_weights(seed, g, s: Spec) -> dict:
    """Layer g's weights. Norm gains start at zero (weight one) and the
    round trains only LoRA factors, so they stay so and are left out."""
    def w(path, *shape):
        return seeded.layer_leaf(seed, f"groups/0/{path}", g, shape)

    q_dim, kv_dim = s.heads * s.hd, s.kv_heads * s.hd
    out = {"wq": w("attn/wq", s.d, q_dim), "wk": w("attn/wk", s.d, kv_dim),
           "wv": w("attn/wv", s.d, kv_dim), "wo": w("attn/wo", q_dim, s.d)}
    if s.qkv_bias:
        out.update(bq=w("attn/bq", q_dim), bk=w("attn/bk", kv_dim),
                   bv=w("attn/bv", kv_dim))
    if s.experts:
        E, f = s.experts, s.e_ff
        out.update(router=w("moe/router", s.d, E),
                   w_gate=w("moe/w_gate", E, s.d, f),
                   w_up=w("moe/w_up", E, s.d, f),
                   w_down=w("moe/w_down", E, f, s.d))
        width, ffn = s.e_ff * s.shared, "moe/shared"
    else:
        width, ffn = s.d_ff, "ffn"
    if width:
        out.update(gate=w(f"{ffn}/wi_gate", s.d, width),
                   up=w(f"{ffn}/wi_up", s.d, width),
                   down=w(f"{ffn}/wo", width, s.d))
    return out


def lora_init(seed: int, s: Spec, m: int) -> dict:
    """Training's LoRA start: a ~ N(0, 1/d_in) shared by every client,
    b = 0. Leaves are stacked over layers: a (L, m, d_in, r)."""
    dims = {"wq": (s.d, s.heads * s.hd), "wv": (s.d, s.kv_heads * s.hd)}
    out = {}
    for name in s.targets:
        d_in, d_out = dims[name]
        a = jax.vmap(lambda g: seeded.lora_a(
            seed, f"groups/0/attn/{name}/a", g, d_in, s.rank))(
                jnp.arange(s.layers))
        out[name] = {"a": jnp.broadcast_to(a[:, None], (s.layers, m, d_in,
                                                         s.rank)),
                     "b": jnp.zeros((s.layers, m, s.rank, d_out))}
    return out


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def rmsnorm(x, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return y.astype(x.dtype)


def rope(x, pos, theta):
    """x (..., S, H, hd), pos (S,)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def project(x, w, lo, scale, bias=None):
    """x @ w (+ bias) plus the row's LoRA path; x (m, ..., d_in)."""
    y = jnp.einsum("m...d,df->m...f", x, w.astype(x.dtype))
    if bias is not None:
        y = y + bias.astype(x.dtype)
    if lo is not None:
        xa = jnp.einsum("m...d,mdr->m...r", x, lo["a"].astype(x.dtype))
        y = y + jnp.einsum("m...r,mrf->m...f", xa,
                           lo["b"].astype(x.dtype)) * scale
    return y


def attention(q, k, v, kv_heads):
    """Causal grouped-query attention. q (m, b, S, H, hd), k/v
    (m, b, S, KV, hd); scores and softmax in float32."""
    m, b, S, H, hd = q.shape
    qg = q.reshape(m, b, S, kv_heads, H // kv_heads, hd)
    sc = jnp.einsum("mbskgh,mblkh->mbkgsl", qg.astype(jnp.float32),
                    k.astype(jnp.float32)) / math.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    sc = jnp.where(causal, sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("mbkgsl,mblkh->mbskgh", p, v.astype(jnp.float32))
    return out.reshape(m, b, S, H * hd).astype(q.dtype)


def gated_mlp(h, gate, up, down):
    dt = h.dtype
    return (jax.nn.silu(h @ gate.astype(dt)) * (h @ up.astype(dt))) \
        @ down.astype(dt)


def moe(h, w, s: Spec):
    """Top-k routed experts (softmax router, weights renormalised over
    the k chosen) plus the shared experts; experts run one at a time.
    Returns (out, balance loss before its coefficient)."""
    logits = jnp.einsum("...d,de->...e", h.astype(jnp.float32),
                        w["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, -1)
    top_w, top_i = jax.lax.top_k(probs, s.top_k)
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(top_i, s.experts) * top_w[..., None], -2)
    dims = tuple(range(probs.ndim - 1))
    aux = s.experts * jnp.sum(jnp.mean((gate > 0).astype(jnp.float32), dims)
                              * jnp.mean(probs, dims))
    gate = gate.astype(h.dtype)

    @jax.checkpoint
    def one_expert(acc, e):
        y = gated_mlp(h, w["w_gate"][e], w["w_up"][e], w["w_down"][e])
        return acc + y * gate[..., e, None], None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          jnp.arange(s.experts))
    if s.shared:
        out = out + gated_mlp(h, w["gate"], w["up"], w["down"])
    return out, aux


def layer(x, w, lo, s: Spec, pos):
    """One decoder layer on x (m, b, S, d); returns (x, balance loss)."""
    m, b, S, _ = x.shape
    sc = s.lora_scale
    h = rmsnorm(x, s.eps)
    q = project(h, w["wq"], lo.get("wq"), sc, w.get("bq")).reshape(
        m, b, S, s.heads, s.hd)
    k = project(h, w["wk"], lo.get("wk"), sc, w.get("bk")).reshape(
        m, b, S, s.kv_heads, s.hd)
    v = project(h, w["wv"], lo.get("wv"), sc, w.get("bv")).reshape(
        m, b, S, s.kv_heads, s.hd)
    q, k = rope(q, pos, s.rope_theta), rope(k, pos, s.rope_theta)
    x = x + project(attention(q, k, v, s.kv_heads), w["wo"], None, sc)
    h = rmsnorm(x, s.eps)
    if s.experts:
        y, aux = moe(h, w, s)
        return x + y, aux * s.aux_coef
    return x + gated_mlp(h, w["gate"], w["up"], w["down"]), jnp.float32(0)


def hidden(seed, embed, tokens, lora, s: Spec, dtype=jnp.float32):
    """Final-norm hidden states (m, b, S, d) and the summed balance loss.
    Layer weights are drawn inside the (rematerialised) layer scan."""
    x = (embed[tokens] * math.sqrt(s.d)).astype(dtype)
    pos = jnp.arange(tokens.shape[-1])

    @jax.checkpoint
    def body(carry, g_lo):
        x, aux = carry
        g, lo = g_lo
        w = jax.tree.map(lambda t: t.astype(dtype),
                         layer_weights(seed, g, s))
        x, a = layer(x, w, lo, s, pos)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0)),
                               (jnp.arange(s.layers), lora))
    return rmsnorm(x, s.eps), aux


def logits(x, unembed, s: Spec):
    return (x @ unembed.astype(x.dtype)).astype(jnp.float32)
