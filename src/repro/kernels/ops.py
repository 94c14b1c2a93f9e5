"""Jit'd dispatching wrappers around the Pallas kernels.

On TPU the Pallas path compiles natively; elsewhere (this CPU container)
``ops`` falls back to the ref oracles so the framework runs everywhere.
``force="pallas_interpret"`` routes through the kernels in interpret mode
(used by tests to validate kernel bodies on CPU).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import current_mesh
from repro.kernels import ref
from repro.kernels.expert_matmul import expert_matmul as _expert_mm
from repro.kernels.flash_attention import block_sizes as _flash_blocks
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gossip_mix import gossip_mix as _gossip
from repro.kernels.gossip_mix import gossip_mix_quant as _gossip_quant
from repro.kernels.lora_matmul import lora_matmul as _lora_mm
from repro.kernels.lora_matmul import slot_lora_matmul as _slot_lora_mm
from repro.kernels.paged_attention import paged_attn_decode as _paged_attn
from repro.kernels.rglru_scan import rglru_scan as _rglru

_FORCE: Optional[str] = None   # None | "ref" | "pallas_interpret"


def set_backend(force: Optional[str]) -> None:
    global _FORCE
    assert force in (None, "ref", "pallas_interpret"), force
    _FORCE = force


def _mode() -> str:
    if _FORCE == "ref":
        return "ref"
    if _FORCE == "pallas_interpret":
        return "interpret"
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def lora_matmul(x, w, a, b, scale: float = 1.0):
    m = _mode()
    if m == "ref":
        return ref.lora_matmul_ref(x, w, a, b, scale)
    return _lora_mm(x, w, a, b, scale, interpret=(m == "interpret"))


def slot_lora_matmul(x, w, a, b, slots, scale: float = 1.0):
    """Adapter-pool LoRA matmul: row i applies adapter ``slots[i]``.
    x: (B, K), w: (K, N), a: (N_ad, K, r), b: (N_ad, r, N), slots: (B,)."""
    m = _mode()
    if m == "ref":
        return ref.slot_lora_matmul_ref(x, w, a, b, slots, scale)
    return _slot_lora_mm(x, w, a, b, slots, scale,
                         interpret=(m == "interpret"))


def paged_attn_decode(q, k_pages, v_pages, table, lengths):
    """Single-token decode attention over a paged KV cache (the serving
    core's gather). q: (B, 1, H, hd); k_pages/v_pages: (n_pages, KV,
    page_size, hd); table: (B, P) int32; lengths: (B,). The ref
    oracle is bitwise-identical to the contiguous decode path; the
    Pallas kernel is the flash-decode accumulation (tolerance)."""
    m = _mode()
    if m == "ref":
        return ref.paged_attn_decode_ref(q, k_pages, v_pages, table, lengths)
    B, _, H, hd = q.shape
    n_kv = k_pages.shape[1]
    qg = q.reshape(B, n_kv, H // n_kv, hd)
    out = _paged_attn(qg, k_pages, v_pages, table, lengths,
                      interpret=(m == "interpret"))
    return out.reshape(B, 1, H, hd)


def flash_attention_supported(S: int, L: int, hd: int) -> bool:
    """Whether `flash_attention` runs the kernel for causal attention of
    S queries over L keys of width hd: the kernels run (TPU, or
    interpreted), no multi-device mesh is bound (GSPMD cannot partition a
    Mosaic call), heads fill whole lanes and the lengths tile."""
    mesh = current_mesh()
    return (_mode() != "ref" and (mesh is None or mesh.size == 1)
            and hd % 128 == 0 and S == L and _flash_blocks(S, L) is not None)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None):
    """Differentiable attention. q: (B, S, H, d); k/v: (B, L, KV, d);
    query head h reads kv head h // (H // KV)."""
    m = _mode()
    if m == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _flash(q, k, v, causal=causal, window=window,
                  interpret=(m == "interpret"))


def expert_matmul_supported(K: int, N: int) -> bool:
    """Whether `expert_matmul` runs the kernel for experts of K x N: the
    kernels run (TPU, or interpreted), no multi-device mesh is bound
    (GSPMD cannot partition a Mosaic call) and both widths fill whole
    lanes."""
    mesh = current_mesh()
    return (_mode() != "ref" and (mesh is None or mesh.size == 1)
            and K % 128 == 0 and N % 128 == 0)


def expert_matmul(x, w, group_sizes, layer=None):
    """Grouped expert matmul, differentiable in x. x: (M, K) rows sorted
    by expert; w: (E, K, N), or (L, E, K, N) read at ``layer``;
    group_sizes: (E,) int32 summing to M. Where the kernel does not run,
    XLA's `ragged_dot`."""
    m = _mode()
    if not expert_matmul_supported(*w.shape[-2:]):
        return jax.lax.ragged_dot(x, w if layer is None else w[layer],
                                  group_sizes)
    return _expert_mm(x, w, group_sizes, layer,
                      interpret=(m == "interpret"))


_GOSSIP_BP = 512    # gossip_mix column stripe width


def _on_whole_stripes(call, *xs):
    """``call(*xs)`` on (rows, P) buffers zero-padded to whole gossip_mix
    stripes, with the (rows, P) result sliced back. Zero columns mix to
    zero columns, so the padding is lossless."""
    P = xs[0].shape[1]
    pad = (-P) % _GOSSIP_BP
    if not pad:
        return call(*xs)
    return call(*(jnp.pad(x, ((0, 0), (0, pad))) for x in xs))[:, :P]


def gossip_mix_flat(w: jax.Array, x: jax.Array, mask: jax.Array | float = 1.0):
    """Mix a flattened (m, P) client buffer: y = (mask·W + (1−mask)·I) @ x."""
    m_ = x.shape[0]
    eye = jnp.eye(m_, dtype=w.dtype)
    w_eff = mask * w + (1.0 - mask) * eye
    mode = _mode()
    if mode == "ref":
        return ref.gossip_mix_ref(w_eff, x)
    return _on_whole_stripes(
        lambda x_: _gossip(w_eff, x_, interpret=(mode == "interpret")), x)


def gossip_mix_seg(w: jax.Array, x: jax.Array, seg: jax.Array):
    """Mix a flattened (m, P) buffer with a per-column W_eff:
    y = seg·(W@x) + (1−seg)·x, seg: (1, P). This is the MixPlan fast path —
    unequal a/b masks fold into the single fused pass via the plan's
    column-segment layout instead of a per-leaf blend afterwards."""
    mode = _mode()
    if mode == "ref":
        return ref.gossip_mix_seg_ref(w, x, seg)
    return _on_whole_stripes(
        lambda x_, s_: _gossip(w, x_, s_, interpret=(mode == "interpret")),
        x, seg)


def gossip_mix_quant(w_off: jax.Array, q: jax.Array, scale: jax.Array,
                     x: jax.Array, w_diag: jax.Array, seg: jax.Array):
    """Compressed-gossip contraction with the dequantize fused in:
    y = seg·(w_diag·x + w_off @ (q·scale)) + (1−seg)·x. w_off: (r, m)
    off-diagonal mixing rows; q: (m, P) int8/fp8 payload; scale: (m, 1)
    f32 per-row scales; x: (r, P) fresh local rows; w_diag: (r, 1);
    seg: (1, P). Zero-padded q/x/seg columns dequantize to exact zeros,
    so padding here and slicing back is lossless."""
    mode = _mode()
    if mode == "ref":
        return ref.gossip_mix_quant_ref(w_off, q, scale, x, w_diag, seg)
    return _on_whole_stripes(
        lambda q_, x_, s_: _gossip_quant(w_off, q_, scale, x_, w_diag, s_,
                                         interpret=(mode == "interpret")),
        q, x, seg)


def rglru_scan(a, u):
    m = _mode()
    if m == "ref":
        return ref.rglru_scan_ref(a, u)
    return _rglru(a, u, interpret=(m == "interpret"))
