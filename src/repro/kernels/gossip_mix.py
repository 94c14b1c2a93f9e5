"""Gossip-mixing Pallas kernel — Algorithm 1 lines 7–9 as one fused pass.

y = W_eff @ X with W_eff = mask·W_t + (1−mask)·I (mask folding happens in
ops.py so LORA/FFA/ROLORA/TAD all reduce to a plain blocked matmul), where
X is the (m, P) buffer of all client LoRA factors flattened and
concatenated (both blocks → ONE kernel pass / ONE upstream collective,
the joint-mixing step the paper adds).

With a ``seg`` operand — a (1, P) per-column mask from the MixPlan's a/b
segment layout (core.mixing) — the kernel instead computes
y = seg·(W@X) + (1−seg)·X, i.e. a *per-segment* W_eff: unequal a/b masks
(alternating phases, damped mixing) stay one fused HBM sweep instead of a
per-leaf blend pass after the matmul.

`gossip_mix_quant` is the compressed-gossip variant: the source rows
arrive quantized (int8/fp8 payload + one f32 scale per row, produced by
`core.mixing.quantize_rows`) and the kernel fuses the dequantize into the
same stripe sweep — y = w_diag·x + W_off @ (q·scale), per-column seg
blend — so the reconstruction never materializes an f32 copy of the
halo in HBM.

m (clients) is small (10–64): W_eff stays whole in VMEM; the grid streams
P in bp-wide stripes. VPU/MXU work is trivial — the kernel exists to make
the mixing a single fused HBM sweep instead of per-leaf dispatches.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _resolve_bp(P: int, bp: int) -> int:
    """The stripe width actually used: the largest divisor of P that is
    <= bp (shrink-to-divisor, e.g. P=768 at bp=512 -> 256). Validation
    raises ValueError — the former asserts vanished under ``python -O``
    and ``bp = min(bp, P)`` alone still tripped on non-multiple P."""
    if P <= 0 or bp <= 0:
        raise ValueError(f"gossip_mix needs positive P and bp, got "
                         f"P={P}, bp={bp}")
    bp = min(bp, P)
    if P % bp:
        bp = math.gcd(P, bp)
    return bp


def _kernel(w_ref, x_ref, o_ref):
    o_ref[...] = jnp.dot(w_ref[...].astype(jnp.float32),
                         x_ref[...].astype(jnp.float32),
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def _kernel_seg(w_ref, x_ref, s_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    y = jnp.dot(w_ref[...].astype(jnp.float32), x,
                preferred_element_type=jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    o_ref[...] = (s * y + (1.0 - s) * x).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bp", "interpret"))
def gossip_mix(w_eff: jax.Array, x: jax.Array,
               seg: Optional[jax.Array] = None, *, bp: int = 512,
               interpret: bool = False) -> jax.Array:
    """w_eff: (m, m); x: (m, P) -> (m, P). P padded to bp upstream.
    seg: optional (1, P) per-column blend mask (see module docstring)."""
    m, P = x.shape
    if w_eff.shape != (m, m):
        raise ValueError(f"gossip_mix: w_eff {w_eff.shape} does not match "
                         f"x client axis {m}")
    bp = _resolve_bp(P, bp)
    in_specs = [
        pl.BlockSpec((m, m), lambda j: (0, 0)),
        pl.BlockSpec((m, bp), lambda j: (0, j)),
    ]
    operands = (w_eff, x)
    kernel = _kernel
    if seg is not None:
        if seg.shape != (1, P):
            raise ValueError(f"gossip_mix: seg must be (1, {P}), got "
                             f"{seg.shape}")
        in_specs.append(pl.BlockSpec((1, bp), lambda j: (0, j)))
        operands = (w_eff, x, seg)
        kernel = _kernel_seg
    return pl.pallas_call(
        kernel,
        grid=(P // bp,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m, bp), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, P), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*operands)


def _kernel_quant(w_ref, q_ref, s_ref, x_ref, wd_ref, seg_ref, o_ref):
    z = q_ref[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    y = wd_ref[...].astype(jnp.float32) * x + jnp.dot(
        w_ref[...].astype(jnp.float32), z,
        preferred_element_type=jnp.float32)
    s = seg_ref[...].astype(jnp.float32)
    o_ref[...] = (s * y + (1.0 - s) * x).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bp", "interpret"))
def gossip_mix_quant(w_off: jax.Array, q: jax.Array, scale: jax.Array,
                     x: jax.Array, w_diag: jax.Array, seg: jax.Array, *,
                     bp: int = 512, interpret: bool = False) -> jax.Array:
    """Compressed-gossip contraction with the dequantize fused in.

    w_off: (r, m) mixing rows, diagonal zeroed; q: (m, P) int8/fp8
    quantized source rows; scale: (m, 1) f32 per-row scales; x: (r, P)
    fresh full-precision local rows; w_diag: (r, 1) diagonal
    coefficients; seg: (1, P) per-column blend mask. Returns
    seg·(w_diag·x + w_off @ (q·scale)) + (1−seg)·x, shape (r, P).
    P is padded to bp upstream (ops.py); zero-padded q columns
    dequantize to exact zeros."""
    r, m = w_off.shape
    if q.shape[0] != m:
        raise ValueError(f"gossip_mix_quant: q rows {q.shape} do not "
                         f"match w_off columns {m}")
    P = q.shape[1]
    if x.shape != (r, P):
        raise ValueError(f"gossip_mix_quant: x must be ({r}, {P}), got "
                         f"{x.shape}")
    if scale.shape != (m, 1) or w_diag.shape != (r, 1):
        raise ValueError(f"gossip_mix_quant: scale/w_diag must be "
                         f"({m}, 1)/({r}, 1), got {scale.shape}/"
                         f"{w_diag.shape}")
    if seg.shape != (1, P):
        raise ValueError(f"gossip_mix_quant: seg must be (1, {P}), got "
                         f"{seg.shape}")
    bp = _resolve_bp(P, bp)
    return pl.pallas_call(
        _kernel_quant,
        grid=(P // bp,),
        in_specs=[
            pl.BlockSpec((r, m), lambda j: (0, 0)),
            pl.BlockSpec((m, bp), lambda j: (0, j)),
            pl.BlockSpec((m, 1), lambda j: (0, 0)),
            pl.BlockSpec((r, bp), lambda j: (0, j)),
            pl.BlockSpec((r, 1), lambda j: (0, 0)),
            pl.BlockSpec((1, bp), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((r, bp), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((r, P), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(w_off, q, scale, x, w_diag, seg)
