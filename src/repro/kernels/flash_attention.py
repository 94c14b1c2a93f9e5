"""Blocked online-softmax attention (flash-style) with causal and
sliding-window masks — the prefill hot-spot (gemma3 / mixtral /
recurrentgemma local layers use windows).

Grid: (B*H, nq, nk) with the KV dimension innermost and sequential
("arbitrary"); running max / sum / accumulator live in VMEM scratch across
KV steps. Mask is computed from absolute block offsets, so causal and
windowed variants share one kernel. Fully-masked KV blocks still run
(grid pruning is a §Perf follow-up on real hardware; interpret-mode
validation is mask-correctness-only).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            sm_scale: float, causal: bool, window: Optional[int],
            bq: int, bk: int, nk: int):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                 # (bq, d)
    k = k_ref[0].astype(jnp.float32)                 # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale

    q_pos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[...]                              # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kb == nk - 1)
    def _finish():
        o_ref[0, ...] = (acc_ref[...] /
                         jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, H, S, d); k/v: (B, H, L, d) -> (B, H, S, d)."""
    B, H, S, d = q.shape
    L = k.shape[2]
    bq, bk = min(bq, S), min(bk, L)
    assert S % bq == 0 and L % bk == 0, (S, L, bq, bk)
    nq, nk = S // bq, L // bk
    sm_scale = 1.0 / math.sqrt(d)

    qf = q.reshape(B * H, S, d)
    kf = k.reshape(B * H, L, d)
    vf = v.reshape(B * H, L, d)

    out = pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, causal=causal,
                          window=window, bq=bq, bk=bk, nk=nk),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, d)
