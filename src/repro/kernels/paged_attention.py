"""Paged-attention decode Pallas kernel (flash-decode over KV pages).

One decode token per batch row attends over a KV cache stored as
fixed-size physical pages shared by all rows: ``k_pages``/``v_pages``
are ``(n_pages, KV, page_size, hd)`` and each row's block table maps its
logical page index to a physical page. Heads lead the page so that one
grid step's K/V block ``(1, 1, page_size, hd)`` ends in the page's own
two trailing dims, which Mosaic's (8, 128) tiling rule always admits.
The gather happens INSIDE the kernel via scalar-prefetched block index
maps (the same
`PrefetchScalarGridSpec` pattern as `slot_lora_matmul`): grid step
``(b, kv, p)`` DMAs physical page ``table[b, p]``, so page occupancy is
data — growing, shrinking, or remapping a row's pages never changes a
traced shape.

The page sweep is the classic online-softmax accumulation (running max
``m``, normalizer ``l``, unnormalized accumulator ``acc`` in VMEM
scratch, rescaled by ``exp(m_prev - m_new)`` each step, normalized on
the last page). Positions past ``lengths[b]`` mask to -1e30, matching
the masking constant of `models.attention._attend`; page 0 is the
serving core's null page, reachable only through masked-out entries of
an inactive row's table.

Numerics: online softmax reassociates the reduction, so kernel output is
tolerance-equal (not bitwise) to `ref.paged_attn_decode_ref`; the REF
oracle is the one that is bitwise against the contiguous decode path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, page_size: int, n_pseq: int,
                   scale: float):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)               # (page_size, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    k_pos = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    s = jnp.where(k_pos < len_ref[b], s, -1e30)       # (G, page_size)

    m_prev = m_ref[...]                               # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        pexp, v, preferred_element_type=jnp.float32)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(p == n_pseq - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attn_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      table: jax.Array, lengths: jax.Array, *,
                      interpret: bool = False) -> jax.Array:
    """q: (B, KV, G, hd) grouped decode queries; k_pages/v_pages:
    (n_pages, KV, page_size, hd); table: (B, P) int32; lengths: (B,)
    valid context per row (>= 1 for rows whose output is read).
    Returns (B, KV, G, hd)."""
    B, KV, G, hd = q.shape
    page_size = k_pages.shape[2]
    P = table.shape[1]
    scale = 1.0 / math.sqrt(hd)

    grid = (B, KV, P)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, hd),
                         lambda b, kv, p, tbl, lens: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, kv, p, tbl, lens: (tbl[b * P + p], kv,
                                                      0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, kv, p, tbl, lens: (tbl[b * P + p], kv,
                                                      0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, kv, p, tbl, lens: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, page_size=page_size, n_pseq=P,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(table.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pages, v_pages)
