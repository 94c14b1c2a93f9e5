"""Fused LoRA matmul Pallas kernel: y = x@W + scale·(x@a)@b.

The low-rank path rides along the MXU base-matmul tiles: for each (i, j)
output block we sweep K in bk-sized steps, accumulating BOTH the dense
partial product x_blk @ W_blk and the rank-r projection x_blk @ a_blk in
VMEM scratch; on the final K step the (bm, r) @ (r, bn) correction lands on
the accumulator. One HBM sweep over x instead of two (dense + adapter),
which is the hot-spot of LoRA fine-tuning at framework scale.

Block sizes default to MXU-aligned 128 multiples; rank r stays whole (it is
8–64, far below a VMEM tile).

`slot_lora_matmul` is the multi-adapter serving variant: the adapter tensors
carry a leading pool axis (N_adapters, ...) and every batch row selects its
adapter by a per-row slot id. The gather happens INSIDE the kernel via
scalar-prefetched block index maps (the id picks which adapter row the a/b
BlockSpecs DMA), so one compiled decode step serves heterogeneous adapters —
swapping an adapter or retargeting a slot never changes any traced shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, a_ref, b_ref, o_ref, acc_ref, xa_ref, *,
            scale: float, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xa_ref[...] = jnp.zeros_like(xa_ref)

    xb = x_ref[...]
    acc_ref[...] += jnp.dot(xb, w_ref[...],
                            preferred_element_type=jnp.float32)
    xa_ref[...] += jnp.dot(xb, a_ref[...],
                           preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finish():
        corr = jnp.dot(xa_ref[...], b_ref[...].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + scale * corr).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "bk",
                                             "interpret"))
def lora_matmul(x: jax.Array, w: jax.Array, a: jax.Array, b: jax.Array,
                scale: float = 1.0, *, bm: int = 128, bn: int = 128,
                bk: int = 128, interpret: bool = False) -> jax.Array:
    """x: (M, K), w: (K, N), a: (K, r), b: (r, N) -> (M, N)."""
    M, K = x.shape
    N = w.shape[1]
    r = a.shape[1]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    nk = K // bk

    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, r), lambda i, j, k: (k, 0)),
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, a, b)


def _slot_kernel(slot_ref, x_ref, w_ref, a_ref, b_ref, o_ref, acc_ref,
                 xa_ref, *, scale: float, nk: int):
    del slot_ref                      # consumed by the block index maps
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xa_ref[...] = jnp.zeros_like(xa_ref)

    xb = x_ref[0]                     # (1, bk) — one decode slot's row
    acc_ref[...] += jnp.dot(xb, w_ref[...],
                            preferred_element_type=jnp.float32)
    xa_ref[...] += jnp.dot(xb, a_ref[0],
                           preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finish():
        corr = jnp.dot(xa_ref[...], b_ref[0].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        o_ref[0] = (acc_ref[...] + scale * corr).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bn", "bk",
                                             "interpret"))
def slot_lora_matmul(x: jax.Array, w: jax.Array, a: jax.Array, b: jax.Array,
                     slots: jax.Array, scale: float = 1.0, *, bn: int = 128,
                     bk: int = 128, interpret: bool = False) -> jax.Array:
    """Per-row adapter-indexed LoRA matmul (the multi-adapter decode step).

    x: (B, K), w: (K, N), a: (N_ad, K, r), b: (N_ad, r, N),
    slots: (B,) int32 adapter ids -> y[i] = x[i]@w + scale·(x[i]@a[s_i])@b[s_i].

    ``slots`` is a scalar-prefetch operand: the a/b index maps read it to DMA
    adapter row s_i for grid row i, so the gather costs one block choice, not
    a materialized (B, K, r) gather in HBM. Row blocks are bm=1 (decode B is
    the slot count, single tokens); the dense product still tiles (bk, bn)
    on the MXU. x and the output are viewed as (B, 1, K) / (B, 1, N) so a
    row block ends in (1, bk) / (1, bn) over a size-1 axis: Mosaic needs
    the last two block dims divisible by (8, 128) or equal to the array's.
    """
    B, K = x.shape
    N = w.shape[1]
    r = a.shape[2]
    bn, bk = min(bn, N), min(bk, K)
    assert N % bn == 0 and K % bk == 0, (N, K, bn, bk)
    assert a.shape[1] == K and b.shape[1] == r and b.shape[2] == N, \
        (a.shape, b.shape)
    nk = K // bk

    grid = (B, N // bn, nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bk), lambda i, j, k, slots: (i, 0, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k, slots: (k, j)),
            pl.BlockSpec((1, bk, r), lambda i, j, k, slots: (slots[i], k, 0)),
            pl.BlockSpec((1, r, bn), lambda i, j, k, slots: (slots[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, bn), lambda i, j, k, slots: (i, 0, j)),
        scratch_shapes=[
            pltpu.VMEM((1, bn), jnp.float32),
            pltpu.VMEM((1, r), jnp.float32),
        ],
    )
    y = pl.pallas_call(
        functools.partial(_slot_kernel, scale=scale, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(slots.astype(jnp.int32), x.reshape(B, 1, K), w, a, b)
    return y.reshape(B, N)
