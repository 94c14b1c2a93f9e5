"""Grouped expert matmul for routed MoE, with its backward to the rows.

The rows of x are sorted by expert: the first ``group_sizes[0]`` belong
to expert 0, the next ``group_sizes[1]`` to expert 1, and so on. Each
group is multiplied by its own expert's weights:

- ``moe_gmm``: y[rows of e] = x[rows of e] @ w[e], w (E, K, N) -> (M, N)
- ``moe_gmm_t``: dx[rows of e] = dy[rows of e] @ w[e]^T -> (M, K)

w may also be the experts of every layer, (L, E, K, N), with the index of
the layer to use: a layer's slice of a scanned stack handed to a kernel
would be copied whole first, so the kernels read their blocks from the
stack itself.

``expert_matmul`` is the first under a `jax.custom_vjp` whose backward
is the second. The expert weights belong to the frozen base: no weight
gradient is computed.

Grid (column tiles, row tiles visited). Row tiles are `TM` rows of x; a
tile that two groups share is visited once by each, in turn, and each
visit writes only its own group's rows. The weight block of a visit is
the group's whole contraction by `tn` output columns, so consecutive
visits of one group keep the same block index and Pallas fetches it
once: each expert's weights stream from HBM once per column tile. They
stay float32 in HBM and are cast to the operand dtype in VMEM, once per
group. Empty groups are not visited. Rows past the last group are left
unwritten and are the caller's to drop.

Precision: the operands round to one dtype and products accumulate in
float32. The dtype follows the matmul precision in force, as a float32
XLA matmul on the TPU does: bfloat16 at the default, float32 where more
is asked for (`jax.default_matmul_precision("highest")`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from repro.kernels.flash_attention import _operands

TM = 256                    # rows of x per tile
_W_BLOCK_BYTES = 12 << 20   # largest float32 weight block held in VMEM
_NN = (((1,), (0,)), ((), ()))           # a @ b
_NT = (((1,), (1,)), ((), ()))           # a @ b.T


@dataclasses.dataclass(frozen=True)
class _Config:
    """What a call fixes at trace time."""
    tn: int
    transpose: bool
    operands: Any
    interpret: bool


def column_tile(n: int, k: int) -> int:
    """Output columns per weight block: all ``n`` where a float32 block
    of ``k`` x ``n`` fits `_W_BLOCK_BYTES`, else the largest multiple of
    128 that divides ``n`` and fits."""
    if k * n * 4 <= _W_BLOCK_BYTES:
        return n
    tn = 128
    for c in range(256, n, 128):
        if n % c == 0 and k * c * 4 <= _W_BLOCK_BYTES:
            tn = c
    return tn


def _kernel(layer, offsets, groups, tiles, x_ref, w_ref, o_ref, *scratch,
            c: _Config):
    del layer
    t = pl.program_id(1)
    g = groups[t]
    if scratch:
        (w_sc,) = scratch

        @pl.when(jnp.logical_or(t == 0, groups[jnp.maximum(t - 1, 0)] != g))
        def _cast():
            w_sc[...] = w_ref[...].astype(w_sc.dtype)

        w = w_sc[...]
    else:
        w = w_ref[...]
    precision = jax.lax.Precision.HIGHEST if w.dtype == jnp.float32 \
        else None
    acc = jax.lax.dot_general(x_ref[...], w, _NT if c.transpose else _NN,
                              precision=precision,
                              preferred_element_type=jnp.float32)
    rows = tiles[t] * TM + jax.lax.broadcasted_iota(jnp.int32, acc.shape,
                                                      0)
    mine = jnp.logical_and(rows >= offsets[g], rows < offsets[g + 1])
    o_ref[...] = jnp.where(mine, acc, o_ref[...].astype(jnp.float32)
                           ).astype(o_ref.dtype)


def _gmm(x, w, layer, group_sizes, out_dtype, c: _Config):
    """x (M, K) in the operand dtype, M a multiple of `TM`; w (L, E,
    K, N) float32, or (L, E, N, K) with ``c.transpose``; layer (1,)."""
    M, K = x.shape
    N = w.shape[2] if c.transpose else w.shape[3]
    meta, n_tiles = make_group_metadata(
        group_sizes=group_sizes, m=M, tm=TM,
        start_group=jnp.zeros((), jnp.int32),
        num_nonzero_groups=w.shape[1], visit_empty_groups=False)
    if c.transpose:
        w_block = pl.BlockSpec((None, None, c.tn, K),
                               lambda n, t, l, o, g, m: (l[0], g[t], n, 0))
    else:
        w_block = pl.BlockSpec((None, None, K, c.tn),
                               lambda n, t, l, o, g, m: (l[0], g[t], 0, n))
    w_bytes = K * c.tn * 4
    scratch = [] if c.operands == w.dtype else \
        [pltpu.VMEM(w_block.block_shape[2:], c.operands)]
    vmem = 2 * w_bytes + w_bytes // 2 + 2 * TM * (K * x.dtype.itemsize
                                                    + c.tn * 4)
    return pl.pallas_call(
        functools.partial(_kernel, c=c),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N // c.tn, n_tiles),
            in_specs=[pl.BlockSpec((TM, K),
                                   lambda n, t, l, o, g, m: (m[t], 0)),
                      w_block],
            out_specs=pl.BlockSpec((TM, c.tn),
                                   lambda n, t, l, o, g, m: (m[t], n)),
            scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, vmem + (8 << 20))),
        name="moe_gmm_t" if c.transpose else "moe_gmm",
        interpret=c.interpret,
    )(layer, *meta, x, w)


def _padded(x):
    pad = (-x.shape[0]) % TM
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _call(x, w, layer, group_sizes, c: _Config):
    M = x.shape[0]
    y = _gmm(_padded(x.astype(c.operands)), w, layer, group_sizes,
             x.dtype, c)
    return y[:M]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _expert_matmul(x, w, layer, group_sizes, c: _Config):
    return _call(x, w, layer, group_sizes, c)


def _expert_matmul_fwd(x, w, layer, group_sizes, c: _Config):
    return _call(x, w, layer, group_sizes, c), (w, layer, group_sizes)


def _expert_matmul_bwd(c: _Config, res, dy):
    w, layer, group_sizes = res
    ct = dataclasses.replace(c, transpose=True,
                             tn=column_tile(w.shape[2], w.shape[3]))
    return _call(dy, w, layer, group_sizes, ct), None, None, None


_expert_matmul.defvjp(_expert_matmul_fwd, _expert_matmul_bwd)


def _stacked(w, layer):
    """(L, E, K, N) weights and the layer as an int32 (1,) array."""
    if layer is None:
        return w[None], jnp.zeros((1,), jnp.int32)
    return w, jnp.reshape(layer, (1,)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_matmul(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
                  layer=None, *, interpret: bool = False) -> jax.Array:
    """x (M, K) sorted by expert, w (E, K, N) float32 (or (L, E, K, N)
    with ``layer``), group_sizes (E,) int32 summing to at most M -> (M,
    N) in x's dtype; rows past the groups are unspecified.
    Differentiable in x (`moe_gmm_t`); w gets no gradient. Products
    follow the matmul precision in force."""
    w, layer = _stacked(w, layer)
    c = _Config(column_tile(w.shape[3], w.shape[2]), False, _operands(),
                interpret)
    return _expert_matmul(x, w, layer, group_sizes.astype(jnp.int32), c)

