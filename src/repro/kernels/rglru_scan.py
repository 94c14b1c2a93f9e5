"""RG-LRU diagonal linear recurrence Pallas kernel.

h_t = a_t ⊙ h_{t-1} + u_t over time, carried across time-blocks in VMEM
scratch. Grid: (B, nT) with time sequential; each block does bt in-VMEM
steps with a fori_loop (VPU elementwise — no MXU). This is the TPU-native
shape of the recurrence (contrast: the GPU kernels in the Griffin paper use
warp-level scans; here the parallelism is the W lane dimension).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, u_ref, o_ref, h_ref, *, bt: int):
    tb = pl.program_id(1)

    @pl.when(tb == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)          # (bt, W)
    u = u_ref[0].astype(jnp.float32)

    def step(t, h):
        h = a[t] * h + u[t]
        o_ref[0, t, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bt, step, h_ref[0])
    h_ref[0, :] = h


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def rglru_scan(a: jax.Array, u: jax.Array, *, bt: int = 256,
               interpret: bool = False) -> jax.Array:
    """a, u: (B, T, W) -> h: (B, T, W)."""
    B, T, W = a.shape
    bt = min(bt, T)
    assert T % bt == 0, (T, bt)
    return pl.pallas_call(
        functools.partial(_kernel, bt=bt),
        grid=(B, T // bt),
        in_specs=[
            pl.BlockSpec((1, bt, W), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, bt, W), lambda b, t: (b, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, W), lambda b, t: (b, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, W), u.dtype),
        scratch_shapes=[pltpu.VMEM((1, W), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a, u)
