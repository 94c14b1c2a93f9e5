"""Flash attention with its backward: blocked online-softmax attention
for the training and prefill paths, with causal and sliding-window masks
and grouped-query heads. Scores, probabilities, dP and dS live only in
VMEM tiles; nothing of size S x L is written to HBM.

Layout. Callers pass q (B, S, H, d) and k/v (B, L, KV, d). Inside, q, dO,
o and dQ travel transposed, (B, H*d, S): one head's block is a (d, rows)
slice, which is how XLA's projections on the chip produce q and consume
o, so no copy surrounds a call. k and v travel as (B, L, KV*d), and k^T
and v^T where a product wants them; they are H/KV times smaller than q.
On the chip d is a multiple of 128. Query head h reads kv head
h // (H // KV) through the index maps: no repeat of k or v.

Every tile is held keys-on-rows: s^T = k q^T is (keys, queries). The
per-query statistics (running max and sum, log-sum-exp, D) are then rows
that broadcast down sublanes and reduce over sublanes, and every product
is NN or NT, so no kernel transposes or reduces across lanes.

Precision: every product (QK^T, PV, dO V^T, dS K, dS^T Q, P^T dO) rounds
its operands to one dtype and accumulates in float32. The dtype follows
the matmul precision in force, as a float32 XLA matmul on the TPU does:
bfloat16 at the default, float32 where more is asked for
(`jax.default_matmul_precision("highest")`). The 1/sqrt(d) scale applies
to the float32 scores; max, sum, exp, the log-sum-exp and the accumulators are
float32, and o and the gradients come back in the inputs' dtype.

Kernels, named so that a device trace tells them apart:

- ``flash_attn_fwd``: grid (B, H, q blocks, kv blocks), kv innermost;
  o^T += v^T p^T. Writes o and the per-query log-sum-exp, the one
  residual besides q, k, v and o.
- ``flash_attn_bwd_dq``: the same grid; recomputes each probability tile
  from q, k and the log-sum-exp and accumulates dQ^T += k^T dS^T over kv
  blocks.
- ``flash_attn_bwd_dkv``: grid (B, KV, kv blocks, G, q blocks);
  accumulates dK and dV over the G query heads of its kv head and over q
  blocks.

D = rowsum(dO * O) is computed once, outside the kernels. A block that
the mask hides entirely does not run, and its index map repeats the
nearest live block so that no copy is issued for it. Inside a block the
work goes by sub-tiles of at most `SUB` rows and columns: those the mask
hides are skipped, those it leaves entirely visible run without it. Where
a grid axis has one block the block's position is static, and so is all
of this.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_NT = (((1,), (1,)), ((), ()))           # a @ b.T
BLOCK = 512
SUB = 128


def block_sizes(S: int, L: int) -> Optional[tuple]:
    """(bq, bk) for S queries over L keys, or None when the kernel does
    not tile these lengths: each a multiple of `SUB`, and of `BLOCK`
    beyond it."""
    if S % SUB or L % SUB:
        return None
    bq, bk = min(S, BLOCK), min(L, BLOCK)
    if S % bq or L % bk:
        return None
    return bq, bk


def _operands():
    """The dtype the products round their operands to, from the matmul
    precision in force: bfloat16 at the default, float32 otherwise."""
    p = jax.config.jax_default_matmul_precision
    return jnp.bfloat16 if p in (None, "default", "bfloat16") else \
        jnp.float32


@dataclasses.dataclass(frozen=True)
class _Config:
    """What a call fixes at trace time: the mask, the blocks, the
    operands' dtype (`_operands`), the head width."""
    causal: bool
    window: Optional[int]
    bq: int
    bk: int
    operands: Any
    d: int
    interpret: bool

    @property
    def tq(self) -> int:
        return min(self.bq, SUB)

    @property
    def tk(self) -> int:
        return min(self.bk, SUB)

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.d)

    @staticmethod
    def band(n: int, size: int) -> slice:
        return slice(n * size, (n + 1) * size)


# ---------------------------------------------------------------------------
# Mask geometry. Positions are absolute; q0 / k0 are a tile's first query
# and key, Python ints where the tile's position is static.
# ---------------------------------------------------------------------------

def _flags(q0, k0, tq: int, tk: int, c: _Config):
    """(live, full): whether some / every (query, key) pair of the tile
    is visible; Python bools where q0 and k0 are."""
    live = full = True
    if c.causal:
        live = k0 <= q0 + tq - 1
        full = k0 + tk - 1 <= q0
    if c.window is not None:
        live = live & (q0 - (k0 + tk - 1) < c.window)
        full = full & (q0 + tq - 1 - k0 < c.window)
    return live, full


def _and_not(a, b):
    """``a and not b`` for Python bools or traced ones."""
    if isinstance(a, bool) and isinstance(b, bool):
        return a and not b
    return jnp.logical_and(a, jnp.logical_not(b))


def _when(cond, fn) -> None:
    if cond is True:
        fn()
    elif cond is not False:
        pl.when(cond)(fn)


def _tiles(tile, q0, k0, c: _Config) -> None:
    """Call ``tile(a, b, masked)`` for every sub-tile (query band a, key
    band b) of the block at (q0, k0) that the mask does not hide, with
    ``masked`` where it hides part of it."""
    for a in range(c.bq // c.tq):
        for b in range(c.bk // c.tk):
            live, full = _flags(q0 + a * c.tq, k0 + b * c.tk, c.tq, c.tk, c)
            _when(full, functools.partial(tile, a, b, False))
            _when(_and_not(live, full), functools.partial(tile, a, b, True))


def _mask(st, q0, k0, c: _Config):
    """A keys-on-rows tile with the pairs the mask hides set to -1e30."""
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
    ok = jnp.ones(st.shape, dtype=bool)
    if c.causal:
        ok &= k_pos <= q_pos
    if c.window is not None:
        ok &= q_pos - k_pos < c.window
    return jnp.where(ok, st, _NEG)


def _kv_block(i, j, nk: int, c: _Config):
    """kv block j for q block i, clamped to the blocks it sees."""
    q0 = i * c.bq
    lo = 0 if c.window is None else \
        jnp.maximum((q0 - c.window + 1) // c.bk, 0)
    hi = jnp.minimum((q0 + c.bq - 1) // c.bk, nk - 1) if c.causal \
        else nk - 1
    return jnp.clip(j, lo, hi)


def _q_block(j, i, nq: int, c: _Config):
    """q block i for kv block j, clamped to the blocks that see it."""
    k0 = j * c.bk
    lo = k0 // c.bq if c.causal else 0
    hi = nq - 1 if c.window is None else \
        jnp.minimum((k0 + c.bk + c.window - 2) // c.bq, nq - 1)
    return jnp.clip(i, lo, hi)


def _axis(n: int, axis: int):
    """Grid index along ``axis``, static when the axis has one block."""
    return pl.program_id(axis) if n > 1 else 0


def _precision(a):
    """Float32 operands multiply at full precision: Mosaic is told so."""
    return jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None


def _nt(a, b):
    return jax.lax.dot_general(a, b, _NT, precision=_precision(a),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jnp.dot(a, b, precision=_precision(a),
                   preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Kernels (keys on rows; q, dO, o and dQ as (d, queries) per head)
# ---------------------------------------------------------------------------

def _fwd_kernel(qt_ref, k_ref, vt_ref, ot_ref, lse_ref, m_sc, l_sc, acc_sc,
                *, c: _Config, nq: int, nk: int):
    i, j = _axis(nq, 2), _axis(nk, 3)

    def init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    _when(j == 0, init)

    def tile(a, b, masked):
        qs, ks = c.band(a, c.tq), c.band(b, c.tk)
        st = _nn(k_ref[ks, :], qt_ref[:, qs]) * c.scale     # (tk, tq)
        if masked:
            st = _mask(st, i * c.bq + a * c.tq, j * c.bk + b * c.tk, c)
        m_prev = m_sc[:, qs]                                # (1, tq)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        pt = jnp.exp(st - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[:, qs] = alpha * l_sc[:, qs] + jnp.sum(pt, axis=0,
                                                    keepdims=True)
        acc_sc[:, qs] = alpha * acc_sc[:, qs] + _nn(
            vt_ref[:, ks], pt.astype(vt_ref.dtype))         # (d, tq)
        m_sc[:, qs] = m_new

    _tiles(tile, i * c.bq, j * c.bk, c)

    def finish():
        l = l_sc[...]
        ot_ref[...] = (acc_sc[...] / l).astype(ot_ref.dtype)
        lse_ref[...] = m_sc[...] + jnp.log(l)

    _when(j == nk - 1, finish)


def _dq_kernel(qt_ref, k_ref, kt_ref, v_ref, dot_ref, lse_ref, d_ref,
               dqt_ref, acc_sc, *, c: _Config, nq: int, nk: int):
    i, j = _axis(nq, 2), _axis(nk, 3)

    def init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    _when(j == 0, init)

    def tile(a, b, masked):
        qs, ks = c.band(a, c.tq), c.band(b, c.tk)
        st = _nn(k_ref[ks, :], qt_ref[:, qs]) * c.scale     # (tk, tq)
        if masked:
            st = _mask(st, i * c.bq + a * c.tq, j * c.bk + b * c.tk, c)
        pt = jnp.exp(st - lse_ref[:, qs])
        dpt = _nn(v_ref[ks, :], dot_ref[:, qs])
        dst = pt * (dpt - d_ref[:, qs])
        acc_sc[:, qs] += _nn(kt_ref[:, ks], dst.astype(kt_ref.dtype))

    _tiles(tile, i * c.bq, j * c.bk, c)

    def finish():
        dqt_ref[...] = (acc_sc[...] * c.scale).astype(dqt_ref.dtype)

    _when(j == nk - 1, finish)


def _dkv_kernel(qt_ref, k_ref, v_ref, dot_ref, lse_ref, d_ref, dk_ref,
                dv_ref, dk_sc, dv_sc, *, c: _Config, nq: int, nk: int,
                groups: int):
    j, g, i = _axis(nk, 2), pl.program_id(3), _axis(nq, 4)

    def init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    pl.when(jnp.logical_and(g == 0, i == 0))(init)

    def tile(a, b, masked):
        qs, ks = c.band(a, c.tq), c.band(b, c.tk)
        st = _nn(k_ref[ks, :], qt_ref[:, qs]) * c.scale     # (tk, tq)
        if masked:
            st = _mask(st, i * c.bq + a * c.tq, j * c.bk + b * c.tk, c)
        pt = jnp.exp(st - lse_ref[:, qs])
        dv_sc[ks, :] += _nt(pt.astype(dot_ref.dtype), dot_ref[:, qs])
        dpt = _nn(v_ref[ks, :], dot_ref[:, qs])
        dst = pt * (dpt - d_ref[:, qs])
        dk_sc[ks, :] += _nt(dst.astype(qt_ref.dtype), qt_ref[:, qs])

    _tiles(tile, i * c.bq, j * c.bk, c)

    def finish():
        dk_ref[...] = (dk_sc[...] * c.scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)

    pl.when(jnp.logical_and(g == groups - 1, i == nq - 1))(finish)


# ---------------------------------------------------------------------------
# Calls
# ---------------------------------------------------------------------------

def _flat(x):
    """(B, S, H, d) -> (B, S, H*d): a view, heads side by side in lanes."""
    return x.reshape(*x.shape[:2], -1)


def _tr(x):
    """(B, S, H, d) -> (B, H*d, S): each head's rows become columns."""
    return jnp.swapaxes(_flat(x), 1, 2)


def _untr(xt, d: int):
    """(B, H*d, S) -> (B, S, H, d)."""
    B, Hd, S = xt.shape
    return jnp.swapaxes(xt, 1, 2).reshape(B, S, Hd // d, d)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _forward(qc, kc, vc, out_dtype, c: _Config):
    B, S, H, d = qc.shape
    G, nq, nk = H // kc.shape[2], S // c.bq, kc.shape[1] // c.bk

    def kv(i, j):
        return _kv_block(i, j, nk, c)

    cols = pl.BlockSpec((None, d, c.bq), lambda b, h, i, j: (b, h, i))
    ot, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, c=c, nq=nq, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[cols,
                  pl.BlockSpec((None, c.bk, d),
                               lambda b, h, i, j: (b, kv(i, j), h // G)),
                  pl.BlockSpec((None, d, c.bk),
                               lambda b, h, i, j: (b, h // G, kv(i, j)))],
        out_specs=[cols, pl.BlockSpec((None, None, 1, c.bq),
                                      lambda b, h, i, j: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, H * d, S), out_dtype),
                   jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, c.bq), jnp.float32),
                        pltpu.VMEM((1, c.bq), jnp.float32),
                        pltpu.VMEM((d, c.bq), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        name="flash_attn_fwd",
        interpret=c.interpret,
    )(_tr(qc), _flat(kc), _tr(vc))
    return _untr(ot, d), lse


def _backward(qc, kc, vc, doc, lse, delta, c: _Config):
    B, S, H, d = qc.shape
    L, KV = kc.shape[1], kc.shape[2]
    G, nq, nk = H // KV, S // c.bq, L // c.bk
    qt, k, kt, v, dot = _tr(qc), _flat(kc), _tr(kc), _flat(vc), _tr(doc)

    def kv(i, j):
        return _kv_block(i, j, nk, c)

    cols = pl.BlockSpec((None, d, c.bq), lambda b, h, i, j: (b, h, i))
    krow = pl.BlockSpec((None, c.bk, d),
                        lambda b, h, i, j: (b, kv(i, j), h // G))
    kcol = pl.BlockSpec((None, d, c.bk),
                        lambda b, h, i, j: (b, h // G, kv(i, j)))
    stat = pl.BlockSpec((None, None, 1, c.bq),
                        lambda b, h, i, j: (b, h, 0, i))
    dqt = pl.pallas_call(
        functools.partial(_dq_kernel, c=c, nq=nq, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[cols, krow, kcol, krow, cols, stat, stat],
        out_specs=cols,
        out_shape=jax.ShapeDtypeStruct((B, H * d, S), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, c.bq), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        name="flash_attn_bwd_dq",
        interpret=c.interpret,
    )(qt, k, kt, v, dot, lse, delta)

    def qb(j, i):
        return _q_block(j, i, nq, c)

    qcols = pl.BlockSpec((None, d, c.bq),
                         lambda b, kh, j, g, i: (b, kh * G + g, qb(j, i)))
    qstat = pl.BlockSpec((None, None, 1, c.bq),
                         lambda b, kh, j, g, i: (b, kh * G + g, 0, qb(j, i)))
    key = pl.BlockSpec((None, c.bk, d), lambda b, kh, j, g, i: (b, j, kh))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, c=c, nq=nq, nk=nk, groups=G),
        grid=(B, KV, nk, G, nq),
        in_specs=[qcols, key, key, qcols, qstat, qstat],
        out_specs=[key, key],
        out_shape=[jax.ShapeDtypeStruct((B, L, KV * d), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((c.bk, d), jnp.float32),
                        pltpu.VMEM((c.bk, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary", "arbitrary"),
        name="flash_attn_bwd_dkv",
        interpret=c.interpret,
    )(qt, k, v, dot, lse, delta)
    return _untr(dqt, d), dk.reshape(B, L, KV, d), dv.reshape(B, L, KV, d)


def _cast(dtype, *xs):
    return tuple(x.astype(dtype) for x in xs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attention(q, k, v, c: _Config):
    return _forward(*_cast(c.operands, q, k, v), q.dtype, c)[0]


def _attention_fwd(q, k, v, c: _Config):
    qc, kc, vc = _cast(c.operands, q, k, v)
    o, lse = _forward(qc, kc, vc, q.dtype, c)
    return o, (qc, kc, vc, o, lse)


def _attention_bwd(c: _Config, res, do):
    qc, kc, vc, o, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.swapaxes(delta, 1, 2)[:, :, None, :]        # (B, H, 1, S)
    grads = _backward(qc, kc, vc, *_cast(c.operands, do), lse, delta, c)
    return tuple(g.astype(o.dtype) for g in grads)


_attention.defvjp(_attention_fwd, _attention_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    bq: Optional[int] = None, bk: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B, S, H, d); k/v: (B, L, KV, d), one dtype, H a multiple of KV
    -> (B, S, H, d) in q's dtype. Differentiable in q, k and v; the
    gradients come back in q's dtype. Products follow the matmul
    precision in force (`_operands`). Blocks default to `block_sizes`."""
    S, L = q.shape[1], k.shape[1]
    if bq is None or bk is None:
        sizes = block_sizes(S, L)
        assert sizes is not None, (S, L)
        bq, bk = bq or sizes[0], bk or sizes[1]
    bq, bk = min(bq, S), min(bk, L)
    assert S % bq == 0 and L % bk == 0, (S, L, bq, bk)
    assert q.shape[2] % k.shape[2] == 0, (q.shape, k.shape)
    return _attention(q, k, v, _Config(causal, window, bq, bk, _operands(),
                                       q.shape[3], interpret))
