"""Gossip mixing of stacked client LoRA states (Algorithm 1, lines 7-9).

Every LoRA leaf carries the client axis at position -3 (see core.lora), so
mixing is uniformly  x'_i = Σ_j (W_t)_ij x_j  — a contraction over that
axis, which under a client-sharded mesh *is* the paper's communication
step.

``mix_masks`` lets one compiled step express all four paper methods: a leaf
is mixed when its mask is 1, left untouched when 0 (traced scalars, so the
method/phase never triggers recompilation).

  mix_tree         — per-leaf einsum + blend: the oracle the tests hold the
                     other two to.
  mix_tree_planned — the dense round's mix: a MixPlan (built once per
                     treedef/shape signature, cached) fixes every leaf's
                     columns in one flat (m, padded) buffer, padded to the
                     gossip_mix kernel's bp stripe, with the a/b column
                     segment indicator. Per round: one gather into the
                     flat buffer, ONE ``ops.gossip_mix_seg`` call (unequal
                     masks folded into the per-column W_eff; column-sharded
                     across a multi-device mesh) and one unflatten.
  mix_tree_sparse  — the cluster communication lowering
                     (`mix_comm="sparse"/"sparse_overlap"`): the same
                     MixPlan flat layout, but the cross-process exchange
                     moves ONLY the rows the topology's support couples
                     (a `repro.dist.comm.CommPlan`), inside one
                     shard_map region — one small halo all-gather per
                     round instead of per-leaf full-axis all-gathers.
                     Missing rows stay zero and meet exact-zero W
                     entries, so the sparse result equals the dense
                     contraction bit-for-bit. With ``lora_prev`` the
                     off-diagonal terms read the PREVIOUS round's state
                     (one-round-delayed/overlapped gossip, DeCAF-style):
                     the halo has no data dependency on this round's
                     local steps, so XLA can overlap communication with
                     compute; only the diagonal stays fresh, making the
                     semantics independent of the process count.

Compressed gossip (``quant`` on the sparse lowerings): the exchanged
source rows are quantized per row to int8 (or fp8) with one f32 scale per
row — the halo then moves ~1/4 of the fp32 bytes — while each client's own
diagonal contribution stays full precision. A per-client error-feedback
accumulator (EF21-style) carries the quantization residual into the next
round's payload, e_j' = (x_j + e_j) − Q(x_j + e_j), so the compression
noise stays summable and the consensus contraction survives (asserted
against the Lemma A.10 budget in the conformance tier). The dequantize is
fused into the ``ops.gossip_mix_quant`` sweep. Quantization is per-row and
the degenerate path quantizes ALL off-diagonal sources, so single- and
multi-process runs still agree bit-for-bit.
"""
from __future__ import annotations

import math
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as _sharding
from repro.kernels import ops

# the gossip exchange's lowering: the dense all-clients contraction, the
# topology-support halo, or that halo one round delayed
MIX_COMM_MODES = ("dense", "sparse", "sparse_overlap")


def mix_leaf(W: jax.Array, leaf: jax.Array) -> jax.Array:
    """leaf: (..., m, d0, d1); W: (m, m)."""
    return jnp.einsum("ij,...jdr->...idr", W.astype(leaf.dtype), leaf)


def _leaf_mask_name(path) -> str:
    """The a/b factor name of a LoRA leaf path. Any other leaf name is a
    malformed tree — silently mixing it with mask_b (the historical
    fallback) hid real bugs, so it raises instead."""
    name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    if name not in ("a", "b"):
        raise ValueError(
            f"LoRA leaf {jax.tree_util.keystr(path)!r} is named {name!r}; "
            f"gossip mixing is defined for 'a'/'b' factor leaves only")
    return name


def mix_tree(W: jax.Array, lora, mask_a: jax.Array, mask_b: jax.Array):
    """Gossip-mix the a-leaves with weight mask_a and b-leaves with mask_b.

    mask=1 -> fully mixed; mask=0 -> untouched (frozen-block no-mix, i.e.
    the RoLoRA baseline behaviour); fractional values interpolate (used by
    the beyond-paper damped-mixing variant).
    """
    def one(path, leaf):
        mask = mask_a if _leaf_mask_name(path) == "a" else mask_b
        mixed = mix_leaf(W, leaf)
        return (mask * mixed + (1.0 - mask) * leaf).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(one, lora)


# ===========================================================================
# Planned fused mixing (the dense round's mix)
# ===========================================================================

_KERNEL_BP = 512    # gossip_mix stripe width the flat buffer is padded to


@dataclass(frozen=True)
class _LeafSlot:
    """Static placement of one LoRA leaf inside the flat (m, P) buffer."""
    offset: int          # first column
    cols: int            # columns per client (= leaf.size / m)
    lead: tuple          # leading (group-stack) dims before the client axis
    tail: tuple          # trailing (d0, d1) dims
    is_a: bool           # "a" leaf -> mask_a segment, else mask_b


@dataclass(frozen=True)
class MixPlan:
    """Precomputed flatten plan for one LoRA tree structure.

    Built once per (treedef, leaf shapes/dtypes, bp) signature — see
    ``get_mix_plan`` — and reused for every round on that structure, so
    the per-round path never walks tree paths or re-derives offsets.
    ``a_indicator`` is the (1, padded) column-segment constant that folds
    unequal a/b masks into the kernel's per-segment W_eff.
    """
    m: int               # clients
    cols: int            # total columns per client (unpadded)
    padded: int          # cols rounded up to a multiple of bp
    bp: int
    slots: tuple         # tuple[_LeafSlot, ...] in tree-flatten order
    treedef: Any
    a_indicator: np.ndarray   # (1, padded) float32; 1.0 on "a" columns

    def segment_mask(self, mask_a, mask_b):
        """(1, padded) per-column blend mask from the two scalar masks."""
        ind = self.a_indicator
        return mask_a * ind + mask_b * (1.0 - ind)


# LRU-bounded plan cache: keyed on treedef/shape signatures, which a
# long-lived serving process can churn through indefinitely (every new
# adapter-pool layout is a fresh key) — unbounded growth was a leak.
_PLAN_CACHE: "OrderedDict" = OrderedDict()
_PLAN_CACHE_MAX = 64
_PLAN_BUILDS = [0]


def plan_builds() -> int:
    """How many MixPlans have been constructed (test/diagnostic hook)."""
    return _PLAN_BUILDS[0]


def clear_mix_plans() -> None:
    """Drop every cached MixPlan (long-lived processes, tests)."""
    _PLAN_CACHE.clear()


def build_mix_plan(lora, *, bp: int = _KERNEL_BP) -> MixPlan:
    """Walk the tree ONCE: record each leaf's slot and the a/b segments."""
    leaves_p, treedef = jax.tree_util.tree_flatten_with_path(lora)
    if not leaves_p:
        raise ValueError("empty LoRA tree")
    m = leaves_p[0][1].shape[-3]
    slots, ind_parts = [], []
    off = 0
    for path, leaf in leaves_p:
        name = _leaf_mask_name(path)
        cols = math.prod(leaf.shape) // m
        slots.append(_LeafSlot(offset=off, cols=cols,
                               lead=tuple(leaf.shape[:-3]),
                               tail=tuple(leaf.shape[-2:]),
                               is_a=(name == "a")))
        ind_parts.append(np.full(cols, 1.0 if name == "a" else 0.0,
                                 np.float32))
        off += cols
    padded = off + ((-off) % bp)
    if padded > off:
        ind_parts.append(np.zeros(padded - off, np.float32))
    _PLAN_BUILDS[0] += 1
    return MixPlan(m=m, cols=off, padded=padded, bp=bp, slots=tuple(slots),
                   treedef=treedef,
                   a_indicator=np.concatenate(ind_parts)[None, :])


def get_mix_plan(lora, *, bp: int = _KERNEL_BP) -> MixPlan:
    """Cached ``build_mix_plan`` keyed on the tree's static signature."""
    leaves, treedef = jax.tree_util.tree_flatten(lora)
    key = (treedef, bp,
           tuple((tuple(x.shape), jnp.dtype(x.dtype).name) for x in leaves))
    plan = _PLAN_CACHE.get(key)
    if plan is None:
        plan = _PLAN_CACHE[key] = build_mix_plan(lora, bp=bp)
        while len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
            _PLAN_CACHE.popitem(last=False)      # evict least-recently-used
    else:
        _PLAN_CACHE.move_to_end(key)
    return plan


def _column_sharded_mix(w, flat, seg):
    """``ops.gossip_mix_seg`` on the flat (m, P) buffer, under the bound
    mesh when it spans several devices.

    A Mosaic kernel cannot be partitioned by GSPMD, so on a multi-device
    mesh the call runs inside ``jax.shard_map``: columns are independent
    (y[:, j] needs only x[:, j] and W), so every device mixes its own
    stripe of columns with the whole W. The reshard from client-sharded
    rows to column stripes and back is the round's one exchange."""
    mesh = _sharding.current_mesh()
    if mesh is None or mesh.size == 1:
        return ops.gossip_mix_seg(w, flat, seg)
    axes = tuple(mesh.axis_names)
    cols = flat.shape[1]
    pad = (-cols) % mesh.size
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
        seg = jnp.pad(seg, ((0, 0), (0, pad)))
    stripes = P(None, axes)
    mixed = jax.shard_map(ops.gossip_mix_seg, mesh=mesh,
                          in_specs=(P(), stripes, stripes),
                          out_specs=stripes, check_vma=False)(w, flat, seg)
    return mixed[:, :cols]


def _flat_buffer(leaves, m: int, pad: int = 0):
    """(m, cols + pad) flat view of the stacked tree in plan layout, with
    ``pad`` zero columns at the end. The sparse path passes no padding:
    the halo exchange should not ship padding bytes."""
    parts = [jnp.moveaxis(x, -3, 0).reshape(m, -1) for x in leaves]
    if pad:
        parts.append(jnp.zeros((m, pad), parts[0].dtype))
    return jnp.concatenate(parts, axis=1)


def _unflatten(plan: MixPlan, mixed, leaves):
    """The tree of ``mixed``'s (m, ≥cols) flat plan layout, in the dtypes
    of ``leaves``."""
    out = []
    for slot, leaf in zip(plan.slots, leaves):
        chunk = mixed[:, slot.offset:slot.offset + slot.cols]
        restored = chunk.reshape(plan.m, *slot.lead, *slot.tail)
        restored = jnp.moveaxis(restored, 0, len(slot.lead))
        out.append(restored.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(plan.treedef, out)


def mix_tree_planned(W: jax.Array, lora, mask_a, mask_b, *,
                     plan: Optional[MixPlan] = None):
    """Plan-cached fused mixing: the dense round's mix.

    The whole tree is mixed by ONE gossip_mix_seg call on the plan's
    padded flat layout (column-sharded across a multi-device mesh — see
    `_column_sharded_mix`). Masks fold into the per-column effective
    mixing matrix W_eff = seg·W + (1−seg)·I, so the blend never touches
    the (m, P) payload as a separate pass. Numerically equal to mix_tree
    for all masks.
    """
    plan = plan if plan is not None else get_mix_plan(lora)
    leaves = jax.tree_util.tree_leaves(lora)
    flat = _flat_buffer(leaves, plan.m, plan.padded - plan.cols)
    seg = plan.segment_mask(mask_a, mask_b).astype(flat.dtype)
    mixed = _column_sharded_mix(W.astype(flat.dtype), flat, seg)
    return _unflatten(plan, mixed, leaves)


# ===========================================================================
# Sparse (neighbor-only) gossip lowering — repro.dist.comm.CommPlan
# ===========================================================================

# ---------------------------------------------------------------------------
# compressed gossip: per-row quantization + error feedback
# ---------------------------------------------------------------------------

MIX_QUANT_MODES = ("off", "int8", "fp8")   # compression of the sparse halo


def _quant_spec(quant: str):
    """(payload dtype, max representable magnitude) of a quant mode."""
    if quant == "int8":
        return jnp.int8, 127.0
    if quant == "fp8":
        return jnp.float8_e4m3fn, 448.0
    raise ValueError(f"unknown mix quant mode {quant!r}; "
                     f"known: {MIX_QUANT_MODES}")


def quantize_rows(x: jax.Array, quant: str):
    """Per-row scaled quantization of a (rows, cols) buffer.

    Returns (q, scale): q is int8 (round-to-nearest, clipped symmetric)
    or fp8 (e4m3) with one f32 ``scale`` per row chosen so the row's max
    magnitude maps to the top of the representable range. All-zero rows
    quantize to zeros under scale 1 (no 0/0). Row-independent by
    construction, so per-shard quantization of a block equals the global
    quantization of those rows — the property the bitwise grid-parity of
    `mix_tree_sparse` rests on.
    """
    dtype, qmax = _quant_spec(quant)
    x32 = x.astype(jnp.float32)
    rowmax = jnp.max(jnp.abs(x32), axis=1, keepdims=True)
    scale = jnp.where(rowmax > 0.0, rowmax / qmax, 1.0)
    y = x32 / scale
    if dtype == jnp.int8:
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(jnp.int8)
    else:
        q = y.astype(dtype)
    return q, scale


def dequantize_rows(q: jax.Array, scale: jax.Array) -> jax.Array:
    """f32 reconstruction of `quantize_rows` output: q * scale."""
    return q.astype(jnp.float32) * scale.astype(jnp.float32)


def _split_diag(w_rows, row0):
    """(w_off_rows, w_diag) of mixing rows [row0, row0+r): the diagonal
    coefficient per row, and the rows with the diagonal zeroed. Shared by
    the degenerate and shard_map paths so both reduce identically."""
    r, m = w_rows.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (r, m), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (r, m), 1)
    eye = (col == row + row0).astype(w_rows.dtype)
    w_diag = jnp.sum(w_rows * eye, axis=1, keepdims=True)
    return w_rows * (1.0 - eye), w_diag


def _sparse_contract(w_rows, x_rows, z, mask_a, mask_b, plan: MixPlan,
                     w_diag=None):
    """Blend-mixed rows from the exchanged source buffer.

    w_rows: (r, m) mixing rows (diagonal zeroed when w_diag is given);
    x_rows: (r, cols) fresh locally-owned rows; z: (m, cols) source rows
    (fresh for plain sparse, previous-round for overlap; rows outside the
    support are zero and meet exact-zero W entries). w_diag: (r, 1)
    diagonal coefficients applied to the FRESH rows (overlap mode).
    """
    mixed = w_rows @ z
    if w_diag is not None:
        mixed = w_diag * x_rows + mixed
    seg = plan.segment_mask(mask_a, mask_b)[:, :plan.cols]
    seg = seg.astype(x_rows.dtype)
    return seg * mixed + (1.0 - seg) * x_rows


def _sparse_contract_quant(w_off, x_rows, zq, zscale, mask_a, mask_b,
                           plan: MixPlan, w_diag):
    """Blend-mixed rows from a QUANTIZED source buffer.

    w_off: (r, m) mixing rows with the diagonal zeroed; x_rows: (r, cols)
    fresh full-precision local rows; zq/zscale: the (m, cols)/(m, 1)
    quantized source rows + per-row scales (rows outside the support are
    zero and meet exact-zero W entries); w_diag: (r, 1) diagonal
    coefficients applied to the FRESH rows — the local contribution never
    pays quantization noise. The dequantize is fused into the
    `gossip_mix_quant` kernel sweep.
    """
    seg = plan.segment_mask(mask_a, mask_b)[:, :plan.cols]
    seg = jnp.asarray(seg).astype(x_rows.dtype)
    return ops.gossip_mix_quant(w_off, zq, zscale, x_rows, w_diag, seg)


def mix_tree_sparse(W: jax.Array, lora, mask_a, mask_b, *, comm_plan,
                    lora_prev=None, plan: Optional[MixPlan] = None,
                    quant: str = "off", ef: Optional[jax.Array] = None):
    """Neighbor-only gossip mixing on the MixPlan flat layout.

    Without a bound multi-device mesh (or with a 1-shard ``comm_plan``)
    this is the degenerate local contraction — bit-for-bit what the
    distributed path computes, so single- and multi-process runs agree
    exactly. Under a bound cluster mesh whose size matches
    ``comm_plan.n_shards``, one shard_map region per round: each shard
    gathers its export rows, ONE all-gather moves the (n, k, cols) halo,
    rows scatter into a zero (m, cols) source buffer, and the shard's W
    rows contract against it. W entries outside the support are exact
    zeros (Metropolis construction), so zero-filled missing rows never
    contribute a bit of difference.

    ``lora_prev`` switches on one-round-delayed (overlapped) mixing: the
    exchanged/off-diagonal source rows come from the ROUND-INPUT state
    while each client's own (diagonal) contribution stays fresh —
    y_i = seg·(W_ii·post_i + Σ_{j≠i} W_ij·pre_j) + (1−seg)·post_i.
    The halo then has no data dependency on this round's local steps
    (XLA overlaps it with compute), and the semantics are independent of
    the process count — the staleness penalty is bounded against Lemma
    A.10 in the conformance tier, not swept under parity.

    ``quant`` ("off" | "int8" | "fp8") compresses the exchanged rows:
    every OFF-diagonal contribution reads the per-row-quantized source
    Q(src + ef) while the diagonal keeps the fresh full-precision rows,
    and ``ef`` — the (m, cols) f32 error-feedback accumulator, required
    when quant is on — is updated to the new residual. Quantized calls
    return ``(mixed_tree, ef_new)`` instead of the tree alone. The
    degenerate and distributed paths quantize identically (per-row), so
    grid parity stays bitwise.
    """
    plan = plan if plan is not None else get_mix_plan(lora)
    leaves = jax.tree_util.tree_leaves(lora)
    m = plan.m
    if quant not in MIX_QUANT_MODES:
        raise ValueError(f"unknown mix quant mode {quant!r}; "
                         f"known: {MIX_QUANT_MODES}")
    if quant != "off" and ef is None:
        raise ValueError("quantized mixing needs the (m, cols) f32 "
                         "error-feedback accumulator (ef=...)")

    flat = _flat_buffer(leaves, m)
    prev_flat = None
    if lora_prev is not None:
        prev_flat = _flat_buffer(jax.tree_util.tree_leaves(lora_prev), m)

    mesh = _sharding.current_mesh()
    ef_new = None
    if mesh is not None and mesh.size > 1 and comm_plan is not None:
        # a mesh/plan mismatch used to fall through to the degenerate
        # local contraction: parity held but every byte saving silently
        # vanished — refuse instead of degrading
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"mix_tree_sparse: the sparse comm lowering needs a 1-D "
                f"mesh over the client axis; bound mesh has axes "
                f"{mesh.axis_names}")
        if comm_plan.n_shards != mesh.size:
            raise ValueError(
                f"mix_tree_sparse: comm_plan was compiled for "
                f"{comm_plan.n_shards} shards but the bound mesh has "
                f"{mesh.size} devices — rebuild the CommPlan for this "
                f"grid (the degenerate fallback would silently all-gather "
                f"nothing and drop the sparse savings)")
        distributed = True
    else:
        distributed = False
    if distributed:
        res = _exchange_and_mix(W, flat, prev_flat, mask_a, mask_b,
                                plan, comm_plan, mesh, quant=quant, ef=ef)
        mixed, ef_new = res if quant != "off" else (res, None)
    else:
        w_rows = W.astype(flat.dtype)
        if quant != "off":
            src = prev_flat if prev_flat is not None else flat
            s = src.astype(jnp.float32) + ef
            q, scale = quantize_rows(s, quant)
            ef_new = s - dequantize_rows(q, scale)
            w_off, w_diag = _split_diag(w_rows, 0)
            mixed = _sparse_contract_quant(w_off, flat, q, scale, mask_a,
                                           mask_b, plan, w_diag)
        elif prev_flat is not None:
            w_rows, w_diag = _split_diag(w_rows, 0)
            mixed = _sparse_contract(w_rows, flat, prev_flat, mask_a,
                                     mask_b, plan, w_diag)
        else:
            mixed = _sparse_contract(w_rows, flat, flat, mask_a, mask_b,
                                     plan)

    tree = _unflatten(plan, mixed, leaves)
    if quant != "off":
        return tree, ef_new
    return tree


def _exchange_and_mix(W, flat, prev_flat, mask_a, mask_b, plan: MixPlan,
                      cp, mesh, *, quant: str = "off",
                      ef=None):
    """The distributed body: halo exchange + contraction in ONE shard_map
    region, so the per-process divergent intermediates (export rows, the
    reconstruction buffer) never exist as replicated-but-different global
    arrays. Output rows are client-sharded, matching the round's layout.

    With ``quant`` on, each shard quantizes its source block (src + ef,
    per row) BEFORE the exchange: the halo all-gather moves the 1-byte
    payload rows plus one f32 scale per row — the wire compression — and
    every shard dequantizes the reconstruction buffer identically. The
    fresh local rows feed only the diagonal term. Returns
    (mixed, ef_new_block) when quantizing, both client-sharded."""
    axis = mesh.axis_names[0]
    n, m, m_loc, k = cp.n_shards, cp.m, cp.m_loc, cp.k
    exp_local = jnp.asarray(cp.export_local)      # (n, k) int32
    exp_global = jnp.asarray(cp.export_global)    # (n*k,) int32
    overlap = prev_flat is not None
    quantized = quant != "off"

    def body(w, x_blk, ma, mb, *rest):
        pid = jax.lax.axis_index(axis)
        rest = list(rest)
        src_blk = rest.pop(0) if overlap else x_blk  # rows this shard offers
        cols = x_blk.shape[-1]
        w_rows = jax.lax.dynamic_slice(w, (pid * m_loc, 0), (m_loc, m))
        if quantized:
            ef_blk = rest.pop(0)
            s_blk = src_blk.astype(jnp.float32) + ef_blk
            q_blk, sc_blk = quantize_rows(s_blk, quant)
            ef_new = s_blk - dequantize_rows(q_blk, sc_blk)
            zq = jnp.zeros((m, cols), q_blk.dtype)
            zs = jnp.zeros((m, 1), jnp.float32)
            if k > 0:
                # the compressed wire payload: 1-byte rows + f32 scales
                halo_q = jax.lax.all_gather(
                    jnp.take(q_blk, exp_local[pid], axis=0), axis)
                halo_s = jax.lax.all_gather(
                    jnp.take(sc_blk, exp_local[pid], axis=0), axis)
                zq = zq.at[exp_global].set(halo_q.reshape(n * k, -1))
                zs = zs.at[exp_global].set(halo_s.reshape(n * k, 1))
            zq = jax.lax.dynamic_update_slice(zq, q_blk, (pid * m_loc, 0))
            zs = jax.lax.dynamic_update_slice(zs, sc_blk, (pid * m_loc, 0))
            w_off, w_diag = _split_diag(w_rows, pid * m_loc)
            mixed = _sparse_contract_quant(w_off, x_blk, zq, zs, ma, mb,
                                           plan, w_diag)
            return mixed, ef_new
        z = jnp.zeros((m, cols), x_blk.dtype)
        if k > 0:
            exp = jnp.take(src_blk, exp_local[pid], axis=0)   # (k, cols)
            halo = jax.lax.all_gather(exp, axis)              # (n, k, cols)
            z = z.at[exp_global].set(halo.reshape(n * k, -1))
        z = jax.lax.dynamic_update_slice(z, src_blk, (pid * m_loc, 0))
        w_diag = None
        if overlap:
            w_rows, w_diag = _split_diag(w_rows, pid * m_loc)
        return _sparse_contract(w_rows, x_blk, z, ma, mb, plan, w_diag)

    in_specs = [P(), P(axis, None), P(), P()]
    args = [W.astype(flat.dtype), flat, mask_a, mask_b]
    if overlap:
        in_specs.append(P(axis, None))
        args.append(prev_flat)
    if quantized:
        in_specs.append(P(axis, None))
        args.append(ef)
    out_specs = (P(axis, None), P(axis, None)) if quantized \
        else P(axis, None)
    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=out_specs, check_vma=False)
    return fn(*args)
