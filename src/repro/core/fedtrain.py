"""The decentralized FL round (Algorithm 1), compiled once for all methods.

One round = ``local_steps`` per-client AdamW updates on the active LoRA
block + one gossip mixing step. Clients are *stacked* (axis -3 of every LoRA
leaf) and sharded over the mesh's client axes; local updates are batched
einsums, mixing is the W_t contraction (core.mixing).

Method/phase enter ONLY through the 4-scalar ``masks`` input
(core.alternating.RoundMasks), and the topology through the W_t input
array — so a single jit-compiled round serves every (method, phase, graph
sample). Per-client AdamW falls out of elementwise moments on the stacked
tree; the (1/m) loss scaling from averaging over clients cancels inside
AdamW's mu/sqrt(nu) normalization (scale invariance, eps aside).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import mixing
from repro.core.lora import shard_lora_tree
from repro.dist.sharding import gather_clients, replicated
from repro.optim.adamw import AdamW, AdamWState


def _ab_mask(masks):
    """Per-leaf update mask: 'a' leaves -> masks[0], 'b' leaves -> masks[1]."""
    def fn(path):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        return masks[0] if name == "a" else masks[1]
    return fn


def make_dfl_round(loss_fn: Callable, optimizer: AdamW, *,
                   local_steps: int = 1,
                   mix_gather: bool = False,
                   mix_comm: str = "dense",
                   mix_quant: str = "off",
                   comm_plan=None,
                   donate: bool = False):
    """Build the jit-able round function.

    loss_fn(base_params, lora, microbatch) -> scalar loss, or
      (scalar loss, per_client_vec) — the vector (shard-local entries)
      is surfaced as metrics["loss_per_client"] for grid-invariant loss
      reporting; scalar-only loss_fns report through a length-1 vector.
      A third element, a dict of counters (e.g. the MoE layers'
      "expert_load"), is summed over the local steps into the metrics
      under the same names, on the device.
      microbatch carries the per-client batch (leading client axis matching
      the LoRA client axis).

    Returns round_fn(base_params, lora, opt_state, batch, W, masks)
      -> (lora, opt_state, metrics)
    ``batch`` leaves have a leading (local_steps, ...) axis.

    The dense mix is `mixing.mix_tree_planned`: one gossip_mix_seg call
    on the cached MixPlan's flat buffer.
    With ``mix_gather`` the stacked LoRA state is constrained fully
    replicated BEFORE the mixing contraction: under a cluster mesh
    (repro.dist.multihost) this pins the communication step to one
    all-gather of the client axis + a replicated contraction, whose
    arithmetic is bitwise equal to the single-process round (GSPMD is
    otherwise free to pick a psum decomposition with a different
    reduction order). Off-mesh it is a no-op.
    ``mix_comm`` selects the cluster communication lowering of the mixing
    step: "dense" keeps the full-support contraction (optionally behind
    the ``mix_gather`` all-gather); "sparse" exchanges only the rows the
    topology's support couples (``comm_plan`` — a
    `repro.dist.comm.CommPlan` — is required under a multi-device mesh),
    bit-for-bit equal to dense; "sparse_overlap" additionally feeds the
    off-diagonal terms the ROUND-INPUT state (one-round-delayed gossip),
    so the halo exchange overlaps with the local steps.
    ``mix_quant`` ("off" | "int8" | "fp8") compresses the sparse halo
    exchange: off-diagonal source rows ship as a quantized payload + one
    f32 per-row scale, with the per-client quantization residual carried
    as error feedback. When on, the round signature changes to
    ``round_fn(base, lora, opt_state, batch, W, masks, ef)
    -> (lora, opt_state, metrics, ef_new)`` where ``ef`` is the (m, P)
    f32 error-feedback buffer of the MixPlan flat layout. "off" keeps the
    exact unquantized round function (same signature, same jaxpr).
    With ``donate`` the returned function is jitted with the lora/opt_state
    buffers donated (in-place round at production scale) — callers must
    then treat the passed-in trees as consumed.
    """
    if mix_comm not in mixing.MIX_COMM_MODES:
        raise ValueError(f"unknown mix_comm {mix_comm!r}; "
                         f"known: {mixing.MIX_COMM_MODES}")
    if mix_quant not in mixing.MIX_QUANT_MODES:
        raise ValueError(f"unknown mix_quant {mix_quant!r}; "
                         f"known: {mixing.MIX_QUANT_MODES}")
    if mix_quant != "off" and mix_comm == "dense":
        raise ValueError("mix_quant compresses the sparse halo exchange; "
                         "it requires mix_comm='sparse' or 'sparse_overlap'")

    def _local_phase(base_params, lora, opt_state, batch, masks):
        """The local-steps scan — shared between the plain and the
        quantized round functions (identical ops, identical jaxpr)."""
        mask_fn = _ab_mask(masks)

        def local_step(carry, micro):
            lo, opt = carry

            def objective(l):
                # loss_fn may return (scalar, per_client_vec); the vector
                # rides along as aux so the loss can be re-reduced in a
                # grid-invariant order on host (scalar-only loss_fns get
                # a length-1 vector — reporting then equals the scalar)
                with jax.named_scope("loss"):
                    out = loss_fn(base_params, l, micro)
                if not isinstance(out, tuple):
                    return out, (jnp.reshape(out, (1,)), {})
                return out[0], (out[1], out[2] if len(out) > 2 else {})

            (loss, (per, counters)), grads = jax.value_and_grad(
                objective, has_aux=True)(lo)
            with jax.named_scope("opt"):
                lo, opt = optimizer.update(grads, opt, lo,
                                           update_mask=mask_fn)
            lo = shard_lora_tree(lo)
            return (lo, opt), (loss, per, counters)

        return jax.lax.scan(local_step, (lora, opt_state), batch)

    def _metrics(losses, per_client, counters):
        # loss_per_client (local_steps, n) is replicated so every process
        # can host-read it: the session reduces it in ONE fixed order, so
        # the reported loss is bitwise identical across process grids
        # (the in-graph scalars may reduce in a grid-dependent order)
        return {"loss": jnp.mean(losses), "loss_per_step": losses,
                "loss_per_client": replicated(per_client),
                **{k: jnp.sum(v, axis=0) for k, v in counters.items()}}

    def round_fn(base_params, lora, opt_state: AdamWState, batch, W, masks):
        (lora_new, opt_new), (losses, per_client, counters) = _local_phase(
            base_params, lora, opt_state, batch, masks)

        # Joint mixing (Algorithm 1 lines 7–9): masks select per method.
        with jax.named_scope("mix"):
            if mix_comm == "dense":
                if mix_gather:
                    lora_new = gather_clients(lora_new)
                lora_new = mixing.mix_tree_planned(W, lora_new, masks[2],
                                                   masks[3])
            else:
                # overlap feeds the ROUND-INPUT state to the off-diagonal
                # terms: its exchange is independent of the local-steps
                # scan
                lora_new = mixing.mix_tree_sparse(
                    W, lora_new, masks[2], masks[3], comm_plan=comm_plan,
                    lora_prev=(lora if mix_comm == "sparse_overlap"
                               else None))
            lora_new = shard_lora_tree(lora_new)
        metrics = _metrics(losses, per_client, counters)
        return lora_new, opt_new, metrics

    def round_fn_quant(base_params, lora, opt_state: AdamWState, batch, W,
                       masks, ef):
        (lora_new, opt_new), (losses, per_client, counters) = _local_phase(
            base_params, lora, opt_state, batch, masks)

        with jax.named_scope("mix"):
            lora_new, ef_new = mixing.mix_tree_sparse(
                W, lora_new, masks[2], masks[3], comm_plan=comm_plan,
                lora_prev=(lora if mix_comm == "sparse_overlap" else None),
                quant=mix_quant, ef=ef)
            lora_new = shard_lora_tree(lora_new)
        metrics = _metrics(losses, per_client, counters)
        return lora_new, opt_new, metrics, ef_new

    if mix_quant != "off":
        if donate:
            return jax.jit(round_fn_quant, donate_argnums=(1, 2, 6))
        return round_fn_quant
    if donate:
        return jax.jit(round_fn, donate_argnums=(1, 2))
    return round_fn


def make_microbatches(batch, local_steps: int):
    """Reshape a round's batch (m, local_steps*b, ...) ->
    (local_steps, m, b, ...) for the scan."""
    def one(x):
        m, tb = x.shape[:2]
        b = tb // local_steps
        return jnp.moveaxis(x.reshape(m, local_steps, b, *x.shape[2:]), 1, 0)
    return jax.tree.map(one, batch)
