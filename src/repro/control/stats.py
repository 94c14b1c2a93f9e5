"""`RoundStats` — the unified per-round observation payload.

One object per completed round carries everything an observer may want:
the realized mixing matrix W_t, the per-client loss vector, the comm
bytes the round moved, and the phase index. Both halves of the former
split surface consume it — `RoundEvent` callbacks (repro.api.session)
and `ControlPlane.observe()` (repro.control.plane) — replacing the
ad-hoc `observe_mixing_matrix` / `observe_frozen_contraction` call sites
that used to live in `repro.api.schedule`.

Derived quantities (loss reduction, consensus stats, the frozen-block Δ²
probe) are memoized lazily: constructing a RoundStats on the hot path
costs a few attribute stores and never syncs a device array.
"""
from __future__ import annotations

from typing import Mapping, Optional

import numpy as np


def metric_loss(metrics: Mapping) -> float:
    """The reported round loss: host-side reduction of the replicated
    per-client loss vector, in one fixed order — bitwise identical on
    every process grid. Falls back to the in-graph scalar (whose
    cross-client reduction XLA may decompose differently per grid) for
    round functions that predate `loss_per_client`."""
    pc = metrics.get("loss_per_client") if hasattr(metrics, "get") else None
    if pc is not None:
        a = np.asarray(pc, np.float32)          # (local_steps, n)
        return float(a.mean(axis=-1, dtype=np.float32)
                      .mean(dtype=np.float32))
    return float(metrics["loss"])


class RoundStats:
    """One round's observation record.

    Required fields are the round index `t` and the realized mixing
    matrix `W`; everything else is optional so the same class serves the
    live round loop (full payload), checkpoint replay, and direct
    schedule use (`RoundStats(t, W)` — a W-only observation). Lazy
    accessors return None when the underlying payload is absent instead
    of raising, so estimators can skip what a given stats object cannot
    provide.
    """

    def __init__(self, t: int, W: np.ndarray, *, phase: int = 0,
                 masks=None, metrics: Optional[Mapping] = None,
                 lora=None, comm_bytes: int = 0):
        self.t = int(t)
        self.W = np.asarray(W)
        self.phase = int(phase)          # phase index (increments at every
                                         # A/B boundary, not parity)
        self.masks = masks               # RoundMasks or None
        self.metrics = metrics           # jax arrays — not yet synced
        self.lora = lora                 # this round's post-mix state
        self.comm_bytes = int(comm_bytes)
        self._loss: Optional[float] = None
        self._loss_pc: Optional[np.ndarray] = None
        self._consensus: Optional[dict] = None
        self._w_gap: Optional[float] = None
        self._imbalance: Optional[np.ndarray] = None

    # -- losses -------------------------------------------------------------
    @property
    def loss(self) -> float:
        """Fixed-order scalar loss (``metric_loss``); NaN without metrics."""
        if self.metrics is None:
            return float("nan")
        if self._loss is None:
            self._loss = metric_loss(self.metrics)
        return self._loss

    @property
    def loss_per_client(self) -> Optional[np.ndarray]:
        """(m,) per-client loss averaged over the round's local steps;
        None when the round carried no per-client metrics."""
        if self.metrics is None:
            return None
        pc = self.metrics.get("loss_per_client") \
            if hasattr(self.metrics, "get") else None
        if pc is None:
            return None
        if self._loss_pc is None:
            a = np.asarray(pc, np.float32)      # (local_steps, m)
            self._loss_pc = a.mean(axis=0, dtype=np.float32)
        return self._loss_pc

    # -- MoE routing --------------------------------------------------------
    @property
    def expert_load_imbalance(self) -> Optional[np.ndarray]:
        """(MoE layers,) max over mean of the (token, expert) pairs routed
        to each expert in the round (the metric ``expert_load``, summed
        over the local steps); 1.0 is an even load. None without MoE
        layers."""
        load = self.metrics.get("expert_load") \
            if hasattr(self.metrics, "get") else None
        if load is None:
            return None
        if self._imbalance is None:
            a = np.asarray(load, np.float64)    # (MoE layers, E)
            self._imbalance = a.max(axis=-1) / a.mean(axis=-1)
        return self._imbalance

    # -- mixing / consensus -------------------------------------------------
    def w_gap(self) -> float:
        """Spectral distance ||W_t − J||₂ of this round's mixing matrix."""
        if self._w_gap is None:
            m = self.W.shape[0]
            J = np.ones((m, m)) / m
            self._w_gap = float(np.linalg.norm(self.W - J, ord=2))
        return self._w_gap

    def consensus(self) -> Optional[dict]:
        """Consensus/theory diagnostics of this round's LoRA state
        (delta_a_sq, delta_b_sq, cross_norm, cs_bound) as floats; None
        when the stats carry no state snapshot."""
        if self.lora is None:
            return None
        if self._consensus is None:
            from repro.core.diagnostics import consensus_stats
            self._consensus = {k: float(v) for k, v in
                               consensus_stats(self.lora).items()}
        return self._consensus

    def frozen_delta_sq(self) -> Optional[float]:
        """Δ² of the round's FROZEN LoRA block — the Lemma A.4 consensus
        probe (the frozen block only gossips, so its disagreement contracts
        at exactly ρ² per round). Needs both the masks (to know which block
        froze) and the state snapshot; None otherwise."""
        if self.lora is None or self.masks is None:
            return None
        cs = self.consensus()
        frozen_b = bool(self.masks.update_a)     # A updates ⇒ B frozen
        return cs["delta_b_sq"] if frozen_b else cs["delta_a_sq"]
