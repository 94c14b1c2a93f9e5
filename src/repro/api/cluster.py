"""`ClusterSession` — a `Session` whose client axis spans processes.

The paper's setting is genuinely decentralized: clients on separate
machines gossiping over a time-varying graph. `ClusterSession` makes the
repo's execution match that reality without forking the round loop — it IS
a `Session`, running the same `build_round` product, the same schedules,
and the same callbacks, but on a global mesh built over every process in
the grid (`repro.dist.multihost`):

  * each process owns a contiguous shard of the client axis (m must divide
    over the grid's devices); local training is shard-local,
  * under ``mix_comm="dense"`` the gossip mix runs with ``mix_gather``
    on (it is on exactly when the grid has >1 process): one all-gather
    of the stacked LoRA state per round (the paper's communication step,
    lowered to a cross-process collective) followed by a replicated W_t
    contraction — bitwise equal to the single-process round. Under
    ``mix_comm="sparse"/"sparse_overlap"`` the round instead runs the
    `repro.dist.comm.CommPlan` halo exchange:
    one small all-gather of only the topology-coupled rows ("sparse" is
    still bitwise equal; "sparse_overlap" delays neighbor terms one
    round so the exchange overlaps local compute),
  * `TopologySchedule` draws that do not declare ``deterministic`` are
    wrapped in `BroadcastSchedule` so every process mixes with rank 0's
    realized W_t; config-derived library schedules replay identically
    per seed on every process and skip the per-round broadcast (a
    blocking host collective that dominated small-payload rounds),
  * checkpoints gather to host and are written by rank 0 only, in the
    exact format `Session.save` writes — a 2-process run's checkpoint
    restores into a single-process `Session` (and vice versa),
  * the control plane (`config.control`, repro.control) runs as
    replicated host math: every process folds the same realized W_t into
    the same estimator state and installs the same deterministic weight
    policy, so T retunes and FMMC weights agree across the grid without
    extra collectives. The frozen-contraction estimator is the exception
    (it reads full client state per round) and is rejected on grids >1
    process — use "spectral" or "gram" there.

Multi-controller contract: every process constructs the same
`ClusterSession` and makes the same calls in the same order. Callbacks run
on all processes — gate side effects (prints, file writes) on
``multihost.is_primary()``, never the computation.

Launch via ``python -m repro.launch.cluster`` (real grids use the
``REPRO_COORDINATOR``/``REPRO_NUM_PROCESSES``/``REPRO_PROCESS_ID`` env
protocol or `jax.distributed` auto-detection; ``--simulate N`` spawns N
local CPU processes over gloo — the CI path). Single-process construction
degrades to an exact `Session` (1-device mesh, passthrough broadcast).
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Optional

import numpy as np

import jax

from repro.api.session import Session
from repro.checkpoint import save_pytree
from repro.dist import multihost, sharding
from repro.optim.adamw import AdamWState
from repro.scenarios.schedule import BroadcastSchedule


class ClusterSession(Session):
    """Multi-process DFL session: one process = one shard of the clients.

    Accepts every `Session` argument. Requires ``config.n_clients`` to be
    divisible by the grid's total device count. The round's pre-mix
    all-gather (`repro.api.session._resolve_mix_gather`) is on exactly
    when the grid has >1 process.
    """

    def __init__(self, config, **kw):
        multihost.initialize()          # env-protocol no-op if not gridded
        cc = config.control
        if cc.active and cc.rho_estimator == "frozen" \
                and jax.process_count() > 1:
            # the consensus probe reads the full client state every round —
            # a per-round blocking gather on a grid; the W-only routes are
            # replicated host math and grid-invariant by construction
            raise ValueError(
                "control.rho_estimator 'frozen' needs host-local client "
                "state each round; on a process grid use 'spectral' or "
                "'gram' (W_t is replicated on every process)")
        self.mesh = multihost.cluster_mesh()
        if config.n_clients % self.mesh.size != 0:
            raise ValueError(
                f"ClusterSession: n_clients={config.n_clients} must divide "
                f"over {self.mesh.size} devices "
                f"({jax.process_count()} processes)")
        self._client_slc = multihost.local_client_slice(config.n_clients,
                                                        self.mesh)
        super().__init__(config, **kw)
        self._wrap_schedule()
        self.base = multihost.replicate_tree(
            self.mesh, jax.tree.map(np.asarray, self.base))

    def _wrap_schedule(self) -> None:
        """Rank-0-owned W_t for schedules whose draws could disagree
        across processes. Deterministic (config-derived) schedules replay
        the identical stream per seed on every process, so the per-round
        broadcast — a blocking host collective — is skipped for them."""
        if not getattr(self.topo_schedule, "deterministic", False):
            self.topo_schedule = BroadcastSchedule(self.topo_schedule)

    # -- mesh binding (trace-time logical-axis resolution) ------------------
    @contextmanager
    def _bound(self):
        """Bind the cluster mesh for logical-axis resolution (the round's
        `shard_lora_tree` / `gather_clients` constraints) and restore the
        previous binding after — the session never leaks mesh state into
        other code running in this process."""
        prev_mesh = sharding.current_mesh()
        prev_map = sharding.current_axis_map()
        sharding.set_mesh(self.mesh)
        try:
            yield
        finally:
            if prev_mesh is None:
                sharding.clear_mesh()
            else:
                sharding.set_mesh(prev_mesh, prev_map)

    # -- state globalization ------------------------------------------------
    def _shard_client_tree(self, tree):
        """Full host-identical tree -> global arrays sharded over the
        client axis (-3). Each process contributes exactly its block; the
        slice is pure data movement, so the global state equals the
        single-process state bit-for-bit."""
        def one(x):
            x = np.asarray(x)
            local = x[..., self._client_slc, :, :]
            return multihost.shard_clients(self.mesh, local, x.shape,
                                           axis=x.ndim - 3)
        return jax.tree.map(one, tree)

    def _globalize_state(self) -> None:
        self.lora = self._shard_client_tree(self.lora)
        self.opt_state = AdamWState(
            step=multihost.replicate(self.mesh,
                                     np.asarray(self.opt_state.step)),
            mu=self._shard_client_tree(self.opt_state.mu),
            nu=self._shard_client_tree(self.opt_state.nu))
        if self.ef is not None:
            # the error-feedback buffer is (m, cols) with the client axis
            # leading — shard it like the round's other client state
            ef = np.asarray(self.ef)
            self.ef = multihost.shard_clients(
                self.mesh, ef[self._client_slc], ef.shape, axis=0)

    def reset_state(self) -> None:
        super().reset_state()
        self._globalize_state()

    # -- device placement hooks --------------------------------------------
    def _device_scalar_inputs(self, x):
        return multihost.replicate(self.mesh, np.asarray(x))

    def _to_device(self, raw):
        """Every process draws the identical full round batch from the
        shared data RNG (numpy, cheap at client counts that fit a grid)
        and contributes its client block; leaves become global arrays
        sharded over the batch's client axis (dim 1)."""
        def one(x):
            x = np.asarray(x)
            return multihost.shard_clients(self.mesh, x[:, self._client_slc],
                                           x.shape, axis=1)
        return jax.tree.map(one, self._raw_round_batch(raw))

    # -- cold joins ---------------------------------------------------------
    def _apply_client_matrix(self, R, zero_ef_rows=()):
        """The warm-start repair mixes *across* client rows, which live on
        different processes here: gather the sharded state to identical
        full host arrays (exact all-gather), apply the repair in numpy on
        every process (same inputs -> bitwise same result, no broadcast
        needed), then re-shard onto the grid."""
        self.lora = multihost.to_host(self.lora, self.mesh)
        self.opt_state = AdamWState(
            step=multihost.to_host(self.opt_state.step, self.mesh),
            mu=multihost.to_host(self.opt_state.mu, self.mesh),
            nu=multihost.to_host(self.opt_state.nu, self.mesh))
        if self.ef is not None:
            self.ef = multihost.to_host(self.ef, self.mesh)
        super()._apply_client_matrix(R, zero_ef_rows)
        self._globalize_state()

    # -- the round / evaluation under the bound mesh ------------------------
    def _one_round(self, **kw):
        with self._bound():
            return super()._one_round(**kw)

    def evaluate(self, n: Optional[int] = None,
                 seed: Optional[int] = None) -> dict:
        with self._bound():
            return super().evaluate(n, seed)

    # -- checkpoint / restore -----------------------------------------------
    def save(self, path: str) -> None:
        """Gather to host (exact all-gather) and write on rank 0 only, in
        `Session.save`'s format — restorable by any process count."""
        state = {
            "lora": multihost.to_host(self.lora, self.mesh),
            "opt": {"step": multihost.to_host(self.opt_state.step,
                                              self.mesh),
                    "mu": multihost.to_host(self.opt_state.mu, self.mesh),
                    "nu": multihost.to_host(self.opt_state.nu, self.mesh)},
            "meta": {"round": np.int64(self.t)},
        }
        if self.ef is not None:
            state["ef"] = multihost.to_host(self.ef, self.mesh)
        if multihost.is_primary():
            save_pytree(path, state)
        multihost.sync("ckpt-save")

    def restore(self, path: str) -> int:
        """`Session.restore` (every process reads the checkpoint and
        replays the RNG streams in lockstep), then re-globalize the
        restored state onto the grid."""
        saved = super().restore(path)
        if self._user_topo_schedule is None:
            # super().restore rebuilt the schedule unwrapped
            self._wrap_schedule()
        self._globalize_state()
        return saved
