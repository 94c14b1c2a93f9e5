"""Multi-process execution substrate: one process = one shard of the
client axis.

`repro.dist.sharding` maps logical axes onto a mesh; this module is the
layer below that makes the mesh *span processes*. A cluster run calls
``initialize()`` once (reading ``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES``
/ ``REPRO_PROCESS_ID`` when launched by ``repro.launch.cluster``), builds a
``cluster_mesh()`` over every process's devices, and then the exact same
jitted DFL round runs SPMD: each process owns ``m / process_count`` clients,
the planned gossip mix lowers to cross-process collectives, and everything
above (`Session`, schedules, callbacks) is unchanged.

All helpers degrade to exact no-ops in a single-process run, so the same
code path serves a laptop and a cluster. On CPU the collective backend is
gloo (``jax_cpu_collectives_implementation``), which is what the
``--simulate N`` CI mode exercises; on TPU pods ``jax.distributed`` uses
the native fabric.

Two rules for code running under a cluster mesh:

1. Every process executes the same jax computations in the same order
   (multi-controller SPMD). Callbacks run on all processes; gate *side
   effects* (prints, file writes) on ``is_primary()``, never the
   computation itself.
2. Host-side randomness must agree across processes. Config-derived
   schedules agree by construction (same seed); user-supplied stateful
   schedules are wrapped in ``repro.scenarios.BroadcastSchedule`` so rank
   0's draw is the only one that counts.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_INITIALIZED = [False]

# env protocol of repro.launch.cluster (also honored by initialize())
ENV_COORDINATOR = "REPRO_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_PROCESS_ID"


def initialize(coordinator: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> bool:
    """Join the process grid (idempotent; no-op for single-process runs).

    Arguments default to the ``REPRO_*`` env protocol set by
    ``repro.launch.cluster``; with neither args nor env this is a
    single-process run and nothing happens. Returns True when
    ``jax.distributed`` was (or already is) initialized.

    Must be called before any jax device/computation use — CPU collectives
    (gloo) are selected here and jax backends are frozen on first use.
    """
    if _INITIALIZED[0]:
        return True
    coordinator = coordinator or os.environ.get(ENV_COORDINATOR)
    if num_processes is None and os.environ.get(ENV_NUM_PROCESSES):
        num_processes = int(os.environ[ENV_NUM_PROCESSES])
    if process_id is None and os.environ.get(ENV_PROCESS_ID):
        process_id = int(os.environ[ENV_PROCESS_ID])
    if coordinator is None or num_processes is None or num_processes <= 1:
        return False
    # CPU multi-process collectives route through gloo
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    _INITIALIZED[0] = True
    return True


def shutdown() -> None:
    if _INITIALIZED[0]:
        jax.distributed.shutdown()
        _INITIALIZED[0] = False


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def is_distributed() -> bool:
    return jax.process_count() > 1


def is_primary() -> bool:
    """True on the process that owns side effects (logs, checkpoints)."""
    return jax.process_index() == 0


def cluster_mesh(axis: str = "data") -> Mesh:
    """1-D mesh over ALL processes' devices on the given axis name.

    ``DEFAULT_AXIS_MAP`` routes the logical "clients"/"batch" axes over
    ("pod", "data"), so with axis="data" the client axis shards across the
    whole process grid — the decentralized setting: each process is a
    "site" owning a contiguous block of clients.
    """
    return Mesh(np.array(jax.devices()), (axis,))


def local_client_slice(m: int, mesh: Optional[Mesh] = None) -> slice:
    """This process's contiguous block of the client axis.

    Requires ``m`` divisible by the total device count (enforced by
    ``ClusterSession``); devices are laid out process-major in
    ``jax.devices()``, so process p owns clients [p*m/np, (p+1)*m/np).
    """
    n_dev = mesh.size if mesh is not None else jax.device_count()
    if m % n_dev != 0:
        raise ValueError(f"client axis {m} must divide over {n_dev} devices")
    per_proc = m // jax.process_count()
    lo = jax.process_index() * per_proc
    return slice(lo, lo + per_proc)


# ---------------------------------------------------------------------------
# host<->global array movement
# ---------------------------------------------------------------------------

def replicate(mesh: Mesh, x) -> jax.Array:
    """Global fully-replicated array from identical per-host values.

    Every process must pass the same value (exact replication, no
    arithmetic); single-process this is a plain device put.
    """
    x = np.asarray(x)
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, P()), x, x.shape)


def replicate_tree(mesh: Mesh, tree):
    return jax.tree.map(lambda x: replicate(mesh, x), tree)


def shard_clients(mesh: Mesh, x, global_shape, axis: int) -> jax.Array:
    """Global array sharded over the client axis from this process's
    local block (``x`` covers exactly ``local_client_slice`` rows of
    ``axis``). The mesh's single axis carries the client dim; every other
    dim is replicated."""
    spec = [None] * len(global_shape)
    spec[axis] = mesh.axis_names[0]
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(*spec)), np.ascontiguousarray(x),
        tuple(global_shape))


@functools.lru_cache(maxsize=None)
def _gather_identity(out_sharding: NamedSharding):
    # one jitted identity per out-sharding: repeated gathers (ServeSync
    # every K rounds, 4 trees per checkpoint save) must not retrace
    return jax.jit(lambda t: t, out_shardings=out_sharding)


def fully_replicated(tree, mesh: Optional[Mesh] = None):
    """Gather every leaf to full replication (one jitted identity; the
    allgather is exact — no arithmetic). Leaves become addressable on
    every process, so ``np.asarray`` works directly afterwards."""
    if mesh is None or mesh.size == 1:
        return tree
    return _gather_identity(NamedSharding(mesh, P()))(tree)


def to_host(tree, mesh: Optional[Mesh] = None):
    """Gather a (possibly client-sharded) tree to plain numpy on every
    process — the checkpoint-save path under a cluster mesh."""
    return jax.tree.map(np.asarray, fully_replicated(tree, mesh))


def sync(tag: str = "repro") -> None:
    """Barrier across the process grid (no-op single-process)."""
    if is_distributed():
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(tag)


def broadcast_from_primary(x: np.ndarray) -> np.ndarray:
    """Rank 0's array on every process, BIT-EXACT (no-op single-process).

    The payload travels as raw bytes (uint8 view), so float64 host values
    — e.g. a TopologySchedule's W_t, which adaptive-T estimators consume
    at full precision — arrive with the identical bits rank 0 drew; jax's
    default float64→float32 demotion never touches them. Every process
    must pass an array of the same shape and dtype.
    """
    x = np.asarray(x)
    if not is_distributed():
        return x
    from jax.experimental import multihost_utils
    raw = np.ascontiguousarray(x).ravel().view(np.uint8)
    # integer transport is value-exact even though the collective may
    # upcast uint8 (e.g. to int32) — convert back before re-viewing bytes
    out = np.asarray(multihost_utils.broadcast_one_to_all(raw))
    return out.astype(np.uint8).view(x.dtype).reshape(x.shape)
