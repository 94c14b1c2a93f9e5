"""`Session` — a running DFL experiment built from a `DFLConfig`.

Owns everything the seven former hand-wired loops re-implemented: model +
LoRA init, topology sampling, the data pipeline, the jitted DFL round
(mesh-aware via `repro.dist` — it runs unchanged under a bound production
mesh — with optional buffer donation), checkpoint/resume through
`repro.checkpoint`, and a callback hook list.

    cfg = DFLConfig(model="gemma3-1b", task="lm", n_clients=6, rounds=15)
    sess = Session(cfg, callbacks=[ConsoleLogger()])
    result = sess.run()

The round loop is deliberately bare — sample W_t, ask the `MaskSchedule`
for this round's masks, step the compiled round, notify callbacks — so a
Session round costs the same as a hand-wired loop (BENCH_round_loop.json
tracks the overhead). Per-round derived quantities (consensus stats, W
spectral gap, float(loss)) are computed lazily by `RoundEvent` only when
a callback asks, never on the hot path.

Builds are cached per model/task signature, so sweeps that vary only
seeds/topology/T (the benchmark grids) re-use one set of init params and
one compiled round function.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Iterator, Mapping, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.api.config import DFLConfig
from repro.api.rounds import build_round
from repro.api.schedule import AdaptiveSchedule, MaskSchedule, StaticSchedule
from repro.checkpoint import load_pytree, save_pytree
from repro.configs import get_config
from repro.control.plane import ControlPlane
from repro.control.stats import RoundStats, metric_loss as _metric_loss
from repro.core.alternating import RoundMasks
from repro.core.diagnostics import consensus_stats
from repro.core import mixing
from repro.core.lora import build_lora_tree
from repro.core.topology import Topology, make_topology, \
    optimal_switching_interval
from repro.data.partition import make_partition
from repro.data.shards import ShardSet
from repro.data.stream import FederatedStream
from repro.data.synthetic import (eval_batch, federated_batches,
                                  label_skew_partitions, lm_token_stream,
                                  make_task)
from repro.dist.comm import CommPlan, build_comm_plan, dense_recv_bytes
from repro.optim.adamw import AdamW, AdamWState
from repro.scenarios.library import estimate_rho_sq, schedule_from_config
from repro.scenarios.schedule import TopologySchedule, schedule_support


# ---------------------------------------------------------------------------
# round events (lazy views handed to callbacks)
# ---------------------------------------------------------------------------

class RoundEvent:
    """One round's outcome, as callbacks see it. A thin view over the
    round's `RoundStats` payload (repro.control.stats) — the SAME object
    `ControlPlane.observe()` consumed, so derived quantities (loss
    reduction, consensus stats) are memoized once and shared between the
    control loop and every callback. The stats snapshot THIS round's lora
    tree, so a deferred `consensus()` call still describes round t —
    though under `donate=True` the buffers are consumed by the next
    round, so compute consensus inside on_round_end there."""

    def __init__(self, session: "Session", t: int, masks: RoundMasks,
                 W: np.ndarray, metrics: Mapping, is_last: bool,
                 stats: Optional[RoundStats] = None):
        self.session = session
        self.t = t
        self.masks = masks
        self.W = W
        self.metrics = metrics          # jax arrays — not yet synced
        self.is_last = is_last
        self.stats = stats if stats is not None else RoundStats(
            t, W, masks=masks, metrics=metrics, lora=session.lora)
        self.lora = self.stats.lora     # this round's state (post-mix)

    @property
    def phase(self) -> str:
        return "A" if self.masks.update_a else "B"

    @property
    def loss(self) -> float:
        return self.stats.loss

    def consensus(self) -> dict:
        """Consensus/theory diagnostics of THIS round's LoRA state
        (delta_a_sq, delta_b_sq, cross_norm, cs_bound) as floats."""
        return self.stats.consensus()

    def w_gap(self) -> float:
        """Spectral distance ||W_t - J||_2 of this round's mixing matrix."""
        return self.stats.w_gap()


@dataclass
class RunResult:
    rounds: int
    wall_s: float
    final_loss: float
    T: int


# ---------------------------------------------------------------------------
# cached builds (model init + compiled round per model/task signature)
# ---------------------------------------------------------------------------

@dataclass
class _Built:
    model_cfg: object
    task: object                 # SyntheticTask or None for "lm"
    base: object
    lora0: object
    opt: AdamW
    round_fn: Callable
    acc_fn: Optional[Callable]
    comm_plan: Optional[CommPlan]


_BUILD_CACHE: dict = {}


def _resolve_mix_gather() -> bool:
    """The pre-mix client all-gather is on exactly when the run spans
    processes (repro.dist.multihost): single-process rounds keep the
    unconstrained lowering, cluster rounds pin the bitwise-parity
    communication step."""
    return jax.process_count() > 1


def _comm_plan_for(cfg: DFLConfig) -> Optional[CommPlan]:
    """The sparse-exchange CommPlan a config describes (None for dense).

    The union support comes from a FRESH config-derived schedule replica
    (support is static — probing it consumes no RNG the round loop owns),
    compiled against the process grid's total device count. One shard
    (single process, CPU) degenerates to a local contraction."""
    if cfg.mix_comm == "dense":
        return None
    support = schedule_support(schedule_from_config(cfg))
    return build_comm_plan(support, n_shards=jax.device_count())


def _build_key(cfg: DFLConfig, comm_plan: Optional[CommPlan] = None):
    return (cfg.model, cfg.reduced, cfg.model_kw, cfg.task,
            cfg.feature_shift, cfg.n_clients, cfg.lr, cfg.local_steps,
            _resolve_mix_gather(), cfg.donate, cfg.init_seed,
            cfg.mix_comm, cfg.mix_quant,
            cfg.data_source, cfg.data_path,
            comm_plan.signature() if comm_plan is not None else None)


def _build(cfg: DFLConfig, model_cfg, loss_fn) -> _Built:
    cacheable = model_cfg is None and loss_fn is None
    comm_plan = _comm_plan_for(cfg)
    key = _build_key(cfg, comm_plan)
    if cacheable and key in _BUILD_CACHE:
        return _BUILD_CACHE[key]

    base_key = jax.random.key(cfg.init_seed)
    lora_key = jax.random.key(cfg.init_seed + 1)
    acc_fn = None
    task = None

    if cfg.task == "lm":
        from repro.models import transformer as tf
        mc = model_cfg
        if mc is None:
            mc = get_config(cfg.model)
            if cfg.reduced:
                mc = mc.reduced()
        base = tf.init_params(base_key, mc)
        if loss_fn is None:
            def loss_fn(bp, lo, micro, _cfg=mc):
                (loss, (_, _, load)), per = tf.lm_loss(
                    bp, _cfg, micro["tokens"], micro["targets"],
                    frontend=micro.get("frontend"), lora=lo,
                    per_client=True)
                if load is None:
                    return loss, per
                return loss, per, {"expert_load": load}
    else:
        from repro.models.classifier import (classifier_accuracy,
                                             classifier_loss, encoder_config,
                                             init_classifier)
        mc = model_cfg if model_cfg is not None \
            else encoder_config(**dict(cfg.model_kw))
        if cfg.data_source == "shards":
            # task identity comes from the shard manifest; its token ids
            # must live inside the model's embedding table
            task = ShardSet(cfg.data_path)
            if task.vocab_size > mc.vocab_size:
                raise ValueError(
                    f"shard set {task.name!r} has vocab_size="
                    f"{task.vocab_size} > model vocab_size="
                    f"{mc.vocab_size}; regenerate the shards or widen "
                    f"model_kw['vocab_size']")
        else:
            # task tokens must live inside the model's embedding table
            task = make_task(cfg.task, feature_shift=cfg.feature_shift,
                             vocab_size=mc.vocab_size)
        base = init_classifier(base_key, mc, n_classes=task.n_classes)
        if loss_fn is None:
            def loss_fn(bp, lo, micro, _cfg=mc):
                return classifier_loss(bp, _cfg, micro["tokens"],
                                       micro["labels"], lora=lo,
                                       per_client=True)
        acc_fn = jax.jit(lambda bp, toks, labs, lo, _cfg=mc:
                         classifier_accuracy(bp, _cfg, toks, labs, lora=lo))

    lora0 = build_lora_tree(lora_key, base, mc, n_clients=cfg.n_clients)
    opt = AdamW(lr=cfg.lr)
    round_fn = build_round(loss_fn, opt, local_steps=cfg.local_steps,
                           mix_gather=_resolve_mix_gather(),
                           mix_comm=cfg.mix_comm,
                           mix_quant=cfg.mix_quant,
                           comm_plan=comm_plan,
                           donate=cfg.donate)
    if not cfg.donate:
        round_fn = jax.jit(round_fn)

    built = _Built(model_cfg=mc, task=task, base=base, lora0=lora0,
                   opt=opt, round_fn=round_fn, acc_fn=acc_fn,
                   comm_plan=comm_plan)
    if cacheable:
        _BUILD_CACHE[key] = built
    return built


def clear_build_cache() -> None:
    _BUILD_CACHE.clear()


# ---------------------------------------------------------------------------
# the Session
# ---------------------------------------------------------------------------

class Session:
    """One DFL experiment: state + the compiled round + the round loop.

    Construction is cheap when an equal model/task signature was built
    before (init params and the jitted round are cached module-wide).
    `model_cfg` overrides the architecture with a custom ModelConfig;
    `loss_fn(base, lora, micro) -> scalar` overrides the objective;
    `schedule` overrides the mask schedule (default: static T from the
    config, or a controller-driven `AdaptiveSchedule` when
    config.control.t_policy == "adaptive");
    `topology_schedule` overrides the communication condition (default:
    built from config.scenario via `repro.scenarios`).

    An *active* config.control (repro.control.ControlConfig) additionally
    instantiates a `ControlPlane` at `session.control`: each round's
    `RoundStats` is fed to `control.observe()` before callbacks fire, the
    plane's weight policy is installed into the topology schedule's
    `set_weights` hook, and — for t_policy "adaptive" — the plane's
    controller drives the mask schedule, retuning T only at phase
    boundaries (the compiled round never retraces).
    """

    def __init__(self, config: DFLConfig, *, model_cfg=None,
                 loss_fn: Optional[Callable] = None,
                 schedule: Optional[MaskSchedule] = None,
                 topology_schedule: Optional[TopologySchedule] = None,
                 callbacks: Sequence = ()):
        self.config = config
        self.callbacks = list(callbacks)
        built = _build(config, model_cfg, loss_fn)
        self.model_cfg = built.model_cfg
        self.task = built.task
        self.base = built.base
        self.opt = built.opt
        self.round_fn = built.round_fn
        self._acc_fn = built.acc_fn
        self._lora0 = built.lora0
        self.comm_plan = built.comm_plan    # None for mix_comm="dense"

        # the underlying graph + legacy sampler stay exposed as
        # `session.topology`; the round loop itself draws W_t from the
        # TopologySchedule the config's scenario selects (the "gossip"
        # default wraps self.topology, sharing its RNG stream)
        self.topology: Topology = make_topology(
            config.topology, config.n_clients, config.p, seed=config.seed,
            **dict(config.topology_kw))
        self._user_topo_schedule = topology_schedule
        self.topo_schedule: TopologySchedule = topology_schedule \
            if topology_schedule is not None \
            else schedule_from_config(config, topology=self.topology)
        if self.comm_plan is not None and topology_schedule is not None:
            # the sparse exchange only moves rows inside the CONFIG's
            # support; a user schedule coupling rows outside it would
            # silently mix against zeros
            extra = schedule_support(topology_schedule) \
                & ~self.comm_plan.support
            if extra.any():
                raise ValueError(
                    "topology_schedule couples clients outside the "
                    "config-derived support the sparse CommPlan was "
                    "compiled for; use mix_comm='dense' or align the "
                    "schedule's support_adjacency() with the config")
        self._rho: Optional[float] = None
        self._T: Optional[int] = config.T or None
        self._comm_bytes: Optional[int] = None
        self.control = self._make_control()
        self._install_weight_policy()
        self._user_schedule = schedule
        self.schedule = schedule if schedule is not None \
            else self._default_schedule()

        self.t = 0
        self.last_metrics: Optional[Mapping] = None
        self.last_event: Optional[RoundEvent] = None
        self.reset_state()

    def _make_control(self) -> Optional[ControlPlane]:
        """The ControlPlane this config asks for (None when the control
        struct is inert — the open-loop default costs nothing). Under
        sparse comm the plane's FMMC policy is fed the CommPlan's
        per-link byte accounting as its bandwidth cost."""
        cc = self.config.control
        if cc is None or not cc.active:
            return None
        link_cost = None
        if self.comm_plan is not None:
            plan = mixing.get_mix_plan(self._lora0)
            link_cost = self.comm_plan.link_bytes(plan.cols)
        return ControlPlane(cc, link_cost=link_cost)

    def _install_weight_policy(self) -> None:
        """Install the control plane's weight policy into the topology
        schedule's `set_weights` hook (no-op for the Metropolis baseline,
        which must stay byte-identical to pre-control runs)."""
        if self.control is None or self.control.weight_policy is None:
            return
        hook = getattr(self.topo_schedule, "set_weights", None)
        if hook is None:
            raise ValueError(
                f"control.weight_policy="
                f"{self.config.control.weight_policy!r} needs a topology "
                f"schedule with a set_weights() hook; "
                f"{type(self.topo_schedule).__name__} exposes none — use a "
                f"Metropolis-based scenario schedule or drop the weight "
                f"policy")
        hook(self.control.weight_policy)

    def _default_schedule(self) -> MaskSchedule:
        cfg = self.config
        if self.control is not None and self.control.controller is not None:
            # the plane owns rho estimation (ControlPlane.observe); the
            # schedule only advances the shared controller's calendar
            return AdaptiveSchedule(cfg.method, estimator="none",
                                    controller=self.control.controller)
        return StaticSchedule(cfg.method, self.T)

    # -- state --------------------------------------------------------------
    @property
    def rho(self) -> float:
        """Monte-Carlo contraction estimate of the communication condition
        (memoized). The legacy gossip scenario keeps the per-sample
        Topology estimator (identical T* selection to pre-scenario runs);
        every other scenario measures a fresh replica of its schedule via
        the time-averaged ||E[WᵀW] − J||₂ gram route. Undefined for a
        user-supplied topology_schedule: the live schedule's RNG belongs
        to the round loop and cannot be probed, so set T explicitly (or
        pass a mask schedule) instead of relying on T*(rho)."""
        if self._rho is None:
            if self._user_topo_schedule is not None:
                raise ValueError(
                    "rho/T*(rho) is undefined for a user-supplied "
                    "topology_schedule (probing it would consume the run's "
                    "W_t stream); set config.T explicitly or pass a mask "
                    "schedule")
            if self.config.scenario == "gossip":
                self._rho = self.topology.rho_estimate(100)
            else:
                # probe a FRESH config-derived replica — never the live
                # schedule, whose RNG the round loop owns (a user-supplied
                # schedule is proxied by the config's scenario)
                self._rho = float(np.sqrt(estimate_rho_sq(
                    schedule_from_config(self.config), rounds=100)))
        return self._rho

    @property
    def T(self) -> int:
        """The static switching interval: config.T, or T*(rho) on first
        access (lazy — adaptive/custom-schedule sessions never pay for
        the Monte-Carlo rho estimate behind it)."""
        if self._T is None:
            self._T = optimal_switching_interval(self.rho)
        return self._T

    def reset_state(self) -> None:
        """(Re)initialize lora/opt state and the data pipeline at round 0.
        The topology RNG is NOT reset — call sites that need a bit-for-bit
        replay construct a fresh Session instead."""
        lora0 = self._lora0
        if self.config.donate:
            # donated buffers are consumed by the round — never hand the
            # cached init tree itself to a donating round function
            lora0 = jax.tree.map(lambda x: jnp.array(x, copy=True), lora0)
        self.lora = lora0
        self.opt_state: AdamWState = self.opt.init(self.lora)
        # compressed gossip carries the per-client error-feedback
        # accumulator as round state, zero at round 0 (the MixPlan's
        # unpadded (m, cols) flat layout)
        self.ef = None
        if self.config.mix_quant != "off":
            plan = mixing.get_mix_plan(self.lora)
            self.ef = jnp.zeros((plan.m, plan.cols), jnp.float32)
        old = getattr(self, "_batches", None)
        if old is not None and hasattr(old, "close"):
            old.close()                 # join a prefetching stream's worker
        self._batches = self._raw_batch_iter()
        self.t = 0
        self.last_metrics = None
        self.last_stats: Optional[RoundStats] = None
        # phase-index tracking for RoundStats (increments at every A/B
        # boundary; the frozen-contraction estimator pairs Δ² samples only
        # within one phase)
        self._phase_idx = 0
        self._prev_update_a: Optional[bool] = None

    def _track_phase(self, masks: RoundMasks) -> int:
        ua = bool(masks.update_a)
        if self._prev_update_a is not None and ua != self._prev_update_a:
            self._phase_idx += 1
        self._prev_update_a = ua
        return self._phase_idx

    def _round_comm_bytes(self) -> int:
        """Per-round gossip bytes this process RECEIVES under the live
        lowering (memoized: the flat layout is static across rounds).
        Dense single-process runs receive 0 — the exchange never leaves
        the process."""
        if self._comm_bytes is None:
            plan = mixing.get_mix_plan(self._lora0)
            cfg = self.config
            if self.comm_plan is None:
                self._comm_bytes = dense_recv_bytes(
                    cfg.n_clients, jax.process_count(), plan.cols)
            elif cfg.mix_quant != "off":
                self._comm_bytes = \
                    self.comm_plan.sparse_recv_bytes_quant(plan.cols)
            else:
                self._comm_bytes = \
                    self.comm_plan.sparse_recv_bytes(plan.cols)
        return self._comm_bytes

    # -- data ---------------------------------------------------------------
    # raw (numpy) draws and device conversion are split so checkpoint
    # replay can advance the data RNG without materializing device arrays
    # (the shard stream skips even that: its batches are pure functions of
    # the round index, so replay is an O(1) seek)
    def _raw_batch_iter(self) -> Iterator:
        cfg = self.config
        if cfg.data_source == "shards":
            shards: ShardSet = self.task
            parts = make_partition(cfg.partitioner, shards.labels("train"),
                                   cfg.n_clients, seed=cfg.data_seed,
                                   domains=shards.domains("train"),
                                   **dict(cfg.partitioner_kw))
            return FederatedStream(shards, parts, batch=cfg.batch_size,
                                   local_steps=cfg.local_steps,
                                   seed=cfg.data_seed,
                                   prefetch=cfg.data_prefetch)
        return self._synthetic_batch_iter()

    def _synthetic_batch_iter(self) -> Iterator:
        cfg = self.config
        if cfg.task == "lm":
            m, ls, b, S = (cfg.n_clients, cfg.local_steps, cfg.batch_size,
                           cfg.seq_len)
            stream = lm_token_stream(self.model_cfg.vocab_size, b * ls, S,
                                     n_clients=m, seed=cfg.data_seed)
            for raw in stream:
                yield {k: v.reshape(m, ls, b, S).swapaxes(0, 1)
                       for k, v in raw.items()}
        else:
            parts = label_skew_partitions(self.task.n_classes, cfg.n_clients)
            # effectively endless: per-round draws don't depend on the total
            yield from federated_batches(self.task, parts, cfg.batch_size,
                                         cfg.local_steps, rounds=1 << 62,
                                         seed=cfg.data_seed)

    def _device_scalar_inputs(self, x):
        """Placement hook for the round's small replicated inputs (W_t,
        masks). ClusterSession overrides this to build global replicated
        arrays on the cluster mesh; single-process it is a plain put."""
        return jnp.asarray(x)

    def _raw_round_batch(self, raw) -> dict:
        """Complete one round's raw numpy batch (adds the frontend-token
        zeros LM archs expect). Placement-independent: ClusterSession
        reuses this and only changes where the leaves land."""
        cfg = self.config
        raw = dict(raw)
        nft = getattr(self.model_cfg, "n_frontend_tokens", 0)
        if cfg.task == "lm" and nft:
            raw["frontend"] = np.zeros(
                (cfg.local_steps, cfg.n_clients, cfg.batch_size, nft,
                 self.model_cfg.d_model), np.float32)
        return raw

    def _to_device(self, raw):
        return jax.tree.map(jnp.asarray, self._raw_round_batch(raw))

    # -- the round loop -----------------------------------------------------
    def step(self) -> RoundEvent:
        """Run exactly one round (callbacks fire, like run()) and return
        its event."""
        ev = self._one_round(is_last=False, notify=True, want_event=True)
        self.last_event = ev
        return ev

    # -- cold joins (adapter-initialization half of the identity repair) ----
    def _apply_client_matrix(self, R: np.ndarray,
                             zero_ef_rows: tuple = ()) -> None:
        """Apply a host-side (m, m) row-mixing matrix to every client-axis
        state tree (LoRA factors + Adam moments). Runs in numpy on the
        full state so every process grid computes the identical result
        bit-for-bit; `zero_ef_rows` clears those clients' error-feedback
        accumulators (a joiner's residual describes pre-join state).
        ClusterSession overrides this to gather/re-shard around it."""
        R64 = np.asarray(R, np.float64)

        def one(x):
            a = np.asarray(x)
            mixed = np.einsum("ij,...jdr->...idr", R64, a)
            return jnp.asarray(mixed.astype(a.dtype))

        self.lora = jax.tree.map(one, self.lora)
        self.opt_state = AdamWState(
            step=self.opt_state.step,
            mu=jax.tree.map(one, self.opt_state.mu),
            nu=jax.tree.map(one, self.opt_state.nu))
        if self.ef is not None and zero_ef_rows:
            ef = np.array(self.ef)
            ef[list(zero_ef_rows)] = 0.0
            self.ef = jnp.asarray(ef)

    def _warm_start_clients(self, joiners: tuple) -> None:
        """Initialize joining clients' adapters from the average of their
        already-warm graph neighbors (uniform over the support adjacency,
        excluding co-joiners). A joiner with no warm neighbor keeps its
        cold state — the identity row is the only sound fallback."""
        m = self.config.n_clients
        sup = np.asarray(schedule_support(self.topo_schedule), bool)
        js = {int(j) for j in joiners}
        R = np.eye(m)
        for j in js:
            nbrs = [k for k in range(m)
                    if k != j and k not in js and sup[j, k]]
            if nbrs:
                R[j, :] = 0.0
                R[j, nbrs] = 1.0 / len(nbrs)
        self._apply_client_matrix(R, zero_ef_rows=tuple(sorted(js)))

    def _one_round(self, *, is_last: bool, notify: bool,
                   want_event: bool = False) -> Optional[RoundEvent]:
        # host spans on the profiler's clock; recorded only while a
        # profiler trace is on (jax.profiler.start_trace)
        t, cfg = self.t, self.config
        with jax.profiler.StepTraceAnnotation(
                "repro.round", step_num=t,
                tokens=(cfg.n_clients * cfg.local_steps * cfg.batch_size
                        * cfg.seq_len)):
            join_fn = getattr(self.topo_schedule, "join_events", None)
            if join_fn is not None:
                joiners = tuple(join_fn(t))
                if joiners:
                    with jax.profiler.TraceAnnotation("repro.round.joins"):
                        self._warm_start_clients(joiners)
            with jax.profiler.TraceAnnotation("repro.round.batch"):
                batch = self._to_device(next(self._batches))
            with jax.profiler.TraceAnnotation("repro.round.topology"):
                W_np = self.topo_schedule.next_w(t)
                masks = self.schedule.next_masks(
                    t, {"W": W_np, "round": t, "session": self})
            with jax.profiler.TraceAnnotation("repro.round.put"):
                W_dev = self._device_scalar_inputs(
                    np.asarray(W_np, np.float32))
                masks_dev = self._device_scalar_inputs(masks.as_array())
            with jax.profiler.TraceAnnotation("repro.round.dispatch"):
                if self.ef is not None:
                    # quantized round: the error-feedback buffer threads
                    # through
                    self.lora, self.opt_state, metrics, self.ef = \
                        self.round_fn(self.base, self.lora, self.opt_state,
                                      batch, W_dev, masks_dev, self.ef)
                else:
                    self.lora, self.opt_state, metrics = self.round_fn(
                        self.base, self.lora, self.opt_state, batch, W_dev,
                        masks_dev)
            with jax.profiler.TraceAnnotation("repro.round.observe"):
                return self._observe(t, W_np, masks, metrics,
                                     is_last=is_last, notify=notify,
                                     want_event=want_event)

    def _observe(self, t: int, W_np, masks: RoundMasks, metrics, *,
                 is_last: bool, notify: bool,
                 want_event: bool) -> Optional[RoundEvent]:
        self.last_metrics = metrics
        # one observation payload per round, shared by the control loop
        # and every callback (construction is lazy — no device sync here)
        stats = RoundStats(t, W_np, phase=self._track_phase(masks),
                           masks=masks, metrics=metrics, lora=self.lora,
                           comm_bytes=self._round_comm_bytes())
        self.last_stats = stats
        if self.control is not None:
            self.control.observe(stats)
        # t advances BEFORE callbacks fire: a checkpoint taken inside a
        # callback resumes after the round it just observed
        self.t = t + 1
        ev = None
        if want_event or (notify and self.callbacks):
            ev = RoundEvent(self, t, masks, W_np, metrics, is_last,
                            stats=stats)
        if notify and ev is not None:
            for cb in self.callbacks:
                cb.on_round_end(ev)
        return ev

    def run(self, rounds: Optional[int] = None) -> RunResult:
        """Run `rounds` (default config.rounds) rounds from the current
        state; fires on_round_end per round and on_run_end at the end."""
        n = self.config.rounds if rounds is None else rounds
        t0 = time.time()
        end = self.t + n
        while self.t < end:
            self._one_round(is_last=(self.t == end - 1), notify=True)
        jax.block_until_ready(self.lora)
        wall = time.time() - t0
        final = _metric_loss(self.last_metrics) \
            if self.last_metrics is not None else float("nan")
        result = RunResult(rounds=n, wall_s=wall, final_loss=final,
                           T=getattr(self.schedule, "T", self.T))
        for cb in self.callbacks:
            cb.on_run_end(self, result)
        return result

    # -- evaluation / diagnostics ------------------------------------------
    def consensus(self) -> dict:
        return {k: float(v) for k, v in
                consensus_stats(self.lora).items()}

    def client_lora(self, i: int):
        return jax.tree.map(lambda x: x[..., i, :, :], self.lora)

    def evaluate(self, n: Optional[int] = None,
                 seed: Optional[int] = None) -> dict:
        """Mean per-client accuracy on the task's balanced test draw
        (classifier tasks; the paper's evaluation protocol)."""
        if self.task is None:
            raise ValueError("evaluate() is defined for classifier tasks; "
                             "LM runs score held-out loss/perplexity at the "
                             "call site (see examples/dfl_finetune.py)")
        cfg = self.config
        n_eval = n if n is not None else cfg.eval_n
        eval_seed = seed if seed is not None else cfg.eval_seed
        if isinstance(self.task, ShardSet):
            test = self.task.eval_batch(n_eval, seed=eval_seed)
        else:
            test = eval_batch(self.task, n_eval, seed=eval_seed)
        # placement hook: on a cluster the eval batch must be replicated
        # onto the global mesh next to the replicated base params
        toks = self._device_scalar_inputs(test["tokens"])
        labs = self._device_scalar_inputs(test["labels"])
        accs = [float(self._acc_fn(self.base, toks, labs,
                                   self.client_lora(i)))
                for i in range(cfg.n_clients)]
        return {"acc": float(np.mean(accs)),
                "acc_std_clients": float(np.std(accs)),
                "per_client": accs}

    # -- checkpoint / resume ------------------------------------------------
    def save(self, path: str) -> None:
        """Checkpoint lora + optimizer state + round counter (flat npz)."""
        tree = {
            "lora": self.lora,
            "opt": {"step": self.opt_state.step, "mu": self.opt_state.mu,
                    "nu": self.opt_state.nu},
            "meta": {"round": np.int64(self.t)},
        }
        if self.ef is not None:
            tree["ef"] = self.ef
        save_pytree(path, tree)

    def restore(self, path: str) -> int:
        """Resume from a checkpoint: restores state AND replays the
        topology/data/schedule RNGs up to the saved round, so a restored
        run continues bit-for-bit where the original left off — including
        time-varying TopologySchedules (churn Markov state, phase
        switches), whose per-round W_t draws are re-issued in order. A
        user-supplied `schedule`/`topology_schedule` object must be
        freshly constructed (the replay advances it from its current
        state)."""
        tree = load_pytree(path)
        self.reset_state()
        cfg = self.config
        self.topology = make_topology(cfg.topology, cfg.n_clients, cfg.p,
                                      seed=cfg.seed,
                                      **dict(cfg.topology_kw))
        if self._user_topo_schedule is None:
            self.topo_schedule = schedule_from_config(
                cfg, topology=self.topology)
        # fresh control plane (estimator/controller state replays below)
        # and re-install its weight policy into the rebuilt schedule
        self.control = self._make_control()
        self._install_weight_policy()
        if self._user_schedule is None:
            self.schedule = self._default_schedule()
        saved_round = int(np.asarray(tree["meta"]["round"]))
        if hasattr(self._batches, "seek"):
            # shard streams are pure functions of the round index: replay
            # is an O(1) reposition, bit-for-bit equal to re-iteration
            self._batches.seek(saved_round)
        else:
            for _ in range(saved_round):
                next(self._batches)          # data RNG replay (numpy only)
        for t in range(saved_round):
            W = self.topo_schedule.next_w(t)  # topology RNG replay
            masks = self.schedule.next_masks(
                t, {"W": W, "round": t, "session": self})
            self._track_phase(masks)
            if self.control is not None:
                # W-only replay: spectral/gram re-estimate exactly; the
                # frozen probe resets and re-locks from live rounds
                self.control.observe_replay(t, W)
        self.lora = jax.tree.map(jnp.asarray, tree["lora"])
        opt = tree["opt"]
        self.opt_state = AdamWState(
            step=jnp.asarray(opt["step"]),
            mu=jax.tree.map(jnp.asarray, opt["mu"]),
            nu=jax.tree.map(jnp.asarray, opt["nu"]))
        if self.ef is not None and "ef" in tree:
            self.ef = jnp.asarray(tree["ef"])
        self.t = saved_round
        return saved_round
