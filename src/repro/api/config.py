"""`DFLConfig` — the single declarative description of a DFL experiment.

One frozen dataclass captures everything the paper's protocol needs:
model/task, federation geometry (clients, graph family + topology_kw,
communication scenario + scenario_kw, p), method + switching interval,
optimization (rounds, local steps, lr, batch), engine knobs (gossip
exchange and its compression, donation), and seeds. A `Session`
(repro.api.session) turns a config into a running experiment;
`cache_key()` is a stable JSON hash used by the benchmark results cache.

Seed conventions (all derivable from `seed` unless overridden):
  base params   <- jax.random.key(init_seed)        (init_seed = seed)
  LoRA factors  <- jax.random.key(init_seed + 1)
  topology RNG  <- seed
  data pipeline <- data_seed                         (data_seed = seed)
  evaluation    <- eval_seed (classifier tasks)
Benchmark sweeps typically pin `init_seed` while varying `seed`, so every
seed shares one init and only data/topology randomness moves.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import warnings
from dataclasses import dataclass, field
from typing import Mapping, Optional, Union

from repro.control.config import ControlConfig
from repro.core.alternating import METHODS
from repro.core.mixing import MIX_COMM_MODES, MIX_QUANT_MODES
from repro.core.topology import GRAPH_FAMILIES
from repro.data.partition import PARTITIONERS
from repro.scenarios.library import SCENARIOS

CLASSIFIER_TASKS = ("sst2", "qqp", "qnli", "mnli")
TOPOLOGIES = GRAPH_FAMILIES
DATA_SOURCES = ("synthetic", "shards")

_KEY_VERSION = 9   # bump when semantics of any field change

# legacy flat-knob defaults (pre-v8 configs; see DFLConfig.control)
_LEGACY_ADAPTIVE = {"adaptive_T": False, "adaptive_c": 0.35,
                    "adaptive_t_max": 15}


@dataclass(frozen=True)
class DFLConfig:
    """Declarative DFL experiment description (validated, hashable key)."""

    # -- model / task -------------------------------------------------------
    model: str = "gemma3-1b"     # arch name (repro.configs) or "encoder"
    task: str = "lm"             # "lm" or a classifier task (CLASSIFIER_TASKS)
    reduced: bool = True         # reduced() arch config (CPU scale)
    model_kw: tuple = ()         # encoder_config(**kw) overrides (dict ok)

    # -- federation ---------------------------------------------------------
    n_clients: int = 8
    topology: str = "complete"   # underlying graph family (GRAPH_FAMILIES)
    topology_kw: tuple = ()      # graph params (er_q, ws_k/ws_beta, torus_*)
    p: float = 0.2               # edge activation probability
    scenario: str = "gossip"     # communication condition (SCENARIOS):
                                 # "gossip" = the paper's Lemma A.10 sampler
    scenario_kw: tuple = ()      # schedule params (churn leave/rejoin,
                                 # straggler drop, phase_switch knobs)
    method: str = "tad"
    T: int = 0                   # switching interval; 0 = topology-aware T*
    # DEPRECATED flat adaptive knobs (v7-era). Still accepted: non-default
    # values emit a DeprecationWarning and map onto `control`; after
    # resolution they mirror the struct (adaptive_T <-> t_policy,
    # adaptive_c <-> c, adaptive_t_max <-> t_max), so old- and new-style
    # configs compare (and cache-key) identically.
    adaptive_T: Optional[bool] = None     # -> control.t_policy "adaptive"
    adaptive_c: Optional[float] = None    # -> control.c
    adaptive_t_max: Optional[int] = None  # -> control.t_max
    control: Optional[Union[ControlConfig, Mapping]] = None
                                 # closed-loop control plane policies
                                 # (repro.control.ControlConfig; dict ok);
                                 # None resolves to the open-loop default

    # -- optimization -------------------------------------------------------
    rounds: int = 40
    local_steps: int = 4
    batch_size: int = 4          # per-client, per-local-step
    seq_len: int = 64            # LM task only (classifier tasks fix theirs)
    lr: float = 1e-3

    # -- engine -------------------------------------------------------------
    mix_comm: str = "dense"      # gossip comm lowering: "dense" |
                                 # "sparse" (topology-support exchange,
                                 # bitwise equal) | "sparse_overlap"
                                 # (one-round-delayed neighbor terms)
    mix_quant: str = "off"       # compressed gossip: quantize the sparse
                                 # halo exchange ("int8" | "fp8") with
                                 # per-client error feedback; "off" keeps
                                 # the fp32 wire format bit-for-bit
    donate: bool = False         # donate lora/opt buffers (in-place round)

    # -- seeds / data -------------------------------------------------------
    seed: int = 0
    data_seed: Optional[int] = None   # defaults to seed
    init_seed: Optional[int] = None   # defaults to seed
    feature_shift: int = 0       # per-client feature dialects (classifier)
    eval_n: int = 384
    eval_seed: int = 9999
    data_source: str = "synthetic"  # "synthetic" (per-round draws) |
                                 # "shards" (tokenized shard set at
                                 # data_path through FederatedStream)
    data_path: str = ""          # shard-set directory (data_source=shards)
    partitioner: str = "paper"   # non-IID partitioner (repro.data
                                 # PARTITIONERS; shards source only)
    partitioner_kw: tuple = ()   # partitioner params (dirichlet alpha, ...)
    data_prefetch: int = 0       # stream prefetch depth (0 = synchronous)

    def __post_init__(self):
        for kw_field in ("model_kw", "topology_kw", "scenario_kw",
                         "partitioner_kw"):
            v = getattr(self, kw_field)
            if isinstance(v, Mapping):
                object.__setattr__(self, kw_field, tuple(sorted(v.items())))
            else:
                object.__setattr__(self, kw_field, tuple(v))
        if self.data_seed is None:
            object.__setattr__(self, "data_seed", self.seed)
        if self.init_seed is None:
            object.__setattr__(self, "init_seed", self.seed)
        self._resolve_control()
        self._validate()

    def _resolve_control(self) -> None:
        """Resolve the deprecated flat adaptive knobs and the structured
        `control` field into one canonical ControlConfig, then mirror the
        struct back onto the flat fields so old-style and new-style
        configs are field-identical (same equality, same cache key)."""
        flat = {k: getattr(self, k) for k in _LEGACY_ADAPTIVE}
        ctrl = self.control
        if ctrl is not None:
            ctrl = ControlConfig.coerce(ctrl)
            # both given (e.g. a to_dict round-trip carrying the mirror):
            # consistent values pass silently, conflicts are errors
            mirror = {"adaptive_T": ctrl.t_policy == "adaptive",
                      "adaptive_c": ctrl.c, "adaptive_t_max": ctrl.t_max}
            for k, v in flat.items():
                if v is not None and v != mirror[k]:
                    raise ValueError(
                        f"DFLConfig: deprecated {k}={v!r} conflicts with "
                        f"control={ctrl}; set the ControlConfig field only")
        else:
            given = {k: v for k, v in flat.items() if v is not None}
            resolved = {**_LEGACY_ADAPTIVE, **given}
            if any(resolved[k] != _LEGACY_ADAPTIVE[k] for k in given):
                warnings.warn(
                    "DFLConfig adaptive_T/adaptive_c/adaptive_t_max are "
                    "deprecated; use control=ControlConfig(t_policy="
                    "'adaptive', c=..., t_max=...) (repro.control)",
                    DeprecationWarning, stacklevel=4)
            ctrl = ControlConfig(
                t_policy="adaptive" if resolved["adaptive_T"] else "fixed",
                c=resolved["adaptive_c"],
                t_max=resolved["adaptive_t_max"])
        object.__setattr__(self, "control", ctrl)
        object.__setattr__(self, "adaptive_T", ctrl.t_policy == "adaptive")
        object.__setattr__(self, "adaptive_c", ctrl.c)
        object.__setattr__(self, "adaptive_t_max", ctrl.t_max)

    def _validate(self) -> None:
        def check(cond, msg):
            if not cond:
                raise ValueError(f"DFLConfig: {msg}")

        check(self.task == "lm" or self.task in CLASSIFIER_TASKS,
              f"unknown task {self.task!r}; known: 'lm' + {CLASSIFIER_TASKS}")
        if self.task == "lm":
            check(self.model != "encoder",
                  "task 'lm' needs an architecture name, not 'encoder'")
            check(not self.model_kw,
                  "model_kw applies to the 'encoder' classifier model only")
        else:
            check(self.model == "encoder",
                  f"classifier task {self.task!r} requires model='encoder'")
        check(self.method in METHODS,
              f"unknown method {self.method!r}; known: {METHODS}")
        check(self.topology in TOPOLOGIES,
              f"unknown topology {self.topology!r}; known: {TOPOLOGIES}")
        check(self.scenario in SCENARIOS,
              f"unknown scenario {self.scenario!r}; known: {SCENARIOS}")
        check(not (self.scenario in ("gossip", "static")
                   and self.scenario_kw),
              f"scenario {self.scenario!r} takes no scenario_kw")
        check(self.mix_comm in MIX_COMM_MODES,
              f"unknown mix_comm {self.mix_comm!r}; "
              f"known: {MIX_COMM_MODES}")
        check(self.mix_quant in MIX_QUANT_MODES,
              f"unknown mix_quant {self.mix_quant!r}; "
              f"known: {MIX_QUANT_MODES}")
        check(self.mix_quant == "off" or self.mix_comm != "dense",
              f"mix_quant {self.mix_quant!r} compresses the sparse halo "
              f"exchange; it requires mix_comm='sparse' or "
              f"'sparse_overlap'")
        check(self.data_source in DATA_SOURCES,
              f"unknown data_source {self.data_source!r}; "
              f"known: {DATA_SOURCES}")
        if self.data_source == "shards":
            check(bool(self.data_path),
                  "data_source 'shards' requires data_path (a shard-set "
                  "directory; see repro.data.shards.write_shards)")
            check(self.task != "lm",
                  "data_source 'shards' serves classifier tasks (the LM "
                  "stream stays synthetic)")
        else:
            check(self.partitioner == "paper" and not self.partitioner_kw,
                  "partitioner/partitioner_kw apply to data_source="
                  "'shards' (the synthetic source hard-codes the paper "
                  "rows)")
        check(self.partitioner in PARTITIONERS,
              f"unknown partitioner {self.partitioner!r}; "
              f"known: {sorted(PARTITIONERS)}")
        check(self.data_prefetch >= 0, "data_prefetch must be >= 0")
        check(self.n_clients >= 2, "n_clients must be >= 2")
        check(0.0 < self.p <= 1.0, "p must be in (0, 1]")
        check(self.rounds > 0, "rounds must be positive")
        check(self.local_steps > 0, "local_steps must be positive")
        check(self.batch_size > 0, "batch_size must be positive")
        check(self.T >= 0, "T must be >= 0 (0 selects T*(rho))")
        if self.control.t_policy == "adaptive":
            check(self.method in ("tad", "rolora"),
                  "control.t_policy 'adaptive' (deprecated alias "
                  "adaptive_T) applies to alternating methods only")
        if self.control.weight_policy == "fmmc":
            check(self.scenario != "gossip",
                  "control.weight_policy 'fmmc' rewires Metropolis-weight "
                  "construction; the 'gossip' pairwise sampler has no "
                  "weight matrix to optimize — pick a scenario such as "
                  "'static' or 'edge_activation'")

    # -- serialization ------------------------------------------------------
    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        for kw_field in ("model_kw", "topology_kw", "scenario_kw",
                         "partitioner_kw"):
            d[kw_field] = dict(getattr(self, kw_field))
        return d

    @classmethod
    def from_dict(cls, d: Mapping) -> "DFLConfig":
        return cls(**dict(d))

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    def cache_key(self) -> str:
        """Stable 16-hex id of the full setting (benchmark results cache)."""
        blob = json.dumps({"v": _KEY_VERSION, **self.to_dict()},
                          sort_keys=True)
        return hashlib.md5(blob.encode()).hexdigest()[:16]

    def replace(self, **kw) -> "DFLConfig":
        """dataclasses.replace with seed re-derivation: when `seed`
        changes and data_seed/init_seed were following it (equal to the
        old seed) and are not explicitly overridden, they follow the new
        seed instead of freezing at their old resolved values. Control
        fields re-resolve analogously: replacing a deprecated flat knob
        re-derives `control` from the flat triple, and replacing
        `control` drops the stale flat mirror."""
        legacy = [k for k in _LEGACY_ADAPTIVE if k in kw]
        if legacy and "control" not in kw:
            kw["control"] = None          # flat knobs win; struct re-derives
            for k in _LEGACY_ADAPTIVE:
                kw.setdefault(k, getattr(self, k))
        elif "control" in kw and not legacy:
            for k in _LEGACY_ADAPTIVE:
                kw[k] = None              # struct wins; mirror re-derives
        if "seed" in kw:
            if "data_seed" not in kw and self.data_seed == self.seed:
                kw["data_seed"] = None
            if "init_seed" not in kw and self.init_seed == self.seed:
                kw["init_seed"] = None
        return dataclasses.replace(self, **kw)
