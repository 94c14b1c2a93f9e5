"""The paper's primary contribution: TAD-LoRA — topology-aware decentralized
alternating LoRA (Algorithm 1) plus baselines, topologies, and theory
diagnostics."""
from repro.core.alternating import (METHODS, RoundMasks, phase_is_a,
                                    round_masks, schedule)
from repro.core.diagnostics import consensus_stats, effective_update_norm
from repro.core.fedtrain import make_dfl_round, make_microbatches
from repro.core.lora import (build_lora_tree, client_mean, client_slice,
                             lora_specs, merge_lora, param_count,
                             shard_lora_tree, target_names)
from repro.core.mixing import (MixPlan, build_mix_plan, get_mix_plan,
                               mix_leaf, mix_tree, mix_tree_planned,
                               plan_builds)
from repro.core.topology import (Topology, make_topology,
                                 optimal_switching_interval,
                                 optimal_switching_interval_edge_activation,
                                 sample_mixing_matrix, lambda2)

__all__ = [
    "METHODS", "RoundMasks", "phase_is_a", "round_masks", "schedule",
    "consensus_stats", "effective_update_norm",
    "make_dfl_round", "make_microbatches",
    "build_lora_tree", "client_mean", "client_slice", "lora_specs",
    "merge_lora", "param_count", "shard_lora_tree", "target_names",
    "MixPlan", "build_mix_plan", "get_mix_plan",
    "mix_leaf", "mix_tree", "mix_tree_planned", "plan_builds",
    "Topology", "make_topology", "optimal_switching_interval",
    "optimal_switching_interval_edge_activation", "sample_mixing_matrix",
    "lambda2",
]
