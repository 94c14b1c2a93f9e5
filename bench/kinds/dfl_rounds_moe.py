"""DFL training rounds of a routed-MoE configuration: `dfl_rounds` with
the required work of the grouped expert matmuls (bench/expert_counts.py)
added to the counts, for `moe_gmm_roofline`.

Traffic parameters as for `dfl_rounds`.
"""
from __future__ import annotations

from bench import expert_counts
from bench.kinds import dfl_rounds


class Run(dfl_rounds.Run):
    def counts(self) -> dict:
        t = self.mix
        shape = (t["n_clients"], t["local_steps"], t["batch"], t["seq_len"])
        return {**super().counts(),
                "moe_gmm_flops": expert_counts.gmm_flops(self.config, *shape),
                "moe_gmm_bytes": expert_counts.gmm_bytes(self.config, *shape)}
