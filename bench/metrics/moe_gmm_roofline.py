"""Roofline share of the grouped expert matmuls (kernels/expert_matmul.py:
`moe_gmm` forward, `moe_gmm_t` backward to the rows): the least time their
required work can take, the larger of FLOPs over the bf16 peak and bytes
over the HBM peak (bench/expert_counts.py), divided by the kernels' device
time per round. The rematerialised forward runs the kernels a second
time and is not required work, so it counts in the time only. Every
event of the kernels counts, not only leaf operations: a kernel holds
no operation of its own, and one that an asynchronous copy overlaps is
no leaf of the trace."""
import re

KERNEL = re.compile(r"^moe_gmm")


def _seconds(trace) -> float:
    """Device time of the kernels' events, averaged over devices."""
    devs = trace.devices
    return sum(e.dur for d in devs for e in trace.ops[d]
               if KERNEL.search(e.name)) / max(len(devs), 1)


def read(record):
    c = record["counts"]
    if c.get("kind") != "train" or not c.get("rounds") \
            or "moe_gmm_bytes" not in c:
        return None
    seconds = _seconds(record["trace"])
    if seconds <= 0:
        return None
    per_round = seconds / c["rounds"]
    peaks = record["peaks"]
    least = max(c["moe_gmm_flops"] / peaks["flops_bf16"],
                c["moe_gmm_bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / per_round
