"""Roofline share of the gossip mix kernel (kernels/gossip_mix.py): the
least time the mix can take, its required bytes (read and write every
client's LoRA factors in float32, plus W) over the HBM peak, divided by
the kernel's device time per round. The mix is bound by bandwidth: its
FLOPs (2*m per element) are far below the compute bound."""

KERNEL = r"^gossip_mix"


def read(record):
    c = record["counts"]
    if c.get("kind") != "train" or not c.get("rounds"):
        return None
    seconds = record["trace"].op_seconds(KERNEL)
    if seconds <= 0:
        return None
    per_round = seconds / c["rounds"]
    least = c["mix_bytes"] / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / per_round
