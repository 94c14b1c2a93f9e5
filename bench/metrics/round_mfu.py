"""Model FLOP/s utilisation of the DFL round: the round's required FLOPs
(bench/counts.py: no rematerialised forward, no frozen weight gradients,
active experts only) times the rounds completed in the traced window,
over the window, the chips and the chip's peak bf16 FLOP/s."""


def read(record):
    c = record["counts"]
    if c.get("kind") != "train" or not c.get("rounds"):
        return None
    flops = c["round_flops"] * c["rounds"]
    peak = record["peaks"]["flops_bf16"] * record["chips"]
    return 100.0 * flops / (record["window_s"] * peak)
