"""Roofline share of the slot-LoRA matmul kernel
(kernels/lora_matmul.py::slot_lora_matmul) in the decode program: per
traced tick the least time, the larger of its required FLOPs over the
peak bf16 FLOP/s and its required bytes (W once, each active slot's A/B
rows, x and out) over the HBM peak, summed and divided by the kernel's
device time. At decode batch sizes the bytes bound it: W dominates."""

from bench import programs


def read(record):
    c = record["counts"]
    if c.get("kind") != "serve" or not c.get("ticks"):
        return None
    seconds = programs.kernel_seconds(record["trace"], programs.SLOT_LORA)
    if seconds <= 0:
        return None
    pk = record["peaks"]
    least = sum(max(x["flops"] / pk["flops_bf16"],
                    x["bytes"] / pk["hbm_bytes_per_s"])
                for x in c["slot_lora"])
    return 100.0 * least / seconds
