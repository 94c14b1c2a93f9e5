"""Roofline share of the paged decode attention kernel
(kernels/paged_attention.py): the bytes it must move for the traced
ticks (the live K/V pages of every layer, whole pages, plus q and out,
float32) over the HBM peak, divided by the kernel's device time inside
the decode program. Bound by bandwidth: its FLOPs per byte are ~1."""

from bench import programs


def read(record):
    c = record["counts"]
    if c.get("kind") != "serve" or not c.get("ticks"):
        return None
    seconds = programs.kernel_seconds(record["trace"], programs.PAGED_ATTN)
    if seconds <= 0:
        return None
    least = c["paged_attn_bytes"] / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
