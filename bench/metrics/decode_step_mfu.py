"""FLOP utilisation of the decode step (models/transformer.py::decode_step
with per-slot adapters): the required FLOPs of every traced tick's active
slots (bench/counts.py) over the device time of the decode program's
executions times the chip's peak bf16 FLOP/s."""

from bench import programs


def read(record):
    c = record["counts"]
    if c.get("kind") != "serve" or not c.get("ticks"):
        return None
    runs = programs.decode_runs(record["trace"])
    seconds = sum(r.dur for r in runs)
    if seconds <= 0:
        return None
    peak = record["peaks"]["flops_bf16"]
    return 100.0 * c["decode_flops"] / (seconds * peak)
