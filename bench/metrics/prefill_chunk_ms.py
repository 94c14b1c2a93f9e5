"""Mean device time of one execution of the chunk-prefill program
(serving/prefill.py -> models/attention.py::attn_chunk_paged)."""

from bench import programs


def read(record):
    if record["counts"].get("kind") != "serve":
        return None
    runs = programs.prefill_runs(record["trace"])
    if not runs:
        return None
    return 1e3 * sum(r.dur for r in runs) / len(runs)
