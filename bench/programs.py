"""Names by which the trace reduction finds the program's compiled
steps and kernels. The decode step is jitted as ``_step`` and the
adapter chunk prefill as ``_step_lora`` (launch/serving.py,
serving/prefill.py); Pallas kernels appear as operations named after
their kernel functions."""
from __future__ import annotations

import re

DECODE = r"^jit__step(\(|$)"
PREFILL = r"^jit__step_lora(\(|$)"
PAGED_ATTN = r"^paged_attn_decode"
SLOT_LORA = r"^slot_lora_matmul"


def decode_runs(tr):
    return tr.module_runs(DECODE)


def prefill_runs(tr):
    return tr.module_runs(PREFILL)


def kernel_seconds(tr, kernel: str, program: str = DECODE) -> float:
    """Device time of a kernel's operations inside a program's runs."""
    rx = re.compile(kernel)
    return sum(e.dur for e in tr.ops_within(tr.module_runs(program))
               if rx.search(e.name))
