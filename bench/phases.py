"""The compiled DFL round by phase and scope, and the program's own host
spans, read from a profiler trace.

The device trace names an operation by its HLO instruction only
(``fusion.463``; bench/trace.py). The text of the compiled program,
``jit(f).lower(...).compile().as_text()``, gives every instruction the
JAX name stack it was traced under as ``metadata={op_name="..."}``, and
autodiff marks the phase in it: under ``jax.value_and_grad`` the forward
of a scope ``s`` reads ``jvp(s)``, its backward ``transpose(jvp(s))``,
and the forward that a ``jax.checkpoint`` body recomputes for the
backward reads ``checkpoint/rematted_computation``. The round names its
scopes (core/fedtrain.py: ``loss`` around the objective, ``opt`` around
the optimizer update, ``mix`` around the gossip mix; models/transformer.py:
``head`` around the unembedding and cross-entropy, ``attn`` and ``ffn``
around the sublayers). So joining the trace's instruction names with the
compiled text gives every leaf operation of a round a phase, with no
change to the compiled code.

The program's host spans are the ``jax.profiler`` annotations whose names
start with ``repro.``: ``repro.round`` per `Session` round (with its
``step_num`` and ``tokens``) and its children ``repro.round.joins``,
``.batch``, ``.topology``, ``.put``, ``.dispatch``, ``.observe``;
``repro.tick`` per serving engine tick and its children; ``repro.prefill``
per chunk-prefill call.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Optional

from bench.trace import Event

ROUND = r"^jit_round_fn(\(|$)"
PHASES = ("fwd", "bwd", "remat", "opt", "mix", "unscoped")
COVERAGE = 0.99          # least share of the round's op time the names cover
SPAN_PREFIX = "repro."

_INSTR = re.compile(r'^\s*(?:ROOT )?%([^\s=]+) = ([^\n]*)$', re.M)
_OP_NAME = re.compile(r'\bmetadata=\{[^\n]*?\bop_name="((?:[^"\\]|\\.)*)"')
_WRAP = re.compile(r"^(?:[\w-]+\()+([^()]*)\)+$")


def op_names(hlo_text: str) -> dict:
    """{instruction name: op_name} of every instruction in a compiled
    program's text; ``""`` for one that carries no op_name (XLA inserts
    some, such as the bfloat16 casts of float32 weights and the halves
    of asynchronous copies; they classify as ``unscoped``)."""
    return {name: (m.group(1) if (m := _OP_NAME.search(rhs)) else "")
            for name, rhs in _INSTR.findall(hlo_text)}


def scopes(op_name: str) -> list:
    """The parts of an op_name with transforms unwrapped:
    ``a/transpose(jvp(loss))/head/dot`` -> ``[a, loss, head, dot]``."""
    return [m.group(1) if (m := _WRAP.match(p)) else p
            for p in op_name.split("/")]


def phase(op_name: str) -> str:
    """``remat``, ``bwd``, ``opt``, ``mix``, ``fwd`` or ``unscoped``, in
    that order of precedence."""
    if "rematted_computation" in op_name:
        return "remat"
    if "transpose(" in op_name:
        return "bwd"
    parts = scopes(op_name)
    if "opt" in parts:
        return "opt"
    if "mix" in parts:
        return "mix"
    if "jvp(" in op_name:
        return "fwd"
    return "unscoped"


def under(op_name: str, scope: str) -> bool:
    """Whether the op was traced inside ``jax.named_scope(scope)``, in
    any phase."""
    return scope in scopes(op_name)


def round_phases(red, names: dict, program: str = ROUND
                 ) -> Optional[dict]:
    """Device seconds per execution of the round program, by phase and
    for the ``head`` scope, from a reduced trace (bench/trace.py) and the
    program's op names.

    None when the trace holds no execution, when the compiled text names
    less than `COVERAGE` of the executions' leaf-operation time (it is
    then another program's), or when the round carries none of its
    scopes: no number is attributed to the wrong operations."""
    runs = red.module_runs(program)
    if not runs:
        return None
    ops = red.ops_within(runs)
    total = sum(e.dur for e in ops)
    named = [(e, names[e.name]) for e in ops if e.name in names]
    covered = sum(e.dur for e, _ in named)
    if total <= 0 or covered < COVERAGE * total or \
            not any(under(n, "loss") for _, n in named):
        return None
    n = len(runs)
    seconds = dict.fromkeys(PHASES, 0.0)
    head = 0.0
    for e, name in named:
        seconds[phase(name)] += e.dur / n
        if under(name, "head"):
            head += e.dur / n
    return {"executions": n, "total": total / n, "coverage": covered / total,
            "seconds": seconds, "head": head}


@dataclass
class Span(Event):
    stats: dict = field(default_factory=dict)


def program_spans(path) -> list:
    """The program's host spans in a trace file (``.xplane.pb``), each
    with its stats (``step_num``, ``tokens``, ...), in order of start."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend(Span(e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9,
                            stats=dict(e.stats))
                       for e in line.events
                       if e.name.startswith(SPAN_PREFIX))
    return sorted(out, key=lambda s: (s.start, -s.end))
