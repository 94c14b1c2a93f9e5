"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer readers
need: per-device busy time (the union of operation intervals), every
operation's device time by name, executions of each compiled program,
the benchmark's own host spans, and the device's idle gaps labelled by
the host span open at the time.

Only `jax.profiler.ProfileData` is used to read the file. Device planes
are ``/device:TPU:<n>``; on each, the line ``XLA Ops`` holds one event per
operation and ``XLA Modules`` one per program execution (named like
``jit_round_fn(<hash>)``). An operation event is named by its HLO text,
``%fusion.463 = f32[8,512]{...} fusion(...)``; its instruction name (here
``fusion.463``; a Pallas kernel's is its function's, ``gossip_mix.1``)
is what the readers match. Operations nest: a ``while`` loop's event spans
the operations of its body, so times are summed over leaf operations only.
Host spans are the ``jax.profiler.TraceAnnotation`` events whose names
start with ``bench.``.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


@dataclass
class Event:
    name: str             # instruction or span name
    start: float          # seconds on the trace's clock
    end: float
    text: str = ""        # the full HLO text of an operation

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def label(self) -> str:
        """Instruction name, opcode and result type, layouts stripped."""
        rhs = self.text.split(" = ", 1)[1] if " = " in self.text else ""
        for _ in range(3):
            rhs = re.sub(r"\{[^{}]*\}", "", rhs)
        m = re.match(r"(.*?) ([\w-]+)\(", rhs)
        if not m:
            return self.name
        return f"{self.name} {m.group(2)} -> {m.group(1)}"[:160]


def leaves(events) -> list:
    """The events that contain no other event (a loop's event spans the
    operations of its body)."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt.start >= e.end:
            out.append(e)
    return out


def merge(intervals):
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(intervals, lo: float, hi: float) -> float:
    """Length of the union of disjoint ``intervals`` inside [lo, hi]."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals)


@dataclass
class Reduced:
    ops: dict             # device -> [Event]
    modules: dict         # device -> [Event]
    spans: list           # [Event] host spans of the benchmark
    t0: float
    t1: float

    def __post_init__(self):
        self.leaf_ops = {d: leaves(evs) for d, evs in self.ops.items()}

    @property
    def devices(self):
        return sorted(self.ops)

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def busy(self, dev) -> list:
        return merge((e.start, e.end) for e in self.ops[dev]
                     if e.end > self.t0 and e.start < self.t1)

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.ops:
            return 0.0
        return sum(overlap(self.busy(d), self.t0, self.t1)
                   for d in self.devices) / len(self.ops)

    def op_seconds(self, pattern: str, dev=None) -> float:
        """Device time of leaf operations whose instruction name matches
        ``pattern`` (a regular expression), averaged over devices."""
        rx = re.compile(pattern)
        devs = [dev] if dev is not None else self.devices
        return sum(e.dur for d in devs for e in self.leaf_ops[d]
                   if rx.search(e.name)) / max(len(devs), 1)

    def module_runs(self, pattern: str, dev=None) -> list:
        rx = re.compile(pattern)
        d = dev if dev is not None else self.devices[0]
        return [e for e in self.modules.get(d, []) if rx.search(e.name)]

    def ops_within(self, runs, dev=None) -> list:
        """Operations of device ``dev`` that lie inside the given program
        executions."""
        d = dev if dev is not None else self.devices[0]
        wins = merge((r.start, r.end) for r in runs)
        out, i = [], 0
        for e in self.leaf_ops[d]:
            while i < len(wins) and wins[i][1] < e.start:
                i += 1
            if i < len(wins) and wins[i][0] <= e.start <= wins[i][1]:
                out.append(e)
        return out

    def gaps(self, dev=None) -> list:
        """Idle intervals of the device inside the window."""
        d = dev if dev is not None else self.devices[0]
        out, cur = [], self.t0
        for s, e in self.busy(d):
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if cur < self.t1:
            out.append((cur, self.t1))
        return out

    def span_at(self, t: float) -> str:
        """The innermost benchmark span open at time t."""
        best = None
        for s in self.spans:
            if s.start <= t <= s.end and (best is None or s.dur < best.dur):
                best = s
        return best.name if best is not None else "no bench span"

    def breakdown(self, n: int = 10) -> dict:
        per_op = defaultdict(float)
        for d in self.devices:
            for e in self.leaf_ops[d]:
                per_op[e.label] += e.dur / len(self.devices)
        top = sorted(per_op.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[self.span_at((s + e) / 2), e - s]
                              for s, e in gaps]}


def _events(line, ops: bool = False):
    for e in line.events:
        name, text = e.name, ""
        if ops and " = " in name:
            text, name = name, name.split(" = ", 1)[0].lstrip("%")
        yield Event(name, e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9, text)


def reduce(path, n_devices: int = 1) -> Reduced:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops, modules, spans = {}, {}, []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if dev >= n_devices:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] = list(_events(line, ops=True))
                elif line.name == MODULES_LINE:
                    modules[dev] = list(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in _events(line)
                             if e.name.startswith(SPAN_PREFIX))
    for dev in range(n_devices):
        ops.setdefault(dev, [])
    # the window opens with the first benchmark span and closes when the
    # last span or device operation has ended (the device may finish
    # after the host's last span closes)
    every = [e for evs in ops.values() for e in evs] + spans
    t0 = min((e.start for e in (spans or every)), default=0.0)
    t1 = max((e.end for e in every), default=0.0)
    return Reduced(ops=ops, modules=modules, spans=spans, t0=t0, t1=t1)

