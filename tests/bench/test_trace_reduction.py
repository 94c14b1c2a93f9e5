"""bench/trace.py on a small trace recorded on a TPU v5e chip
(fixtures/small.xplane.pb): two runs of a jitted matmul inside a
``bench.round`` span, then a ``bench.tick`` span that sleeps 30 ms on
the host before one run of the Pallas gossip-mix kernel. Each quantity
is checked against a brute-force reading of the raw events."""
from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).parent / "fixtures" / "small.xplane.pb"


@pytest.fixture(scope="module")
def raw():
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(FIXTURE))
    ops, spans = [], []
    for plane in data.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9, e.name)
                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9, e.name)
                          for e in line.events
                          if e.name.startswith("bench.")]
    return ops, spans


@pytest.fixture(scope="module")
def red():
    return trace.reduce(FIXTURE)


def _busy_by_grid(ops, t0, t1, step=1e-7):
    """Busy time by marking a fine grid of instants (independent of the
    interval merge under test)."""
    n = int((t1 - t0) / step) + 1
    hit = bytearray(n)
    for s, e, _ in ops:
        a, b = max(s, t0), min(e, t1)
        for i in range(int((a - t0) / step), int((b - t0) / step)):
            hit[i] = 1
    return sum(hit) * step


def test_spans_and_window(red, raw):
    _, spans = raw
    assert sorted(s.name for s in red.spans) == ["bench.round",
                                                  "bench.tick"]
    assert red.t0 == pytest.approx(min(s for s, _, _ in spans))
    assert red.window_s >= max(e for _, e, _ in spans) - red.t0


def test_busy_union_and_idle_share(red, raw):
    ops, _ = raw
    want = _busy_by_grid(ops, red.t0, red.t1)
    assert red.busy_s == pytest.approx(want, abs=2e-6)
    idle = 1 - red.busy_s / red.window_s
    # the 30 ms host sleep leaves the device idle for most of the window
    assert 0.3 < idle < 1.0


def test_kernel_and_program_times(red, raw):
    ops, _ = raw
    leaves = [o for o in ops if not any(
        p is not o and p[0] <= o[0] and o[1] <= p[1] and p[1] - p[0] >
        o[1] - o[0] for p in ops)]
    mix = sum(e - s for s, e, n in leaves
              if n.split(" = ")[0].lstrip("%").startswith("gossip_mix"))
    assert mix > 0
    assert red.op_seconds(r"^gossip_mix") == pytest.approx(mix, rel=1e-9)
    runs = red.module_runs(r"^jit__lambda\(")
    assert len(runs) == 3
    names = [r.name for r in runs]
    matmul_runs = [r for r in runs if names.count(r.name) == 2]
    assert len(matmul_runs) == 2
    inside = red.ops_within(matmul_runs)
    assert inside and all(
        any(r.start <= e.start <= r.end for r in matmul_runs)
        for e in inside)


def test_gap_attributed_to_the_open_span(red):
    gaps = red.breakdown()["idle_gaps"]
    label, seconds = gaps[0]
    assert label == "bench.tick"
    assert 0.025 < seconds < 0.05
    top = red.breakdown()["device_ops"]
    assert top and all(t > 0 for _, t in top)
