"""The serving engine's host spans on the profiler's clock: each tick is
one ``repro.tick`` span holding its phases, read back from a CPU trace.
(`Session`'s ``repro.round`` spans: tests/bench/test_phases.py.)"""
from pathlib import Path

import jax
import numpy as np
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.launch.serving import ServeEngine
from repro.models import transformer as tf


def _spans(trace_dir) -> list:
    """(start_ns, end_ns, name, stats) of every ``repro.`` host span."""
    path = next(Path(trace_dir).rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                         dict(e.stats))
                        for e in line.events if e.name.startswith("repro.")]
    return sorted(out, key=lambda s: (s[0], -s[1]))


def _inside(spans, parent) -> list:
    """Names of the spans that lie within ``parent``, in order of start."""
    return [s[2] for s in spans
            if s is not parent and parent[0] <= s[0] and s[1] <= parent[1]]


def test_serving_tick_spans_nest_once_per_tick(tmp_path):
    cfg = get_config("gemma3-1b").reduced()
    params = tf.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    eng = ServeEngine(params, cfg, n_slots=2, max_len=64, paged=True,
                      page_size=8, prefill_chunk=4)
    warm = eng.submit(rng.integers(0, cfg.vocab_size, 9).astype(np.int32),
                      max_new=2)
    eng.run()                           # compiles outside the trace
    assert eng.requests[warm].done
    for n in (9, 3, 6):
        eng.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                   max_new=3)
    ticks0 = eng.ticks
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    spans = _spans(tmp_path)
    ticks = [s for s in spans if s[2] == "repro.tick"]
    assert [t[3]["step_num"] for t in ticks] == list(
        range(ticks0, eng.ticks))
    assert ticks[0][3]["queued"] == 3 and ticks[0][3]["active"] == 0
    prefills = 0
    for t in ticks:
        inside = _inside(spans, t)
        prefills += inside.count("repro.prefill")
        assert [n for n in inside if n != "repro.prefill"] == [
            "repro.tick.admit", "repro.tick.table", "repro.tick.dispatch",
            "repro.tick.readback", "repro.tick.sample"]
    # every chunk call lies inside its tick's admission
    admits = [s for s in spans if s[2] == "repro.tick.admit"]
    assert prefills == sum(_inside(spans, a).count("repro.prefill")
                           for a in admits)
    # prompts of 9, 3 and 6 tokens prefill 8, 2 and 5 in chunks of 4
    assert prefills == 2 + 1 + 2
    assert len(spans) == len(ticks) * 6 + prefills
