"""What every kind of run shares: seeds, the traced window, the reduction
of the trace to per-layer metrics, and the result line."""
from __future__ import annotations

import shutil
import time
from contextlib import contextmanager
from pathlib import Path

import numpy as np

from bench import core, trace as trace_mod


def derived_seeds(seed: int, names) -> dict:
    """Independent 31-bit seeds for each named stream, drawn from the
    run's seed (which may exceed 32 bits)."""
    state = np.random.SeedSequence(int(seed)).generate_state(len(names))
    return {n: int(v) & 0x7FFFFFFF for n, v in zip(names, state)}


class RunBase:
    """One run of one cell. A kind subclasses it and implements
    `setup`, `window` and `check`."""

    TRACE_SECONDS = 8.0          # length of the traced steady window

    def __init__(self, *, spec, cell, mix, devs, seed, seconds, trace,
                 t_start, require_tpu=True,
                 config=None, model_cfg=None):
        self.spec, self.cell, self.mix = spec, cell, mix
        self.devs, self.seed, self.seconds = devs, seed, seconds
        self.trace, self.t_start = trace, t_start
        self.config = config if config is not None \
            else core.config(cell["config"])
        self.model_cfg = model_cfg if model_cfg is not None \
            else core.model_config(self.config)
        self.peaks = core.peaks(devs[0].device_kind) if require_tpu \
            else None
        self.compiles = core.CompileCounter()

    # -- the kind's parts ----------------------------------------------------
    def setup(self) -> None:
        raise NotImplementedError

    def window(self, seconds: float) -> dict:
        """Run the measured window; returns end-to-end metrics (as
        `core.metric` dicts) plus "attempted" and "failed"."""
        raise NotImplementedError

    def check(self) -> dict:
        """After the window: free the program's state, run the reference,
        return {name: {"value", "limit"}}."""
        raise NotImplementedError

    def counts(self) -> dict:
        """Required work for the per-layer readers (bench/counts.py)."""
        return {}

    # -- driving -------------------------------------------------------------
    def execute(self):
        self.setup()
        setup_s = time.perf_counter() - self.t_start
        before = self.compiles.n
        if self.trace:
            out, record = self._traced_window()
        else:
            out = self.window(self.seconds)
            record = None
        in_window = self.compiles.n - before
        device = core.device_info(self.devs)
        checks = self.check()
        correct = all(c["value"] <= c["limit"] for c in checks.values())
        result = {"correct": bool(correct), "attempted": out["attempted"],
                  "failed": out["failed"],
                  # compilations or cache loads inside the window: 0 when
                  # set-up warmed every shape the window uses
                  "compiles_in_window": in_window}
        if self.trace:
            result["metrics"] = self._per_layer(record)
            device["busy_s"] = record["busy_s"]
            device["window_s"] = record["window_s"]
            result["breakdown"] = record["breakdown"]
        else:
            names = [m["name"] for m in core.metrics_of(
                self.cell["name"], "end_to_end", self.spec)]
            metrics = {"setup_s": core.metric(setup_s, "s")}
            for n in names:
                if n != "setup_s":
                    metrics[n] = out["metrics"][n]
            result["metrics"] = metrics
        result["device"] = device
        return result, checks

    def _traced_window(self):
        import jax

        tdir = core.ROOT / ".bench_trace"
        shutil.rmtree(tdir, ignore_errors=True)
        seconds = min(self.seconds, self.TRACE_SECONDS)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        t0 = time.perf_counter()
        try:
            out = self.window(seconds)
        finally:
            jax.profiler.stop_trace()
        window_s = time.perf_counter() - t0
        path = next(Path(tdir).rglob("*.xplane.pb"))
        red = trace_mod.reduce(path, n_devices=len(self.devs))
        shutil.rmtree(tdir, ignore_errors=True)
        record = {"trace": red, "window_s": red.window_s or window_s,
                  "busy_s": red.busy_s, "counts": self.counts(),
                  "peaks": self.peaks,
                  "chips": len(self.devs),
                  "breakdown": red.breakdown()}
        return out, record

    def _per_layer(self, record) -> dict:
        got = {}
        for m in core.metrics_of(self.cell["name"], "per_layer", self.spec):
            value = core.metric_reader(m["name"])(record)
            if value is not None:
                got[m["name"]] = core.metric(value, m["unit"])
        return got

    @contextmanager
    def span(self, name: str):
        """A host span the trace reduction attributes idle gaps to."""
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield
