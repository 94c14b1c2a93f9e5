"""Harness core: finds cells, configurations, traffic mixes, peaks and
per-layer readers by name, checks the device, counts compilations, and
assembles the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own under ``bench/`` and is found here by
the name ``BENCHMARK.json`` gives it:

  bench/configs/<config>.json     sizes as run, with source and cuts
  bench/traffic/<traffic>.json    parameters of one mix, ``kind`` names
                                  the generator in bench/kinds/<kind>.py
  bench/metrics/<metric>.py       ``read(record) -> float | None``
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import sys
from pathlib import Path
from typing import Callable, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class HarnessError(RuntimeError):
    """A run that must stop before it prints a result."""


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise HarnessError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def benchmark() -> dict:
    return _load_json(ROOT / "BENCHMARK.json")


def cell(name: str, spec: Optional[dict] = None) -> dict:
    spec = spec if spec is not None else benchmark()
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise HarnessError(f"unknown workload {name!r}; known: "
                       f"{[w['name'] for w in spec['workloads']]}")


def config(name: str) -> dict:
    return _load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    mix = _load_json(BENCH / "traffic" / f"{name}.json")
    if "kind" not in mix:
        raise HarnessError(f"traffic {name!r} names no kind")
    return mix


def kind(name: str):
    """The general generator of one kind of traffic (bench/kinds/)."""
    if not (BENCH / "kinds" / f"{name}.py").is_file():
        raise HarnessError(f"unknown traffic kind {name!r}")
    return importlib.import_module(f"bench.kinds.{name}")


def peaks(device_kind: str) -> dict:
    table = _load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise HarnessError(f"device kind {device_kind!r} is not in "
                           f"bench/peaks.json; known: {sorted(table)}")
    return table[device_kind]


def metric_reader(name: str) -> Callable:
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise HarnessError(f"no reader for per-layer metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(cell_name: str, group: str, spec: Optional[dict] = None
               ) -> list[dict]:
    """The `end_to_end` or `per_layer` entries a cell reports."""
    spec = spec if spec is not None else benchmark()
    return [m for m in spec[group]
            if "workloads" not in m or cell_name in m["workloads"]]


# ---------------------------------------------------------------------------
# the model configuration as run
# ---------------------------------------------------------------------------

_WIDTHS = {                      # config.json key -> ModelConfig field
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "n_routed_experts": "n_experts",
    "n_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "moe_d_ff",
}


def model_config(cfg: dict):
    """The program's registered architecture cut to the file's depth.

    Every published size the file states is checked against the
    registered `repro.configs.ModelConfig`; a mismatch is an error, so
    the program runs exactly the widths the file declares."""
    from repro.configs import get_config

    mc = get_config(cfg["repro_arch"])
    for key, field in _WIDTHS.items():
        if key in cfg and getattr(mc, field) != cfg[key]:
            raise HarnessError(
                f"{cfg['repro_arch']}: {key}={cfg[key]} but the program "
                f"registers {field}={getattr(mc, field)}")
    if "n_routed_experts" not in cfg and mc.d_ff != cfg["intermediate_size"]:
        raise HarnessError(f"{cfg['repro_arch']}: intermediate_size")
    if "n_routed_experts" in cfg and (
            mc.router_aux_coef != cfg["aux_loss_alpha"]
            or cfg["norm_topk_prob"] is not True):
        raise HarnessError(f"{cfg['repro_arch']}: routing")
    if mc.qkv_bias != cfg.get("attention_bias", False):
        raise HarnessError(f"{cfg['repro_arch']}: attention_bias")
    lora = cfg["lora"]
    if (mc.lora_rank, mc.lora_alpha, tuple(mc.lora_targets)) != (
            lora["rank"], lora["alpha"], tuple(lora["targets"])):
        raise HarnessError(f"{cfg['repro_arch']}: lora")
    import jax
    if not cfg["matmul_precision"].startswith("default") or \
            jax.config.jax_default_matmul_precision not in (None, "default"):
        raise HarnessError(f"{cfg['repro_arch']}: matmul precision "
                           f"{jax.config.jax_default_matmul_precision!r}")
    return dataclasses.replace(mc, n_layers=cfg["num_hidden_layers"])


# ---------------------------------------------------------------------------
# device and compile accounting
# ---------------------------------------------------------------------------

def require_chips(n: int) -> list:
    """The first ``n`` TPU devices; any other platform, or fewer chips,
    is an error (the benchmark never falls back to the CPU)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise HarnessError(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < n:
        raise HarnessError(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


class CompileCounter:
    """Counts backend compilations and persistent-cache loads, so a run
    can tell whether anything compiled inside its measured window."""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.n = 0

        def listener(event, duration, **_):
            if event in self._EVENTS:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listener)


def enable_compile_cache() -> str:
    """JAX's persistent cache in the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), for every program however small, so
    that only the first run of a cell in a checkout compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------

def metric(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def emit(result: dict, checks: dict) -> None:
    """Print the compared numbers beside their limits as the last lines
    on stderr, and the result as the last line on stdout, with the
    checks under the key that comes last."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), flush=True)
