"""Puts the checkout root on the path so the tests import `bench`, and
builds benchmark runs at a CPU size."""
import dataclasses
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = str(Path(__file__).resolve().parents[2])
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=128, vocab_size=500, num_hidden_layers=2)


def _tiny_run(config, traffic, seed, seconds):
    """A run of a configuration under a traffic mix at a CPU size: every
    width and length shrunk, the chip check skipped."""
    import jax
    from bench import core
    from repro.configs import get_config

    spec = core.benchmark()
    cell = {"name": f"{config}.{traffic}", "config": config,
            "traffic": traffic, "chips": 1}
    cfg = dict(core.config(config), **TINY)
    mc = dataclasses.replace(get_config(cfg["repro_arch"]), d_model=64,
                             n_heads=4, n_kv_heads=2, d_ff=128,
                             vocab_size=500, n_layers=2)
    if "n_routed_experts" in cfg:
        cfg.update(n_routed_experts=4, num_experts_per_tok=2,
                   n_shared_experts=1, moe_intermediate_size=32)
        mc = dataclasses.replace(mc, n_experts=4, top_k=2,
                                 n_shared_experts=1, moe_d_ff=32)
    mix = json.loads(json.dumps(core.traffic(cell["traffic"])))
    if mix["kind"] == "dfl_rounds":
        mix.update(seq_len=16, n_clients=4)
    else:
        mix["engine"].update(n_slots=4, prefill_chunk=16, max_len=96)
        mix["prompt"].update(median=24, min=4, max=64)
        mix["output"].update(median=8, min=2, max=32)
        mix.update(rate_per_s=3.0)
        mix["check"]["min_tokens"] = 30
    driver = core.kind(mix["kind"])
    return driver.Run(spec=spec, cell=cell, mix=mix, devs=jax.devices(),
                      seed=seed, seconds=seconds, trace=False,
                      t_start=time.perf_counter(), require_tpu=False,
                      config=cfg, model_cfg=mc)


@pytest.fixture
def tiny_run():
    return _tiny_run
