"""The routed-MoE training cell (`deepseek-moe-16b.train.ring8`) at a CPU
size: its kind runs the program's routed experts and agrees with the
plain reference, and its counts of the grouped expert matmuls hold by
hand at the cell's widths."""
import dataclasses
import json
import time

import pytest

from bench import core, expert_counts

CONFIG, TRAFFIC = "deepseek-moe-16b-l4", "train.ring8.s256.moe"


def _tiny_moe_run(seed, seconds):
    """The cell's configuration, traffic and kind with every width and
    length shrunk (8 experts of which 2 chosen, one shared), the chip
    check skipped."""
    import jax
    from repro.configs import get_config

    sizes = dict(hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=4, vocab_size=500, num_hidden_layers=2,
                 n_routed_experts=8, num_experts_per_tok=2,
                 n_shared_experts=1, moe_intermediate_size=32)
    cfg = dict(core.config(CONFIG), **sizes)
    mc = dataclasses.replace(get_config(cfg["repro_arch"]), d_model=64,
                             n_heads=4, n_kv_heads=4, vocab_size=500,
                             n_layers=2, n_experts=8, top_k=2,
                             n_shared_experts=1, moe_d_ff=32)
    mix = json.loads(json.dumps(core.traffic(TRAFFIC)))
    mix.update(seq_len=16, n_clients=4)
    spec = core.benchmark()
    cell = core.cell("deepseek-moe-16b.train.ring8", spec)
    return core.kind(mix["kind"]).Run(
        spec=spec, cell=cell, mix=mix, devs=jax.devices(), seed=seed,
        seconds=seconds, trace=False, t_start=time.perf_counter(),
        require_tpu=False, config=cfg, model_cfg=mc)


def test_moe_cell_kind_follows_the_reference():
    run = _tiny_moe_run(seed=2 ** 32 + 29, seconds=1.0)
    result, checks = run.execute()
    assert result["correct"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    # float32 on the CPU: the routed program and the reference, which
    # runs every expert on every token under its gate, agree to rounding
    for name in ("grad_gap", "change_gap"):
        assert checks[name]["value"] < 1e-5, (name, checks[name])
    assert result["compiles_in_window"] == 0
    counts = run.counts()
    shape = (4, 4, 1, 16)
    assert counts["moe_gmm_flops"] == expert_counts.gmm_flops(run.config,
                                                              *shape)
    assert counts["moe_gmm_bytes"] == expert_counts.gmm_bytes(run.config,
                                                              *shape)
    assert counts["round_flops"] > counts["moe_gmm_flops"] > 0


def test_expert_counts_by_hand_at_the_cell():
    cfg = core.config(CONFIG)
    # 8 clients x 4 steps x 1 x 256 tokens, each through 6 experts' three
    # 2048 x 1408 projections, forward and backward, 4 layers
    flops = expert_counts.gmm_flops(cfg, 8, 4, 1, 256)
    assert flops == 8192 * 6 * 3 * 2048 * 1408 * 2 * 2 * 4
    assert flops == pytest.approx(6.804e12, rel=1e-3)
    # 64 experts x 3 x 2048 x 1408 float32 weights (2.214 GB) a layer, a
    # local step and a pass; 12288 routed rows a step, each read as 2048
    # and 1408 bfloat16 operands and written as float32, three products a
    # pass
    weights = 64 * 3 * 2048 * 1408 * 4
    assert weights == pytest.approx(2.214e9, rel=1e-3)
    row = 2 * (2048 * 2 + 1408 * 4) + 1408 * 2 + 2048 * 4 \
        + 2 * (1408 * 2 + 2048 * 4) + 2048 * 2 + 1408 * 4
    assert expert_counts.gmm_bytes(cfg, 8, 4, 1, 256) == \
        4 * 4 * (2 * weights + 12288 * row)


def test_moe_cell_config_runs_the_registered_widths():
    mc = core.model_config(core.config(CONFIG))
    assert (mc.n_layers, mc.d_model, mc.n_heads, mc.n_kv_heads) == \
        (4, 2048, 16, 16)
    assert (mc.n_experts, mc.top_k, mc.n_shared_experts, mc.moe_d_ff) == \
        (64, 6, 2, 1408)
