"""Required-work counts against hand counts, the traffic generator's
determinism and open-loop timing, and the harness's lookups by name."""
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import core, counts
from bench.kinds import serve_open_loop as so


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def test_qwen_layer_by_hand():
    cfg = core.config("qwen2-7b")
    p = counts.layer_matmul_params(cfg)
    # q 3584x3584, k and v 3584x512 (4 kv heads x 128)
    assert p["qkv"] == 3584 * 3584 + 2 * 3584 * 512
    # o 3584x3584, gate/up/down 3584x18944
    assert p["rest"] == 3584 * 3584 + 3 * 3584 * 18944
    # LoRA r=8 on wq (3584 -> 3584) and wv (3584 -> 512), 4 layers
    assert counts.lora_params(cfg) == 4 * (3584 * 8 + 8 * 3584
                                           + 3584 * 8 + 8 * 512)


def test_deepseek_layer_by_hand():
    cfg = core.config("deepseek-moe-16b")
    p = counts.layer_matmul_params(cfg)
    assert p["qkv"] == 3 * 2048 * 2048
    # o, then 6 routed + 2 shared experts of width 1408, router 2048x64
    assert p["rest"] == 2048 * 2048 + 3 * 2048 * 1408 * 8 + 2048 * 64


def test_round_flops_by_hand():
    cfg = {"hidden_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 1, "intermediate_size": 16,
           "vocab_size": 10, "num_hidden_layers": 1,
           "lora": {"rank": 2, "targets": ["wq", "wv"]}}
    # per token: qkv 8*(8+4+4)=128, rest 8*8+3*8*16=448, head 80
    fwd = 2 * (128 + 448 + 80)
    bwd = 2 * (128 + 448 + 80 - 128)
    lora_fwd = 2 * ((8 * 2 + 2 * 8) + (8 * 2 + 2 * 4))
    lora_grad = 2 * (2 * 8 + 2 * 4)
    attn = 3 * 2 * 4 * 4 * 8          # S=4: scores and values, fwd + bwd
    per_seq = 4 * (fwd + bwd + lora_fwd + lora_grad) + attn
    assert counts.round_flops(cfg, m=2, ls=3, b=1, S=4) == 2 * 3 * per_seq
    assert counts.mix_bytes(cfg, 2) == 2 * 2 * 56 * 4 + 4 * 4


def test_decode_counts_by_hand():
    cfg = core.config("qwen2-7b")
    one = counts.decode_flops(cfg, [1])
    two = counts.decode_flops(cfg, [1, 17])
    # a second slot adds its weights pass and 16 more keys of attention
    assert two - one == pytest.approx(one + 4.0 * 4 * 3584 * 16)
    # 17 positions fill two pages of 16: K and V, 4 layers, 512 floats
    b = counts.paged_attn_bytes(cfg, [17], 16)
    assert b == 4 * (2 * 2 * 16 * 512 * 4 + 2 * 3584 * 4)
    c = counts.slot_lora_cost(cfg, 2)
    assert c["bytes"] > 4 * 4 * 3584 * (3584 + 512)
    assert c["flops"] == 4 * 2 * 2 * ((3584 * 3584 + 3584 * 8 + 8 * 3584)
                                      + (3584 * 512 + 3584 * 8 + 8 * 512))


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

def _mix():
    return core.traffic("serve.steady")


def test_same_seed_same_requests():
    a = so.plan(_mix(), 10.0, 2 ** 33 + 7, 1000)
    b = so.plan(_mix(), 10.0, 2 ** 33 + 7, 1000)
    assert [(r.due, r.max_new, r.row) for r in a] == \
        [(r.due, r.max_new, r.row) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_reorder_the_same_work():
    mix = _mix()
    a = so.plan(mix, 20.0, 1, 1000)
    b = so.plan(mix, 20.0, 2, 1000)
    assert len(a) == len(b) == int(mix["rate_per_s"] * 20)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[-1].due == pytest.approx(b[-1].due, rel=0.2)
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    assert all(r.row in range(-1, mix["adapters"]["clients"]) for r in a)


def test_zipf_popularity():
    ranks = so._zipf_set(9, 1.1, 9000)
    share = np.bincount(ranks, minlength=9) / 9000
    p = 1 / np.arange(1, 10) ** 1.1
    assert np.allclose(share, p / p.sum(), atol=2e-3)


class _FakeEngine:
    """Serves one token per tick to every admitted request; one tick can
    be told to stall."""

    def __init__(self, stall_tick=None, stall_s=0.0):
        self.requests, self.slots = {}, []
        self.scheduler = SimpleNamespace(n_queued=0)
        self.stall_tick, self.stall_s, self.n = stall_tick, stall_s, 0

    def submit(self, prompt, adapter=None, max_new=1):
        rid = len(self.requests)
        self.requests[rid] = SimpleNamespace(prompt=prompt, tokens_out=[],
                                             done=False, max_new=max_new)
        self.slots.append(SimpleNamespace(req=self.requests[rid]))
        return rid

    def tick(self):
        if self.n == self.stall_tick:
            time.sleep(self.stall_s)
        self.n += 1
        for s in self.slots:
            r = s.req
            if r is not None and not r.done:
                r.tokens_out.append(0)
                r.done = len(r.tokens_out) >= r.max_new
                if r.done:
                    s.req = None


def _open_loop(stall_tick=None, stall_s=0.0):
    mix = json.loads(json.dumps(_mix()))
    mix.update(rate_per_s=20.0, drain_s=5.0)
    mix["prompt"].update(median=4, min=2, max=8)
    mix["output"].update(median=2, min=1, max=3)
    eng = _FakeEngine(stall_tick, stall_s)
    run = so.Run.__new__(so.Run)
    run.mix, run.seeds = mix, {"traffic": 5}
    run.model_cfg = SimpleNamespace(vocab_size=100)
    run.serving = run.engine = eng
    out = run.window(1.0)
    return out, run


def test_latency_runs_from_the_due_time():
    base, _ = _open_loop()
    stalled, run = _open_loop(stall_tick=3, stall_s=0.4)
    assert base["failed"] == stalled["failed"] == 0
    assert base["attempted"] == stalled["attempted"] == 20
    # requests that fell due while the engine stalled waited for it
    assert stalled["metrics"]["ttft_p90_ms"]["value"] > 200.0
    assert base["metrics"]["ttft_p90_ms"]["value"] < 100.0


# ---------------------------------------------------------------------------
# lookups by name
# ---------------------------------------------------------------------------

def test_every_cell_resolves_by_name():
    spec = core.benchmark()
    for c in spec["configs"]:
        assert (core.ROOT / c["file"]).is_file()
        core.config(c["name"])
    for w in spec["workloads"]:
        mix = core.traffic(w["traffic"])
        assert hasattr(core.kind(mix["kind"]), "Run")
        assert core.metrics_of(w["name"], "per_layer", spec)
        assert len(core.metrics_of(w["name"], "end_to_end", spec)) >= 2
    for m in spec["per_layer"]:
        assert callable(core.metric_reader(m["name"]))


def test_unknown_names_are_refused():
    with pytest.raises(core.HarnessError):
        core.peaks("TPU v99")
    with pytest.raises(core.HarnessError):
        core.cell("no.such.cell")
    with pytest.raises(core.HarnessError):
        core.metric_reader("no_such_metric")
    assert core.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_model_config_widths_match_the_program():
    mc = core.model_config(core.config("qwen2-7b"))
    assert (mc.n_layers, mc.d_model, mc.n_kv_heads, mc.d_ff) == \
        (4, 3584, 4, 18944)
    bad = dict(core.config("qwen2-7b"), hidden_size=4096)
    with pytest.raises(core.HarnessError):
        core.model_config(bad)
