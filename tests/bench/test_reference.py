"""On the CPU at a tiny size, the plain references agree with what
`Session` and `ServingSession` compute on the cells' timed paths, so a
disagreement on the chip is the program's and not the reference's."""


def test_training_reference_follows_session(tiny_run):
    run = tiny_run("qwen2-7b", "train.ring8.s512", seed=2 ** 32 + 11,
                   seconds=1.0)
    result, checks = run.execute()
    assert result["correct"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    # float32 on the CPU: the program and the reference agree to rounding
    for name in ("grad_gap", "change_gap"):
        assert checks[name]["value"] < 1e-5, (name, checks[name])
    assert result["compiles_in_window"] == 0


def test_serving_reference_follows_serving_session(tiny_run):
    run = tiny_run("qwen2-7b", "serve.steady", seed=2 ** 33 + 5,
                   seconds=4.0)
    run.setup()
    compiles = run.compiles.n
    out = run.window(4.0)
    assert run.compiles.n == compiles
    checks = run.check()
    assert out["failed"] == 0 and out["attempted"] == 12
    # every served token is the reference's own best, to rounding
    assert checks["served_logit_gap"]["value"] < 1e-4
    assert checks["checked_tokens_short"]["value"] == 0
    m = out["metrics"]
    assert m["serve_tokens_per_s"]["value"] > 0
    assert m["ttft_p90_ms"]["value"] > 0 and m["itl_p95_ms"]["value"] > 0


def test_moe_training_reference_follows_session(tiny_run):
    run = tiny_run("deepseek-moe-16b", "train.ring8.s256", seed=7,
                   seconds=1.0)
    result, checks = run.execute()
    assert result["correct"]
    for name in ("grad_gap", "change_gap"):
        assert checks[name]["value"] < 1e-5, (name, checks[name])
