"""Each cell's comparison catches the faults its timed path can have,
and its control (the reference computed in bfloat16, the precision below
the float32 the configurations state) fails the cell's limits. The runs
skip the look for a chip and drive everything else at a CPU size, with
the timed path broken underneath."""
import jax
import jax.numpy as jnp
import numpy as np

from bench.kinds.base import derived_seeds
from bench.kinds.dfl_rounds import rel_gaps
from bench.reference import serve as ref_serve, train as ref_train


def _fails(result, checks, *names):
    assert not result["correct"]
    assert any(checks[n]["value"] > checks[n]["limit"] for n in names), \
        checks


def test_round_that_returns_its_state_unchanged(tiny_run, monkeypatch):
    import repro.api.session as session

    real = session.build_round

    def broken(*a, **kw):
        step = real(*a, **kw)

        def round_fn(base, lora, opt, batch, W, masks):
            copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
            _, _, metrics = step(base, copy(lora), copy(opt), batch, W,
                                 masks)
            return lora, opt, metrics
        return round_fn
    monkeypatch.setattr(session, "build_round", broken)
    run = tiny_run("qwen2-7b", "train.ring8.s512", seed=31, seconds=0.5)
    _fails(*run.execute(), "grad_gap", "change_gap")


def test_round_that_drops_half_the_batch(tiny_run, monkeypatch):
    import repro.models.transformer as tf

    real = tf.lm_loss

    def half(params, cfg, tokens, targets, *, lora=None, **kw):
        h = tokens.shape[0] // 2
        keep = lambda x: x[..., :h, :, :]  # noqa: E731
        (loss, aux), _ = real(params, cfg, tokens[:h], targets[:h],
                              lora=jax.tree.map(keep, lora), **kw)
        _, vec = real(params, cfg, tokens, targets, lora=lora, **kw)
        return (loss, aux), jax.lax.stop_gradient(vec)
    monkeypatch.setattr(tf, "lm_loss", half)
    run = tiny_run("qwen2-7b", "train.ring8.s512", seed=32, seconds=0.5)
    _fails(*run.execute(), "grad_gap", "change_gap")


def test_served_token_altered_where_it_is_produced(tiny_run, monkeypatch):
    import repro.models.transformer as tf

    real = tf.decode_step

    def altered(params, cfg, tokens, cache, **kw):
        logits, cache = real(params, cfg, tokens, cache, **kw)
        top = jnp.argmax(logits[..., :cfg.vocab_size], -1)
        other = (top + 1) % cfg.vocab_size
        bump = jax.nn.one_hot(other, logits.shape[-1]) * 1e3
        return logits + bump, cache
    monkeypatch.setattr(tf, "decode_step", altered)
    run = tiny_run("qwen2-7b", "serve.steady", seed=33, seconds=3.0)
    _fails(*run.execute(), "served_logit_gap")


def _train_gaps(a, b):
    loss = float(np.max(np.abs(a["loss"] - b["loss"]) / np.abs(b["loss"])))
    return {"loss_gap": loss, "grad_gap": rel_gaps(a["grad"], b["grad"]),
            "change_gap": rel_gaps(a["change"], b["change"])}


def test_training_control_fails(tiny_run):
    run = tiny_run("qwen2-7b", "train.ring8.s512", seed=34, seconds=0.5)
    seeds = derived_seeds(34, ("init", "topology", "data"))
    rounds = run.mix["check_rounds"]
    ref = ref_train.run_rounds(run.config, run.mix, seeds, rounds)
    low = ref_train.run_rounds(run.config, run.mix, seeds, rounds,
                               dtype=jnp.bfloat16)
    gaps = _train_gaps(low, ref)
    # the program in float32 on the CPU reads ~1e-7 on each (see
    # test_reference); bfloat16 reads orders of magnitude above it
    assert min(gaps.values()) > 1e-4, gaps


def test_serving_control_fails(tiny_run):
    run = tiny_run("qwen2-7b", "serve.steady", seed=35, seconds=3.0)
    run.setup()
    run.window(3.0)
    sample = run._sample()
    run.serving = run.engine = None
    eng = run.mix["engine"]
    got = ref_serve.served_gaps(run.config, run.seeds["init"],
                                run.seeds["adapters"],
                                run.mix["adapters"]["clients"], sample,
                                eng["max_len"], run.mix["output"]["max"],
                                control=True)
    # served tokens are the reference's best; bfloat16 picks others
    assert got["gap"] < 1e-4
    assert got["control_gap"] > 100 * max(got["gap"], 1e-5), got
