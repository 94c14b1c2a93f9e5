"""Per-architecture smoke tests (task-mandated): instantiate the REDUCED
variant of each assigned family (<=2 pattern repeats, d_model<=512,
<=4 experts), run one forward and one train step on CPU, assert output
shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.core import build_lora_tree, make_dfl_round, round_masks
from repro.models import transformer as tf
from repro.optim import AdamW

B, S = 2, 16


def _inputs(cfg, key, batch=B, seq=S):
    tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
    frontend = None
    if cfg.n_frontend_tokens:
        frontend = jax.random.normal(
            key, (batch, cfg.n_frontend_tokens, cfg.d_model)) * 0.02
    return tokens, frontend


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_forward(arch, key):
    cfg = get_config(arch).reduced()
    assert cfg.d_model <= 512 and cfg.n_experts <= 4
    assert cfg.n_layers <= 2 * max(len(cfg.pattern), 1)
    params = tf.init_params(key, cfg)
    tokens, frontend = _inputs(cfg, key)
    logits, aux = tf.forward(params, cfg, tokens, frontend=frontend,
                             remat=False)
    assert logits.shape == (B, S, cfg.vocab_padded)
    assert not jnp.isnan(logits).any()
    assert not jnp.isnan(aux)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_train_step(arch, key):
    """One DFL round (the paper's technique) on the reduced config."""
    cfg = get_config(arch).reduced()
    m, local_steps, b = 4, 2, 2
    params = tf.init_params(key, cfg)
    lora = build_lora_tree(jax.random.key(7), params, cfg, n_clients=m)
    assert jax.tree.leaves(lora), f"no LoRA targets found for {arch}"
    opt = AdamW(lr=1e-3)
    opt_state = opt.init(lora)

    def loss_fn(bp, lo, micro):
        return tf.lm_loss(bp, cfg, micro["tokens"], micro["targets"],
                          frontend=micro.get("frontend"), lora=lo)[0]

    round_fn = make_dfl_round(loss_fn, opt, local_steps=local_steps)
    tokens = jax.random.randint(key, (local_steps, m, b, S), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=-1)}
    if cfg.n_frontend_tokens:
        batch["frontend"] = jax.random.normal(
            key, (local_steps, m, b, cfg.n_frontend_tokens,
                  cfg.d_model)) * 0.02
    W = jnp.eye(m) * 0.5 + 0.5 / m   # valid doubly-stochastic mix
    masks = round_masks("tad", 0, 2).as_array()
    lora2, opt2, metrics = jax.jit(round_fn)(params, lora, opt_state,
                                             batch, W, masks)
    assert jnp.isfinite(metrics["loss"])
    # the active block must have moved on at least one leaf
    diffs = jax.tree.map(lambda a, b_: float(jnp.max(jnp.abs(a - b_))),
                         lora, lora2)
    assert max(jax.tree.leaves(diffs)) > 0.0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_decode_step(arch, key):
    cfg = get_config(arch).reduced()
    params = tf.init_params(key, cfg)
    cache = tf.init_cache(cfg, B, 32)
    tok = jax.random.randint(key, (B, 1), 0, cfg.vocab_size)
    logits, cache2 = tf.decode_step(params, cfg, tok, cache)
    assert logits.shape == (B, 1, cfg.vocab_padded)
    assert not jnp.isnan(logits).any()
    # cache advanced
    flat1 = jax.tree_util.tree_flatten_with_path(cache)[0]
    flat2 = {tuple(str(k) for k in p): v
             for p, v in jax.tree_util.tree_flatten_with_path(cache2)[0]}
    for p, v in flat1:
        kp = tuple(str(k) for k in p)
        if kp[-1].endswith("'t'") or "t" == getattr(p[-1], "key", ""):
            assert (flat2[kp] == v + 1).all()


@pytest.mark.parametrize("arch", ["qwen2-7b", "deepseek-moe-16b"])
def test_init_params_draws_each_layer_into_the_stack(arch, key):
    """Scanned layers are drawn straight into their stacked leaves, layer
    g of pattern position j from its own key, as one layer alone draws."""
    import dataclasses

    cfg = dataclasses.replace(get_config(arch).reduced(), n_layers=3)
    params = tf.init_params(key, cfg)
    kG = jax.random.split(key, 5)[2]
    for g in range(cfg.n_groups):
        one = tf._init_layer(jax.random.fold_in(kG, g), cfg, cfg.pattern[0],
                             jnp.float32, False)
        for got, want in zip(jax.tree.leaves(params["groups"][0]),
                             jax.tree.leaves(one)):
            assert got.shape[1:] == want.shape
            np.testing.assert_allclose(got[g], want, rtol=1e-6, atol=1e-7)


def test_init_params_compiles_one_program_for_every_seed(monkeypatch):
    """The stacked layers' draw takes its key as an argument: two seeds
    lower to the same program, so a persistent compile cache serves
    every seed after the first."""
    cfg = get_config("qwen2-7b").reduced()
    lowered = []
    real_jit = jax.jit

    def spy(fn, **kw):
        jitted = real_jit(fn, **kw)

        def call(*args):
            lowered.append(jitted.lower(*args).as_text())
            return jitted(*args)
        return call

    monkeypatch.setattr(jax, "jit", spy)
    tf.init_params(jax.random.key(1), cfg)
    n = len(lowered)
    tf.init_params(jax.random.key(2), cfg)
    assert n == len(cfg.pattern)
    assert lowered[n:] == lowered[:n]
