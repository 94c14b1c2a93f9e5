"""Routed MoE: the grouped expert matmul (kernels/expert_matmul.py, in
Pallas interpret mode, and XLA's ragged_dot path) against a plain
per-expert loop; the routed dispatch against dense dispatch in value,
balance loss and LoRA gradients; and the round's expert-load counter."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import DFLConfig, Session
from repro.configs import get_config
from repro.core.lora import build_lora_tree
from repro.kernels import expert_matmul as em
from repro.kernels import ops, ref
from repro.models import moe as moe_mod
from repro.models import transformer as tf

# ragged groups over three 256-row tiles: empty ones (first, middle and
# last), single rows, groups that start and end inside a tile and groups
# that span several; "ragged" and "short" leave trailing rows unrouted
GROUPS = {
    "ragged": [37, 0, 1, 500, 130],
    "empty_ends": [0, 390, 0, 0, 378],
    "single_rows": [1, 1, 1, 765, 0],
    "one_group": [0, 0, 768, 0, 0],
    "short": [3, 0, 270, 0, 7],
}
E, K, N, M = 5, 256, 384, 768


@pytest.fixture(scope="module")
def operands():
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (M, K))
    dy = jax.random.normal(ks[1], (M, N))
    w = jax.random.normal(ks[2], (E, K, N)) / 16
    return x, dy, w


def _sizes(name):
    return jnp.asarray(GROUPS[name], jnp.int32)


def _rows(name):
    return int(sum(GROUPS[name]))


@pytest.mark.parametrize("groups", sorted(GROUPS))
@pytest.mark.parametrize("transpose", [False, True], ids=["gmm", "gmm_t"])
def test_expert_matmul_kernel_matches_per_expert_loop(operands, groups,
                                                      transpose):
    x, dy, w = operands
    gs, n = _sizes(groups), _rows(groups)
    with jax.default_matmul_precision("highest"):
        y, back = jax.vjp(
            lambda a: em.expert_matmul(a, w, gs, interpret=True), x)
        # the transposed form is the backward to the rows
        got = back(dy)[0] if transpose else y
        want = ref.expert_matmul_ref(dy if transpose else x, w, gs,
                                     transpose=transpose)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got[:n]), np.asarray(want[:n]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("groups", sorted(GROUPS))
def test_expert_matmul_xla_path_matches_per_expert_loop(operands, groups):
    """Off the chip `ops.expert_matmul` is XLA's ragged_dot; rows past the
    groups read zero there."""
    x, _, w = operands
    gs = _sizes(groups)
    assert not ops.expert_matmul_supported(K, N)
    with jax.default_matmul_precision("highest"):
        got = ops.expert_matmul(x, w, gs)
        want = ref.expert_matmul_ref(x, w, gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_expert_matmul_operands_follow_the_matmul_precision(operands):
    """At the default precision the products take bfloat16 operands (the
    float32 weights are cast in VMEM) and accumulate in float32; under
    "highest" they are float32."""
    x, _, w = operands
    gs, n = _sizes("ragged"), _rows("ragged")
    as_bf16 = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)  # noqa
    with jax.default_matmul_precision("highest"):
        want16 = ref.expert_matmul_ref(as_bf16(x), as_bf16(w), gs)[:n]
        want32 = ref.expert_matmul_ref(x, w, gs)[:n]
    got16 = em.expert_matmul(x, w, gs, interpret=True)[:n]
    with jax.default_matmul_precision("highest"):
        got32 = em.expert_matmul(x, w, gs, interpret=True)[:n]
    np.testing.assert_allclose(np.asarray(got16), np.asarray(want16),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got32), np.asarray(want32),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(got16 - want32))) > 1e-3


def test_expert_matmul_gradient_reaches_the_rows_only(operands):
    """The backward is `moe_gmm_t` on the rows; the frozen experts get no
    gradient."""
    x, dy, w = operands
    gs, n = _sizes("ragged"), _rows("ragged")

    def loss(fn, x, w):
        return jnp.sum(fn(x, w)[:n] * dy[:n])

    kernel = lambda x, w: em.expert_matmul(  # noqa: E731
        x, w, gs, interpret=True)
    plain = lambda x, w: ref.expert_matmul_ref(x, w, gs)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        gx, gw = jax.grad(lambda x, w: loss(kernel, x, w),
                          argnums=(0, 1))(x, w)
        want = jax.grad(lambda x: loss(plain, x, w))(x)
    # rows past the groups are unspecified, in the backward as forward
    np.testing.assert_allclose(np.asarray(gx[:n]), np.asarray(want[:n]),
                               rtol=1e-5, atol=1e-5)
    assert not np.any(np.asarray(gw))


def test_expert_matmul_tiles_columns_to_fit_vmem():
    assert em.column_tile(1408, 2048) == 1408        # 11.5 MB: whole
    assert em.column_tile(2048, 1408) == 2048
    assert em.column_tile(14336, 4096) == 512        # 8 MB of 4096 x 512
    assert em.column_tile(384, 256) == 384


# ---------------------------------------------------------------------------
# routed against dense dispatch
# ---------------------------------------------------------------------------

def _moe_cfg(shared: int):
    return dataclasses.replace(get_config("deepseek-moe-16b").reduced(),
                               n_experts=8, top_k=3, n_shared_experts=shared,
                               moe_d_ff=128, n_layers=2)


@pytest.mark.parametrize("shared", [0, 2], ids=["no_shared", "shared"])
@pytest.mark.parametrize("backend", [None, "pallas_interpret"],
                         ids=["xla", "kernel"])
def test_routed_moe_equals_dense_dispatch(shared, backend):
    cfg = _moe_cfg(shared)
    params = moe_mod.init_moe(jax.random.key(3), cfg)
    x = jax.random.normal(jax.random.key(4), (2, 3, 16, cfg.d_model))
    ops.set_backend(backend)
    try:
        with jax.default_matmul_precision("highest"):
            y_r, a_r, load_r = moe_mod.moe_layer(params, cfg, x, "routed")
            y_d, a_d, load_d = moe_mod.moe_layer(params, cfg, x, "dense")
    finally:
        ops.set_backend(None)
    np.testing.assert_allclose(np.asarray(y_r), np.asarray(y_d),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(a_r), float(a_d), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(load_r), np.asarray(load_d))
    assert int(load_r.sum()) == 2 * 3 * 16 * cfg.top_k


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("k", [1, 2, 6])
def test_gather_rows_equals_the_indexed_gather(k, seed):
    """`moe._gather_rows` against plain indexing under JAX's autodiff, in
    value and VJP, bit for bit: the token gather into expert order (each
    token read k times, its k cotangents summed) and the gather back."""
    T, d, E = 24, 16, 5
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (T, d))
    rows = jax.random.normal(ks[1], (T * k, d))
    dy = jax.random.normal(ks[2], (T * k, d))
    order = jnp.argsort(jax.random.randint(ks[3], (T * k,), 0, E))
    back = jnp.argsort(order)
    cases = [
        (x, lambda a: moe_mod._gather_rows(a, order // k, back),
         lambda a: jnp.repeat(a, k, axis=0)[order]),
        (rows, lambda a: moe_mod._gather_rows(a, back, order),
         lambda a: a[back]),
    ]
    for a, got, want in cases:
        y_got, vjp_got = jax.vjp(got, a)
        y_want, vjp_want = jax.vjp(want, a)
        np.testing.assert_array_equal(np.asarray(y_got), np.asarray(y_want))
        np.testing.assert_array_equal(np.asarray(vjp_got(dy)[0]),
                                      np.asarray(vjp_want(dy)[0]))


def test_routed_is_the_default_without_an_expert_axis():
    assert moe_mod._resolve(None) == "routed"
    assert moe_mod._resolve("fused") == "fused"


def test_multi_device_meshes_keep_the_pinned_dense_lowering(monkeypatch):
    """On any mesh of more than one device (clients or experts sharded)
    the dispatch is the one `set_dispatch` pins, dense by default."""
    class FourDevices:
        size = 4

    monkeypatch.setattr(moe_mod, "current_mesh", lambda: FourDevices())
    assert moe_mod._resolve(None) == "dense"
    moe_mod.set_dispatch("fused")
    try:
        assert moe_mod._resolve(None) == "fused"
    finally:
        moe_mod.set_dispatch("dense")
    monkeypatch.setattr(moe_mod, "current_mesh", lambda: None)
    assert moe_mod._resolve(None) == "routed"


def _lora_loss(cfg):
    """The whole model's loss in its LoRA tree (wq/wv, 3 clients, a
    nonzero B so that both factors' gradients are exercised), with the
    balance loss and the expert load."""
    base = tf.init_params(jax.random.key(5), cfg)
    lora = build_lora_tree(jax.random.key(6), base, cfg, n_clients=3)
    lora = jax.tree_util.tree_map_with_path(
        lambda p, t: t + 0.01 if p[-1].key == "b" else t, lora)
    tokens = jax.random.randint(jax.random.key(7), (3, 2, 16), 0,
                                cfg.vocab_size)

    def loss(lo):
        (total, (_, aux, load)), _ = tf.lm_loss(
            base, cfg, tokens, tokens, lora=lo, per_client=True)
        return total, (aux, load)

    return loss, lora


@pytest.mark.parametrize(
    "shared,backend",
    [(0, None), (2, None), (0, "pallas_interpret"), (2, "pallas_interpret")],
    ids=["no_shared", "shared", "no_shared-kernel", "shared-kernel"])
def test_routed_moe_lora_gradients_equal_dense(shared, backend, monkeypatch):
    """Through the whole model, LoRA on wq/wv: the loss, the balance loss
    and every LoRA gradient agree between routed and dense dispatch, on
    the grouped-matmul kernel's backward too, with the routed rows kept
    through the layer remat."""
    cfg = _moe_cfg(shared)
    loss, lora = _lora_loss(cfg)

    def grads(dispatch):
        monkeypatch.setattr(moe_mod, "_resolve", lambda d: dispatch)
        ops.set_backend(backend)
        try:
            with jax.default_matmul_precision("highest"):
                return jax.value_and_grad(loss, has_aux=True)(lora)
        finally:
            ops.set_backend(None)

    (l_r, (aux_r, load_r)), g_r = grads("routed")
    (l_d, (aux_d, load_d)), g_d = grads("dense")
    np.testing.assert_allclose(float(l_r), float(l_d), rtol=1e-6)
    np.testing.assert_allclose(float(aux_r), float(aux_d), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(load_r), np.asarray(load_d))
    assert load_r.shape == (cfg.n_layers, cfg.n_experts)
    for a, b in zip(jax.tree.leaves(g_r), jax.tree.leaves(g_d)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5 * scale)


def _count(jaxpr, primitive: str, where=lambda eqn: True) -> int:
    """Equations of ``primitive`` (those ``where`` holds for) in a jaxpr and
    every jaxpr nested in it (scan, remat and custom-vjp bodies), each body
    counted once."""
    return sum((eqn.primitive.name == primitive and where(eqn))
               + sum(_count(sub, primitive, where)
                     for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


def test_routed_backward_gathers_the_rows_without_scatter_adds():
    """The routed rows' backward gathers by the inverse permutation: the
    gradient of the routed layer scatter-adds into no (tokens x k)-row
    buffer, where JAX's transposes of the two row gathers would."""
    cfg = _moe_cfg(2)
    params = moe_mod.init_moe(jax.random.key(3), cfg)
    x = jax.random.normal(jax.random.key(4), (2, 3, 16, cfg.d_model))
    rows = x.size // cfg.d_model * cfg.top_k

    def loss(x):
        return jnp.sum(moe_mod.moe_layer(params, cfg, x, "routed")[0] ** 2)

    grad = jax.make_jaxpr(jax.grad(loss))(x)
    assert _count(grad.jaxpr, "scatter-add",
                  lambda eqn: eqn.invars[0].aval.shape[:1] == (rows,)) == 0
    # the two row gathers forward, and their two backwards
    assert _count(grad.jaxpr, "gather",
                  lambda eqn: eqn.outvars[0].aval.shape[0] in
                  (rows, rows // cfg.top_k)) == 4


def test_layer_remat_keeps_the_routed_rows():
    """The gradient program runs the grouped-matmul kernels six times per
    scanned MoE layer: gate, up and down forward, and their three
    backwards to the rows. The layer remat keeps the routed rows
    (`moe.ROUTED_ROWS`), so it runs no expert product again, and the
    router's choice, so it chooses no experts again: the kept rows stay
    in the order the backward reads."""
    cfg = _moe_cfg(2)
    assert cfg.n_groups == 2 and [s.ffn for s in cfg.pattern] == ["moe"]
    loss, lora = _lora_loss(cfg)
    ops.set_backend("pallas_interpret")
    try:
        grad = jax.make_jaxpr(jax.grad(lambda lo: loss(lo)[0]))(lora)
    finally:
        ops.set_backend(None)
    # the scan body holds the pattern's one MoE layer
    assert _count(grad.jaxpr, "pallas_call") == 6
    assert _count(grad.jaxpr, "top_k") == 1


# ---------------------------------------------------------------------------
# the round's expert-load counter
# ---------------------------------------------------------------------------

def test_round_counts_the_tokens_routed_to_each_expert():
    m, ls, b, S = 4, 2, 1, 16
    sess = Session(DFLConfig(model="deepseek-moe-16b", reduced=True,
                             task="lm", n_clients=m, rounds=1,
                             local_steps=ls, batch_size=b, seq_len=S,
                             p=1.0, T=1, seed=0))
    ev = sess.step()
    cfg = sess.model_cfg
    load = np.asarray(ev.metrics["expert_load"])
    assert load.shape == (cfg.n_layers, cfg.n_experts)
    # every (token, expert) pair of every local step, once per layer
    np.testing.assert_array_equal(load.sum(axis=1),
                                  np.full(cfg.n_layers, ls * m * b * S
                                          * cfg.top_k))
    imb = ev.stats.expert_load_imbalance
    np.testing.assert_allclose(imb, load.max(1) / load.mean(1))
    assert np.all(imb >= 1.0)


def test_dense_models_carry_no_expert_load():
    sess = Session(DFLConfig(model="gemma3-1b", reduced=True, task="lm",
                             n_clients=2, rounds=1, local_steps=1,
                             batch_size=1, seq_len=16, p=1.0, T=1, seed=0))
    ev = sess.step()
    assert "expert_load" not in ev.metrics
    assert ev.stats.expert_load_imbalance is None
