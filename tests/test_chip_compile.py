"""Compile the main path's kernels and the sharded round for a TPU v5e.

The chip is described, not attached: `jax.experimental.topologies` gives
the devices of a v5e:2x2 host and XLA's TPU compiler runs against them,
so what the chip's compiler refuses (unaligned blocks, too much VMEM, a
kernel GSPMD cannot partition) fails here, at no chip time. Nothing runs.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file. Code that dispatches on ``jax.default_backend()`` still
sees the CPU here, so each test steers `kernels.ops` to the Pallas path
itself.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.api.rounds import build_round
from repro.configs import get_config
from repro.core import mixing
from repro.core.lora import build_lora_tree
from repro.dist import sharding
from repro.kernels import ops
from repro.kernels.gossip_mix import gossip_mix, gossip_mix_quant
from repro.kernels.lora_matmul import slot_lora_matmul
from repro.kernels.paged_attention import paged_attn_decode
from repro.models import transformer as tf
from repro.optim.adamw import AdamW

M_CLIENTS = 8
N_SLOTS = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas(monkeypatch):
    """Send `kernels.ops` dispatch to the compiled Pallas kernels."""
    monkeypatch.setattr(ops, "_mode", lambda: "pallas")


def _spec(shape, dtype, sharding_):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding_)


def _compile(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


def _gemma_lora_cols() -> int:
    """Columns per client of gemma3-1b's flat LoRA buffer (padded)."""
    cfg = get_config("gemma3-1b")
    lora = jax.eval_shape(
        lambda k: build_lora_tree(k, tf.init_params(k, cfg), cfg,
                                  n_clients=M_CLIENTS),
        jax.random.key(0))
    return mixing.build_mix_plan(lora).padded


def test_gossip_mix_seg_compiles_at_gemma3_1b_width(topo, one_chip):
    P_ = _gemma_lora_cols()
    hlo = _compile(lambda w, x, s: gossip_mix(w, x, s),
                   _spec((M_CLIENTS, M_CLIENTS), jnp.float32, one_chip),
                   _spec((M_CLIENTS, P_), jnp.float32, one_chip),
                   _spec((1, P_), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("quant", [jnp.int8, jnp.float8_e4m3fn])
def test_gossip_mix_quant_compiles_at_gemma3_1b_width(topo, one_chip, quant):
    P_ = _gemma_lora_cols()
    r = M_CLIENTS // 4                    # one chip's rows of a 4-chip grid
    hlo = _compile(gossip_mix_quant,
                   _spec((r, M_CLIENTS), jnp.float32, one_chip),
                   _spec((M_CLIENTS, P_), quant, one_chip),
                   _spec((M_CLIENTS, 1), jnp.float32, one_chip),
                   _spec((r, P_), jnp.float32, one_chip),
                   _spec((r, 1), jnp.float32, one_chip),
                   _spec((1, P_), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("target", ["wq", "wv"])
def test_slot_lora_matmul_compiles_at_gemma3_1b_width(topo, one_chip,
                                                      target):
    cfg = get_config("gemma3-1b")
    K = cfg.d_model
    N = cfg.n_heads * cfg.hd if target == "wq" else cfg.n_kv_heads * cfg.hd
    r, n_ad = cfg.lora_rank, M_CLIENTS + 2     # + base and consensus rows
    hlo = _compile(lambda x, w, a, b, s: slot_lora_matmul(x, w, a, b, s,
                                                          2.0),
                   _spec((N_SLOTS, K), jnp.float32, one_chip),
                   _spec((K, N), jnp.float32, one_chip),
                   _spec((n_ad, K, r), jnp.float32, one_chip),
                   _spec((n_ad, r, N), jnp.float32, one_chip),
                   _spec((N_SLOTS,), jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n_kv,n_heads,hd", [(1, 4, 256),    # gemma3-1b
                                             (4, 28, 128)])  # qwen2-7b
def test_paged_attn_decode_compiles(topo, one_chip, n_kv, n_heads, hd):
    page_size, pages_per_seq = 16, 16
    n_pages = 1 + N_SLOTS * pages_per_seq
    G = n_heads // n_kv
    page = (n_pages, n_kv, page_size, hd)
    hlo = _compile(paged_attn_decode,
                   _spec((N_SLOTS, n_kv, G, hd), jnp.float32, one_chip),
                   _spec(page, jnp.float32, one_chip),
                   _spec(page, jnp.float32, one_chip),
                   _spec((N_SLOTS, pages_per_seq), jnp.int32, one_chip),
                   _spec((N_SLOTS,), jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


def test_flash_attention_grad_compiles_at_qwen2_7b_cell(topo, one_chip,
                                                        pallas):
    """The attention core of the qwen2-7b DFL cell (8 clients x 1 x 512
    tokens, 28 query heads over 4 kv heads of 128) under value_and_grad:
    forward and both backward kernels are Mosaic calls, and no
    score-shaped float32 (..., heads, S, L) buffer is left to XLA."""
    import re

    B, S, H, KV, hd = 8, 512, 28, 4, 128

    def loss(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, causal=True) ** 2)

    hlo = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                   _spec((B, S, H, hd), jnp.float32, one_chip),
                   _spec((B, S, KV, hd), jnp.float32, one_chip),
                   _spec((B, S, KV, hd), jnp.float32, one_chip))
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    for name in ("flash_attn_fwd", "flash_attn_bwd_dq",
                 "flash_attn_bwd_dkv"):
        assert any(f"%{name}" in ln for ln in calls), name
    assert not re.search(rf"f32\[\d+,\d+,[\d,]*{S},{S}\]", hlo)


@pytest.mark.parametrize("B,S,H,KV,hd,window,precision", [
    pytest.param(8, 256, 16, 16, 128, None, "default",
                 id="deepseek-moe-16b"),
    pytest.param(1, 1024, 4, 1, 256, 512, "default", id="gemma3-1b-local"),
    pytest.param(1, 1024, 4, 1, 256, None, "default", id="gemma3-1b-global"),
    pytest.param(1, 384, 28, 4, 128, None, "default", id="s384"),
    pytest.param(1, 512, 28, 4, 128, 200, "highest", id="highest"),
])
def test_flash_attention_grad_compiles(B, S, H, KV, hd, window, precision,
                                       topo, one_chip, pallas):
    """The attention core under value_and_grad at the other shapes the
    route sends to the kernels: the deepseek-moe-16b cell (MHA 16 x 128,
    S=256 in 128-row sub-tiles), gemma3-1b's 256-wide heads past one block
    with and without its 512-token window (grid-clamped blocks, masks
    decided at run time), three sub-tiles a side, and float32 products
    under "highest"."""
    import re

    assert ops.flash_attention_supported(S, S, hd)

    def loss(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, causal=True,
                                           window=window) ** 2)

    with jax.default_matmul_precision(precision):
        hlo = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                       _spec((B, S, H, hd), jnp.float32, one_chip),
                       _spec((B, S, KV, hd), jnp.float32, one_chip),
                       _spec((B, S, KV, hd), jnp.float32, one_chip))
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    for name in ("flash_attn_fwd", "flash_attn_bwd_dq",
                 "flash_attn_bwd_dkv"):
        assert any(f"%{name}" in ln for ln in calls), name
    assert not re.search(rf"f32\[\d+,\d+,[\d,]*{S},{S}\]", hlo)


def test_flash_attention_route_keeps_untiled_lengths_off_the_kernel(pallas):
    """Lengths off the 128-row tiles (gemma3-1b's 64-token smoke batches,
    an odd prompt), or past one 512-row block without filling the next,
    and heads narrower than a lane keep the XLA core."""
    assert ops.flash_attention_supported(512, 512, 128)
    for S, hd in ((64, 256), (37, 128), (640, 128), (512, 64)):
        assert not ops.flash_attention_supported(S, S, hd), (S, hd)


def _four_chip_round_hlo(topo, cfg) -> str:
    """The DFL round compiled for a 4-chip mesh with the client axis
    sharded over it (flat gossip kernel, 8 clients, one local step)."""
    ls, b, S = 1, 2, 16
    mesh = Mesh(np.array(topo.devices), ("data",))
    rep = NamedSharding(mesh, P())

    def client_sharded(x, axis):
        spec = [None] * x.ndim
        spec[axis] = "data"
        return _spec(x.shape, x.dtype, NamedSharding(mesh, P(*spec)))

    def loss_fn(bp, lo, micro):
        out, per = tf.lm_loss(bp, cfg, micro["tokens"], micro["targets"],
                              lora=lo, per_client=True)
        return out[0], per

    opt = AdamW(lr=1e-3)
    key = jax.random.key(0)
    base = jax.eval_shape(lambda k: tf.init_params(k, cfg), key)
    lora = jax.eval_shape(
        lambda k: build_lora_tree(k, tf.init_params(k, cfg), cfg,
                                  n_clients=M_CLIENTS), key)
    opt_state = jax.eval_shape(opt.init, lora)
    tok = jax.ShapeDtypeStruct((ls, M_CLIENTS, b, S), jnp.int32)
    specs = (
        jax.tree.map(lambda x: _spec(x.shape, x.dtype, rep), base),
        jax.tree.map(lambda x: client_sharded(x, x.ndim - 3), lora),
        opt_state._replace(
            step=_spec((), jnp.int32, rep),
            mu=jax.tree.map(lambda x: client_sharded(x, x.ndim - 3),
                            opt_state.mu),
            nu=jax.tree.map(lambda x: client_sharded(x, x.ndim - 3),
                            opt_state.nu)),
        {"tokens": client_sharded(tok, 1), "targets": client_sharded(tok, 1)},
        _spec((M_CLIENTS, M_CLIENTS), jnp.float32, rep),
        _spec((4,), jnp.float32, rep),
    )
    round_fn = build_round(loss_fn, opt, local_steps=ls)
    sharding.set_mesh(mesh)
    try:
        return _compile(round_fn, *specs)
    finally:
        sharding.clear_mesh()


def test_round_compiles_on_four_chips_with_clients_sharded(topo, pallas):
    """The DFL round with the flat gossip kernel, client axis sharded over
    a 4-chip mesh: the kernel must sit inside a shard_map (a Mosaic call
    cannot be partitioned automatically)."""
    hlo = _four_chip_round_hlo(topo, get_config("gemma3-1b").reduced())
    assert "tpu_custom_call" in hlo


def test_moe_round_compiles_on_four_chips_with_clients_sharded(topo, pallas):
    """An MoE round on the client-sharded 4-chip mesh keeps dense dispatch:
    each device runs every expert on its own clients' tokens, with no
    sort of the (token, expert) pairs across devices and no grouped
    matmul kernel (a Mosaic call GSPMD cannot partition)."""
    import re

    hlo = _four_chip_round_hlo(topo, get_config("deepseek-moe-16b").reduced())
    assert "tpu_custom_call" in hlo           # the gossip kernel
    assert not re.search(r"moe_gmm|ragged", hlo)
    assert not re.search(r"= \S+ sort\(", hlo)


def test_moe_cell_round_compiles_routed_on_one_chip(topo, one_chip, pallas):
    """The deepseek-moe-16b cell's DFL round (4 MoE layers at published
    widths, 8 clients x 4 local steps x 1 x 256 tokens, the round donated
    as `Session` builds it) fits one v5e with routed experts: the grouped
    matmul kernels are Mosaic calls in the program, no dense-dispatch
    float32 (..., 64, 1408) tensor is left, and arguments plus
    temporaries fit the chip's 15.75 GB. The layer remat keeps the routed
    rows, so the scanned layer holds three `moe_gmm` calls (gate, up,
    down) and three `moe_gmm_t` (their backwards): no recomputed forward.
    The rows' backward is gathers, with no float32 scatter over them."""
    import dataclasses
    import re

    cfg = dataclasses.replace(get_config("deepseek-moe-16b"), n_layers=4)
    ls, b, S = 4, 1, 256

    def loss_fn(bp, lo, micro):
        (loss, (_, _, load)), per = tf.lm_loss(
            bp, cfg, micro["tokens"], micro["targets"], lora=lo,
            per_client=True)
        return loss, per, {"expert_load": load}

    opt = AdamW(lr=1e-3)
    key = jax.random.key(0)
    base = jax.eval_shape(lambda k: tf.init_params(k, cfg), key)
    lora = jax.eval_shape(
        lambda k: build_lora_tree(k, tf.init_params(k, cfg), cfg,
                                  n_clients=M_CLIENTS), key)

    def on_chip(tree):
        return jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip),
                            tree)

    tok = _spec((ls, M_CLIENTS, b, S), jnp.int32, one_chip)
    round_fn = build_round(loss_fn, opt, local_steps=ls, donate=True)
    compiled = round_fn.lower(
        on_chip(base), on_chip(lora), on_chip(jax.eval_shape(opt.init, lora)),
        {"tokens": tok, "targets": tok},
        _spec((M_CLIENTS, M_CLIENTS), jnp.float32, one_chip),
        _spec((4,), jnp.float32, one_chip)).compile()
    hlo = compiled.as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    for name in ("moe_gmm", "moe_gmm_t"):
        n = sum(bool(re.search(rf"%{name}[.\s]", ln)) for ln in calls)
        assert n == 3, (name, n)
    assert not re.search(r"f32\[[\d,]*64,1408\]", hlo)
    # the routed rows' backward gathers by the inverse permutation: no
    # scatter-add over the 2048 tokens x top-6 rows
    assert not re.search(r"f32\[12288,2048\]\{[^}]*\} scatter\(", hlo)
    # the kernels read each layer's blocks from the stack: no layer's
    # expert projection is copied out of it
    assert not re.search(r"(f32|bf16)\[(1,)?64,(2048,1408|1408,2048)\]",
                         hlo)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        <= 15.75e9, mem


@pytest.mark.parametrize("step", ["decode", "chunk_prefill"])
def test_moe_serving_steps_read_the_expert_stack(topo, one_chip, pallas,
                                                 step):
    """deepseek-moe-16b's serving steps at published widths (4 MoE layers,
    8 paged slots) take the routed path on one v5e, and the `moe_gmm`
    kernel reads each layer's blocks from the whole expert stack: no
    per-layer (64, 2048, 1408) copy of an expert projection is made."""
    import dataclasses
    import re

    cfg = dataclasses.replace(get_config("deepseek-moe-16b"), n_layers=4)
    max_len, page_size, chunk = 512, 16, 256
    base = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                          jax.random.key(0))
    cache = tf.init_cache(cfg, N_SLOTS, max_len, specs_only=True,
                          paging=(1 + N_SLOTS * max_len // page_size,
                                  page_size))

    def on_chip(tree):
        return jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip),
                            tree)

    scalar = _spec((), jnp.int32, one_chip)
    if step == "decode":
        hlo = _compile(lambda p, t, c: tf.decode_step(p, cfg, t, c),
                       on_chip(base), _spec((N_SLOTS, 1), jnp.int32,
                                            one_chip), on_chip(cache))
    else:
        hlo = _compile(
            lambda p, t, c, slot, start, limit: tf.chunk_prefill_step(
                p, cfg, t, c, slot, start, limit),
            on_chip(base), _spec((1, chunk), jnp.int32, one_chip),
            on_chip(cache), scalar, scalar, scalar)
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert any(re.search(r"%moe_gmm[.\s]", ln) for ln in calls)
    assert not re.search(r"(f32|bf16)\[(1,)?64,(2048,1408|1408,2048)\]",
                         hlo)
