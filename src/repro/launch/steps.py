"""Step functions + ShapeDtypeStruct input specs per (arch × shape).

Everything a dry-run / real launcher needs:
  train_4k    -> the DFL round (paper's technique): per-client local LoRA
                 AdamW steps + joint gossip mixing; clients sharded over the
                 mesh client axes.
  prefill_32k -> serving prefill (forward, last-position logits).
  decode_*    -> one-token serve_step against a seq_len KV cache.

``input_specs`` (spec'd in the task) returns weak-type-correct,
sharding-annotated ShapeDtypeStructs — no device allocation ever happens for
the full configs.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.api.rounds import build_round
from repro.configs.base import ModelConfig
from repro.configs.shapes import InputShape
from repro.core.lora import lora_specs as lora_spec_tree
from repro.dist import sharding as shd
from repro.launch.mesh import client_count
from repro.models import transformer as tf
from repro.optim.adamw import AdamW, AdamWState


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------

def _ns(mesh: Mesh, shape, names, axis_map) -> NamedSharding:
    """NamedSharding from logical dim names with divisibility checks."""
    return NamedSharding(mesh, shd.spec_for(shape, names, mesh, axis_map))


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _with_shardings(spec_tree, sharding_tree):
    return jax.tree.map(
        lambda s, sh: _sds(s.shape, s.dtype, sh), spec_tree, sharding_tree)


def lora_shardings(lora_tree, mesh: Mesh, axis_map):
    """Client axis at -3 over "clients"; matrix dims REPLICATED. LoRA
    factors are tiny (d×r); sharding them over "model" gave GSPMD an
    incentive to re-layout full activations instead (measured 19 GB f32
    all-gathers in the gemma3 dry-run — EXPERIMENTS.md §Perf)."""
    def one(leaf):
        names = [None] * leaf.ndim
        names[-3] = "clients"
        return _ns(mesh, leaf.shape, names, axis_map)
    return jax.tree.map(one, lora_tree)


def cache_shardings(cache_tree, mesh: Mesh, axis_map):
    """KV caches: batch over "batch", seq over "seq"; states: batch (+ width
    over "model"); scalars replicated."""
    def one(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        in_groups = any(getattr(k, "key", None) == "groups" for k in path)
        nd = leaf.ndim
        names = [None] * nd
        base = 1 if in_groups else 0   # leading scan-group axis unsharded
        if name == "t" or nd <= base:
            return _ns(mesh, leaf.shape, names, axis_map)
        names[base] = "batch"
        if name in ("k", "v") and nd >= base + 4:
            names[base + 1] = "seq"
        elif name in ("ck", "cv", "conv", "C", "n", "h", "c", "m") \
                and nd >= base + 2:
            names[-1] = "model" if name in ("h", "conv") else None
        return _ns(mesh, leaf.shape, names, axis_map)
    return jax.tree_util.tree_map_with_path(one, cache_tree)



def _needs_fsdp(cfg: ModelConfig, mesh: Mesh, dtype) -> bool:
    """TP-only must fit ~10 GB/device of weights (v5e has 16 GB); otherwise
    add FSDP sharding over "data" (mixtral-8x22b is the only assigned arch
    that needs it on a 16x16 pod)."""
    itemsize = jnp.dtype(dtype).itemsize
    model_n = mesh.shape["model"]
    return cfg.param_count() * itemsize / model_n > 10e9

# ---------------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------------

def fl_geometry(mesh: Mesh, shape: InputShape,
                axis_map: Optional[dict] = None) -> tuple[int, int]:
    """(n_clients, per-client batch) for a training shape. Client count =
    product of the mesh axes the "clients" logical axis maps to (the
    client-parallel §Perf variant maps ALL axes -> m = chip count)."""
    if axis_map and axis_map.get("clients"):
        m = shd.axes_size(mesh, axis_map["clients"])
    else:
        m = client_count(mesh)
    assert shape.global_batch % m == 0, (shape.global_batch, m)
    return m, shape.global_batch // m


# ---------------------------------------------------------------------------
# TRAIN (the DFL round)
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, *, local_steps: int = 1,
                    lr: float = 2e-4):
    opt = AdamW(lr=lr)

    def loss_fn(base_params, lo, micro):
        return tf.lm_loss(base_params, cfg, micro["tokens"],
                          micro["targets"], frontend=micro.get("frontend"),
                          lora=lo)[0]

    round_fn = build_round(loss_fn, opt, local_steps=local_steps)
    return round_fn, opt


def train_input_specs(cfg: ModelConfig, shape: InputShape, mesh: Mesh, *,
                      local_steps: int = 1, dtype=jnp.bfloat16,
                      axis_map: Optional[dict] = None):
    """(base_params, lora, opt_state, batch, W, masks) specs w/ shardings."""
    axis_map = axis_map or shd.current_axis_map() or shd.DEFAULT_AXIS_MAP
    m, b = fl_geometry(mesh, shape, axis_map)
    S = shape.seq_len

    base_specs = tf.param_specs(cfg, dtype)
    base_sh = shd.param_shardings(base_specs, mesh, axis_map,
                                  fsdp=_needs_fsdp(cfg, mesh, dtype))
    base = _with_shardings(base_specs, base_sh)

    lora_raw = lora_spec_tree(base_specs, cfg, n_clients=m, dtype=jnp.float32)
    lora_sh = lora_shardings(lora_raw, mesh, axis_map)
    lora = _with_shardings(lora_raw, lora_sh)

    opt_state = AdamWState(
        step=_sds((), jnp.int32, NamedSharding(mesh, P())),
        mu=lora, nu=jax.tree.map(lambda x: x, lora))

    batch = {
        "tokens": _sds((local_steps, m, b, S), jnp.int32,
                       _ns(mesh, (local_steps, m, b, S),
                           (None, "clients", None, None), axis_map)),
        "targets": _sds((local_steps, m, b, S), jnp.int32,
                        _ns(mesh, (local_steps, m, b, S),
                            (None, "clients", None, None), axis_map)),
    }
    if cfg.n_frontend_tokens:
        fshape = (local_steps, m, b, cfg.n_frontend_tokens, cfg.d_model)
        batch["frontend"] = _sds(
            fshape, dtype,
            _ns(mesh, fshape, (None, "clients", None, None, "model"),
                axis_map))

    W = _sds((m, m), jnp.float32, NamedSharding(mesh, P()))
    masks = _sds((4,), jnp.float32, NamedSharding(mesh, P()))
    return (base, lora, opt_state, batch, W, masks)


# ---------------------------------------------------------------------------
# PREFILL
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig):
    def step(params, tokens, frontend=None):
        return tf.prefill(params, cfg, tokens, frontend=frontend)
    return step


def prefill_input_specs(cfg: ModelConfig, shape: InputShape, mesh: Mesh, *,
                        dtype=jnp.bfloat16,
                        axis_map: Optional[dict] = None):
    axis_map = axis_map or shd.current_axis_map() or shd.DEFAULT_AXIS_MAP
    B, S = shape.global_batch, shape.seq_len
    base_specs = tf.param_specs(cfg, dtype)
    base_sh = shd.param_shardings(base_specs, mesh, axis_map,
                                  fsdp=_needs_fsdp(cfg, mesh, dtype))
    base = _with_shardings(base_specs, base_sh)
    tokens = _sds((B, S), jnp.int32,
                  _ns(mesh, (B, S), ("batch", None), axis_map))
    args = [base, tokens]
    if cfg.n_frontend_tokens:
        fshape = (B, cfg.n_frontend_tokens, cfg.d_model)
        args.append(_sds(fshape, dtype,
                         _ns(mesh, fshape, ("batch", None, "model"),
                             axis_map)))
    return tuple(args)


# ---------------------------------------------------------------------------
# DECODE (serve_step)
# ---------------------------------------------------------------------------

def make_decode_step(cfg: ModelConfig):
    def serve_step(params, cache, tokens):
        return tf.decode_step(params, cfg, tokens, cache)
    return serve_step


def decode_input_specs(cfg: ModelConfig, shape: InputShape, mesh: Mesh, *,
                       dtype=jnp.bfloat16,
                       axis_map: Optional[dict] = None):
    axis_map = axis_map or shd.current_axis_map() or shd.DEFAULT_AXIS_MAP
    B = shape.global_batch
    base_specs = tf.param_specs(cfg, dtype)
    base_sh = shd.param_shardings(base_specs, mesh, axis_map,
                                  fsdp=_needs_fsdp(cfg, mesh, dtype))
    base = _with_shardings(base_specs, base_sh)

    cache_raw = tf.init_cache(cfg, B, shape.seq_len, dtype, specs_only=True)
    cache_sh = cache_shardings(cache_raw, mesh, axis_map)
    cache = _with_shardings(cache_raw, cache_sh)

    tokens = _sds((B, 1), jnp.int32,
                  _ns(mesh, (B, 1), ("batch", None), axis_map))
    return (base, cache, tokens)


# ---------------------------------------------------------------------------
# unified dispatch (the dry-run's entry point)
# ---------------------------------------------------------------------------

def build(cfg: ModelConfig, shape: InputShape, mesh: Mesh, *,
          local_steps: int = 1, dtype=jnp.bfloat16,
          axis_map: Optional[dict] = None):
    """Returns (step_fn, input_specs, n_tokens, training_flag)."""
    if shape.kind == "train":
        step, _ = make_train_step(cfg, local_steps=local_steps)
        specs = train_input_specs(cfg, shape, mesh,
                                  local_steps=local_steps, dtype=dtype,
                                  axis_map=axis_map)
        n_tokens = local_steps * shape.global_batch * shape.seq_len
        return step, specs, n_tokens, True
    if shape.kind == "prefill":
        step = make_prefill_step(cfg)
        specs = prefill_input_specs(cfg, shape, mesh, dtype=dtype,
                                    axis_map=axis_map)
        return step, specs, shape.global_batch * shape.seq_len, False
    if shape.kind == "decode":
        step = make_decode_step(cfg)
        specs = decode_input_specs(cfg, shape, mesh, dtype=dtype,
                                   axis_map=axis_map)
        return step, specs, shape.global_batch, False
    raise ValueError(shape.kind)
