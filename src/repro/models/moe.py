"""Mixture-of-Experts FFN: routed top-k + optional shared experts
(DeepSeekMoE / Moonlight fine-grained style; Mixtral when shared=0).

The router (float32 products, softmax over every expert, top-k weights
renormalised) and the Switch balance loss are shared by every dispatch.
Expert weights are stacked (E, d, e_ff). The dispatch follows where they
live (`_resolve`):

- ``routed`` where no multi-device mesh is bound (one device holds
  every expert and every token): the (token, expert) pairs are sorted by
  expert, each token's row is gathered once for each of its k experts,
  the grouped expert matmuls (`kernels.ops.expert_matmul`) run each
  expert on its own rows only, and the rows are gathered back to their
  tokens and summed under their gates. Both row gathers are permutations
  with their inverse at hand, so their backward is the gather by the
  inverse permutation (`_gather_rows`), not a scatter-add. Every leading
  axis is routed together (all clients' tokens of a local step).
- ``dense`` on a multi-device mesh, whether it shards the experts over
  "model" or the clients' tokens over their own axis: every expert runs
  on every token, gated — no data-dependent sort or gather, so GSPMD
  keeps each device's tokens local and partitions the experts as dense
  expert parallelism. ``fused`` (`set_dispatch`, pinned by
  `launch/dryrun.py`) folds the gates into the down-projection
  contraction (no per-expert output tensor): identical numerics,
  different lowering.

Scopes ``router``, ``dispatch``, ``experts``, ``combine`` and ``shared``
name the phases in the compiled program.

The layer remat (`transformer._backbone`) keeps what is named
`ROUTED_ROWS`: the router's choice of experts, and in routed dispatch the
rows its backward reads: the ``w_gate`` and ``w_up`` products, and the
down-projection rows gathered back to their tokens (the gates' gradient
reads them). So the backward runs no expert product a second time: the
kernels' own residuals are the weights, the layer and the group sizes,
never their rows, so with these three kept the recompute needs neither
the kernels nor the dispatch gather. That costs (token, expert) rows x
(2 e_ff + d) x 4 bytes per layer and local step: 12,288 x (2 x 1408 +
2048) x 4 = 239 MB a layer at the deepseek-moe-16b training cell.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.dist.sharding import axis_size, current_mesh, logical
from repro.kernels import ops
from repro.models.common import dense_init, init_mlp, mlp
from repro.models.layers import shard_act

_ROUTER_PRECISION = jax.lax.Precision.HIGHEST
_DISPATCH = ["dense"]   # multi-device meshes; launch code overrides
_EXPERTS = ("w_gate", "w_up", "w_down")
ROUTED_ROWS = "moe_routed_rows"   # what the layer remat keeps


class Stacked(NamedTuple):
    """Layer ``layer`` of the experts of every scanned layer, ``stack``
    (layers, E, ...): the grouped matmuls read the layer's blocks from the
    stack, where a slice handed to a kernel would be copied whole."""
    stack: jax.Array
    layer: jax.Array


def pull_experts(layer_params: dict):
    """A scanned pattern position's params (leading layer axis) split into
    (the rest, its stacked expert weights or None)."""
    moe = layer_params.get("moe")
    if moe is None:
        return layer_params, None
    rest = {k: v for k, v in moe.items() if k not in _EXPERTS}
    return {**layer_params, "moe": rest}, \
        {k: moe[k] for k in _EXPERTS}


def put_experts(layer_params: dict, stacks, layer) -> dict:
    """One layer's params with its expert weights as `Stacked` views."""
    if stacks is None:
        return layer_params
    views = {k: Stacked(v, layer) for k, v in stacks.items()}
    return {**layer_params, "moe": {**layer_params["moe"], **views}}


def _layer(w):
    """The layer's own (E, ...) expert weights."""
    return w.stack[w.layer] if isinstance(w, Stacked) else w


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d = cfg.d_model
    e_ff = cfg.moe_d_ff or cfg.d_ff
    E = cfg.n_experts
    ks = jax.random.split(key, 5)
    scale = 1.0 / jnp.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, E, dtype),
        "w_gate": (jax.random.normal(ks[1], (E, d, e_ff)) * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, e_ff)) * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, e_ff, d)) *
                   (1.0 / jnp.sqrt(e_ff))).astype(dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], d, e_ff * cfg.n_shared_experts, dtype)
    return p


def _route(params: dict, cfg: ModelConfig, x: jax.Array):
    """Softmax router over every expert: (probs (..., E), top-k weights
    renormalised to sum to one (..., k), their experts (..., k)). The
    logits are float32 products: a one-pass bfloat16 router flips the
    choice of near-tied experts. The choice is kept through the layer
    remat (`ROUTED_ROWS`): recomputed, the logits can round otherwise and
    flip a near tie, and the kept rows would no longer be in the order
    the backward reads them."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32),
                        precision=_ROUTER_PRECISION)
    probs = jax.nn.softmax(logits, axis=-1)
    top_idx = checkpoint_name(jax.lax.top_k(probs, cfg.top_k)[1],
                              ROUTED_ROWS)
    # top_k's values, read at the kept experts by a masked sum (exact: one
    # term and zeros); a gather here takes 0.16 ms a call on a TPU v5e
    chosen = top_idx[..., None] == jnp.arange(probs.shape[-1])
    top_w = jnp.sum(jnp.where(chosen, probs[..., None, :], 0.0), axis=-1)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)   # renormalize
    return probs, top_w, top_idx


def _balance_loss(probs, top_w, top_idx, E: int):
    """Switch-style load-balance loss E * sum_e f_e * P_e over every token
    (f_e: share of tokens that chose e, P_e: mean router probability)."""
    chose = jnp.sum(jax.nn.one_hot(top_idx, E, dtype=jnp.float32)
                    * (top_w > 0)[..., None], axis=-2)
    dims = tuple(range(probs.ndim - 1))
    return E * jnp.sum(jnp.mean(chose, axis=dims) * jnp.mean(probs, axis=dims))


def set_dispatch(mode: str) -> None:
    """Dispatch on every multi-device mesh (`launch/dryrun.py`)."""
    assert mode in ("dense", "fused"), mode
    _DISPATCH[0] = mode


def _resolve(dispatch: str | None) -> str:
    """Routed on one device; on a multi-device mesh the pinned dense
    lowering, since routed's sort and gather over every token would cross
    the devices that shard the clients or the experts."""
    if dispatch is not None:
        return dispatch
    mesh = current_mesh()
    return "routed" if mesh is None or mesh.size == 1 else _DISPATCH[0]


def _hg_spec(E: int, ndim: int):
    """Intermediate (..., E, e_ff) sharding: expert-parallel over "model"
    when E divides it (each device computes only its local experts on all
    tokens — dense-EP, the TPU-native MoE layout), else e_ff TP."""
    names = ["batch"] + [None] * (ndim - 1)
    if axis_size("model") > 1 and E % axis_size("model") == 0:
        names[-2] = "model"
    else:
        names[-1] = "model"
    return names


@jax.custom_vjp
def _gather_rows(x, rows, inv):
    """``x[rows]``, where ``rows`` reads every row of ``x`` the same number
    of times, k, and ``inv`` says where each read lands: ``rows[inv]`` is
    ``repeat(arange(len(x)), k)``. The backward is the gather by ``inv``,
    each row's k reads summed, where JAX would transpose the gather into a
    scatter-add into zeros: the same sums, but XLA runs the (12288, 2048)
    float32 scatter-add at about a tenth of a TPU v5e's HBM bandwidth."""
    return x[rows]


def _gather_rows_fwd(x, rows, inv):
    return x[rows], inv.reshape(x.shape[0], -1)


def _gather_rows_bwd(inv, g):
    return jnp.sum(g[inv], axis=1), None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def _routed(params: dict, cfg: ModelConfig, x: jax.Array, top_w, top_idx,
            group_sizes):
    """Each token through its top-k experts only. The (token, expert)
    pairs of every leading index are sorted by expert (``order``; ``back``
    is its inverse), the grouped matmuls run each expert on its rows, and
    the rows come back to their tokens weighted by the gates. The tokens
    are gathered straight into expert order, row ``i`` from token
    ``order[i] // k``, with no k-fold repeated copy of them; the backward
    of that gather is the gather by ``back`` summed over each token's k
    rows, and of the gather back the gather by ``order``."""
    d, k = x.shape[-1], cfg.top_k
    xt = x.reshape(-1, d)
    with jax.named_scope("dispatch"):
        order = jnp.argsort(top_idx.reshape(-1))
        back = jnp.argsort(order)
        xs = _gather_rows(xt, order // k, back)
    with jax.named_scope("experts"):
        def matmul(rows, name):
            w = params[name]
            if isinstance(w, Stacked):
                return ops.expert_matmul(rows, w.stack, group_sizes, w.layer)
            return ops.expert_matmul(rows, w, group_sizes)

        g = checkpoint_name(matmul(xs, "w_gate"), ROUTED_ROWS)
        u = checkpoint_name(matmul(xs, "w_up"), ROUTED_ROWS)
        y = matmul(jax.nn.silu(g) * u, "w_down")
    with jax.named_scope("combine"):
        y = checkpoint_name(_gather_rows(y, back, order),
                            ROUTED_ROWS).reshape(-1, k, d)
        out = jnp.sum(y * top_w.reshape(-1, k, 1).astype(y.dtype), axis=1)
    return out.reshape(x.shape)


def _dense(params: dict, cfg: ModelConfig, x: jax.Array, combine,
           fused: bool):
    """Every expert on every token, gated by ``combine`` (..., E)."""
    w_gate, w_up, w_down = (_layer(params[k]) for k in _EXPERTS)
    hg = jnp.einsum("...d,edf->...ef", x, w_gate)
    hu = jnp.einsum("...d,edf->...ef", x, w_up)
    hg = logical(hg, *_hg_spec(cfg.n_experts, hg.ndim))
    h = jax.nn.silu(hg) * hu
    if fused:
        # fold the combine weight into the down-projection contraction: the
        # (..., E, d) per-expert output tensor (the §Perf-measured memory
        # bomb: 17 GB/device for moonshot train_4k) never materializes, and
        # with expert-sharded weights the contraction over E psums across
        # the model axis — dense expert parallelism.
        h = h * combine[..., None].astype(x.dtype)
        return jnp.einsum("...ef,efd->...d", h, w_down)
    per_exp = jnp.einsum("...ef,efd->...ed", h, w_down)
    return jnp.einsum("...ed,...e->...d", per_exp, combine)


def moe_layer(params: dict, cfg: ModelConfig, x: jax.Array,
              dispatch: str | None = None):
    """x: (..., d) -> (out (..., d), aux_loss, load (E,) int32: the
    (token, expert) pairs routed to each expert)."""
    dispatch = _resolve(dispatch)
    with jax.named_scope("router"):
        probs, top_w, top_idx = _route(params, cfg, x)
        aux = _balance_loss(probs, top_w, top_idx, cfg.n_experts)
        load = jnp.bincount(top_idx.reshape(-1), length=cfg.n_experts
                            ).astype(jnp.int32)
    if dispatch == "routed":
        out = _routed(params, cfg, x, top_w, top_idx, load)
    elif dispatch in ("dense", "fused"):
        combine = jnp.put_along_axis(jnp.zeros_like(probs), top_idx, top_w,
                                     axis=-1, inplace=False).astype(x.dtype)
        out = _dense(params, cfg, x, combine, fused=dispatch == "fused")
    else:
        raise ValueError(dispatch)
    if cfg.n_shared_experts:
        with jax.named_scope("shared"):
            out = out + mlp(params["shared"], x)
    return shard_act(out), aux * cfg.router_aux_coef, load


def moe_ffn(params: dict, cfg: ModelConfig, x: jax.Array,
            dispatch: str | None = None):
    """x: (..., d) -> (out (..., d), aux_loss)."""
    out, aux, _ = moe_layer(params, cfg, x, dispatch)
    return out, aux
