"""Required operations and bytes of the routed experts' grouped matmuls
(the program's `moe_gmm` and `moe_gmm_t` kernels) in one DFL round.

As in bench/counts.py, required work only: the experts the router chose
(k per token), the forward and the backward to the rows (no weight
gradient of the frozen experts), no rematerialised forward. Sizes come
from a bench/configs/<name>.json (Hugging Face key names).
"""
from __future__ import annotations

F32, BF16 = 4, 2


def _sizes(cfg: dict) -> tuple:
    return (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["n_routed_experts"], cfg["num_experts_per_tok"],
            cfg["num_hidden_layers"])


def gmm_flops(cfg: dict, m: int, ls: int, b: int, S: int) -> float:
    """Every token through its k experts' gate, up and down projections
    (2 FLOPs per weight), in the forward and again in the backward's
    input gradient, in every layer."""
    d, f, _, k, L = _sizes(cfg)
    tokens = m * ls * b * S
    return float(2 * L * tokens * k * 3 * d * f * 2)


def gmm_bytes(cfg: dict, m: int, ls: int, b: int, S: int) -> float:
    """Every expert's float32 weights read once per layer and local step
    in the forward and once in the backward, plus the routed rows: each
    product reads its rows as bfloat16 operands and writes float32."""
    d, f, E, k, L = _sizes(cfg)
    rows = m * b * S * k                      # (token, expert) pairs a step
    weights = E * 3 * d * f * F32
    # forward: x -> gate, up (d in, f out); h -> down (f in, d out);
    # backward: the same products transposed
    fwd_rows = rows * (2 * (d * BF16 + f * F32) + f * BF16 + d * F32)
    bwd_rows = rows * (2 * (f * BF16 + d * F32) + d * BF16 + f * F32)
    return float(L * ls * (2 * weights + fwd_rows + bwd_rows))
