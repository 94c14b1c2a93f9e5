"""Required operations and bytes, computed from shapes.

Every count is of the work the algorithm needs, not of what the program
executes: no rematerialised forward, no experts that the router did not
choose, no weight gradients of the frozen base. A share of a peak built
from these counts therefore cannot pass 100% unless the time is wrong.

Sizes come from a bench/configs/<name>.json (Hugging Face key names).
"""
from __future__ import annotations

F32 = 4


def _dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    r = cfg["lora"]["rank"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    out = {"wq": q, "wk": kv, "wv": kv}
    return {"d": d, "hd": hd, "q": q, "kv": kv, "r": r, "L":
            cfg["num_hidden_layers"], "V": cfg["vocab_size"],
            "targets": {t: out[t] for t in cfg["lora"]["targets"]}}


def layer_matmul_params(cfg: dict) -> dict:
    """Weights one token multiplies through in one layer, split into the
    q/k/v input projections and the rest (attention output, FFN on the
    active experts, router)."""
    x = _dims(cfg)
    d = x["d"]
    qkv = d * (x["q"] + 2 * x["kv"])
    rest = x["q"] * d
    if "n_routed_experts" in cfg:
        e_ff = cfg["moe_intermediate_size"]
        active = cfg["num_experts_per_tok"] + cfg.get("n_shared_experts", 0)
        rest += 3 * d * e_ff * active + d * cfg["n_routed_experts"]
    else:
        rest += 3 * d * cfg["intermediate_size"]
    return {"qkv": qkv, "rest": rest}


def lora_params(cfg: dict) -> int:
    """LoRA parameters of one client (both factors, every layer)."""
    x = _dims(cfg)
    return x["L"] * sum(x["d"] * x["r"] + x["r"] * o
                        for o in x["targets"].values())


def attn_core_flops(cfg: dict, S: int) -> float:
    """Causal scores and weighted values of one sequence in one layer,
    forward: 2 products of S*S/2 * heads * hd multiply-adds."""
    x = _dims(cfg)
    return 2.0 * S * S * x["q"]


def round_flops(cfg: dict, m: int, ls: int, b: int, S: int) -> float:
    """Required FLOPs of one DFL round: for every client, local step and
    sequence, the frozen base's forward (2 per weight per token) and the
    backward to activations (2 per weight per token) of every layer, the
    head included, except the input gradient of the lowest layer's q/k/v
    projections, which nothing trainable lies below; causal attention
    forward and twice that backward; the LoRA forward and the gradient of
    the one factor TAD trains. The mix is counted apart (mix_bytes)."""
    x = _dims(cfg)
    p = layer_matmul_params(cfg)
    L, d, r = x["L"], x["d"], x["r"]
    per_layer = p["qkv"] + p["rest"]
    fwd = 2 * (L * per_layer + d * x["V"])
    bwd = 2 * (L * per_layer + d * x["V"] - p["qkv"])
    lora_fwd = 2 * L * sum(d * r + r * o for o in x["targets"].values())
    lora_grad = 2 * L * sum(r * o for o in x["targets"].values())
    per_token = fwd + bwd + lora_fwd + lora_grad
    per_seq = S * per_token + 3 * L * attn_core_flops(cfg, S)
    return float(m * ls * b * per_seq)


def mix_bytes(cfg: dict, m: int) -> float:
    """Bytes the gossip mix must move: read and write every client's
    LoRA factors in float32, plus W."""
    return float(2 * m * lora_params(cfg) * F32 + m * m * F32)


def decode_flops(cfg: dict, contexts) -> float:
    """Required FLOPs of one decode step for the active slots, given each
    slot's context length (keys attended to, the new one included): the
    base weights and head (2 per weight), both LoRA factors of every
    target, and attention over the context."""
    x = _dims(cfg)
    p = layer_matmul_params(cfg)
    L, d, r = x["L"], x["d"], x["r"]
    per_token = 2 * (L * (p["qkv"] + p["rest"]) + d * x["V"]) \
        + 2 * L * sum(d * r + r * o for o in x["targets"].values())
    attn = sum(4.0 * L * x["q"] * c for c in contexts)
    return float(len(contexts) * per_token + attn)


def paged_attn_bytes(cfg: dict, contexts, page_size: int) -> float:
    """Bytes one paged-attention decode call must move for the active
    slots: the live K and V pages of every layer (whole pages, as the
    kernel reads them), the queries and the outputs, float32."""
    x = _dims(cfg)
    kv_row = x["kv"] * F32                     # one position, K or V
    pages = sum(-(-c // page_size) for c in contexts)
    per_layer = 2 * pages * page_size * kv_row \
        + 2 * len(contexts) * x["q"] * F32
    return float(x["L"] * per_layer)


def slot_lora_cost(cfg: dict, n_active: int) -> dict:
    """FLOPs and bytes the slot-LoRA matmuls of one decode step need
    (every target, every layer) for ``n_active`` slots: x @ W plus each
    slot's own adapter. Bytes are W once, each slot's A and B rows, x and
    out, float32."""
    x = _dims(cfg)
    L, d, r = x["L"], x["d"], x["r"]
    flops = bytes_ = 0.0
    for o in x["targets"].values():
        flops += 2.0 * n_active * (d * o + d * r + r * o)
        bytes_ += F32 * (d * o + n_active * (d * r + r * o + d + o))
    return {"flops": L * flops, "bytes": L * bytes_}
