"""What a training step of the routed, windowed decoder needs, counted from
its shapes (configs/mellum2_12b_a2p5b_train.json): parameters, the model's
FLOPs of a step with attention counted over what the mask lets through and
the experts over the pairs that were held, the FLOPs of one flash call of
each kind, and the grouped products' FLOPs and bytes. Matrix products only,
2 FLOPs a multiply-add; the backward is twice the forward and nothing is
counted for recomputation (the MFU convention, lib/peaks.py).
"""
from __future__ import annotations

SLIDING, FULL = "sliding_attention", "full_attention"
# products of one flash call over the allowed (row, key) pairs: the forward
# call QK^T and PV; dq: scores again, dP, dQ; dk/dv: scores again, dP, dV, dK
CALL_UNITS = {"fwd": 2, "dq": 3, "dkv": 4}
# grouped products an expert layer makes in one step under per-layer remat:
# W1, W3, W2 forward, the same again recomputed, their three input
# gradients (gmm) and their three weight gradients (tgmm)
GMM_CALLS, TGMM_CALLS = 9, 3


def held(sizes: dict) -> int:
    n = sizes.get("experts_held")
    if n is None:
        return int(sizes["num_experts"])
    return int(n) if isinstance(n, int) else len(n)


def attention_params(s: dict) -> int:
    D, d = s["hidden_size"], s["head_dim"]
    H, Hkv = s["num_attention_heads"], s["num_key_value_heads"]
    return D * H * d + 2 * D * Hkv * d + H * d * D + 2 * d


def expert_params(s: dict) -> int:
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def layer_params(s: dict) -> int:
    D = s["hidden_size"]
    return attention_params(s) + 2 * D + D * s["num_experts"] \
        + held(s) * expert_params(s)


def weight_params(s: dict) -> int:
    D = s["hidden_size"]
    return s["num_hidden_layers"] * layer_params(s) \
        + 2 * s["vocab_size"] * D + D


def allowed_pairs(seq: int, window) -> int:
    """(row, key) pairs one causal sequence attends over: the triangle, or
    the band of `window` inside it."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def window_of(s: dict, kind: str):
    return int(s["sliding_window"]) if kind == SLIDING else None


def flash_call_flops(s: dict, batch: int, seq: int, kind: str,
                     call: str) -> float:
    """FLOPs one flash call (`call`: fwd, dq or dkv) of a layer of `kind`
    must do: its products over the pairs the mask lets through, every
    query head."""
    return float(CALL_UNITS[call] * 2 * batch * s["num_attention_heads"]
                 * allowed_pairs(seq, window_of(s, kind)) * s["head_dim"])


def expert_product_flops(s: dict, rows: float) -> float:
    """One grouped product over `rows` held pairs (any of the twelve: each
    is rows x hidden x moe_intermediate)."""
    return 2.0 * rows * s["hidden_size"] * s["moe_intermediate_size"]


def expert_product_bytes(s: dict, rows: float, itemsize: int = 2) -> float:
    """Bytes one grouped product must move: the held experts' matrix once
    (read, or written by a weight gradient) and the pairs' rows on both
    sides of it."""
    D, F = s["hidden_size"], s["moe_intermediate_size"]
    return float(itemsize * (held(s) * D * F + rows * (D + F)))


def forward_flops(s: dict, batch: int, seq: int, pairs_held: float) -> dict:
    """The forward pass's FLOPs by part. `pairs_held`: token-expert pairs
    whose expert is held, summed over the layers."""
    n = batch * seq
    D, d = s["hidden_size"], s["head_dim"]
    H, Hkv = s["num_attention_heads"], s["num_key_value_heads"]
    L = s["num_hidden_layers"]
    proj = 2.0 * n * (D * H * d + 2 * D * Hkv * d + H * d * D) * L
    router = 2.0 * n * D * s["num_experts"] * L
    attn = sum(flash_call_flops(s, batch, seq, kind, "fwd")
               for kind in s["layer_types"])
    experts = 3 * expert_product_flops(s, pairs_held)
    head = 2.0 * n * D * s["vocab_size"]
    return {"projections": proj, "router": router, "attention": attn,
            "experts": experts, "head": head}


def step_flops(s: dict, batch: int, seq: int, pairs_held: float) -> float:
    return 3.0 * sum(forward_flops(s, batch, seq, pairs_held).values())
