"""Bytes and parameters of a decoder whose attention layers are of two
kinds, sliding-window and full, with routed experts beside a shared one
(configs/trinity_mini_serve.json: the AFMoE block), computed from shapes
(the peaks they are set against are lib/peaks.py's). `sizes` holds the
published keys under `config.json`'s names. Every count is the MODEL's
need: what a layout pads, or a kernel copies beyond it, is theirs."""
from __future__ import annotations

SLIDING, FULL = "sliding_attention", "full_attention"


def layers_of(sizes: dict, kind: str) -> int:
    return sum(1 for k in sizes["layer_types"] if k == kind)


def kv_row_bytes(sizes: dict, itemsize: int = 2) -> float:
    """K and V of one token in one layer (2,048 B at 4 KV heads of 128 in
    bf16). The KV heads count, not the query heads."""
    return float(2 * sizes["num_key_value_heads"] * sizes["head_dim"]
                 * itemsize)


def window_read_bytes(window_rows: int, sizes: dict,
                      itemsize: int = 2) -> float:
    """What the sliding layers' paged-attention calls must read in decode
    steps whose live slots attend to `window_rows` positions in all (the
    sum over the slots of min(context, sliding_window)): each position's K
    and V once in every sliding layer."""
    return float(window_rows) * kv_row_bytes(sizes, itemsize) \
        * layers_of(sizes, SLIDING)


def full_read_bytes(ctx_tokens: int, sizes: dict, itemsize: int = 2) -> float:
    """What the full layers' calls must read in decode steps that read
    `ctx_tokens` live context tokens: each token's K and V once in every
    full layer."""
    return float(ctx_tokens) * kv_row_bytes(sizes, itemsize) \
        * layers_of(sizes, FULL)


def routing_bytes_a_token(sizes: dict) -> int:
    """The routing part: the experts chosen in every expert layer, int8 up
    to 127 experts and int16 beyond."""
    moe = sizes["num_hidden_layers"] - sizes["num_dense_layers"]
    return moe * sizes["num_experts_per_tok"] \
        * (1 if sizes["num_experts"] <= 127 else 2)


def page_bytes(sizes: dict, page_size: int, itemsize: int = 2) -> dict:
    """Bytes one page holds by kind of K/V state: {"global": the full
    layers' K and V and the routing part, "window": the sliding layers' K
    and V} (131,072 + 4,096 and 655,360 at pages of 64)."""
    row = kv_row_bytes(sizes, itemsize)
    return {"global": page_size * (layers_of(sizes, FULL) * row
                                   + routing_bytes_a_token(sizes)),
            "window": page_size * layers_of(sizes, SLIDING) * row}


def whole_cache_bytes_a_token(sizes: dict, itemsize: int = 2) -> float:
    """What a cache that kept every layer whole would hold for one token
    (12,288 B at 6 layers, and the routing part)."""
    return sizes["num_hidden_layers"] * kv_row_bytes(sizes, itemsize) \
        + routing_bytes_a_token(sizes)


def ring_pages(sizes: dict, page_size: int) -> int:
    """Pages of a request's ring: the window in pages and one more (33)."""
    return -(-sizes["sliding_window"] // page_size) + 1


def attention_params(sizes: dict) -> int:
    D, d = sizes["hidden_size"], sizes["head_dim"]
    H, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return 3 * D * H * d + 2 * D * Hkv * d + 2 * d      # q, gate, o; k, v


def layer_params(sizes: dict, l: int) -> int:
    D = sizes["hidden_size"]
    base = attention_params(sizes) + 4 * D              # the four norms
    if l < sizes["num_dense_layers"]:
        return base + 3 * D * sizes["intermediate_size"]
    E, F = sizes["num_experts"], sizes["moe_intermediate_size"]
    return base + E * 3 * D * F + D * E + E \
        + 3 * D * sizes["num_shared_experts"] * F


def weight_params(sizes: dict) -> int:
    """Every parameter held: embedding, untied head, final norm, layers
    (4,306,554,880 at 6 layers)."""
    D, V = sizes["hidden_size"], sizes["vocab_size"]
    return 2 * V * D + D + sum(layer_params(sizes, l)
                               for l in range(sizes["num_hidden_layers"]))


def weight_bytes(sizes: dict, itemsize: int = 2) -> float:
    return float(weight_params(sizes) * itemsize)
