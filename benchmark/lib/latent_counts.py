"""Bytes and operations of a decoder with multi-head latent attention and
routed experts beside shared ones (configs/kanana2_30b_a3b_serve.json: the
DeepSeek-V3 block), computed from shapes (the peaks they are set against
are lib/peaks.py's). `sizes` holds the published keys under `config.json`'s
names."""
from __future__ import annotations


def latent_width(sizes: dict) -> int:
    """Numbers a token leaves in a layer: the row [c | kr]."""
    return int(sizes["kv_lora_rank"]) + int(sizes["qk_rope_head_dim"])


def latent_row_bytes(sizes: dict, itemsize: int = 2) -> float:
    """One token's row in one layer (1,152 B at 512 + 64 in bf16). The
    model's bytes: what a layout pads them to is the layout's."""
    return float(latent_width(sizes) * itemsize)


def latent_bytes_a_token(sizes: dict, itemsize: int = 2) -> float:
    """One token's rows, every layer held (8,064 B at 7 layers)."""
    return sizes["num_hidden_layers"] * latent_row_bytes(sizes, itemsize)


def expanded_bytes_a_token(sizes: dict, itemsize: int = 2) -> float:
    """What a cache of keys and values per head would hold for the same
    token (143,360 B at 7 layers): H x (192 + 128) numbers a layer."""
    per_head = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"] \
        + sizes["v_head_dim"]
    return float(sizes["num_hidden_layers"] * sizes["num_attention_heads"]
                 * per_head * itemsize)


def paged_latent_bytes(ctx_tokens: int, sizes: dict,
                       itemsize: int = 2) -> float:
    """What the latent paged-attention calls of the decode steps that read
    `ctx_tokens` live context tokens must read: each token's row once in
    every layer. The row is key AND value: it is read once."""
    return float(ctx_tokens) * latent_bytes_a_token(sizes, itemsize)


def latent_attn_flops(ctx_tokens: int, sizes: dict) -> float:
    """Multiply-adds x 2 of the same calls: every head's query of C + dr
    against the row, and its weighted sum over the row's first C numbers.
    At the published sizes 69,632 a token-layer, 17 a byte: under the
    chip's 240 a byte, so the kernel is bound by bandwidth."""
    H, C = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    return float(ctx_tokens) * sizes["num_hidden_layers"] * 2 * H \
        * (latent_width(sizes) + C)


def attention_params(sizes: dict) -> int:
    D, H, C = sizes["hidden_size"], sizes["num_attention_heads"], \
        sizes["kv_lora_rank"]
    dn, dr, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], \
        sizes["v_head_dim"]
    return D * H * (dn + dr) + D * (C + dr) + C * H * (dn + dv) \
        + H * dv * D + C + 2 * D          # the three norms' gains


def layer_params(sizes: dict, l: int) -> int:
    D = sizes["hidden_size"]
    if l < sizes["first_k_dense_replace"]:
        return attention_params(sizes) + 3 * D * sizes["intermediate_size"]
    E, F = sizes["n_routed_experts"], sizes["moe_intermediate_size"]
    return attention_params(sizes) + E * 3 * D * F + D * E + E \
        + 3 * D * sizes["n_shared_experts"] * F


def weight_params(sizes: dict) -> int:
    """Every parameter held: embedding, untied head, final norm, layers
    (4,429,613,312 at 7 layers)."""
    D, V = sizes["hidden_size"], sizes["vocab_size"]
    return 2 * V * D + D + sum(layer_params(sizes, l)
                               for l in range(sizes["num_hidden_layers"]))


def weight_bytes(sizes: dict, itemsize: int = 2) -> float:
    return float(weight_params(sizes) * itemsize)
