"""Bytes the kernels of a looped decoder must move (one stack of layers run
several times a token, K/V kept for every layer of every pass), computed
from shapes (the peaks they are set against are lib/peaks.py's)."""
from __future__ import annotations


def cache_rows(sizes: dict) -> int:
    """K/V rows a token owns: one a layer a pass."""
    return int(sizes["total_ut_steps"]) * int(sizes["num_hidden_layers"])


def kv_bytes_a_token(sizes: dict, itemsize: int = 2) -> float:
    """K and V of one token: every layer of every pass, the KV heads."""
    return float(2 * cache_rows(sizes) * sizes["num_key_value_heads"]
                 * sizes["head_dim"] * itemsize)


def paged_kv_bytes(ctx_tokens: int, sizes: dict, itemsize: int = 2) -> float:
    """What the paged-attention calls of the decode steps that read
    `ctx_tokens` live context tokens must read: each token's K and V once
    in every pass and layer (pass t reads pass t's rows only)."""
    return float(ctx_tokens) * kv_bytes_a_token(sizes, itemsize)


def layer_weight_bytes(sizes: dict, itemsize: int = 2) -> float:
    """One layer's matrices: q, k, v, o and the three of the SwiGLU, and
    its four norms' gains."""
    D, F, d = sizes["hidden_size"], sizes["intermediate_size"], \
        sizes["head_dim"]
    Hq, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return float((2 * D * Hq * d + 2 * D * Hkv * d + 3 * D * F + 4 * D)
                 * itemsize)


def head_weight_bytes(sizes: dict, itemsize: int = 2) -> float:
    """The untied head [D, V]."""
    return float(sizes["hidden_size"] * sizes["vocab_size"] * itemsize)


def decode_weight_bytes(sizes: dict, itemsize: int = 2) -> float:
    """Weights one decode step must read whatever its batch: the stacked
    layers once a pass, and the head once. (The embedding's rows are a
    gather of one row a slot and are not counted.)"""
    return sizes["total_ut_steps"] * sizes["num_hidden_layers"] \
        * layer_weight_bytes(sizes, itemsize) \
        + head_weight_bytes(sizes, itemsize)
