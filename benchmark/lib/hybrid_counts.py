"""Bytes the kernels of a hybrid convolution / grouped-query / routed-
expert decoder must move, computed from shapes (the peaks they are set
against are lib/peaks.py's)."""
from __future__ import annotations


def expert_weight_bytes(hidden: int, expert_width: int,
                        itemsize: int = 2) -> float:
    """The three matrices of one SwiGLU expert: what a grouped product
    must read of an expert that at least one of its rows reached."""
    return float(3 * hidden * expert_width * itemsize)


def gqa_kv_bytes(ctx_tokens: int, kv_heads: int, head_dim: int,
                 layers: int, itemsize: int = 2) -> float:
    """K and V of every live context token, each attention layer held:
    what the paged-attention calls of the steps that read `ctx_tokens`
    must read. The KV heads count, not the query heads."""
    return float(ctx_tokens * 2 * kv_heads * head_dim * itemsize * layers)
