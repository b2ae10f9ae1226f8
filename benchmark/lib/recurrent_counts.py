"""Bytes of a decoder whose layers keep a recurrence's state per slot
beside a few multi-query attention layers (configs/jamba2_3b_serve.json:
the Jamba block), computed from shapes (the peaks they are set against are
lib/peaks.py's). `sizes` holds the published keys under `config.json`'s
names. Every count is the MODEL's need: what a layout pads, or a kernel
copies beyond it, is theirs."""
from __future__ import annotations

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def mamba_layers(sizes: dict) -> int:
    return sum(1 for l in range(sizes["num_hidden_layers"])
               if l % sizes["attn_layer_period"]
               != sizes["attn_layer_offset"])


def attention_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] - mamba_layers(sizes)


def channels(sizes: dict) -> int:
    """E: the mixer's inner width (5,120)."""
    return sizes["mamba_expand"] * sizes["hidden_size"]


def state_bytes_a_layer(sizes: dict, state_itemsize: int = 4,
                        itemsize: int = 2) -> int:
    """One slot's state in one Mamba layer: h [N, E] (327,680 B in
    float32) and the convolution's last K-1 inputs [K-1, E] (30,720 B in
    bf16)."""
    E = channels(sizes)
    return sizes["mamba_d_state"] * E * state_itemsize \
        + (sizes["mamba_d_conv"] - 1) * E * itemsize


def state_bytes_a_slot(sizes: dict, state_itemsize: int = 4,
                       itemsize: int = 2) -> int:
    """One slot's recurrent state, every Mamba layer (9,318,400 B)."""
    return mamba_layers(sizes) * state_bytes_a_layer(sizes, state_itemsize,
                                                     itemsize)


def step_state_bytes(state_rows: int, sizes: dict, state_itemsize: int = 4,
                     itemsize: int = 2) -> float:
    """What the one-step updates of decode steps that advanced
    `state_rows` live slots' states must move: each slot's state read once
    and written once in every Mamba layer."""
    return 2.0 * state_rows * state_bytes_a_slot(sizes, state_itemsize,
                                                 itemsize)


def scan_stream_bytes(scan_len: int, sizes: dict, stream_itemsize: int = 2
                      ) -> float:
    """What the prefill scans over buckets of `scan_len` positions in all
    must move, every Mamba layer: u and delta in and y out at E wide in
    the model's dtype, B and C at N in float32, and a prompt's carry
    [N, E] float32 out (in: zeros). A FLOOR for the kernel's time: it is
    bound by the vector and transcendental units (E N `exp` and six
    multiply-adds a token a layer), not by HBM."""
    E, N = channels(sizes), sizes["mamba_d_state"]
    a_position = 3 * E * stream_itemsize + 2 * N * 4
    return float(mamba_layers(sizes)) * scan_len * a_position


def scan_carry_bytes(prompts: int, sizes: dict) -> float:
    """The carries `prompts` scans hand out, every Mamba layer."""
    return float(mamba_layers(sizes)) * prompts \
        * sizes["mamba_d_state"] * channels(sizes) * 4


def kv_row_bytes(sizes: dict, itemsize: int = 2) -> int:
    """K and V of one token in one attention layer: ONE shared head (512 B
    at a head of 128 in bf16), whatever the query heads."""
    return 2 * sizes["num_key_value_heads"] * sizes["head_dim"] * itemsize


def kv_bytes_a_token(sizes: dict, itemsize: int = 2) -> int:
    """One token's K and V, every attention layer (1,024 B)."""
    return attention_layers(sizes) * kv_row_bytes(sizes, itemsize)


def mqa_read_bytes(ctx_tokens: int, sizes: dict, itemsize: int = 2) -> float:
    """What the paged attention calls of decode steps that read
    `ctx_tokens` live context tokens must read: each token's row once in
    every attention layer."""
    return float(ctx_tokens) * kv_bytes_a_token(sizes, itemsize)
