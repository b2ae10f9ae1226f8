"""Published peaks by `device_kind`, and the operations and bytes a call
needs, computed from its shapes. A device that is not in the table is an
error, never a default.

Source for "TPU v5 lite": Google Cloud documentation, "TPU v5e" system
architecture page: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip.
(The program keeps the same numbers in observability/perf.py; this copy
is the one the benchmark reads.)
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16 * 10**9},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                "hbm_bytes": 16 * 10**9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind "
                       f"{device_kind!r}; add it to benchmark/lib/peaks.py "
                       f"with its source") from None


def gpt_train_flops_per_step(sizes: dict, batch: int, seq: int) -> float:
    """Matmul-only FLOPs of one training step, forward plus twice that
    for backward, nothing counted for recomputation (the MFU convention;
    arithmetic copied from bench.py::gpt_train_flops_per_step, attention
    counted over the full square as there)."""
    H, L, V = sizes["hidden_size"], sizes["num_layers"], sizes["vocab_size"]
    F = sizes["intermediate_size"]
    per_layer = (3 * 2 * H * H + 2 * H * H + 2 * 2 * seq * H
                 + 2 * H * F + 2 * F * H)
    return 3.0 * batch * seq * (L * per_layer + 2 * H * V)


def causal_attention_call_flops(rows: int, seq: int, head_dim: int,
                                units: float) -> float:
    """FLOPs one causal flash-attention kernel call must do on `rows`
    (batch x heads) sequences: `units` matmuls of rows x seq x seq x
    head_dim over the lower triangle (2 FLOPs a multiply-add, halved for
    the triangle). The forward call needs 2 (QK^T, PV); the dq call 3
    (scores again, dP, dQ); the dk/dv call 4 (scores again, dP, dV, dK)."""
    return float(units * rows * seq * seq * head_dim)


def paged_attention_bytes(ctx_tokens: int, heads: int, head_dim: int,
                          itemsize: int = 2) -> float:
    """Bytes one paged-attention call (one layer) must read: K and V of
    every live context token."""
    return float(ctx_tokens * 2 * heads * head_dim * itemsize)
