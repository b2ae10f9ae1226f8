"""Bytes of a residual of several streams mixed by hyper-connections
(configs/xing4_29b_a4b_serve.json: `hc_mult` streams of `hidden_size`),
computed from shapes (the peak they are set against is lib/peaks.py's).
`sizes` holds the published keys under `config.json`'s names."""
from __future__ import annotations


def stream_width(sizes: dict) -> int:
    """Numbers a token carries through the layers: hc_mult x hidden."""
    return int(sizes["hc_mult"]) * int(sizes["hidden_size"])


def mix_bytes_a_token(sizes: dict, itemsize: int = 2) -> float:
    """What the stream's steps of ONE sub-layer must move for one token:
    the n streams read once and written once, the branch's input h out
    and its output F(h) in: (2 n + 2) C numbers (71,680 B at 4 x 3584 in
    bf16). The 24 coefficients and phi's 0.69 MB a sub-layer, read once a
    program, are left out: under 1% of a bucket's bytes."""
    n, C = int(sizes["hc_mult"]), int(sizes["hidden_size"])
    return float((2 * n + 2) * C * itemsize)


def sublayers(sizes: dict) -> int:
    """Attention and feed-forward of every layer held."""
    return 2 * int(sizes["num_hidden_layers"])


def mix_bytes(tokens: int, sizes: dict, itemsize: int = 2) -> float:
    """The same for `tokens` tokens through every sub-layer (0.86 MB a
    token at 12 sub-layers)."""
    return float(tokens) * sublayers(sizes) * mix_bytes_a_token(sizes,
                                                                itemsize)
