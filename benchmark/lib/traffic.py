"""One general generator for every traffic mix.

A traffic file holds distributions. They become a FIXED multiset (an
"epoch") of requests by taking evenly spaced quantiles, so every seed
offers the same work; `--seed` only permutes each epoch, draws the token
ids, the requests' sampling seeds and (open loop) permutes the epoch's
fixed multiset of inter-arrival gaps. Consecutive epochs are permuted
anew, so any stretch of `epoch` requests holds the same tokens.
"""
from __future__ import annotations

import json
import math
import os
from statistics import NormalDist

import numpy as np


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def quantile_lengths(spec: dict, n: int) -> list[int]:
    """`n` evenly spaced quantiles of the distribution in `spec`, clipped
    and rounded: the same list whatever the seed."""
    qs = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    vals = [math.exp(mu + sigma * NormalDist().inv_cdf(q)) for q in qs]
    lo, hi = spec.get("min", 1), spec.get("max", math.inf)
    return [int(min(hi, max(lo, round(v)))) for v in vals]


def exponential_gaps(rate: float, n: int) -> list[float]:
    """`n` evenly spaced quantiles of the exponential inter-arrival gap
    at `rate` per second, rescaled so that they sum to exactly n/rate:
    a Poisson-like stream that offers every seed the same load."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = (n / rate) / sum(raw)
    return [g * scale for g in raw]


def epoch(traffic: dict) -> list[dict]:
    """The fixed multiset: prompt length, output length and sampling of
    each of the epoch's requests. Lengths are paired by a permutation
    that depends on the file alone (`pairing_key`), never on the seed."""
    n = int(traffic["epoch"])
    prompts = quantile_lengths(traffic["prompt"], n)
    outputs = quantile_lengths(traffic["output"], n)
    pair = np.random.Generator(np.random.Philox(
        key=int(traffic.get("pairing_key", 0)))).permutation(n)
    samp = traffic.get("sampling", {})
    greedy_every = int(samp.get("greedy_every", 1))
    items = []
    for i in range(n):
        greedy = greedy_every > 0 and i % greedy_every == 0
        items.append({
            "prompt_len": prompts[i], "max_new": outputs[int(pair[i])],
            "temperature": 0.0 if greedy else float(samp["temperature"]),
            "top_p": 1.0 if greedy else float(samp.get("top_p", 1.0)),
            "top_k": 0 if greedy else int(samp.get("top_k", 0))})
    return items


class RequestStream:
    """The endless, seeded sequence of requests of one run: epoch after
    epoch, each a fresh permutation of the fixed multiset."""

    def __init__(self, traffic: dict, vocab_size: int, seed: int):
        self.items = epoch(traffic)
        self.vocab = int(vocab_size)
        self.rng = np.random.Generator(np.random.Philox(key=int(seed)))
        self._order: list[int] = []
        self.gaps = None
        if traffic.get("loop") == "open":
            self.gaps = exponential_gaps(float(traffic["rate_rps"]),
                                         len(self.items))
        self._gap_order: list[int] = []
        self.index = 0
        self._due = 0.0

    def next(self) -> dict:
        """The next request: the epoch's item plus its token ids, its
        sampling seed and (open loop) `due`, seconds after the stream's
        start at which it is to be sent."""
        if not self._order:
            self._order = list(self.rng.permutation(len(self.items)))
            if self.gaps is not None:
                self._gap_order = list(self.rng.permutation(len(self.gaps)))
        item = dict(self.items[self._order.pop()])
        item["prompt"] = self.rng.integers(
            0, self.vocab, size=item["prompt_len"], dtype=np.int32)
        item["seed"] = int(self.rng.integers(0, 2**31 - 1))
        item["index"] = self.index
        self.index += 1
        if self.gaps is not None:
            self._due += self.gaps[self._gap_order.pop()]
            item["due"] = self._due
        return item


def train_batches(traffic: dict, vocab_size: int, seed: int, stream: int):
    """Generator of int32 [batch, seq] token batches, every row different,
    a new batch each step. `stream` keeps set-up's and the window's draws
    apart while both follow from the seed."""
    rng = np.random.Generator(np.random.Philox(key=[int(seed), stream]))
    shape = (int(traffic["batch"]), int(traffic["seq"]))
    while True:
        yield rng.integers(0, vocab_size, size=shape, dtype=np.int32)


def find(root: str, kind: str, name: str) -> str:
    """Path of the data file `benchmark/<kind>/<name>.json`."""
    p = os.path.join(root, kind, name + ".json")
    if not os.path.exists(p):
        raise FileNotFoundError(f"no file for {kind} {name!r}: {p}")
    return p
