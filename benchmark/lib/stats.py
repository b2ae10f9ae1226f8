"""Order statistics used by every runner and reader."""
from __future__ import annotations

import math


def percentile(values, p: float):
    """Nearest-rank percentile (p in 0..100) of `values`; None when empty.
    An entry of `math.inf` (a failed request) sorts beyond every finite
    reading, so it counts as missing any percentile it reaches."""
    if not values:
        return None
    s = sorted(values)
    k = max(0, min(len(s) - 1, math.ceil(p / 100.0 * len(s)) - 1))
    return s[k]


def median(values):
    if not values:
        return None
    s = sorted(values)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def slice_rates(steps, t0: float, seconds: float, min_slice: float = 2.0):
    """Cut the window into equal slices of at least `min_slice` seconds,
    move each cut to the end of the step in progress there, and return
    each slice's rate: what its steps emitted over the time they took.
    `steps` is a list of (end time, units emitted), in order, the window
    starting at `t0` on a step boundary. Cutting at step ends keeps a
    slice from reading a step more or less than its neighbour."""
    n = max(1, int(seconds // min_slice))
    width = seconds / n
    rates, prev_end, acc, k = [], t0, 0.0, 1
    last = None
    for end, units in steps:
        acc += units
        last = end
        if end >= t0 + k * width:
            rates.append(acc / (end - prev_end))
            prev_end, acc = end, 0.0
            while end >= t0 + k * width:
                k += 1
    if acc and last is not None and last > prev_end and not rates:
        rates.append(acc / (last - prev_end))
    return rates
