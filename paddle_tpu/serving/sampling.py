"""Replayable stochastic decode: Philox-keyed temperature/top-k/top-p.

The sampler lives INSIDE the jitted decode body (engine.py closes over
`sample_tokens`), with every sampling parameter a slot-wide traced array
— so stochastic decode keeps the one-compile-per-(slots,pages)-bucket
contract, and a greedy request (temperature 0) still gets the literal
`argmax` it always did, bit-for-bit.

Randomness is the counter-based Philox4x32-10 generator implemented
directly in uint32 lane math (no uint64 — runs with jax x64 disabled),
keyed by the request's 64-bit seed and COUNTED by the decode step:

    uniform = philox(key=(seed_lo, seed_hi), counter=(step, 0, 0, 0))

One uniform per (seed, step) feeds an inverse-CDF draw over the
temperature-scaled, top-k/top-p-filtered distribution. Because the
stream is a pure function of (seed, step) — no RNG state anywhere — a
replayed request emits the identical token sequence: transport retries,
router failover to a survivor replica (the router pins the same wire
request id, so the same derived seed), and same-seed loadgen reruns all
reproduce token-for-token (docs/SERVING.md replay contract; the chaos
drill in tests/test_router.py pins it).

`philox_uniform_host` is the numpy mirror of the device stream — the
unit tests pin the two against each other so the device implementation
can never drift silently.

The draw needs each row sorted, and the sort carries the values: one
stable `lax.sort` of (negated logits, indices) gives the sorted row and
the order together. Nothing of `[S, V]` is gathered afterwards (on a TPU
an element gather of that size cost ten times the sort itself, PERF.md
section 6, PR 27); the one gather left picks `S` tokens out of the order.
"""
from __future__ import annotations

import hashlib

import numpy as np

__all__ = ["SamplingParams", "sample_tokens", "seed_to_key",
           "derive_seed", "philox_uniform_host"]

# Philox4x32 round/bump constants (Salmon et al., SC'11)
_M0 = 0xD2511F53
_M1 = 0xCD9E8D57
_W0 = 0x9E3779B9
_W1 = 0xBB67AE85


class SamplingParams:
    """Validated wire/request sampling knobs. temperature == 0 means
    greedy (top_k/top_p ignored); seed None means "derive from the
    request id" (frontend.py), which is exactly what makes replays
    byte-identical without the client ever choosing a seed."""

    __slots__ = ("temperature", "top_k", "top_p", "seed")

    def __init__(self, temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: int | None = None):
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = None if seed is None else int(seed)
        if self.temperature < 0:
            raise ValueError("temperature must be >= 0")
        if self.top_k < 0:
            raise ValueError("top_k must be >= 0 (0 = disabled)")
        if not 0 < self.top_p <= 1:
            raise ValueError("top_p must be in (0, 1]")

    @classmethod
    def from_request(cls, req: dict) -> "SamplingParams":
        return cls(temperature=req.get("temperature", 0.0),
                   top_k=req.get("top_k", 0),
                   top_p=req.get("top_p", 1.0),
                   seed=req.get("seed"))

    def to_request(self, out: dict) -> dict:
        """Write non-default knobs into a wire request dict."""
        if self.temperature > 0:
            out["temperature"] = self.temperature
        if self.top_k > 0:
            out["top_k"] = self.top_k
        if self.top_p < 1.0:
            out["top_p"] = self.top_p
        if self.seed is not None:
            out["seed"] = self.seed
        return out


def derive_seed(request_id) -> int:
    """Stable 64-bit seed from a request identity. The router relays
    the ORIGINAL wire request id on failover (exactly-once relay), so
    every replica derives the same seed for the same logical request."""
    h = hashlib.blake2b(str(request_id).encode(), digest_size=8)
    return int.from_bytes(h.digest(), "big")


def seed_to_key(seed: int) -> np.ndarray:
    """64-bit seed -> uint32[2] Philox key (lo, hi)."""
    s = int(seed) & 0xFFFFFFFFFFFFFFFF
    return np.array([s & 0xFFFFFFFF, s >> 32], np.uint32)


def _mulhilo(xp, a, b):
    """Full 32x32->64 product in uint32 lanes: (hi, lo)."""
    m16 = xp.uint32(0xFFFF)
    al, ah = a & m16, a >> xp.uint32(16)
    bl, bh = b & m16, b >> xp.uint32(16)
    lo = (a * b).astype(xp.uint32)       # wraps mod 2^32
    t = ah * bl + ((al * bl) >> xp.uint32(16))
    t2 = al * bh + (t & m16)
    hi = ah * bh + (t >> xp.uint32(16)) + (t2 >> xp.uint32(16))
    return hi, lo


def _philox4(xp, k0, k1, c0, c1, c2, c3):
    """Ten Philox4x32 rounds; all args uint32 arrays (broadcastable)."""
    for _ in range(10):
        hi0, lo0 = _mulhilo(xp, xp.uint32(_M0), c0)
        hi1, lo1 = _mulhilo(xp, xp.uint32(_M1), c2)
        c0, c1, c2, c3 = hi1 ^ c1 ^ k0, lo1, hi0 ^ c3 ^ k1, lo0
        k0 = k0 + xp.uint32(_W0)
        k1 = k1 + xp.uint32(_W1)
    return c0


def _uniform(xp, seeds, steps):
    """One float32 uniform in [0, 1) per lane from key=(seed lo, hi),
    counter=(step, 0, 0, 0). seeds [..., 2] uint32, steps [...] int."""
    step = steps.astype(xp.uint32)
    zero = xp.zeros_like(step)
    x = _philox4(xp, seeds[..., 0], seeds[..., 1], step, zero, zero,
                 zero)
    # top 24 bits -> [0, 1): exact in float32
    return (x >> xp.uint32(8)).astype(xp.float32) \
        * xp.float32(1.0 / (1 << 24))


def philox_uniform_host(seed: int, step: int) -> float:
    """Numpy mirror of the device stream (tests pin device == host)."""
    key = seed_to_key(seed)
    with np.errstate(over="ignore"):
        u = _uniform(np, key.reshape(1, 2),
                     np.asarray([step], np.int64))
    return float(u[0])


def sample_tokens(logits, temps, topks, topps, seeds, steps):
    """One token per slot, inside the jitted decode body.

    logits [S, V] f32; temps/topps [S] f32; topks/steps [S] i32;
    seeds [S, 2] u32. Returns [S] i32.

    temperature 0 -> plain argmax (the pre-existing greedy path,
    selected per slot so greedy and sampled requests share one decode
    program). temperature > 0: scale, keep the top-k logits and the
    top-p nucleus (the crossing token included), then one inverse-CDF
    draw with the slot's (seed, step) uniform.
    """
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    V = logits.shape[-1]
    scaled = logits / jnp.where(temps > 0, temps, 1.0)[:, None]
    # descending by the negated key, ties by index (stable): the sorted
    # row is the first result negated back, not a gather through `order`
    iota = jax.lax.broadcasted_iota(jnp.int32, scaled.shape, 1)
    neg_sl, order = jax.lax.sort((-scaled, iota), dimension=1, num_keys=1,
                                 is_stable=True)
    sl = -neg_sl
    probs = jax.nn.softmax(sl, axis=-1)
    k_eff = jnp.where(topks > 0, jnp.clip(topks, 1, V), V)
    rank = jnp.arange(V, dtype=jnp.int32)[None, :]
    csum = jnp.cumsum(probs, axis=-1)
    # nucleus: keep while the mass BEFORE a token is < top_p, which
    # always includes the crossing token (and rank 0)
    keep = (rank < k_eff[:, None]) \
        & ((csum - probs) < topps[:, None])
    w = jnp.where(keep, probs, 0.0)
    cdf = jnp.cumsum(w, axis=-1)
    u = _uniform(jnp, seeds, steps)
    target = u * cdf[:, -1]
    pick = jnp.sum((cdf <= target[:, None]).astype(jnp.int32), axis=-1)
    pick = jnp.clip(pick, 0, V - 1)   # u*total rounding up to total
    sampled = jnp.take_along_axis(order, pick[:, None],
                                  axis=-1)[:, 0].astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)
