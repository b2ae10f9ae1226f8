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
drill in tests/test_router.py pins it). That is a promise WITHIN ONE
BUILD, on any replica of it: the same program adds the same float32
masses in the same order. Across builds (another compiler, another
fusion of the same sums) a draw whose target all but touches an edge of
the CDF may fall to the neighbouring token; greedy tokens are the
`argmax` in every build.

`philox_uniform_host` is the numpy mirror of the device stream — the
unit tests pin the two against each other so the device implementation
can never drift silently.

The draw is defined on each row's order "scaled logit descending, lower
index first among equals", and that order is never laid out: no sort, no
prefix sum and no gather over `[S, V]` (on a TPU v5e the sort of
`[64, 128256]` alone took 10 ms of a 27 ms decode step, PERF.md section
6, PR 33). The count and the mass in front of a place are monotone in
the place, so the two places the sampler needs, where top-k / top-p cut
the row and where the kept mass passes the uniform's target, are found
by searching the 32 bits of an order-preserving key of the float, two
bits a pass, each pass one fused masked reduction over `scaled`; equals
are told apart by index (arithmetic a row, then a search over the
index's bits). The passes are as many for every row and every step.
"""
from __future__ import annotations

import functools
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


_KEY_BITS = 32          # a float32's order fits its own 32 bits
# bits settled by one pass over [S, V]; divides _KEY_BITS. On a v5e at
# [64, 128256]: 1 bit 1.64 ms, 2 bits 1.44, 4 bits 4.87 (the sort 11.5;
# scripts/sampler_step0.py, PERF.md section 6, PR 33)
_DIGIT = 2
assert _KEY_BITS % _DIGIT == 0


def _order_keys(scaled):
    """int32 keys that order as the floats do, -0.0 beside +0.0."""
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scaled == 0, jnp.float32(0), scaled), jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _signed(t):
    """A threshold of the search (uint32, 0 lowest) as `_order_keys`
    holds it."""
    import jax
    import jax.numpy as jnp
    return jax.lax.bitcast_convert_type(t ^ jnp.uint32(0x80000000),
                                        jnp.int32)


def _key_value(t):
    """The float32 whose key is `t` (of a tie at zero, +0.0)."""
    import jax
    import jax.numpy as jnp
    k = _signed(t)
    return jax.lax.bitcast_convert_type(
        jnp.where(k < 0, k ^ jnp.int32(0x7FFFFFFF), k), jnp.float32)


def _search(width, probe, holds, at, above):
    """The largest t in [0, 2**width) a row for which `holds(t, probe(t))`,
    given that it holds at 0 and nowhere above the first t at which it
    fails. `_DIGIT` bits a pass: `probe` takes the 2**_DIGIT - 1
    candidates of a row at once, [S, D] uint32, and returns a tuple of
    [S, D] readings. Also returned: the readings at t (`at` where no
    probe held) and at t + 1 (`above` where none failed); the search
    passes both on its way, whatever the row. A `fori_loop`: the passes
    are compiled once and are as many for every row."""
    import jax
    import jax.numpy as jnp
    D = (1 << _DIGIT) - 1
    passes = -(-width // _DIGIT)
    digits = jnp.arange(1, D + 1, dtype=jnp.uint32)[None, :]

    def one_pass(i, carry):
        t, at, above = carry
        shift = (_DIGIT * (passes - 1 - i)).astype(jnp.uint32)
        cand = t[:, None] | (digits << shift)
        got = probe(cand)
        n = jnp.sum(holds(cand, got), axis=-1, dtype=jnp.int32)
        col = jnp.arange(D, dtype=jnp.int32)[None, :]

        def reading(col_of_row, old, new):
            hit = col == col_of_row[:, None]
            return jnp.where(jnp.any(hit, axis=-1),
                             jnp.sum(jnp.where(hit, new, 0), axis=-1,
                                     dtype=new.dtype), old)
        at = tuple(reading(n - 1, o, g) for o, g in zip(at, got))
        above = tuple(reading(n, o, g) for o, g in zip(above, got))
        return t | (n.astype(jnp.uint32) << shift), at, above

    t0 = jnp.zeros(at[0].shape, jnp.uint32)
    return jax.lax.fori_loop(0, passes, one_pass, (t0, at, above))


def _multiples_below(base, p, level, strict, width):
    """How many j >= 1 keep `base + j * p` below `level` (`<` if strict,
    else `<=`), at most 2**width - 1: float32 as the masses are, a search
    over [S] alone, unrolled."""
    import jax.numpy as jnp
    j = jnp.zeros(base.shape, jnp.int32)
    for bit in reversed(range(width)):
        cand = j | (1 << bit)
        mass = base + cand.astype(jnp.float32) * p
        j = jnp.where((mass < level) if strict else (mass <= level),
                      cand, j)
    return j


def sample_tokens(logits, temps, topks, topps, seeds, steps):
    """One token per slot, inside the jitted decode body.

    logits [S, V] f32; temps/topps [S] f32; topks/steps [S] i32;
    seeds [S, 2] u32. Returns [S] i32.

    temperature 0 -> plain argmax (the pre-existing greedy path,
    selected per slot so greedy and sampled requests share one decode
    program). temperature > 0: scale, keep the top-k logits and the
    top-p nucleus (the crossing token included), then one inverse-CDF
    draw with the slot's (seed, step) uniform: in the order "scaled
    logit descending, lower index first among equals", found by search
    and never laid out. The one `[S, V]` array written is `scaled`;
    every pass reads it and nothing else of that size.
    """
    return _jitted_sampler()(logits, temps, topks, topps, seeds, steps)


@functools.lru_cache(maxsize=None)
def _jitted_sampler():
    """`_sample` under a `jit` of its own: an engine's programs (a decode
    and a prefill a bucket, each traced twice at warm-up) then trace the
    sampler once a shape, `[S, V]` and `[1, V]`, not once a program (0.3 s
    each, a tenth of a GPT cell's warm set-up); the compiler inlines the
    call."""
    import jax
    return jax.jit(_sample)


def _sample(logits, temps, topks, topps, seeds, steps):
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    S, V = logits.shape
    assert V < 1 << 24, "the passes count tokens in float32"
    idx_bits = max(1, (V - 1).bit_length())
    scaled = logits / jnp.where(temps > 0, temps, 1.0)[:, None]
    m = jnp.max(scaled, axis=-1)
    z = jnp.sum(jnp.exp(scaled - m[:, None]), axis=-1)

    def prob(x, m, z):
        return jnp.exp(x - m) / z       # softmax's own two steps

    def at_or_above(cand):
        """Of each row, the tokens whose key is at least each candidate:
        how many, and their mass. One fused read of `scaled`."""
        key = _order_keys(scaled)
        p = prob(scaled, m[:, None], z[:, None])
        cnt, mass = [], []
        for d in range(cand.shape[-1]):
            ge = key >= _signed(cand[:, d])[:, None]
            # counted in float32 ones, exact below 2**24: count and mass
            # are then sums of one type and the compiler makes ONE
            # reduction of all of a pass's (a fifth faster on a v5e)
            cnt.append(jnp.sum(jnp.where(ge, 1.0, 0.0), axis=-1))
            mass.append(jnp.sum(jnp.where(ge, p, 0.0), axis=-1))
        return (jnp.stack(cnt, axis=-1).astype(jnp.int32),
                jnp.stack(mass, axis=-1))

    k_eff = jnp.where(topks > 0, jnp.clip(topks, 1, V), V)
    # a search's readings where no probe held (the whole row lies at or
    # above key 0) and where none failed (nothing lies above the last key)
    whole = (jnp.full((S,), V, jnp.int32), jnp.ones((S,), jnp.float32))
    none = (jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.float32))
    # the cut: the lowest key of which a token is kept is the largest t
    # whose tokens at or above it reach the k-th place or the mass top_p
    # (the token BEFORE which the mass is still < top_p is kept)
    t_cut, (cut_cnt, _), (above_cnt, above_mass) = _search(
        _KEY_BITS, at_or_above,
        lambda cand, got: (got[0] >= k_eff[:, None])
        | (got[1] >= topps[:, None]), whole, none)
    # equals at the cut are kept by index while both conditions hold
    p_cut = prob(_key_value(t_cut), m, z)
    kept_ties = jnp.minimum(
        jnp.minimum(cut_cnt, k_eff) - above_cnt,
        1 + _multiples_below(above_mass, p_cut, topps, True, idx_bits))
    total = above_mass + kept_ties.astype(jnp.float32) * p_cut
    u = _uniform(jnp, seeds, steps)
    target = u * total
    # the draw: the first token at which the kept mass, itself included,
    # passes the target lies among the equals of the largest key whose
    # tokens at or above it (kept ones) weigh more than the target
    t_draw, (draw_cnt, _), (before_cnt, before_mass) = _search(
        _KEY_BITS, at_or_above,
        lambda cand, got: jnp.where(cand > t_cut[:, None], got[1],
                                    total[:, None]) > target[:, None],
        whole, none)
    t_draw = jnp.maximum(t_draw, t_cut)     # u*total rounding up to total
    at_cut = t_draw == t_cut
    before_cnt = jnp.where(at_cut, above_cnt, before_cnt)
    before_mass = jnp.where(at_cut, above_mass, before_mass)
    equals = jnp.where(at_cut, kept_ties, draw_cnt - before_cnt)
    nth = jnp.minimum(
        _multiples_below(before_mass, prob(_key_value(t_draw), m, z),
                         target, False, idx_bits),
        equals - 1)
    # its index: the largest i with at most `nth` of the equals below it
    draw_key = _signed(t_draw)[:, None]

    def equals_below(cand):
        tie = _order_keys(scaled) == draw_key
        iota = jax.lax.broadcasted_iota(jnp.int32, scaled.shape, 1)
        return (jnp.stack([
            jnp.sum(tie & (iota < cand[:, d].astype(jnp.int32)[:, None]),
                    axis=-1, dtype=jnp.int32)
            for d in range(cand.shape[-1])], axis=-1),)

    sampled, _, _ = _search(
        idx_bits, equals_below,
        lambda cand, got: got[0] <= nth[:, None], none[:1], none[:1])
    sampled = jnp.minimum(sampled.astype(jnp.int32), V - 1)
    return jnp.where(temps > 0, sampled, greedy)
