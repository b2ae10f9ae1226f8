#!/usr/bin/env python3
"""Step 0 of PR 33: the sampler ALONE, at the serving cells' shapes
(`[64,128256]` kanana, `[64,65536]` lfm2, `[32,50304]` GPT, `[16,49152]`
ouro, and the prefill programs' `[1,128256]`), the sorting spelling the
parent ships beside the searching ones. Inputs as the cells' random
weights give them: logits N(0, 0.9^2), every other slot greedy, the rest
temperature 0.8 / top-p 0.9 / top-k 0.

Candidates (one ships, `paddle_tpu/serving/sampling.py`; the others live
here only):

  sort       the parent's: `lax.sort((-scaled, iota))`, two prefix sums.
  shipped    `sampling.sample_tokens` as the tree has it.
  b<k>       the search, k bits a pass (2**k - 1 thresholds a fused
             reduction), `exp` and the key recomputed in the pass, which
             reads `scaled` alone.               (ISSUE 33: (i) is b1, (ii) b4)
  b<k>.kp    the same, the key and the probability laid out once as two
             `[S, V]` arrays that every pass reads.
  b<k>.mul   the same as b<k>, `e * (1 / Z)` for `e / Z`.
  b<k>.fc    the same as b<k>, the count summed as float32 ones (exact
             below 2**24) so that count and mass are one reduction's type.

For each: ms a call (median of `--reps`), the draws that differ from the
sorting spelling's, and for every differing draw how many places apart the
two tokens lie in the sorted order, how far, in float32 ulps of the kept
mass, the target lies from the CDF edge between them, and which of the two
the same sampler in float64 draws (numpy, on the host). `--compile` here (no chip): the chip's compiler on each candidate
at `[64,128256]`, its temporaries and what the loop bodies read.

    python3 scripts/sampler_step0.py --out chiprun_out/pr33/step0.json   # on the chip
    JAX_PLATFORMS=cpu python3 scripts/sampler_step0.py --compile
    JAX_PLATFORMS=cpu python3 scripts/sampler_step0.py --rehearse
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.serving import sampling

SHAPES = ((64, 128256), (64, 65536), (32, 50304), (16, 49152), (1, 128256))


def sorting(logits, temps, topks, topps, seeds, steps):
    """`sample_tokens` as the parent (PR 32) has it, body verbatim."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    V = logits.shape[-1]
    scaled = logits / jnp.where(temps > 0, temps, 1.0)[:, None]
    iota = jax.lax.broadcasted_iota(jnp.int32, scaled.shape, 1)
    neg_sl, order = jax.lax.sort((-scaled, iota), dimension=1, num_keys=1,
                                 is_stable=True)
    sl = -neg_sl
    probs = jax.nn.softmax(sl, axis=-1)
    k_eff = jnp.where(topks > 0, jnp.clip(topks, 1, V), V)
    rank = jnp.arange(V, dtype=jnp.int32)[None, :]
    csum = jnp.cumsum(probs, axis=-1)
    keep = (rank < k_eff[:, None]) \
        & ((csum - probs) < topps[:, None])
    w = jnp.where(keep, probs, 0.0)
    cdf = jnp.cumsum(w, axis=-1)
    u = sampling._uniform(jnp, seeds, steps)
    target = u * cdf[:, -1]
    pick = jnp.sum((cdf <= target[:, None]).astype(jnp.int32), axis=-1)
    pick = jnp.clip(pick, 0, V - 1)
    sampled = jnp.take_along_axis(order, pick[:, None],
                                  axis=-1)[:, 0].astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def searching(bits, laid_out=False, mul=False, fcount=False):
    """`sampling.sample_tokens`'s search with the pass's width, what a pass
    reads and the normalisation as parameters. `bits` is read by
    `sampling._search` when it is traced: `its_bits` sets it."""
    _search, _signed = sampling._search, sampling._signed
    _keys, _value = sampling._order_keys, sampling._key_value
    _multiples = sampling._multiples_below

    def f(logits, temps, topks, topps, seeds, steps):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        S, V = logits.shape
        idx_bits = max(1, (V - 1).bit_length())
        scaled = logits / jnp.where(temps > 0, temps, 1.0)[:, None]
        m = jnp.max(scaled, axis=-1)
        z = jnp.sum(jnp.exp(scaled - m[:, None]), axis=-1)
        rz = 1.0 / z

        def prob(x, m=m, z=z, rz=rz):
            e = jnp.exp(x - m)
            return e * rz if mul else e / z

        if laid_out:
            key_all = _keys(scaled)
            p_all = prob(scaled, m[:, None], z[:, None], rz[:, None])

        def at_or_above(cand):
            key = key_all if laid_out else _keys(scaled)
            p = p_all if laid_out else prob(scaled, m[:, None], z[:, None],
                                            rz[:, None])
            cnt, mass = [], []
            for d in range(cand.shape[-1]):
                ge = key >= _signed(cand[:, d])[:, None]
                cnt.append(jnp.sum(jnp.where(ge, 1.0, 0.0), axis=-1)
                           .astype(jnp.int32) if fcount else
                           jnp.sum(ge, axis=-1, dtype=jnp.int32))
                mass.append(jnp.sum(jnp.where(ge, p, 0.0), axis=-1))
            return jnp.stack(cnt, axis=-1), jnp.stack(mass, axis=-1)

        k_eff = jnp.where(topks > 0, jnp.clip(topks, 1, V), V)
        zero_i = jnp.zeros((S,), jnp.int32)
        zero_f = jnp.zeros((S,), jnp.float32)
        whole = (jnp.full((S,), V, jnp.int32), jnp.ones((S,), jnp.float32))
        t_cut, (cut_cnt, _), (above_cnt, above_mass) = _search(
            32, at_or_above,
            lambda cand, got: (got[0] >= k_eff[:, None])
            | (got[1] >= topps[:, None]), whole, (zero_i, zero_f))
        p_cut = prob(_value(t_cut))
        kept_ties = jnp.minimum(
            jnp.minimum(cut_cnt, k_eff) - above_cnt,
            1 + _multiples(above_mass, p_cut, topps, True, idx_bits))
        total = above_mass + kept_ties.astype(jnp.float32) * p_cut
        target = sampling._uniform(jnp, seeds, steps) * total
        t_draw, (draw_cnt, _), (before_cnt, before_mass) = _search(
            32, at_or_above,
            lambda cand, got: jnp.where(cand > t_cut[:, None], got[1],
                                        total[:, None]) > target[:, None],
            whole, (zero_i, zero_f))
        t_draw = jnp.maximum(t_draw, t_cut)
        at_cut = t_draw == t_cut
        before_cnt = jnp.where(at_cut, above_cnt, before_cnt)
        before_mass = jnp.where(at_cut, above_mass, before_mass)
        equals = jnp.where(at_cut, kept_ties, draw_cnt - before_cnt)
        nth = jnp.minimum(
            _multiples(before_mass, prob(_value(t_draw)), target, False,
                       idx_bits), equals - 1)
        draw_key = _signed(t_draw)[:, None]

        def equals_below(cand):
            key = key_all if laid_out else _keys(scaled)
            tie = key == draw_key
            iota = jax.lax.broadcasted_iota(jnp.int32, scaled.shape, 1)
            return (jnp.stack([
                jnp.sum(tie & (iota < cand[:, d].astype(jnp.int32)[:, None]),
                        axis=-1, dtype=jnp.int32)
                for d in range(cand.shape[-1])], axis=-1),)

        sampled, _, _ = _search(
            idx_bits, equals_below,
            lambda cand, got: got[0] <= nth[:, None], (zero_i,), (zero_i,))
        sampled = jnp.minimum(sampled.astype(jnp.int32), V - 1)
        return jnp.where(temps > 0, sampled, greedy)
    f.bits = bits
    return f


def candidates(names):
    every = {"sort": sorting, "shipped": sampling.sample_tokens}
    for b in (1, 2, 4):     # 32 key bits in whole digits
        every[f"b{b}"] = searching(b)
        every[f"b{b}.kp"] = searching(b, laid_out=True)
        every[f"b{b}.mul"] = searching(b, mul=True)
        every[f"b{b}.fc"] = searching(b, fcount=True)
    return {n: every[n] for n in names}


@contextlib.contextmanager
def its_bits(fn):
    """`sampling._DIGIT` at the candidate's width while it is traced."""
    shipped = sampling._DIGIT
    sampling._DIGIT = getattr(fn, "bits", shipped)
    try:
        yield
    finally:
        sampling._DIGIT = shipped


def jitted_with_its_bits(fn):
    jitted = jax.jit(fn)

    def call(*args):
        with its_bits(fn):
            return jitted(*args)

    def lower(*args):
        with its_bits(fn):
            return jitted.lower(*args)
    call.lower = lower
    return call


def inputs(S, V, seed, sigma=0.9):
    """Logits drawn on the device; the cells' sampling parameters."""
    logits = sigma * jax.random.normal(jax.random.PRNGKey(seed), (S, V),
                                       jnp.float32)
    rng = np.random.default_rng([S, V, seed])
    temps = np.where(np.arange(S) % 2 == 0, 0.8, 0.0) if S > 1 \
        else np.asarray([0.8])
    return (logits, jnp.asarray(temps, jnp.float32),
            jnp.zeros((S,), jnp.int32), jnp.full((S,), 0.9, jnp.float32),
            jnp.asarray(rng.integers(0, 2 ** 32, size=(S, 2),
                                     dtype=np.uint32)),
            jnp.full((S,), seed, jnp.int32))


def how_far(logits_row, temp, top_p, seed, step, tok_a, tok_b):
    """A differing draw, in float64 on the host: how many places apart the
    two tokens lie in the sorted order, and how far the target lies from
    the nearest CDF edge between them, in float32 ulps of the kept mass; and
    which of the two float64 draws."""
    scaled = (logits_row.astype(np.float32) / np.float32(temp))
    order = np.lexsort((np.arange(scaled.size), -scaled))
    sl = scaled[order].astype(np.float64)
    p = np.exp(sl - sl[0])
    p /= p.sum()
    before = np.cumsum(p) - p
    w = np.where(before < np.float64(np.float32(top_p)), p, 0.0)
    cdf = np.cumsum(w)
    u = sampling.philox_uniform_host(
        int(seed[0]) | (int(seed[1]) << 32), int(step))
    target = u * cdf[-1]
    ra = int(np.nonzero(order == tok_a)[0][0])
    rb = int(np.nonzero(order == tok_b)[0][0])
    lo, hi = min(ra, rb), max(ra, rb)
    edges = cdf[lo:hi]
    ulp = float(np.spacing(np.float32(cdf[-1])))
    ref = int(order[min(int((cdf <= target).sum()), int((w > 0).sum()) - 1)])
    return {"places": hi - lo,
            "ulps_from_edge": float(np.min(np.abs(edges - target)) / ulp),
            "kept": int((w > 0).sum()),
            "float64_says": "sort" if ref == tok_a else
            "search" if ref == tok_b else "neither"}


def timed(names, shapes, reps, draws_steps):
    rows = []
    for S, V in shapes:
        fns = {n: jitted_with_its_bits(f)
               for n, f in candidates(names).items()}
        args = inputs(S, V, 0)
        row = {"shape": [S, V], "ms": {}, "differ": {}, "far": {}}
        for n, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            row.setdefault("first_call_s", {})[n] = round(
                time.perf_counter() - t0, 2)
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                ts.append(time.perf_counter() - t0)
            # back to back: the dispatch hides behind the call before
            t0 = time.perf_counter()
            outs = [fn(*args) for _ in range(reps)]
            jax.block_until_ready(outs)
            row["ms"][n] = {"one": round(1e3 * float(np.median(ts)), 4),
                            "queued": round(
                                1e3 * (time.perf_counter() - t0) / reps, 4)}
        # the draws: every candidate against the sorting spelling
        if "sort" in fns:
            n_draws = 0
            for step in range(draws_steps):
                a = inputs(S, V, 1000 + step)
                ref = np.asarray(fns["sort"](*a))
                temps = np.asarray(a[1])
                n_draws += int((temps > 0).sum())
                for n, fn in fns.items():
                    if n == "sort":
                        continue
                    got = np.asarray(fn(*a))
                    d = row["differ"].setdefault(
                        n, {"sampled": 0, "greedy": 0})
                    off = got != ref
                    d["sampled"] += int(off[temps > 0].sum())
                    d["greedy"] += int(off[temps == 0].sum())
                    if n == "shipped":
                        lg = None
                        for s in np.nonzero(off)[0]:
                            lg = np.asarray(a[0]) if lg is None else lg
                            row["far"].setdefault(n, []).append(how_far(
                                lg[s], temps[s], 0.9, np.asarray(a[4])[s],
                                1000 + step, int(ref[s]), int(got[s])))
            row["sampled_draws"] = n_draws
        print("STEP0 " + json.dumps(row), flush=True)
        rows.append(row)
    return rows


def compiled_for_v5e(names, shape):
    """The chip's compiler on each candidate, no chip: temporaries, and
    the `[S, V]`-sized operands of the fusions inside its loops."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    S, V = shape

    def sds(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one)
    args = (sds((S, V), jnp.float32), sds((S,), jnp.float32),
            sds((S,), jnp.int32), sds((S,), jnp.float32),
            sds((S, 2), jnp.uint32), sds((S,), jnp.int32))
    for n, f in candidates(names).items():
        t0 = time.perf_counter()
        c = jitted_with_its_bits(f).lower(*args).compile()
        text = c.as_text()
        mem = c.memory_analysis()
        wide = re.compile(r"(f32|s32|u32|pred)\[%d,%d\]" % (S, V))
        lines = [ln for ln in text.splitlines() if " fusion(" in ln
                 or " sort(" in ln or " while(" in ln]
        reads = sorted({(ln.split(" = ")[0].strip().split(" ")[-1],
                         len(wide.findall(ln.split(" = ", 1)[1])))
                        for ln in lines if wide.search(ln)})
        print("COMPILED " + json.dumps({
            "candidate": n, "shape": [S, V],
            "compile_s": round(time.perf_counter() - t0, 1),
            "temp_mb": round(mem.temp_size_in_bytes / 1e6, 1),
            "whiles": text.count(" while("), "sorts": text.count(" sort("),
            "wide_operands_or_results": reads[:40]}), flush=True)
        os.makedirs("chiprun_out/pr33", exist_ok=True)
        with open(f"chiprun_out/pr33/compiled_{n}_{S}x{V}.txt", "w") as fh:
            fh.write(text)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--candidates", default="sort,shipped,b1,b2,b4,b2.kp,"
                    "b2.mul,b1.fc,b2.fc,b4.fc")
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--draw-steps", type=int, default=40)
    ap.add_argument("--out")
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    names = a.candidates.split(",")
    if a.compile:
        return compiled_for_v5e(names, SHAPES[0])
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    if a.rehearse:
        rows = timed(names, ((8, 4099), (1, 4099)), 2, 3)
    else:
        if dev.platform != "tpu":
            sys.exit("needs a TPU (or --rehearse / --compile)")
        rows = timed(names, SHAPES, a.reps, a.draw_steps)
    if a.out:
        os.makedirs(os.path.dirname(a.out), exist_ok=True)
        with open(a.out, "w") as fh:
            json.dump({"device": dev.device_kind, "rehearsal": a.rehearse,
                       "rows": rows}, fh, indent=1)


if __name__ == "__main__":
    main()
