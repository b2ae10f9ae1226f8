#!/usr/bin/env python3
"""Step 0 of PR 35: `models.gpt.decoder_tail` ALONE inside the loop that
serving runs it in, a `lax.scan` over 24 stacked layers of GPT-3 XL's
`wo [2048,2048]`, `w_up [2048,8192]`, `w_down [8192,2048]` in bf16, at the
rows the serving bodies hand it (32 = the decode batch; 64-1536 = the
prefill buckets), three ways:

  kernels  `fused_out_ln` and `fused_ffn_ln` forced (both `*_wins` say yes)
  chain    `cfg.fused_blocks=False`: the composed bf16 chain, what
           `GPTDecodeModel` asks for since this PR
  gate     `cfg.fused_blocks=True` and whatever `ops/autobench.py` draws
           for the two keys of that row count on this machine, this run

The gate times a candidate alone on arrays of its own; here the weights
arrive as the scan's slice of the stack, as they do in serving. Device
time per scan is read from a profiler trace (the program's event on the
line "XLA Modules", median over `--reps`), with the operations inside it
that make an array of a layer's weight shape, by name: those are the
copies. Where the gate draws what another way forces, its scan IS that
way's program (one executable, under the name it was first compiled
with) and the table says so. Nothing here ships.

    python3 scripts/pr35_tail_step0.py --out chiprun_out/pr35/step0.json   # on the chip
    JAX_PLATFORMS=cpu python3 scripts/pr35_tail_step0.py --compile         # here: the chip's compiler, no chip
    JAX_PLATFORMS=cpu PADDLE_TPU_PALLAS_INTERPRET=1 python3 scripts/pr35_tail_step0.py --rehearse
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import re
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp

from paddle_tpu.models.gpt import GPTConfig, decoder_tail
from paddle_tpu.ops import pallas_block

ROWS = (32, 64, 128, 256, 512, 1024, 1536)
WAYS = ("kernels", "chain", "gate")
_WINS = (pallas_block.out_ln_wins, pallas_block.ffn_ln_wins)


def stack_shapes(L, D, F):
    return {"wo": (L, D, D), "bo": (L, D), "w_up": (L, D, F),
            "b_up": (L, F), "w_down": (L, F, D), "b_down": (L, D),
            "ln2_s": (L, D), "ln2_b": (L, D)}


def make_stack(L, D, F, dt, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    out = {}
    for k, (name, shape) in zip(keys, stack_shapes(L, D, F).items()):
        if name == "ln2_s":
            out[name] = jnp.ones(shape, dt)
        else:
            out[name] = (0.02 * jax.random.normal(k, shape,
                                                  jnp.float32)).astype(dt)
    return out


def scan_of_tails(cfg):
    """x [M, D], a [M, D] through every layer's tail, as
    `GPTDecodeModel._layers` runs it (the attention's output is `a`
    throughout: it is not the tail's)."""
    def run(stack, a, x):
        def body(x, p):
            return decoder_tail(p, a, x, cfg), None
        return jax.lax.scan(body, x, stack)[0]
    return run


def the_way(way, cfg):
    """(cfg, wins) for one of WAYS: `wins` replaces both gates while the
    program is traced, None leaves them."""
    if way == "chain":
        return dataclasses.replace(cfg, fused_blocks=False), None
    fused = dataclasses.replace(cfg, fused_blocks=True)
    return fused, ((lambda *a, **k: True) if way == "kernels" else None)


def traced_with(wins, fn):
    """`fn()` with both `*_wins` replaced (decoder_tail imports them from
    the module when it is traced)."""
    if wins is not None:
        pallas_block.out_ln_wins = pallas_block.ffn_ln_wins = wins
    try:
        return fn()
    finally:
        pallas_block.out_ln_wins, pallas_block.ffn_ln_wins = _WINS


def gate_draws(m, D, F, dt, eps):
    """What the gate says for this row count, as decoder_tail asks it."""
    it = jnp.dtype(dt).itemsize
    out = {}
    out["fused_out_ln"] = (
        "refused" if not pallas_block.can_use_fused_out_ln(m, D, D, it)
        else "pallas" if pallas_block.out_ln_wins(m, D, D, dt, 0.0, eps)
        else "xla")
    out["fused_ffn_ln"] = (
        "refused" if not pallas_block.can_use_fused_ffn_ln(m, D, F, it)
        else "pallas" if pallas_block.ffn_ln_wins(m, D, F, dt, "gelu_tanh",
                                                  "none")
        else "xla")
    return out


def same_as(draw):
    """The way whose program the gate's draw spells, if it is one."""
    took = set(draw.values()) - {"refused"}
    return {("pallas",): "kernels", ("xla",): "chain"}.get(
        tuple(took), "neither")


def weight_shaped(D, F):
    """Matches a trace or HLO name whose RESULT is one layer's weight."""
    shapes = "|".join(f"{a},{b}" for a, b in ((D, D), (D, F), (F, D)))
    return re.compile(r"= \w+\[(" + shapes + r")\]")


def read_trace(logdir, names, D, F):
    """{program name: {"ms": [per execution], "ops": {op name: seconds}}}
    from the device's lines."""
    from benchmark.lib import trace as tr
    data = tr.load_xplane(tr.find_xplane(logdir), keep_host=lambda n: False)
    mods, ops = [], []
    for plane in data["planes"]:
        if not tr.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            (mods if line["name"] == tr.MODULES_LINE else ops).extend(
                line["events"])
    out = {n: {"ms": [], "ops": collections.Counter(),
               "copies": collections.Counter()} for n in names}
    spans = []
    for name, s, d in mods:
        for n in names:
            if re.match(rf"jit_{re.escape(n)}(\(|$)", name):
                out[n]["ms"].append(d / 1e6)
                spans.append((s, s + d, n))
    spans.sort()
    is_weight = weight_shaped(D, F)
    ops.sort(key=lambda e: e[1])
    j = 0
    for name, s, d in ops:
        if tr.CONTAINER.match(name) or d <= 0:
            continue
        while j < len(spans) and spans[j][1] <= s:
            j += 1
        if j == len(spans) or not spans[j][0] <= s < spans[j][1]:
            continue
        rec = out[spans[j][2]]
        short = name.split(" = ")[0].lstrip("%") + " " + \
            (re.search(r"= (\w+\[[\d,]*\])", name) or [None, ""])[1]
        rec["ops"][short] += d
        if is_weight.search(name):
            rec["copies"][short] += d
    return out


def on_the_chip(args):
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        sys.exit(f"needs a TPU (found {dev.platform}); --rehearse or "
                 f"--compile here")
    L, D, F = args.layers, args.hidden, args.ffn
    dt = jnp.bfloat16
    cfg = GPTConfig(hidden_size=D, num_layers=L, num_heads=max(D // 128, 1),
                    intermediate_size=F, amp_dtype="bfloat16")
    stack = make_stack(L, D, F, dt)
    jax.block_until_ready(stack)
    rows = [int(m) for m in args.rows.split(",")]
    programs, draws = {}, {}
    for m in rows:
        draws[m] = gate_draws(m, D, F, dt, cfg.layer_norm_eps)
        for way in WAYS:
            wcfg, wins = the_way(way, cfg)
            fn = scan_of_tails(wcfg)
            fn.__name__ = f"tail_{way}_m{m}"
            x = (0.5 * jax.random.normal(jax.random.PRNGKey(m), (m, D),
                                         jnp.float32)).astype(dt)
            a = (0.5 * jax.random.normal(jax.random.PRNGKey(m + 1), (m, D),
                                         jnp.float32)).astype(dt)
            jitted = jax.jit(fn)
            t0 = time.perf_counter()
            y = traced_with(wins, lambda: jitted(stack, a, x))
            jax.block_until_ready(y)
            programs[fn.__name__] = (jitted, a, x, y,
                                     time.perf_counter() - t0)
        print(f"rows {m}: the gate draws {draws[m]}", flush=True)
    # the three ways compute one function: say how far apart they are
    gaps = {}
    for m in rows:
        ref = programs[f"tail_chain_m{m}"][3].astype(jnp.float32)
        scale = float(jnp.max(jnp.abs(ref)))
        gaps[m] = {w: float(jnp.max(jnp.abs(
            programs[f"tail_{w}_m{m}"][3].astype(jnp.float32) - ref)))
            / scale for w in ("kernels", "gate")}
    logdir = tempfile.mkdtemp(prefix="pr35_step0_")
    host = {}
    jax.profiler.start_trace(logdir)
    try:
        for name, (jitted, a, x, _y, _t) in programs.items():
            ts = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(jitted(stack, a, x))
                ts.append((time.perf_counter() - t0) * 1e3)
            host[name] = statistics.median(ts)
    finally:
        jax.profiler.stop_trace()
    table = []
    if dev.platform == "tpu":
        read = read_trace(logdir, list(programs), D, F)
    else:       # a rehearsal has no device line: the host's clock, and said so
        read = {n: {"ms": [], "ops": {}, "copies": {}} for n in programs}
    weights_gb = L * (D * D + 2 * D * F) * 2 / 1e9
    weights_ms = weights_gb / 819 * 1e3
    print(f"\n{L} layers of wo/w_up/w_down [{D},{D}] [{D},{F}] [{F},{D}] "
          f"bf16 = {weights_gb:.3f} GB: "
          f"{weights_ms:.3f} ms at 819 GB/s; device "
          f"{dev.platform} {dev.device_kind}; ms a scan, median of "
          f"{args.reps} (device time from the trace; host clock beside it)")
    print(f"{'rows':>5} | " + " | ".join(f"{w:>22}" for w in WAYS)
          + " | gate's draw (out_ln, ffn_ln) | kernels/chain")
    for m in rows:
        cells, row = [], {"rows": m, "gate": draws[m], "gap": gaps[m]}
        for way in WAYS:
            n = f"tail_{way}_m{m}"
            ms = read[n]["ms"]
            dev_ms = statistics.median(ms) if ms else None
            row[way] = {
                "device_ms": dev_ms, "host_ms": host[n],
                "executions": len(ms), "compile_s": programs[n][4],
                "copies_ms_a_scan": {
                    k: v / 1e6 / max(len(ms), 1)
                    for k, v in read[n]["copies"].items()},
                "top_ops_ms_a_scan": {
                    k: v / 1e6 / max(len(ms), 1) for k, v in
                    collections.Counter(read[n]["ops"]).most_common(8)}}
            # a program the compile cache already holds runs under the
            # name it was first compiled with: the gate's scan, where it
            # drew what another way forces, is that way's program
            cells.append((f"{dev_ms:9.3f}" if dev_ms is not None
                          else f"= {same_as(draws[m])}"
                          if way == "gate" and dev.platform == "tpu"
                          else "no trace")
                         + f" ({host[n]:9.3f})")
        k, c = row["kernels"]["device_ms"], row["chain"]["device_ms"]
        ratio = f"{k / c:.3f}" if k and c else "n/a"
        print(f"{m:>5} | " + " | ".join(f"{c:>22}" for c in cells)
              + f" | {draws[m]['fused_out_ln']}, {draws[m]['fused_ffn_ln']}"
              + f" | {ratio}")
        table.append(row)
    for row in table:
        for way in WAYS:
            cp = row[way]["copies_ms_a_scan"]
            if cp:
                print(f"rows {row['rows']} {way}: makes a layer's weight: "
                      + ", ".join(f"{k} {v:.3f} ms" for k, v in cp.items()))
    print("widest |kernels - chain| and |gate - chain| over the largest "
          "|chain|: " + ", ".join(
              f"{m}: {g['kernels']:.2e} / {g['gate']:.2e}"
              for m, g in gaps.items()))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": f"{dev.platform} {dev.device_kind}",
                       "rehearsal": dev.platform != "tpu",
                       "layers": L, "hidden": D, "ffn": F,
                       "weights_ms_at_819GBs": weights_ms,
                       "rows": table}, f, indent=1)
        print("wrote", args.out)


def compiled_for_v5e(args):
    """The chip's compiler on the three ways at the real size, no chip:
    which instructions of the loop's body make an array of a layer's
    weight shape (a copy of the slice), and whether the kernels are
    there."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    pallas_block.on_tpu = lambda: True      # the kernel for the chip: here only
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    L, D, F = args.layers, args.hidden, args.ffn
    dt = jnp.bfloat16
    cfg = GPTConfig(hidden_size=D, num_layers=L, num_heads=max(D // 128, 1),
                    intermediate_size=F, amp_dtype="bfloat16")
    stack = {n: jax.ShapeDtypeStruct(s, dt, sharding=chip)
             for n, s in stack_shapes(L, D, F).items()}
    is_weight = weight_shaped(D, F)
    for m in (int(r) for r in args.rows.split(",")):
        x = jax.ShapeDtypeStruct((m, D), dt, sharding=chip)
        for way in ("kernels", "chain"):
            wcfg, wins = the_way(way, cfg)
            fn = scan_of_tails(wcfg)
            t0 = time.perf_counter()
            text = traced_with(
                wins, lambda: jax.jit(fn).lower(stack, x, x).compile()
            ).as_text()
            # an instruction of a fused computation is part of its fusion;
            # one of the loop's body or the entry is an operation of its own
            made, fused = collections.Counter(), False
            for line in text.splitlines():
                if line.endswith("{") and not line.startswith(" "):
                    fused = line.startswith("%fused_computation")
                line = line.strip()
                if not fused and is_weight.search(line) \
                        and " parameter(" not in line:
                    head = line.split("(")[0].removeprefix("ROOT ")
                    made[re.sub(r"\{[^}]*\}", "", head)] += 1
            print(f"rows {m} {way}: compiled in "
                  f"{time.perf_counter() - t0:.1f}s; tpu_custom_call "
                  f"{text.count('tpu_custom_call')}x; instructions whose "
                  f"result is a layer's weight: "
                  f"{dict(made) if made else 'none'}", flush=True)
            if args.text:
                os.makedirs(args.text, exist_ok=True)
                with open(os.path.join(args.text,
                                       f"tail_{way}_m{m}.hlo.txt"), "w") as f:
                    f.write(text)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default=",".join(map(str, ROWS)))
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--ffn", type=int, default=8192)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--out", default="")
    ap.add_argument("--text", default="",
                    help="--compile: directory for each program's HLO")
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on whatever device is here: the "
                         "control flow only, never a time")
    args = ap.parse_args()
    if args.rehearse:
        args.layers, args.hidden, args.ffn = 2, 128, 256
        args.rows, args.reps = "8,16", 2
    if args.compile:
        compiled_for_v5e(args)
    else:
        on_the_chip(args)


if __name__ == "__main__":
    main()
