#!/usr/bin/env python3
"""Probes behind claims CHANGES.md / PERF.md make about the chip host.

    chiprun [--chips 4] -- python scripts/chip_probe.py processes
    chiprun --chips 4   -- python scripts/chip_probe.py mesh-kernels
    chiprun             -- python scripts/chip_probe.py dispatch

processes     One process per chip. The parent never imports jax. It prints
              what marks this host as a TPU host (device files, PCI ids,
              TPU_* environment), starts two unpinned jax children at once
              (the second should die on libtpu's lockfile), then lets the
              launcher start one `--serving_replicas` child per chip, and
              two on a one-chip host (pinned by TPU_VISIBLE_CHIPS: each
              sees one device, one numbered past the chips finds none and
              the launcher then stops the rest), and two trainer processes
              (refused).
mesh-kernels  What jax does with a Pallas kernel inside the partitioned
              pp=2 x tp=2 step (gpt_350m, four chips) when nothing wraps
              it: flash attention outside `sharding.kernel_mesh`, and the
              fused decoder-tail kernels forced on. Prints each error with
              the file:line that raised it, then the step as the trainer
              builds it (flash per shard, fused tail off).

dispatch      What one back-to-back dispatch of a trivial jitted program
              costs, by its number of output buffers (the floor under the
              kernel gate's clock, and under every engine step).

Exit code 0 means the probe ran; what it saw is in its output.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import json
import os
import subprocess
import sys
import time
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=REPO)


def say(*parts):
    print(*parts, flush=True)


def child():
    """What a chip-using process does first: ask jax for its devices and
    run one matmul; then hold the chip for a few seconds."""
    t0 = time.time()
    out = {"id": os.environ.get("PROBE_ID")
           or os.environ.get("PADDLE_TPU_REPLICA_ID")
           or os.environ.get("PADDLE_TRAINER_ID"),
           "visible": os.environ.get("TPU_VISIBLE_CHIPS")}
    try:
        import jax
        import jax.numpy as jnp
        x = jnp.ones((256, 256), jnp.bfloat16)
        out.update(ok=True, devices=[str(d) for d in jax.devices()],
                   sum=float(jnp.sum(x @ x)))
    except Exception as e:
        out.update(ok=False, error=f"{type(e).__name__}: {str(e)[:400]}")
    out["secs"] = round(time.time() - t0, 1)
    say(json.dumps(out))
    if out["ok"]:
        time.sleep(float(os.environ.get("PROBE_HOLD", "5")))
    return 0 if out["ok"] else 7


def run(tag, argv, timeout=180, **extra):
    t0 = time.time()
    try:
        r = subprocess.run(argv, env=dict(ENV, **extra), cwd=REPO,
                           capture_output=True, text=True, timeout=timeout)
        rc, out, err = r.returncode, r.stdout, r.stderr
    except subprocess.TimeoutExpired as e:
        rc, out, err = "TIMEOUT", (e.stdout or b"").decode(), \
            (e.stderr or b"").decode()
    err = "\n".join(ln for ln in err.splitlines()
                    if "hugepage" not in ln and "warnings.warn" not in ln)
    say(f"[{tag}] rc={rc} {time.time() - t0:.0f}s\n  stdout: "
        f"{out.strip()[-3000:]}\n  stderr: {err.strip()[-1500:]}")


def processes():
    me = [sys.executable, os.path.abspath(__file__), "child"]
    pci = [os.path.dirname(p) for p in glob.glob("/sys/bus/pci/devices/*/vendor")
           if open(p).read().strip() == "0x1ae0"]
    say("device files:", glob.glob("/dev/accel*"), glob.glob("/dev/vfio/*"))
    say("PCI devices of vendor 0x1ae0:",
        [(os.path.basename(d), open(d + "/device").read().strip())
         for d in pci])
    say("environment:", {k: v for k, v in sorted(os.environ.items())
                         if k.startswith(("TPU_", "JAX_", "XLA_"))})
    from paddle_tpu.distributed import launch
    chips = len(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))
    say(f"launch._host_has_tpu() = {launch._host_has_tpu()}; chips by "
        f"device file: {chips}")

    say("\n-- two unpinned jax processes at once")
    ps = [subprocess.Popen(me, env=dict(ENV, PROBE_ID=str(i), PROBE_HOLD="20"),
                           stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                           text=True, cwd=REPO) for i in range(2)]
    for i, p in enumerate(ps):
        try:
            out, _ = p.communicate(timeout=120)
            say(f"[unpinned {i}] rc={p.returncode} {out.strip()[-700:]}")
        except subprocess.TimeoutExpired:
            p.kill()
            say(f"[unpinned {i}] HUNG for 120 s, killed")

    n = max(chips, 2)
    say(f"\n-- the launcher, {n} serving replicas on {chips} chip(s)")
    eps = ",".join(f"127.0.0.1:{7001 + i}" for i in range(n))
    run("replicas", [sys.executable, "-m", "paddle_tpu.distributed.launch",
                     "--serving_replicas", eps, *me[1:]], PROBE_HOLD="5")
    say("\n-- the launcher, two trainer processes on this node")
    run("trainers", [sys.executable, "-m", "paddle_tpu.distributed.launch",
                     "--nproc_per_node=2", *me[1:]], PROBE_HOLD="2")
    return 0


def mesh_kernels():
    import jax
    import numpy as np
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.parallel.hybrid import HybridParallelTrainStep

    say(f"devices: {jax.devices()}")
    cfg = GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                    max_position_embeddings=1024, amp_dtype="bfloat16",
                    attn_impl="flash")
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (8, 1024)).astype(np.int32)

    def attempt(tag, wrap_flash, fused):
        # every gate says pallas: the question is what lowering does
        os.environ["PADDLE_TPU_AUTOBENCH_FORCE"] = "pallas"
        t0 = time.time()
        try:
            step = HybridParallelTrainStep(cfg, pp=2, tp=2, n_microbatches=4)
            step.cfg = dataclasses.replace(step.cfg, fused_blocks=fused)
            if not wrap_flash:
                step._trace_contexts = contextlib.ExitStack
            loss = float(jax.block_until_ready(step(ids)))
            say(f"[{tag}] ran: loss {loss:.4f} ({time.time() - t0:.0f}s)")
        except Exception as e:
            fr = traceback.extract_tb(e.__traceback__)[-1]
            say(f"[{tag}] {type(e).__name__}: {str(e)[:500]}\n  raised at "
                f"{fr.filename}:{fr.lineno} in {fr.name} "
                f"({time.time() - t0:.0f}s)")
        finally:
            del os.environ["PADDLE_TPU_AUTOBENCH_FORCE"]

    attempt("flash, not wrapped", wrap_flash=False, fused=False)
    attempt("fused decoder tail on", wrap_flash=True, fused=True)
    attempt("as the trainer builds it", wrap_flash=True, fused=False)
    return 0


def dispatch():
    import jax
    import jax.numpy as jnp
    say(f"devices: {jax.devices()}")
    x = jnp.ones((8, 128), jnp.float32)
    for n_out in (1, 2, 4, 8, 16, 48):
        f = jax.jit(lambda x: tuple(x + i for i in range(n_out)))
        jax.block_until_ready(f(x))
        row = []
        for keep in (False, True):     # drop each result, or hold them all
            held, calls = [], 200
            t0 = time.perf_counter()
            for _ in range(calls):
                out = f(x)
                if keep:
                    held.append(out)
            jax.block_until_ready(out)
            row.append((time.perf_counter() - t0) / calls * 1e3)
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))
        say(f"{n_out:3d} outputs: {row[0]:.3f} ms/call back to back, "
            f"{row[1]:.3f} holding every result, "
            f"{(time.perf_counter() - t0) * 1e3:.3f} one call with a sync")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    what = sys.argv[1] if len(sys.argv) > 1 else ""
    fn = {"child": child, "processes": processes, "dispatch": dispatch,
          "mesh-kernels": mesh_kernels}.get(what)
    if fn is None:
        sys.exit(__doc__)
    sys.exit(fn())
