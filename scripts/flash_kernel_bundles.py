"""The flash-attention kernels' instruction bundles, with no chip (PR 48).

    python3 scripts/flash_kernel_bundles.py [--cells a,b] [--tree DIR]

Compiles `flash_attention` under `jax.grad` at a cell's shapes
(`scripts/flash_kernel_step0.py::CELLS`) for a DESCRIBED TPU v5e, with
libtpu dumping each kernel's low-level program
(`LIBTPU_INIT_ARGS="--xla_jf_dump_to=DIR --xla_jf_dump_llo_text=true"`),
and prints, a Mosaic kernel: the bundles of its final program (a bundle is
issued a cycle; the parent's forward at `bf16[256,1024,64]`, 6,466 bundles
a head by its loops' trips, ran at the ledger's 1.13 ms a call = 1.46 G
bundles/s, the chip's clock), its loops (a backward branch and its
target) and what each loop's body keeps busy of the bundle's slots (MXU 4,
XLU 3, VALU 4, EUP 1, loads 3, stores 1). A loop's trips are the reader's
to count: the bodies are static text. Nothing runs: a count is no time,
and where a call waits on its bytes the bundles do not show it
(docs/KERNELS.md, "The flash kernel's tile schedule"). `--tree` names
another checkout whose `paddle_tpu` is compiled instead.

The compiler's process aborts after it has written the dumps (a report
template that is not installed here); the child's exit code is ignored.
"""
from __future__ import annotations

import argparse
import glob
import os
import re
import subprocess
import sys
import tempfile

SLOTS = "MXU XLU VALU EUP VLOAD VFILL VSTORE VSPILL SALU".split()

CHILD = r"""
import os, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, {here!r}); sys.path.insert(0, {tree!r})
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from paddle_tpu.ops import pallas_attention as pa
from scripts.flash_kernel_step0 import CELLS
pa._interpret = lambda: False
one = SingleDeviceSharding(topologies.get_topology_desc(
    platform="tpu", topology_name="v5e:2x2").devices[0])
c = CELLS[{cell!r}]
B, H, Hkv, T, d = c["B"], c["H"], c["Hkv"], c["T"], c["d"]
sds = lambda h, w: jax.ShapeDtypeStruct((B, h, T, w), jnp.bfloat16,
                                        sharding=one)
kw = dict(causal=c.get("causal", True), window=c.get("window"),
          scale=d ** -0.5)
if "block_q" in c:
    kw["block_q"] = c["block_q"]
f = lambda q, k, v: pa.flash_attention(q, k, v, **kw)
if not c.get("fwd_only"):
    f = jax.grad(lambda q, k, v: jnp.sum(pa.flash_attention(
        q, k, v, **kw).astype(jnp.float32)), (0, 1, 2))
with jax.default_matmul_precision("default"):
    jax.jit(f).lower(sds(H, d), sds(Hkv, d), sds(Hkv, c.get("dv", d))).compile()
os._exit(0)
"""


def report(dump):
    for path in sorted(glob.glob(dump + "/*-final_bundles.txt")):
        key = re.match(r"(.*/\d+-.*?)-\d+-final_bundles\.txt", path).group(1)
        name = key.split("-", 1)[1]
        if not re.search(r"jvp|flash|lambda", name):
            continue        # XLA's own fusions and copies
        rows, on = [], False
        for ln in open(glob.glob(
                key + "-*final_hlo-static-per-bundle-utilization.txt")[0]):
            if on and ln.strip():
                rows.append([int(x) for x in ln.split()])
            on = on or ln.startswith("== UTILIZATION")
        print(f"  {name}: {len(rows)} bundles")
        for ln in open(path):
            at = re.match(r"\s*(0x[0-9a-f]+)", ln)
            for m in re.finditer(r"sbr\.rel \([^)]*\) target bundleno = (\d+)",
                                 ln) if at else ():
                here, to = int(at.group(1), 16), int(m.group(1))
                if to < here:       # a loop: its body and the slots it fills
                    body = rows[to:here + 4]
                    busy = {n: sum(r[i] for r in body)
                            for i, n in enumerate(SLOTS)}
                    print(f"    loop {to}..{here + 3}: {len(body)} bundles "
                          f"{busy}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="gpt_350m_train.b16s1024")
    ap.add_argument("--tree", default=os.getcwd())
    a = ap.parse_args()
    for cell in a.cells.split(","):
        with tempfile.TemporaryDirectory() as dump:
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                                        f"--xla_jf_dump_llo_text=true")
            subprocess.run(
                [sys.executable, "-c", CHILD.format(
                    tree=os.path.abspath(a.tree), here=os.getcwd(),
                    cell=cell)],
                env=env, capture_output=True)
            print(cell)
            report(dump)


if __name__ == "__main__":
    main()
