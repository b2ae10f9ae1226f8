#!/usr/bin/env python3
"""Step 0 of PR 49: the hyper-connection steps of ONE sub-layer alone
(`models/layers.py::hc_coefficients`, `hc_read`, `hc_write`) at the
serving cell's two shapes, a prefill bucket's [16384, 4 x 3584] and a
decode batch's [32, 4 x 3584] in bfloat16, XLA's spelling, against the
floor of their bytes: read X once, write X' once, h out and F(h) in,
10 C x 2 B = 71,680 B a token a sub-layer at 819 GB/s.

Spellings timed side by side:

  tuple    the program's: the streams n arrays [T, C] and never one,
           coefficients with their stream axes first ([n, n, T]: the token
           axis minor through the Sinkhorn loop, a `fori_loop`)
  axis     the textbook's: streams a real axis [T, n, C] (the second-minor
           axis of 4 is padded to a bfloat16 tile's 16 rows), coefficients
           [T, n, n], the two mixes as einsums

    python3 scripts/hyper_step0.py                # on the chip: times
    JAX_PLATFORMS=cpu python3 scripts/hyper_step0.py --compile-only
        # here: compiles for a DESCRIBED v5e and prints each program's
        # fusions and temporaries (nothing runs: no time)
"""
import argparse
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

N, C, ITERS, EPS, CLAMP = 4, 3584, 20, 1e-6, (-30.0, 30.0)
FLOOR_BYTES = 10 * C * 2            # a token a sub-layer
HBM = 819e9


def tuple_steps():
    from paddle_tpu.models import layers as L

    def read(p, X):
        pre, post, res = L.hc_coefficients(p, X, ITERS, EPS, CLAMP)
        return L.hc_read(X, pre), post, res

    def write(X, res, post, f):
        return L.hc_write(X, res, post, f)

    def both(p, X, f):      # the branch left out: F(h) = h + f
        h, post, res = read(p, X)
        return write(X, res, post, h + f)
    return read, write, both


def axis_steps():
    def read(p, X):         # X [T, n, C]
        T = X.shape[0]
        xf = X.astype(jnp.float32).reshape(T, -1)
        xt = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + EPS)
        z = jnp.einsum("td,dk->tk", xt.astype(X.dtype), p["phi"],
                       preferred_element_type=jnp.float32)
        a, b = p["hc_scale"].astype(jnp.float32), \
            p["hc_bias"].astype(jnp.float32)
        pre = jax.nn.sigmoid(a[0] * z[:, :N] + b[:N])
        post = 2 * jax.nn.sigmoid(a[1] * z[:, N:2 * N] + b[N:2 * N])
        m = jnp.exp(jnp.clip((a[2] * z[:, 2 * N:] + b[2 * N:]), *CLAMP)
                    ).reshape(T, N, N)
        for _ in range(ITERS):
            m = m / (m.sum(1, keepdims=True) + EPS)
            m = m / (m.sum(2, keepdims=True) + EPS)
        h = jnp.einsum("tn,tnc->tc", pre, X.astype(jnp.float32))
        return h.astype(X.dtype), post, m

    def write(X, res, post, f):
        out = jnp.einsum("tij,tjc->tic", res, X.astype(jnp.float32)) \
            + post[:, :, None] * f.astype(jnp.float32)[:, None, :]
        return out.astype(X.dtype)

    def both(p, X, f):
        h, post, res = read(p, X)
        return write(X, res, post, h + f)
    return read, write, both


def shapes(spelling, T, sharding=None):
    s = lambda sh, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        sh, dt, **({"sharding": sharding} if sharding else {}))
    p = {"phi": s((N * C, N * (N + 2))), "hc_bias": s((N * (N + 2),)),
         "hc_scale": s((3,))}
    X = (s((T, C)),) * N if spelling == "tuple" else s((T, N, C))
    return p, X, s((T, C))


def fusions(text: str) -> list[str]:
    """Names of the entry computation's fusions, kernels and loops."""
    entry = text[text.index("ENTRY"):]
    return re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*? "
                      r"(fusion|custom-call|while|convolution|dot)\(",
                      entry, re.M)


def compile_only(out):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    for spelling, steps in (("tuple", tuple_steps()), ("axis", axis_steps())):
        for T in (16384, 32):
            p, X, f = shapes(spelling, T, one)
            c = jax.jit(steps[2]).lower(p, X, f).compile()
            text = c.as_text()
            ops = fusions(text)
            mem = c.memory_analysis()
            print(f"{spelling} T={T}: {len(ops)} device operations "
                  f"{[o[0] for o in ops][:40]}; temporaries "
                  f"{mem.temp_size_in_bytes / 1e6:.1f} MB, arguments "
                  f"{mem.argument_size_in_bytes / 1e6:.1f} MB, output "
                  f"{mem.output_size_in_bytes / 1e6:.1f} MB")
            if out:
                os.makedirs(out, exist_ok=True)
                with open(os.path.join(out, f"{spelling}_{T}.hlo"),
                          "w") as fh:
                    fh.write(text)


def timed(fn, args, reps):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def on_chip(out):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU: found {dev}", file=sys.stderr)
        return 2
    rows = []
    for spelling, steps in (("tuple", tuple_steps()), ("axis", axis_steps())):
        read, write, both = (jax.jit(s) for s in steps)
        for T in (16384, 32):
            ps, Xs, fs = shapes(spelling, T)
            key = jax.random.PRNGKey(T)
            mk = lambda s, i, std: (std * jax.random.normal(  # noqa: E731
                jax.random.fold_in(key, i), s.shape, jnp.float32)
                ).astype(s.dtype)
            p = {"phi": mk(ps["phi"], 0, 0.02),
                 "hc_bias": mk(ps["hc_bias"], 1, 1.0),
                 "hc_scale": (0.5 + mk(ps["hc_scale"], 2, 0.05))}
            X = tuple(mk(x, 10 + j, 1.0) for j, x in enumerate(Xs)) \
                if spelling == "tuple" else mk(Xs, 3, 1.0)
            f = mk(fs, 4, 1.0)
            reps = 20 if T > 1000 else 200
            h, post, res = read(p, X)
            t_read = timed(read, (p, X), reps)
            t_write = timed(write, (X, res, post, f), reps)
            t_both = timed(both, (p, X, f), reps)
            floor = T * FLOOR_BYTES / HBM
            text = both.lower(p, X, f).compile().as_text()
            row = {"spelling": spelling, "T": T,
                   "read_ms": t_read * 1e3, "write_ms": t_write * 1e3,
                   "both_ms": t_both * 1e3, "floor_ms": floor * 1e3,
                   "both_over_floor": t_both / floor,
                   "device_operations": len(fusions(text)),
                   "device": dev.device_kind}
            rows.append(row)
            print(json.dumps(row), flush=True)
            if out:
                os.makedirs(out, exist_ok=True)
                with open(os.path.join(out, f"{spelling}_{T}.hlo"),
                          "w") as fh:
                    fh.write(text)
    # the two spellings agree (bfloat16 streams, float32 coefficients)
    T = 256
    (pf, Xf, ff), key = shapes("tuple", T), jax.random.PRNGKey(0)
    p = {k: (0.5 if k == "hc_scale" else 0.0) + (
        0.02 if k == "phi" else 0.05 if k == "hc_scale" else 1.0)
        * jax.random.normal(jax.random.fold_in(key, i), s.shape,
                            jnp.float32).astype(s.dtype)
        for i, (k, s) in enumerate(sorted(pf.items()))}
    X = tuple(jax.random.normal(jax.random.fold_in(key, 20 + j), x.shape,
                                jnp.float32).astype(jnp.bfloat16)
              for j, x in enumerate(Xf))
    f = jax.random.normal(jax.random.fold_in(key, 9), ff.shape,
                          jnp.float32).astype(jnp.bfloat16)
    a = jnp.stack(jax.jit(tuple_steps()[2])(p, X, f), 1).astype(jnp.float32)
    b = jax.jit(axis_steps()[2])(p, jnp.stack(X, 1), f)
    print("widest difference of the two spellings:",
          float(jnp.max(jnp.abs(a - b.astype(jnp.float32)))),
          "on values of size", float(jnp.std(a)))
    if out:
        with open(os.path.join(out, "step0.json"), "w") as fh:
            json.dump(rows, fh, indent=1)
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    if a.compile_only:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        compile_only(a.out)
    else:
        sys.exit(on_chip(a.out))
