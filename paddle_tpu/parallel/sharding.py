"""Name-pattern -> PartitionSpec sharding rules (tensor parallelism).

Tensor parallel is absent from the reference (SURVEY SS2.9) and designed
fresh here the TPU way: instead of col/row-parallel layer classes that
hand-insert collectives (Megatron style), parameters are annotated with
`PartitionSpec`s and GSPMD partitions the matmuls and inserts the
all-reduces.  A rule table maps parameter-name regexes to specs, so the same
model code runs unsharded, dp-only, or dp x tp by swapping the rule set.
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Any, Callable, Sequence

import jax
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          get_abstract_mesh)

__all__ = ["ShardingRules", "shard_tree", "spec_for", "kernel_mesh",
           "current_kernel_mesh", "per_shard"]


class ShardingRules:
    """Ordered (regex, PartitionSpec) table; first match wins.

    Axis names appearing in a spec but absent from the mesh are dropped at
    resolution time, so one rule set serves tp=1 and tp>1 meshes.
    """

    def __init__(self, rules: Sequence[tuple[str, P]] | None = None,
                 default: P = P()):
        self.rules = [(re.compile(pat), spec) for pat, spec in (rules or [])]
        self.default = default

    def spec(self, name: str, mesh: Mesh | None = None,
             ndim: int | None = None) -> P:
        spec = self.default
        for pat, s in self.rules:
            if pat.search(name):
                spec = s
                break
        if mesh is not None:
            spec = _restrict(spec, mesh)
        if ndim is not None and len(spec) > ndim:
            raise ValueError(
                f"spec {spec} for {name!r} has more dims than the {ndim}-d "
                f"param")
        return spec

    def sharding(self, name: str, mesh: Mesh, ndim: int | None = None):
        return NamedSharding(mesh, self.spec(name, mesh, ndim))


def _restrict(spec: P, mesh: Mesh) -> P:
    """Drop axis names the mesh doesn't have (or that have size 1)."""
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry
                         if a in mesh.shape and mesh.shape[a] > 1)
            out.append(kept if kept else None)
        else:
            out.append(entry if entry in mesh.shape and
                       mesh.shape[entry] > 1 else None)
    return P(*out)


_kernel_mesh = threading.local()


@contextlib.contextmanager
def kernel_mesh(mesh: Mesh):
    """Marks the multi-device mesh the enclosed trace is partitioned
    over. jax does not partition a Mosaic kernel ("Mosaic kernels cannot
    be automatically partitioned", jax/_src/tpu_custom_call.py
    `_tpu_custom_call_lowering`), so model code that reaches a Pallas
    kernel under GSPMD asks `current_kernel_mesh()` and runs the kernel
    on each device's shard through `per_shard`. Per thread: an engine
    tracing a bucket on another thread is not on this mesh."""
    prev = current_kernel_mesh()
    _kernel_mesh.mesh = mesh
    try:
        yield
    finally:
        _kernel_mesh.mesh = prev


def current_kernel_mesh() -> Mesh | None:
    return getattr(_kernel_mesh, "mesh", None)


def per_shard(fn: Callable, mesh: Mesh, spec: P, n_args: int) -> Callable:
    """`fn` of `n_args` arrays laid out as `spec` (and returning one such
    array), run on each device's local shard: a shard_map manual over
    EVERY mesh axis, which is what the Mosaic lowering asks for. Inside
    another manual region (the 1F1B engine, manual over "pp") the inner
    map is built on the context mesh over the axes still automatic."""
    ctx = get_abstract_mesh()
    manual = frozenset(ctx.manual_axes) if ctx.axis_names else frozenset()
    return jax.shard_map(
        fn, mesh=ctx if manual else mesh, in_specs=(spec,) * n_args,
        out_specs=spec, axis_names=frozenset(mesh.axis_names) - manual,
        check_vma=False)


def spec_for(tree_of_names: Any, rules: ShardingRules, mesh: Mesh):
    """Map a pytree of param names to a pytree of NamedShardings."""
    return jax.tree_util.tree_map(
        lambda n: rules.sharding(n, mesh), tree_of_names)


def shard_tree(params: Any, names: Any, rules: ShardingRules, mesh: Mesh):
    """device_put every leaf with its resolved rule sharding."""
    return jax.tree_util.tree_map(
        lambda v, n: jax.device_put(v, rules.sharding(n, mesh, v.ndim)),
        params, names)
