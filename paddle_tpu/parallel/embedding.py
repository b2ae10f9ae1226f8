"""Mesh-sharded embedding tables — the TPU-native sparse tier.

The reference serves huge embedding tables from a parameter-server runtime
(operators/distributed/large_scale_kv.h, distributed_lookup_table_op,
communicator.h:180).  On TPU the idiomatic design keeps the table IN HBM,
row-sharded over a mesh axis, and turns the lookup into collectives
(SURVEY §7 "sharded embedding tables + all_to_all on the mesh"):

  * each shard owns a contiguous row range [idx*V/n, (idx+1)*V/n);
  * a lookup gathers local hits and psums partial rows over the axis —
    one all-reduce of [B, S, D] replaces the PS pull RPC;
  * the gradient transposes to a local scatter-add (the "push").

The host-resident KV path for beyond-HBM tables stays in
distributed/fleet/runtime/parameter_server_runtime.py.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


__all__ = ["ShardedEmbedding", "sharded_embedding_lookup"]


def sharded_embedding_lookup(table, ids, mesh: Mesh, axis: str = "mp"):
    """table: [V, D] sharded P(axis, None); ids: int [...] replicated over
    `axis` (may be dp-sharded on batch dims). Returns [..., D] embeddings.

    Differentiable: grad wrt table is the scatter-add transpose, sharded
    like the table."""
    n = mesh.shape[axis]
    V = table.shape[0]
    if V % n:
        raise ValueError(f"vocab {V} not divisible by {axis}={n}")
    per = V // n

    def spmd(tbl, ids):
        lo = jax.lax.axis_index(axis) * per
        loc = ids.astype(jnp.int32) - lo
        hit = (loc >= 0) & (loc < per)
        rows = jnp.take(tbl, jnp.clip(loc, 0, per - 1), axis=0)
        rows = jnp.where(hit[..., None], rows, 0)
        return jax.lax.psum(rows, axis)

    return jax.shard_map(
        spmd, mesh=mesh, in_specs=(P(axis, None), P()), out_specs=P(),
        axis_names=frozenset({axis}), check_vma=False)(table, ids)


class ShardedEmbedding:
    """Row-sharded table + lookup. `spec`/`sharding` expose the layout so
    trainers shard optimizer state identically."""

    def __init__(self, vocab_size: int, dim: int, mesh: Mesh,
                 axis: str = "mp", init_std: float = 0.01, seed: int = 0,
                 dtype=jnp.float32):
        self.vocab_size, self.dim = vocab_size, dim
        self.mesh, self.axis = mesh, axis
        self.spec = P(axis, None)
        self.sharding = NamedSharding(mesh, self.spec)
        rng = np.random.RandomState(seed)
        self.table = jax.device_put(
            jnp.asarray(rng.normal(0, init_std, (vocab_size, dim))
                        .astype(np.float32), dtype=dtype), self.sharding)

    def __call__(self, ids, table=None):
        return sharded_embedding_lookup(
            self.table if table is None else table, ids, self.mesh,
            self.axis)
