"""Ring attention — sequence/context parallelism for long sequences.

Absent from the reference (SURVEY §5 "Long-context / sequence parallelism:
Absent... The TPU build must design long-context support fresh: context-
parallel mesh axis, ring attention via ppermute/shard_map") — this module
supplies it natively.

Design: the sequence dim is sharded over an "sp" mesh axis. Each shard
holds its q block permanently and an online-softmax accumulator; k/v
blocks rotate around the ring with `ppermute`, one hop per step, so every
shard sees the full sequence in n_sp steps while HBM holds only 1/n_sp of
the K/V at a time — O(S) memory per chip for O(S^2) attention.  The loop
is a `lax.scan`, so `jax.grad` differentiates straight through it (the
transpose of ppermute is the reverse rotation — the backward pass is the
reverse ring for free).  Everything outside attention is per-token and
stays GSPMD-sharded on the sequence dim with no code changes.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding,
                          PartitionSpec as P, get_abstract_mesh)


__all__ = ["ring_attention", "ring_context", "current_ring"]

_NEG = -1e30

_ring_stack: list[tuple[Mesh, str]] = []


@contextlib.contextmanager
def ring_context(mesh: Mesh, axis: str = "sp"):
    """Marks the mesh axis model code should ring-attend over (consumed by
    models/gpt.py when cfg.attn_impl == "ring")."""
    _ring_stack.append((mesh, axis))
    try:
        yield
    finally:
        _ring_stack.pop()


def current_ring():
    return _ring_stack[-1] if _ring_stack else None


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                   causal: bool = False, scale=None):
    """q, k, v: [B, H, S, D] with S sharded over `axis` (global
    S = n_sp * S_local). Returns [B, H, S, D], same sharding.

    Inside each ring step the local scores block is [S_loc, S_loc]; causal
    masking uses GLOBAL row/col ids, so fully-future blocks contribute
    nothing and the result matches dense causal attention exactly."""
    n = mesh.shape[axis]
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if n == 1:
        return _dense(q, k, v, causal, scale)

    perm = [(j, (j + 1) % n) for j in range(n)]

    def spmd(q, k, v):
        idx = jax.lax.axis_index(axis)
        B, H, S_loc, D = q.shape
        rows = idx * S_loc + jnp.arange(S_loc)

        def update(acc, m, l, kb, vb, src):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, kb,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                cols = src * S_loc + jnp.arange(S_loc)
                s = jnp.where(rows[:, None] >= cols[None, :], s, _NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return acc, m_new, l

        # hop 0 is the LOCAL block — fold it in before the scan so the
        # loop does exactly n-1 rotations (a rotate-after-use loop would
        # waste the final K+V ppermute pair per call)
        acc, m, l = update(jnp.zeros(q.shape, jnp.float32),
                           jnp.full(q.shape[:3], _NEG, jnp.float32),
                           jnp.zeros(q.shape[:3], jnp.float32), k, v, idx)

        def step(carry, i):
            acc, m, l, k_cur, v_cur = carry
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)
            src = (idx - i) % n  # owner of the k/v block we now hold
            if causal:
                # fully-future block (every col id > every row id):
                # contributes nothing — skip the whole scores/softmax
                # block instead of computing it and masking (saves ~2x
                # attention FLOPs at large sp; the ppermute still runs,
                # the ring stays lockstep)
                acc, m, l = jax.lax.cond(
                    src <= idx,
                    lambda ops: update(*ops, k_cur, v_cur, src),
                    lambda ops: ops,
                    (acc, m, l))
            else:
                acc, m, l = update(acc, m, l, k_cur, v_cur, src)
            return (acc, m, l, k_cur, v_cur), None

        (acc, m, l, _, _), _ = jax.lax.scan(
            step, (acc, m, l, k, v), jnp.arange(1, n))
        l = jnp.where(l == 0.0, 1.0, l)
        return (acc / l[..., None]).astype(q.dtype)

    spec = P(None, None, axis, None)
    # nested-in-manual support (sp x pp): when this runs inside another
    # shard_map's manual region (the 1F1B engine manual over "pp"), the
    # inner shard_map must be built on the CONTEXT abstract mesh — the
    # one where pp is already Manual — not the original device mesh
    use_mesh = mesh
    ctx_mesh = get_abstract_mesh()
    if ctx_mesh.axis_names and AxisType.Manual in ctx_mesh.axis_types:
        use_mesh = ctx_mesh
    return jax.shard_map(spmd, mesh=use_mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names=frozenset({axis}),
                         check_vma=False)(q, k, v)


def _dense(q, k, v, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        T = q.shape[2]
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
