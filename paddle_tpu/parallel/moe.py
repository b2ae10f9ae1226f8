"""Mixture-of-Experts with expert parallelism over an "ep" mesh axis.

The reference ships the `expert_parallel` strategy flag in fleet's
DistributedStrategy but (at its vintage) no MoE runtime; SURVEY §2.9 lists
EP/MoE among the parallelism strategies the TPU build must design fresh.
Design follows GShard/Switch-Transformer, shaped for the MXU:

  * top-k routing with a STATIC per-expert capacity (no dynamic shapes —
    overflow tokens are dropped, their residual path carries them),
  * dense one-hot dispatch/combine einsums (batched matmuls, not scatters),
  * experts stacked on a leading E dim; sharding E over the "ep" mesh axis
    makes GSPMD lower the dispatch/combine einsums to all_to_all over ep,
  * router maths in float32 regardless of the compute dtype.

`moe_context(mesh, axis)` marks the ambient mesh so `moe_ffn` can pin the
[E, C, D] expert buffers to the ep axis with a sharding constraint
(mirrors sequence_parallel.ring_context).
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["moe_capacity", "topk_gating", "moe_ffn", "moe_context",
           "current_moe_mesh", "sigmoid_topk_route", "softmax_topk_route",
           "dropless_moe_ffn", "held_rows_bound"]

_moe_stack: list[tuple[Mesh, str]] = []


@contextlib.contextmanager
def moe_context(mesh: Mesh, axis: str = "ep"):
    """Marks the mesh axis expert buffers should shard over (consumed by
    moe_ffn; models/gpt.py enters it when the hybrid step has an ep axis)."""
    _moe_stack.append((mesh, axis))
    try:
        yield
    finally:
        _moe_stack.pop()


def current_moe_mesh():
    return _moe_stack[-1] if _moe_stack else None


def moe_capacity(n_tokens: int, n_experts: int,
                 capacity_factor: float = 1.25, top_k: int = 1,
                 multiple_of: int = 8) -> int:
    """Static per-expert buffer length C: tokens beyond it are dropped
    (their residual connection still carries them forward)."""
    c = math.ceil(capacity_factor * top_k * n_tokens / n_experts)
    return max(multiple_of, multiple_of * math.ceil(c / multiple_of))


def topk_gating(logits, top_k: int, capacity: int):
    """GShard-style router.

    Args:
      logits: [N, E] router scores (any float dtype; softmax runs fp32).
      top_k: experts per token (1 = Switch, 2 = GShard).
      capacity: static per-expert buffer length C.

    Returns:
      dispatch: [N, E, C] 0/1 float32 — token n occupies slot c of expert e.
      combine:  [N, E, C] float32 — dispatch weighted by (normalised) gates.
      aux: scalar load-balance loss (Switch eq. 4: E * Σ_e f_e · P_e),
        differentiable through the router probabilities.
    """
    N, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    masks, gates = [], []
    remaining = probs
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)
        m = jax.nn.one_hot(idx, E, dtype=jnp.float32)       # [N, E]
        masks.append(m)
        gates.append(jnp.sum(probs * m, axis=-1))           # [N]
        remaining = remaining * (1.0 - m)

    # aux loss on the FIRST choice (Switch definition): fraction routed vs
    # mean router prob, per expert.
    f = jnp.mean(masks[0], axis=0)                          # [N,E] -> [E]
    p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * p)

    # normalise kept gates so the combine weights of a token sum to 1
    denom = sum(gates)
    gates = [g / jnp.maximum(denom, 1e-9) for g in gates]

    # slot positions: k-th choices queue up after all earlier choices
    dispatch = jnp.zeros((N, E, capacity), jnp.float32)
    combine = jnp.zeros((N, E, capacity), jnp.float32)
    offset = jnp.zeros((E,), jnp.float32)
    for m, g in zip(masks, gates):
        pos = jnp.cumsum(m, axis=0) - 1.0 + offset[None, :]  # [N, E]
        offset = offset + jnp.sum(m, axis=0)
        keep = m * (pos < capacity)                          # [N, E]
        slot = jnp.sum(pos * keep, axis=-1).astype(jnp.int32)      # [N]
        slot_oh = jax.nn.one_hot(slot, capacity, dtype=jnp.float32)
        d = keep[:, :, None] * slot_oh[:, None, :]           # [N, E, C]
        dispatch = dispatch + d
        combine = combine + d * g[:, None, None]
    return dispatch, combine, aux


def moe_ffn(x, wg, we_up, be_up, we_down, be_down, *,
            capacity_factor: float = 1.25, top_k: int = 1,
            act=None):
    """MoE feed-forward: route, dispatch, expert FFN, combine.

    Args:
      x: [B, T, D] (or [N, D]) activations.
      wg: [D, E] router weights.
      we_up/be_up: [E, D, F] / [E, F] expert up-projections.
      we_down/be_down: [E, F, D] / [E, D] expert down-projections.

    Returns (y, aux): y shaped like x; aux the load-balance scalar.
    """
    if act is None:
        act = lambda u: jax.nn.gelu(u, approximate=True)
    shape = x.shape
    D = shape[-1]
    E = we_up.shape[0]
    xf = x.reshape(-1, D)
    N = xf.shape[0]
    C = moe_capacity(N, E, capacity_factor, top_k)

    logits = xf.astype(jnp.float32) @ wg.astype(jnp.float32)
    dispatch, combine, aux = topk_gating(logits, top_k, C)

    ctx = current_moe_mesh()

    def pin(a, spec):
        if ctx is None:
            return a
        mesh, axis = ctx
        if axis not in mesh.axis_names or mesh.shape[axis] == 1:
            return a
        named = P(*[axis if s == "ep" else None for s in spec])
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, named))

    xin = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), xf)
    xin = pin(xin, ("ep", None, None))            # all_to_all over ep
    h = act(jnp.einsum("ecd,edf->ecf", xin, we_up.astype(x.dtype))
            + be_up[:, None, :].astype(x.dtype))
    out = (jnp.einsum("ecf,efd->ecd", h, we_down.astype(x.dtype))
           + be_down[:, None, :].astype(x.dtype))
    out = pin(out, ("ep", None, None))
    y = jnp.einsum("nec,ecd->nd", combine.astype(x.dtype), out)
    return y.reshape(shape), aux


# ---------------------------------------------------------------------------
# Dropless layer: sigmoid scores, selection on score plus bias, every
# token-expert pair computed. (The capacity-based layer above stays for
# its callers; a token it drops cannot agree with a plain reference.)
# ---------------------------------------------------------------------------

def sigmoid_topk_route(h, wg, bias, top_k: int, norm_topk: bool = True,
                       scale: float = 1.0):
    """Router of the sigmoid-scored, bias-corrected kind (DeepSeek-V3's
    auxiliary-loss-free balancing, as LFM2-MoE uses it): s = sigmoid(h wg)
    in float32, the top_k of s + bias are CHOSEN, and the weights come
    from s alone, normalised over the chosen (1e-6 in the denominator).

    h [N, D], wg [D, E], bias [E] or None. Returns (sel [N, k] int32,
    weights [N, k] float32)."""
    s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32),
                               wg.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    biased = s if bias is None else s + bias.astype(jnp.float32)
    _, sel = jax.lax.top_k(biased, top_k)
    g = jnp.take_along_axis(s, sel, axis=-1)
    if norm_topk:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6)
    return sel.astype(jnp.int32), g * scale


def softmax_topk_route(h, wg, bias, top_k: int, norm_topk: bool = True,
                       scale: float = 1.0):
    """Router of the softmax-scored kind (Qwen3-MoE's, Mellum's): p =
    softmax(h wg) over all E in float32, the top_k of p are chosen, and
    the weights are p over the chosen, normalised over ALL the chosen
    whether or not their expert is held here (no epsilon: a softmax's top
    k sum to more than k / E). No bias: the kind has none.

    Returns as `sigmoid_topk_route`."""
    if bias is not None:
        raise ValueError("the softmax router has no selection bias")
    p = jax.nn.softmax(jnp.dot(h.astype(jnp.float32),
                               wg.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST), axis=-1)
    g, sel = jax.lax.top_k(p, top_k)
    if norm_topk:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return sel.astype(jnp.int32), g * scale


def _held_index(num_experts: int, experts_held):
    """local[e] = row of expert e in the weights held here, or the count
    held for an expert that lives elsewhere."""
    if experts_held is None:
        return None, num_experts
    held = tuple(int(e) for e in experts_held)
    local = [len(held)] * num_experts
    for i, e in enumerate(held):
        local[e] = i
    return jnp.asarray(local, jnp.int32), len(held)


# The rows a share's sorted arrays hold, as a multiple of its even part of
# the pairs (N k Eh / E). 2 from the cell this was built for (PERF.md
# section 6, PR 46 and 47: 16 of 64 softmax-routed experts held, seeded
# weights, 131,072 pairs a layer): over a run's steps a layer held up to
# 30.3% of the pairs against an even 25% (34.2% with a router collapsed at
# a hundred times the cell's rate), and at 3/2 (37.5%) one layer-step of a
# traced run's 368 still passed the bound; 2 holds 50%. A routing over the
# bound takes the whole-size path: the factor buys speed, never
# correctness, so it is no argument and no option.
_HELD_ROWS_OVER_EVEN = (2, 1)
_HELD_ROWS_MULTIPLE = 512       # `_gmm_tiles` / `_tgmm_tiles` keep their rows


def held_rows_bound(N: int, k: int, Eh: int, E: int) -> int:
    """Rows of the arrays in sorted pair order, from shapes alone: all N k
    where every expert is held, else twice the share's even part of the
    pairs, rounded up to 512 rows and never more than N k."""
    pairs = N * k
    num, den = _HELD_ROWS_OVER_EVEN
    rows = -(-num * pairs * Eh // (den * E))
    return min(pairs, -(-rows // _HELD_ROWS_MULTIPLE) * _HELD_ROWS_MULTIPLE)


def _sorted_swiglu(h, local, g, w1, w3, w2, interpret, rows=None,
                   out_dtype=jnp.float32):
    """Sort the token-expert pairs by expert, one grouped product per
    projection over the sorted rows (`_gmm`), unsort, weighted sum. Rows
    move by gathers only (the unsort reads through the inverse
    permutation): a scatter of N*k rows of D costs more than the products
    on a TPU.

    The sort puts the pairs held here first. `rows` (a static bound on
    them, `held_rows_bound`) below N*k keeps every sorted array at `rows`
    rows where the routing fits, and takes all N*k where it does not
    (`_bounded_rows`): no pair is dropped. Without it, work is N*k rows
    whatever the routing. The bounded arrays' sum is handed back in
    `out_dtype`, so that its cotangent arrives, and is gathered, in it:
    the caller's own dtype where it would cast the float32 sum at once."""
    N, k = local.shape
    Eh = w1.shape[0]
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    inverse = jnp.argsort(order)
    sizes = jnp.bincount(flat, length=Eh + 1)[:Eh].astype(jnp.int32)
    if rows is not None and rows < N * k:
        return _bounded_rows(interpret, rows, jnp.dtype(out_dtype), h, order,
                             inverse, sizes, g, w1, w3, w2)
    mm = lambda x, w: _gmm(x, w, sizes, interpret)
    # rows of pairs whose expert lives elsewhere belong to no group: the
    # grouped products leave them unwritten, forward and backward
    held = (flat[order] < Eh)[:, None]
    xs = _pair_rows(h, order, inverse, held, k)              # [N*k, D]
    gated = jax.nn.silu(mm(xs, w1)) * mm(xs, w3)
    ys = mm(gated.astype(xs.dtype), w2)
    ys = jnp.where(held, ys, 0)
    return jnp.einsum("nkd,nk->nd",
                      _permute(ys, inverse, order).reshape(N, k, -1),
                      g.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


# Under autodiff a gather's transpose is a scatter-add of N*k rows of D,
# which costs more than the products on a TPU. Both moves of rows are
# permutations whose inverse is known, so each cotangent is a gather too.

@jax.custom_vjp
def _permute(x, perm, inv):
    """x[perm], with inv the inverse permutation."""
    return x[perm]


_permute.defvjp(lambda x, perm, inv: (x[perm], (perm, inv)),
                lambda res, ct: (ct[res[1]], None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _pair_rows(h, order, inverse, held, k):
    """Row n of h once for each of its k pairs, in the sorted order."""
    return h[order // k]


def _pair_rows_bwd(k, res, ct):
    inverse, held = res
    # a pair no expert here took was never written by the grouped
    # products' backward either
    ct = jnp.where(held, ct, 0)[inverse]
    return (jnp.sum(ct.reshape(-1, k, ct.shape[-1]), axis=1,
                    dtype=jnp.float32).astype(ct.dtype), None, None, None)


_pair_rows.defvjp(
    lambda h, order, inverse, held, k: (h[order // k], (inverse, held)),
    _pair_rows_bwd)


def _gmm_tiles(m: int, k: int, n: int):
    """(tm, tk, tn) for `megablox.gmm`: whole rows of the contraction in
    one tile and half the output's width, so that an expert's matrix is
    read once a row tile in two blocks of a few MB (jax's default of
    128 x 128 x 128 makes ~7,000 grid steps of one layer's products)."""
    tm = next(t for t in (256, 128, 64, 32, 16, 8, m) if m % t == 0)
    tn = n // 2 if n % 256 == 0 else n
    return tm, k, tn


def _tgmm_tiles(m: int, k: int, n: int):
    """(tm, tk, tn) for `megablox.tgmm` (an expert's [k, n] weight
    gradient, summed over its rows): the float32 accumulator is a [tk, tn]
    tile, so the output is cut to a couple of MB where the forward's
    tiles keep a whole contraction."""
    tm = next(t for t in (512, 256, 128, 64, 32, 16, 8, m) if m % t == 0)
    cut = lambda x: next((x // c for c in (1, 2, 3, 4, 6, 8)
                          if x % c == 0 and x // c <= 1024
                          and (x // c) % 128 == 0), x)
    return tm, cut(k), cut(n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(x, w, sizes, interpret):
    """megablox's grouped product with a backward at tiles of its own
    (jax's own custom_vjp hands the forward's tiling to both backward
    calls, where `_gmm_tiles`' whole contraction is the other
    dimension)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    return gmm(x, w, sizes, preferred_element_type=x.dtype,
               tiling=_gmm_tiles(x.shape[0], *w.shape[1:]),
               interpret=interpret)


def _gmm_bwd(interpret, res, ct):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    x, w, sizes = res
    m, (_, k, n) = x.shape[0], w.shape
    dx = gmm(ct, w, sizes, preferred_element_type=x.dtype,
             tiling=_gmm_tiles(m, n, k), transpose_rhs=True,
             interpret=interpret)
    dw = tgmm(x.swapaxes(0, 1), ct, sizes, preferred_element_type=w.dtype,
              tiling=_tgmm_tiles(m, k, n), num_actual_groups=w.shape[0],
              interpret=interpret)
    return dx, dw, None


_gmm.defvjp(lambda x, w, sizes, interpret:
            (_gmm(x, w, sizes, interpret), (x, w, sizes)), _gmm_bwd)


# A strict share's sorted arrays at a bound's rows. The sorted order's
# first M pairs are all the held ones where `sum(sizes) <= M`; the same
# products over the same rows in the same groups as the whole-size text
# above, and the same float32 sums with exact zeros where a pair is held
# elsewhere. Only two arrays have N*k rows, forward and backward: what
# fans back out to every pair's slot.

def _fan_out(rows, inverse, total):
    """[N*k, .]: each pair's row among the first `total` of `rows`, by a
    gather through the inverse permutation; a pair past `total` has no row
    (its expert lives elsewhere, or the grouped products left its row
    unwritten) and reads an exact zero."""
    at = jnp.minimum(inverse, rows.shape[0] - 1)
    return jnp.where((inverse < total)[:, None], rows[at], 0)


def _swiglu(a, b, dtype):
    return (jax.nn.silu(a) * b).astype(dtype)


def _sorted_products(interpret, M, h, order, sizes, k, w1, w3, w2):
    """The sorted order's first M pairs through their experts:
    (xs [M, D], a, b, gated [M, F], ys [M, D])."""
    xs = h[order[:M] // k]
    a, b = _gmm(xs, w1, sizes, interpret), _gmm(xs, w3, sizes, interpret)
    gated = _swiglu(a, b, xs.dtype)
    return xs, a, b, gated, _gmm(gated, w2, sizes, interpret)


def _rows_fwd(interpret, M, out_dtype, h, order, inverse, sizes, g, w1, w3,
              w2):
    N, k = g.shape
    ys = _sorted_products(interpret, M, h, order, sizes, k, w1, w3, w2)[-1]
    # behind a barrier, or the compiler moves the sum out of the `cond`
    # and the fan-out's select is written out whole as the branch's result
    return jax.lax.optimization_barrier(jnp.einsum(
        "nkd,nk->nd", _fan_out(ys, inverse, jnp.sum(sizes)).reshape(N, k, -1),
        g.astype(jnp.float32),
        preferred_element_type=jnp.float32).astype(out_dtype))


def _rows_bwd(interpret, M, h, order, inverse, sizes, g, w1, w3, w2, dy):
    """Cotangents of (h, g, w1, w3, w2) for dy [N, D], the forward
    recomputed. Each cotangent in sorted order is a gather of M rows (the
    weighted sum's own would be a broadcast [N, k, D], then a gather of
    it), and the grouped products' are `_gmm_bwd`'s."""
    N, k = g.shape
    total = jnp.sum(sizes)
    top = order[:M]
    live = (jnp.arange(M) < total)[:, None]
    xs, a, b, gated, ys = _sorted_products(interpret, M, h, order, sizes, k,
                                           w1, w3, w2)
    dyr = dy[top // k].astype(jnp.float32)                   # [M, D]
    dys = jnp.where(live, dyr * g.reshape(-1)[top][:, None],
                    0).astype(ys.dtype)
    dgs = jnp.sum(dyr * jnp.where(live, ys, 0).astype(jnp.float32), axis=1,
                  keepdims=True)
    dg = _fan_out(dgs, inverse, total).reshape(N, k).astype(g.dtype)
    dgated, dw2, _ = _gmm_bwd(interpret, (gated, w2, sizes), dys)
    da, db = jax.vjp(functools.partial(_swiglu, dtype=xs.dtype), a, b)[1](
        dgated)
    dxa, dw1, _ = _gmm_bwd(interpret, (xs, w1, sizes), da)
    dxb, dw3, _ = _gmm_bwd(interpret, (xs, w3, sizes), db)
    dxs = _fan_out(dxa + dxb, inverse, total)
    dh = jnp.sum(dxs.reshape(N, k, -1), axis=1,
                 dtype=jnp.float32).astype(dxs.dtype)
    return dh, dg, dw1, dw3, dw2


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _bounded_rows(interpret, M, out_dtype, h, order, inverse, sizes, g, w1,
                  w3, w2):
    """The sorted arrays at M rows where the held pairs fit them, at all
    N*k where they do not: one algorithm at two sizes, the choice a
    `lax.cond` on the routing. One `custom_vjp` over it: differentiated as
    it stands, a `cond` saves the union of its branches' residuals and
    fills the untaken branch's with zeros, whole-size arrays written on the
    short path. The backward holds its own `cond` and recomputes inside it
    (under `jax.checkpoint` the forward it replaces is dead code), and
    calls the grouped products as the forward does, so that a profile
    shows them under the same names."""
    at = lambda rows: functools.partial(_rows_fwd, interpret, rows, out_dtype)
    return jax.lax.cond(jnp.sum(sizes) <= M, at(M), at(inverse.shape[0]),
                        h, order, inverse, sizes, g, w1, w3, w2)


def _bounded_rows_bwd(interpret, M, out_dtype, res, dy):
    at = lambda rows: functools.partial(_rows_bwd, interpret, rows)
    _h, _order, inverse, sizes = res[:4]
    # behind a barrier: the compiler otherwise moves what reads the
    # gradients into both branches, where each then writes the stacked
    # layers' float32 gradient whole (1.6 GB a branch at Mellum2's sizes)
    dh, dg, dw1, dw3, dw2 = jax.lax.optimization_barrier(jax.lax.cond(
        jnp.sum(sizes) <= M, at(M), at(inverse.shape[0]), *res, dy))
    return dh, None, None, None, dg, dw1, dw3, dw2


_bounded_rows.defvjp(
    lambda interpret, M, out_dtype, *args:
    (_bounded_rows(interpret, M, out_dtype, *args), args), _bounded_rows_bwd)


def grouped_swiglu_gmm(h, local, g, w1, w3, w2, interpret=None, rows=None,
                       out_dtype=jnp.float32):
    """The sorted spelling over jax's own Pallas grouped matmul
    (`pallas.ops.tpu.megablox.gmm`, the kernel `lax.ragged_dot` lowers to
    on a TPU; `ragged_dot` itself runs it at jax's default tiles and lost
    at every row count, docs/KERNELS.md) at tiles chosen for these shapes.
    Off the TPU the kernel is interpreted. Differentiable (`_gmm`).
    `rows`, `out_dtype`: `_sorted_swiglu`'s bound on the sorted arrays and
    the dtype of their sum."""
    from ..ops.pallas_attention import on_tpu
    if interpret is None:
        interpret = not on_tpu()
    return _sorted_swiglu(h, local, g, w1, w3, w2, interpret, rows,
                          out_dtype)


def grouped_swiglu_dense(h, local, g, w1, w3, w2, rows=None,
                         out_dtype=jnp.float32):
    """Every expert held on every row, then a masked weighted sum: E/k
    times the products and no sort (so nothing for `rows` to bound). At
    decode (a few rows an expert) the layer is bound by streaming the
    experts' weights either way."""
    Eh = w1.shape[0]
    a = jnp.einsum("nd,edf->enf", h, w1)
    b = jnp.einsum("nd,edf->enf", h, w3)
    y = jnp.einsum("enf,efd->end", (jax.nn.silu(a) * b).astype(h.dtype), w2)
    hit = local[:, :, None] == jnp.arange(Eh, dtype=jnp.int32)  # [N, k, Eh]
    wgt = jnp.sum(jnp.where(hit, g[:, :, None], 0.0), axis=1)   # [N, Eh]
    return jnp.einsum("end,ne->nd", y, wgt,
                      preferred_element_type=jnp.float32)


_GROUPED = {"gmm": grouped_swiglu_gmm, "dense": grouped_swiglu_dense}


def _gate_grouped(N, k, Eh, D, F, dtype):
    """(key, candidates, make_args) for ops/autobench: the two spellings
    of the grouped products that each win somewhere on the chip, on one
    layer's shapes, random even routing."""
    dtype = jnp.dtype(dtype)
    # the candidates are part of the key: a decision between other
    # spellings does not answer for these
    key = ("moe_grouped_swiglu", "|".join(_GROUPED), N, k, Eh, D, F,
           str(dtype))

    def make_args():
        import numpy as np
        rng = np.random.RandomState(0)
        keys = iter(jax.random.split(jax.random.PRNGKey(0), 4))
        # drawn on the device: 128 experts' trial weights are 600 M
        # numbers, a quarter of a minute of numpy a key
        mk = lambda *s: (0.02 * jax.random.normal(next(keys), s,
                                                  jnp.float32)).astype(dtype)
        local = jnp.asarray(np.argsort(rng.rand(N, Eh), axis=1)[:, :k],
                            jnp.int32)
        g = jnp.full((N, k), 1.0 / k, jnp.float32)
        return (mk(N, D) * 50, local, g,
                mk(Eh, D, F), mk(Eh, D, F), mk(Eh, F, D))

    return key, dict(_GROUPED), make_args


def _auto_grouped(h, local, w1) -> str:
    """Measure-once arbitration on a TPU (ops/autobench.prefer) between
    the Pallas grouped matmul and every-expert-on-every-row; elsewhere
    the latter, which is plain einsum."""
    from ..ops.pallas_attention import on_tpu
    if not on_tpu():
        return "dense"
    from ..ops import autobench
    (N, k), (Eh, D, F) = local.shape, w1.shape
    if Eh * N * F * h.dtype.itemsize > 2 ** 30:
        # every expert on every row holds [Eh, N, F] twice over: past a GiB
        # each (a prefill bucket of thousands of rows over 128 experts) it
        # is E/k times the work AND may not fit beside a full cache; it is
        # not measured
        return "gmm"
    key, cands, make_args = _gate_grouped(N, k, Eh, D, F, h.dtype)
    return autobench.prefer(key, cands, make_args, default="gmm")


def dropless_moe_ffn(h, wg, bias, w1, w3, w2, *, top_k: int,
                     norm_topk: bool = True, scale: float = 1.0,
                     experts_held=None, impl: str | None = None,
                     shared=None, route: str = "sigmoid"):
    """Dropless routed SwiGLU layer over the experts held here.

    h [N, D]; wg [D, E] and bias [E] are the WHOLE router (it routes over
    all E experts); w1/w3 [Eh, D, F] and w2 [Eh, F, D] are the weights of
    `experts_held` (global ids, in the order of the rows; default all E).
    The result is the part of the layer's output that these experts give:
    over the shares of a partition of the experts the parts add up to the
    whole layer. No pair is dropped: where a strict share is held, the
    sorted spelling's arrays hold `held_rows_bound` rows and a routing that
    puts more pairs on the share takes the whole-size path.

    shared: (w1 [D, Fs], w3 [D, Fs], w2 [Fs, D]) of the SHARED experts
    (DeepSeek-V3's: one SwiGLU of n_shared x F that every token takes,
    unrouted and unweighted), added to the routed part. Every share of a
    partition would compute it alike: give it to one, it counts once.

    impl: None = auto (see `_auto_grouped`), "gmm" or "dense".
    route: "sigmoid" (`sigmoid_topk_route`) or "softmax"
    (`softmax_topk_route`).

    Differentiable in h, wg and the experts' weights, both spellings: a
    pair whose expert lives elsewhere adds nothing forward and nothing to
    any gradient but the router's, whose weights are normalised over all
    the chosen.
    Returns (y [N, D] in h's dtype, sel [N, k] int32 global expert ids)."""
    E = wg.shape[1]
    # by name at call time: the benchmark's fault tools replace a router
    # as this module's attribute
    router = {"sigmoid": sigmoid_topk_route,
              "softmax": softmax_topk_route}[route]
    with jax.named_scope("moe.route"):
        sel, g = router(h, wg, bias, top_k, norm_topk, scale)
    table, Eh = _held_index(E, experts_held)
    if w1.shape[0] != Eh:
        raise ValueError(f"{w1.shape[0]} experts' weights for "
                         f"{Eh} experts held")
    local = sel if table is None else table[sel]
    if impl is None:
        impl = _auto_grouped(h, local, w1)
    # the sum is cast to h's dtype below; with nothing added to it first,
    # the bounded arrays may as well hand it back so
    y = _GROUPED[impl](h, local, g, w1, w3, w2,
                       rows=held_rows_bound(h.shape[0], top_k, Eh, E),
                       out_dtype=jnp.float32 if shared is not None
                       else h.dtype)
    if shared is not None:
        s1, s3, s2 = shared
        y = y + jnp.dot((jax.nn.silu(h @ s1) * (h @ s3)), s2,
                        preferred_element_type=jnp.float32)
    return y.astype(h.dtype), sel
