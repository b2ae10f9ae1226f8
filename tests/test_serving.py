"""paddle_tpu.serving: page allocator, scheduler policy, ragged paged
attention (XLA + Pallas-interpret), autobench gate, and the end-to-end
continuous-batching acceptance test (ISSUE 2): >= 8 concurrent requests
of different prompt/output lengths decode token-for-token identically
to sequential batch-1 greedy decode, with at most one compile per
(slots, pages) bucket and deadline preemption returning every page."""
import functools
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.serving import (Engine, GPTDecodeModel, PagePool, QueueFull,
                                Request, Scheduler, defrag_plan,
                                pages_needed)
from paddle_tpu.models.gpt import GPTConfig, gpt_forward
from paddle_tpu.nn.decode import greedy_decode


# ---------------------------------------------------------------------------
# page pool
# ---------------------------------------------------------------------------

def test_page_pool_alloc_free_admission():
    pool = PagePool(num_pages=8, page_size=4)
    assert pool.free_pages == 8 and pool.occupancy == 0.0
    assert pages_needed(1, 4) == 1 and pages_needed(9, 4) == 3
    assert pool.can_admit(32) and not pool.can_admit(33)
    t = pool.alloc_table(10)            # 3 pages
    assert len(t.pages) == 3 and pool.used_pages == 3
    assert pool.alloc(6) is None        # only 5 left — no partial alloc
    assert pool.alloc_failures == 1
    t2 = pool.alloc_table(20)           # 5 pages: pool now full
    assert pool.free_pages == 0 and not pool.can_admit(1)
    pool.free(t)
    assert pool.free_pages == 3 and t.pages == []
    pool.free(t2)
    assert pool.free_pages == 8
    assert pool.stats()["alloc_count"] == 8


def test_page_pool_double_free_rejected():
    pool = PagePool(4, 4)
    t = pool.alloc_table(4)
    pages = list(t.pages)
    pool.free(t)
    with pytest.raises(ValueError, match="double free"):
        pool.free(pages)


def test_page_table_padding_and_defrag_plan():
    pool = PagePool(8, 4)
    a = pool.alloc_table(8)    # pages [0, 1]
    b = pool.alloc_table(4)    # page  [2]
    pool.free(a)
    c = pool.alloc_table(4)    # reuses a freed page
    assert b.padded(4, fill=99) == [2, 99, 99, 99]
    wide = pool.alloc_table(16)
    with pytest.raises(ValueError, match="bucket width"):
        wide.padded(1)
    # defrag_plan requires EVERY allocated page to be declared by a
    # holder (unaccounted pages would be silently dropped from the
    # device copy) — retire the throwaway table first
    pool.free(wide)
    mapping = defrag_plan(pool, [b, c])
    # live pages now occupy the lowest indices, tables rewritten
    assert sorted(b.pages + c.pages) == [0, 1]
    assert pool.free_pages == 8 - 2
    assert set(mapping.values()) == {0, 1}


# ---------------------------------------------------------------------------
# scheduler policy (no model, fake clock)
# ---------------------------------------------------------------------------

def _mk_sched(num_pages=16, page_size=4, num_slots=2, max_queue=4):
    clock = {"t": 0.0}
    pool = PagePool(num_pages, page_size)
    s = Scheduler(pool, num_slots, max_seq_len=num_pages * page_size,
                  max_queue=max_queue, now=lambda: clock["t"])
    return s, pool, clock


def test_scheduler_admission_capacity_and_fifo():
    s, pool, _ = _mk_sched(num_pages=4, page_size=4, num_slots=2)
    r1 = s.submit(Request([1] * 8, 4))       # 3 pages
    r2 = s.submit(Request([1] * 4, 4))       # 2 pages — won't fit with r1
    r3 = s.submit(Request([1], 1))           # 1 page (fits, but FIFO blocks)
    admitted = s.admit()
    assert admitted == [r1] and r1.slot == 0 and pool.used_pages == 3
    assert s.admit() == []                   # r2 blocked; r3 behind it
    s.evict(r1, "done")
    assert pool.used_pages == 0
    assert s.admit() == [r2, r3]
    assert {r2.slot, r3.slot} == {0, 1}


def test_scheduler_eos_and_max_tokens_eviction():
    s, pool, _ = _mk_sched()
    r = s.submit(Request([1, 2], 3, eos_id=7))
    s.admit()
    assert not s.record_token(r, 5)
    assert s.record_token(r, 7)              # EOS
    assert r.status == "done" and r.generated == [5, 7]
    assert pool.used_pages == 0 and s.completed == 1
    r2 = s.submit(Request([1], 2))
    s.admit()
    assert not s.record_token(r2, 3)
    assert s.record_token(r2, 4)             # max_new_tokens
    assert r2.status == "done" and r2.result().tolist() == [3, 4]


def test_scheduler_deadline_preemption_frees_pages():
    # pool of 4 pages: r_run (3 pages) admits, r_q (3 pages) stays queued
    s, pool, clock = _mk_sched(num_pages=4, page_size=4)
    r_run = s.submit(Request([1] * 4, 8, deadline=5.0))
    r_q = s.submit(Request([1] * 4, 8, deadline=1.0))
    assert s.admit() == [r_run]
    s.record_token(r_run, 2)
    assert pool.used_pages > 0
    clock["t"] = 2.0
    hit = s.expire_deadlines()               # queued r_q expires first
    assert hit == [r_q] and r_q.status == "deadline"
    clock["t"] = 6.0
    hit = s.expire_deadlines()               # running r_run preempted
    assert hit == [r_run] and r_run.status == "deadline"
    assert r_run.result().tolist() == [2]    # partial output stands
    assert pool.used_pages == 0              # ALL pages back
    assert s.preemptions == 1 and s.slots == [None, None]


def test_scheduler_backpressure():
    s, _, _ = _mk_sched(max_queue=2)
    s.submit(Request([1], 1))
    s.submit(Request([1], 1))
    with pytest.raises(QueueFull):
        s.submit(Request([1], 1))
    assert s.rejected == 1
    with pytest.raises(ValueError, match="max_seq_len"):
        s.submit(Request([1] * 60, 10))      # 70 > 64


# ---------------------------------------------------------------------------
# ragged paged attention
# ---------------------------------------------------------------------------

def _paged_args(S=4, H=4, d=16, P=12, ps=8, M=3, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(S, H, d).astype(np.float32))
    k = jnp.asarray(rng.randn(P + 1, ps, H, d).astype(np.float32))
    v = jnp.asarray(rng.randn(P + 1, ps, H, d).astype(np.float32))
    pt = jnp.asarray(rng.randint(0, P, (S, M)), jnp.int32)
    ln = jnp.asarray([1, 5, 17, 24], jnp.int32)
    return q, k, v, pt, ln


def test_paged_attention_xla_matches_dense():
    from paddle_tpu.ops.paged_attention import paged_attention_xla
    q, k, v, pt, ln = _paged_args()
    o = paged_attention_xla(q, k, v, pt, ln)
    # reference: per-slot dense softmax over its gathered ragged context
    for s in range(q.shape[0]):
        ctx = int(ln[s])
        kk = np.asarray(k)[np.asarray(pt)[s]].reshape(-1, 4, 16)[:ctx]
        vv = np.asarray(v)[np.asarray(pt)[s]].reshape(-1, 4, 16)[:ctx]
        qq = np.asarray(q)[s]
        logits = np.einsum("hd,thd->ht", qq, kk) / np.sqrt(16)
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("ht,thd->hd", p, vv)
        np.testing.assert_allclose(np.asarray(o)[s], ref, atol=1e-5)


def test_paged_attention_pallas_interpret_matches_xla():
    from paddle_tpu.ops.paged_attention import (paged_attention_pallas,
                                                paged_attention_xla)
    q, k, v, pt, ln = _paged_args()
    a = paged_attention_xla(q, k, v, pt, ln)
    b = paged_attention_pallas(q, k, v, pt, ln, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                               rtol=1e-5)


def _stacked_args(L=3, **kw):
    """A pool stacked over L layers, each layer's pages different."""
    q, k, v, pt, ln = _paged_args(**kw)
    rng = np.random.RandomState(7)
    ks = jnp.asarray(rng.randn(L, *k.shape).astype(np.float32))
    vs = jnp.asarray(rng.randn(L, *v.shape).astype(np.float32))
    return q, ks, vs, pt, ln


def _paged_impl(impl):
    from paddle_tpu.ops import paged_attention as pa
    if impl == "xla":
        return pa.paged_attention_xla
    return functools.partial(pa.paged_attention_pallas, interpret=True)


def _assert_same(impl, got, want):
    """The XLA path gathers the same values whatever the pool's rank:
    exact. The Pallas path to the tolerance it is held to against XLA."""
    if impl == "xla":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("G,d", [(4, 64), (2, 16), (1, 16)])
def test_paged_attention_grouped_query_matches_dense(impl, G, d, fused):
    """G query heads a KV head (LFM2: 4 of 64) against a dense float32
    attention, KV head j serving query heads G j .. G j + G - 1; separate
    K and V pools, and the fused [K | V] pool a head under 128 lanes
    wants. Tolerance: float32 sums in another order."""
    S, Hkv, P, ps, M, L = 4, 2, 12, 8, 3, 2
    H = G * Hkv
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(S, H, d).astype(np.float32))
    k = jnp.asarray(rng.randn(L, P + 1, ps, Hkv, d).astype(np.float32))
    v = jnp.asarray(rng.randn(L, P + 1, ps, Hkv, d).astype(np.float32))
    pt = jnp.asarray(rng.randint(0, P, (S, M)), jnp.int32)
    ln = jnp.asarray([1, 5, 17, 24], jnp.int32)
    pools = (jnp.concatenate([k, v], -1), None) if fused else (k, v)
    o = _paged_impl(impl)(q, *pools, pt, ln, layer=1)
    assert o.shape == (S, H, d)
    for s in range(S):
        n = int(ln[s])
        kk = np.asarray(k)[1][np.asarray(pt)[s]].reshape(-1, Hkv, d)[:n]
        vv = np.asarray(v)[1][np.asarray(pt)[s]].reshape(-1, Hkv, d)[:n]
        for h in range(H):
            sc = kk[:, h // G] @ np.asarray(q)[s, h] / np.sqrt(d)
            pr = np.exp(sc - sc.max())
            pr /= pr.sum()
            np.testing.assert_allclose(np.asarray(o)[s, h],
                                       pr @ vv[:, h // G], atol=2e-5)


def test_paged_gate_key_tells_grouped_query_and_fused_pools_apart():
    from paddle_tpu.ops.paged_attention import _gate_paged
    # "live_pages": the candidate named `pallas` is the kernel that walks
    # a slot's live pages (PR 29). A decision cached for the grid kernel,
    # whose time was the table's width whatever the contexts, says nothing
    # of this one, and parent and change share one gate cache on a
    # machine: under the old key ("paged_attention", "stacked", 32, ...)
    # the new kernel would have been judged by the old one's time.
    mha = ("paged_attention", "live_pages", 32, 16, 128, 3073, 16, 128,
           "bfloat16")
    assert _gate_paged(32, 16, 128, 3073, 16, 128, "bfloat16")[0] == mha
    assert _gate_paged(32, 16, 128, 3073, 16, 128, "bfloat16",
                       Hkv=16)[0] == mha
    gqa = _gate_paged(64, 32, 64, 16385, 16, 256, "bfloat16", Hkv=8)[0]
    fused = _gate_paged(64, 32, 64, 16385, 16, 256, "bfloat16", Hkv=8,
                        fused=True)[0]
    assert gqa[-2:] == ("kv_heads", 8) and fused[-1] == "fused"
    assert len({mha, gqa, fused}) == 3
    # "mxu" (PR 41): a grouped key's `pallas` is the kernel that multiplies
    # a KV head's block on the MXU; a record timed on the VPU loop of
    # before, ("paged_attention", "live_pages", ..., "kv_heads", 8,
    # "fused"), names another kernel and is not found again
    assert "mxu" in gqa and "mxu" in fused and "mxu" not in mha
    assert tuple(w for w in fused if w != "mxu") == mha[:2] + (
        64, 32, 64, 16385, 16, 256, "bfloat16", "kv_heads", 8, "fused")


# The kernel's blocks, at a size a test can hold: 16 KiB of page buffers is
# B = 4 pages a block for both pool forms below (float32, ps 8, 2 KV heads
# of 16: 1 KiB a page of K and of V, 2 KiB a fused one), a table of 10.
_WALK = dict(S=4, Hkv=2, d=16, P=40, ps=8, M=10, L=2, B=4)


def _walk_args(G, fused, lens, dead_page=None, seed=11):
    """(q, pools, table, lens): each slot's live pages drawn from the
    pool, the rest of its row `dead_page` (default: the trash page, P)."""
    c = _WALK
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(c["S"], G * c["Hkv"], c["d"]), jnp.float32)
    shape = (c["L"], c["P"] + 2, c["ps"], c["Hkv"], c["d"])
    k = jnp.asarray(rng.randn(*shape), jnp.float32)
    v = jnp.asarray(rng.randn(*shape), jnp.float32)
    table = np.full((c["S"], c["M"]),
                    c["P"] if dead_page is None else dead_page, np.int32)
    for s, n in enumerate(-(-np.asarray(lens) // c["ps"])):
        table[s, :n] = rng.permutation(c["P"])[:n]
    pools = (jnp.concatenate([k, v], -1), None) if fused else (k, v)
    return q, pools, jnp.asarray(table), jnp.asarray(lens, jnp.int32)


@pytest.fixture
def small_blocks(monkeypatch):
    from paddle_tpu.ops import paged_attention as pa
    monkeypatch.setattr(pa, "_PAGE_BUFFER_BYTES", 16 * 1024)
    for n_pools, page in ((2, 1024), (1, 2048)):
        assert pa._block_pages(page, n_pools, _WALK["M"]) == _WALK["B"]
    return pa


@pytest.mark.parametrize("G,fused", [(1, False), (2, True), (4, False),
                                     (4, True)])
@pytest.mark.parametrize("ctx", [1, 7, 8, 9, 32, 33, 64, 80])
def test_paged_kernel_walks_each_slots_own_pages(small_blocks, ctx, G,
                                                 fused):
    """The body that runs on the chip, interpreted: manual page copies
    under a loop whose trip count is the slot's. Contexts round a page's
    edge (ps - 1, ps, ps + 1), exactly one block of B pages, one token
    more, two blocks, the whole table; beside each, in the same batch, an
    empty slot (one token, a table all trash) and a full one, so that the
    buffer a slot starts in changes from slot to slot; layer 1 of a stack
    of 2; multi-head, grouped heads, separate and fused pools."""
    pa = small_blocks
    q, pools, table, lens = _walk_args(G, fused, [ctx, 1, 80, ctx])
    table = table.at[1].set(_WALK["P"])             # the empty slot
    got = pa.paged_attention_pallas(q, *pools, table, lens, layer=1,
                                    interpret=True)
    want = pa.paged_attention_xla(q, *pools, table, lens, layer=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("G,fused", [(1, False), (4, True)])
def test_paged_kernel_never_reads_a_dead_page(small_blocks, G, fused):
    """Every table entry past cdiv(ctx, ps) names a page of NaN, and what
    the kernel's buffers hold before a copy lands is NaN too: the output
    is finite and is the XLA path's over a table whose dead entries are
    clean. (The grid kernel of before read every entry of the table and
    masked afterwards: 0 x NaN.)"""
    from jax.experimental.pallas import tpu as pltpu
    pa = small_blocks
    nan_page = _WALK["P"] + 1
    lens = [33, 1, 80, 9]
    q, pools, table, lens = _walk_args(G, fused, lens, dead_page=nan_page)
    pools = tuple(None if p is None else p.at[:, nan_page].set(jnp.nan)
                  for p in pools)
    got = pa.paged_attention_pallas(
        q, *pools, table, lens, layer=1,
        interpret=pltpu.InterpretParams(uninitialized_memory="nan"))
    assert np.isfinite(np.asarray(got)).all()
    clean = jnp.where(table == nan_page, _WALK["P"], table)
    want = pa.paged_attention_xla(q, *pools, clean, lens, layer=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# contexts of the grouped kernel's cases, in pages of 8 and blocks of 4: one
# token; a page's first row and its last; the last page of a block, first
# row and last; one page past a block, first row and last; the whole table
_EDGE_LENS = [1, 9, 16, 25, 32, 33, 40, 80]


@pytest.mark.parametrize("G,Hkv,fused,dtype,atol", [
    (G, Hkv, fused, "float32", 1e-5)
    for G in (2, 4, 8) for Hkv in (2, 4, 8) for fused in (False, True)
] + [(G, Hkv, fused, "bfloat16", 2e-2)     # the two cells' head counts
     for G, Hkv in ((8, 4), (4, 8)) for fused in (False, True)])
def test_grouped_kernel_matches_xla_at_head_counts(monkeypatch, G, Hkv, fused,
                                                   dtype, atol):
    """The kernel that multiplies a KV head's block on the MXU, beside the
    gather path: G query heads over Hkv KV heads, K and V pools and the
    fused one, the contexts of `_EDGE_LENS` in one batch. Every row no
    context reaches is NaN: the dead entries of a table name a page of
    NaN, the tail of a slot's last page is NaN in the pool, and the
    buffer's pages that a last block does not fill start as NaN. (Each
    slot has pages of its own, so that one's dead tail is nobody's live
    row.) bfloat16: both paths round the probabilities to the pool's
    dtype, the gather path after it has normalised them."""
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops import paged_attention as pa
    ps, d, M, B, L = 8, 16, 10, 4, 2
    itemsize = jnp.dtype(dtype).itemsize
    monkeypatch.setattr(pa, "_PAGE_BUFFER_BYTES",
                        2 * 2 * B * ps * Hkv * d * itemsize)
    assert pa._block_pages(ps * Hkv * d * itemsize, 2, M) == B
    assert pa._block_pages(ps * Hkv * 2 * d * itemsize, 1, M) == B
    lens = np.asarray(_EDGE_LENS)
    n = -(-lens // ps)
    nan_page = int(n.sum())
    rng = np.random.RandomState(G * 10 + Hkv)
    kv = rng.randn(2, L, nan_page + 1, ps, Hkv, d).astype(np.float32)
    kv[:, :, nan_page] = np.nan
    table = np.full((len(lens), M), nan_page, np.int32)
    for s, at in enumerate(np.cumsum(n) - n):
        table[s, :n[s]] = at + rng.permutation(n[s])
        kv[:, :, table[s, n[s] - 1], lens[s] - (n[s] - 1) * ps:] = np.nan
    q = jnp.asarray(rng.randn(len(lens), G * Hkv, d), dtype)
    k, v = jnp.asarray(kv, dtype)
    pools = (jnp.concatenate([k, v], -1), None) if fused else (k, v)
    got = pa.paged_attention_pallas(
        q, *pools, jnp.asarray(table), jnp.asarray(lens, jnp.int32), layer=1,
        interpret=pltpu.InterpretParams(uninitialized_memory="nan"))
    assert np.isfinite(np.asarray(got, np.float32)).all()
    clean = tuple(None if p is None else jnp.nan_to_num(p) for p in pools)
    want = pa.paged_attention_xla(q, *clean, jnp.asarray(table),
                                  jnp.asarray(lens, jnp.int32), layer=1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol,
                               rtol=atol)


@pytest.mark.parametrize("Hkv", [2, 4, 8])
def test_head_rows_as_words_are_the_indexed_head(Hkv):
    """`_head_rows`' spelling for the chip (bfloat16 pages of 128 lanes:
    32-bit words read with a stride, a head's half moved to the top of its
    word) reads what the indexed head reads, bit for bit; the kernel's
    interpreted runs take the indexed one."""
    from jax.experimental import pallas as pl
    from paddle_tpu.ops.paged_attention import _head_rows
    B, ps, w = 2, 8, 128
    x = jax.random.normal(jax.random.PRNGKey(Hkv), (B, ps, Hkv, w),
                          jnp.bfloat16)

    def heads(words):
        def kernel(x_ref, o_ref):
            for h in range(Hkv):
                o_ref[h] = _head_rows(x_ref, h, words)
        return pl.pallas_call(kernel, interpret=True, out_shape=(
            jax.ShapeDtypeStruct((Hkv, B * ps, w), x.dtype)))(x)

    want = np.asarray(x.reshape(B * ps, Hkv, w).transpose(1, 0, 2), np.float32)
    np.testing.assert_array_equal(np.asarray(heads(False), np.float32), want)
    np.testing.assert_array_equal(np.asarray(heads(True), np.float32), want)


@pytest.mark.parametrize("G,fused", [(1, False), (1, True), (4, False),
                                     (4, True)])
def test_grouped_paged_kernel_multiplies_on_the_mxu(G, fused):
    """The structural guard of PR 41: query heads that share their keys
    (G > 1), or a fused pool, meet a KV head's block as a matrix: the
    kernel's program holds two `dot_general`s a KV head (scores and
    values) and makes no float32 copy of a page [ps, Hkv, w], which the
    VPU loop made once a page and multiplied once a group. Plain
    multi-head attention (G = 1, K and V pools) has one row a head for the
    MXU: its kernel holds no `dot_general` and keeps the page loop."""
    from paddle_tpu.ops import paged_attention as pa
    Hkv = _WALK["Hkv"]
    q, pools, table, lens = _walk_args(G, fused, [33, 1, 80, 9])
    jaxpr = jax.make_jaxpr(lambda *a: pa.paged_attention_pallas(
        *a, layer=1, interpret=True))(q, *pools, table, lens)
    call, = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    inside = list(_eqns(call.params["jaxpr"]))
    dots = [e for e in inside if e.primitive.name == "dot_general"]
    page = (_WALK["ps"], Hkv, pools[0].shape[-1])
    pages = [e for e in inside for o in e.outvars
             if getattr(o.aval, "shape", None) == page
             and o.aval.dtype == jnp.float32]
    if G == 1 and not fused:
        assert not dots and pages
    else:
        assert len(dots) == 2 * Hkv and not pages, (len(dots), pages)


def test_paged_attention_refuses_heads_that_do_not_divide():
    from paddle_tpu.ops.paged_attention import paged_attention_xla
    q, k, v, pt, ln = _paged_args(H=4)
    with pytest.raises(ValueError, match="KV heads"):
        paged_attention_xla(q[:, :3], k, v, pt, ln)


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_attention_stacked_pool_equals_layer_slice(impl, layer):
    """[L, P, ps, H, d] with `layer` is the rank-4 form on pool[layer]:
    first, middle and last layer, the layer a python int."""
    fn = _paged_impl(impl)
    q, ks, vs, pt, ln = _stacked_args()
    want = fn(q, ks[layer], vs[layer], pt, ln)
    _assert_same(impl, fn(q, ks, vs, pt, ln, layer=layer), want)


@pytest.mark.parametrize("how", ["jit", "scan"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_attention_stacked_pool_traced_layer(impl, how):
    """The layer as the decode body has it: a traced int32 scalar, an
    argument of a jitted call or the index a lax.scan carries."""
    fn = _paged_impl(impl)
    q, ks, vs, pt, ln = _stacked_args()
    L = ks.shape[0]
    want = jnp.stack([fn(q, ks[l], vs[l], pt, ln) for l in range(L)])
    if how == "jit":
        f = jax.jit(lambda l: fn(q, ks, vs, pt, ln, layer=l))
        got = jnp.stack([f(jnp.int32(l)) for l in range(L)])
    else:
        _, got = jax.lax.scan(
            lambda c, l: (c, fn(q, ks, vs, pt, ln, layer=l)), 0,
            jnp.arange(L))
    _assert_same(impl, got, want)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_attention_pool_rank_and_layer_must_agree(impl):
    fn = _paged_impl(impl)
    q, ks, vs, pt, ln = _stacked_args()
    with pytest.raises(ValueError, match="needs layer"):
        fn(q, ks, vs, pt, ln)
    with pytest.raises(ValueError, match="rank 4"):
        fn(q, ks[0], vs[0], pt, ln, layer=0)
    with pytest.raises(ValueError, match="layer 3 of a pool of 3"):
        fn(q, ks, vs, pt, ln, layer=3)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it (scan and
    pjit bodies, a Pallas kernel's body)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _gpt_body_jaxpr(model, entry, P=10, ps=8, S=4, M=3, T=16):
    """The jaxpr of one of `GPTDecodeModel`'s three bodies at small
    shapes: a pool of P pages of ps, S slots or a bucket of T tokens."""
    head = (model.params, model.init_cache(P, ps))
    toks, row = jnp.zeros((T,), jnp.int32), jnp.arange(M + 1, dtype=jnp.int32)
    targs = {
        "prefill": (*head, toks, jnp.int32(T - 3), row),
        "prefill_tail": (*head, toks, jnp.int32(ps), jnp.int32(T), row),
        "decode": (*head, jnp.zeros((S,), jnp.int32),
                   jnp.arange(S, dtype=jnp.int32),
                   jnp.full((S, M), P, jnp.int32)),
    }[entry]
    return jax.make_jaxpr(getattr(model, entry))(*targs)


@pytest.mark.parametrize("entry", ["decode", "prefill_tail"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_decode_bodies_never_make_one_layers_pool(impl, entry):
    """The structural guard of PR 25: no equation of the traced program
    outputs an array of one layer's pool shape (P+1, ps, H, d). `ck[l]`
    in front of paged attention did, and on the chip that was a copy of
    201 MB for K and for V in each of 24 layers of every decode step."""
    cfg = GPTConfig.tiny(num_layers=3)
    model = GPTDecodeModel(cfg, seed=0, attn_impl=impl)
    P, ps = 10, 8
    pool = (P + 1, ps, cfg.num_heads, model.head_dim)
    assert model.init_cache(P, ps)["k"].shape[1:] == pool
    jaxpr = _gpt_body_jaxpr(model, entry, P=P, ps=ps)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1          # the layer loop is what is walked
    made = [(e.primitive.name, v.aval.shape)
            for e in _eqns(scans[0].params["jaxpr"].jaxpr)
            for v in e.outvars if getattr(v.aval, "shape", None) == pool]
    assert not made, made


@pytest.mark.parametrize("entry", ["prefill", "prefill_tail", "decode"])
def test_gpt_bodies_are_drivers_over_one_layer_loop(entry, monkeypatch):
    """`GPTDecodeModel` spells its layer once: every body's program holds
    exactly one scan, of `num_layers` steps, whose body is `_layers`'; the
    two paged bodies reach `paged_attention_decode` through the one
    `attend` of `_paged`, and the dense one does not reach it."""
    import sys
    from paddle_tpu.serving import model as model_mod
    callers = {"decoder_tail": [], "paged_attention_decode": []}
    for name in callers:
        def spy(*a, _name=name, _real=getattr(model_mod, name), **kw):
            callers[_name].append(sys._getframe(1).f_code.co_qualname)
            return _real(*a, **kw)
        monkeypatch.setattr(model_mod, name, spy)
    cfg = GPTConfig.tiny(num_layers=3)
    jaxpr = _gpt_body_jaxpr(GPTDecodeModel(cfg, seed=0, attn_impl="xla"),
                            entry)
    scans = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [cfg.num_layers]
    assert callers["decoder_tail"] == ["GPTDecodeModel._layers.<locals>.body"]
    assert callers["paged_attention_decode"] == (
        [] if entry == "prefill"
        else ["GPTDecodeModel._paged.<locals>.attend"])


def _weight_operands(eqns, cfg):
    """The `pallas_call`s among `eqns`, each with those of its operands'
    shapes that are one layer's `wo`, `w_up` or `w_down`."""
    D, F = cfg.hidden_size, cfg.intermediate_size
    return [(e, [v.aval.shape for v in e.invars
                 if getattr(v.aval, "shape", None) in ((D, D), (D, F), (F, D))])
            for e in eqns if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("entry", ["prefill", "prefill_tail", "decode"])
def test_gpt_bodies_put_no_layer_slice_in_front_of_a_kernel(entry,
                                                            monkeypatch):
    """The structural guard of PR 35, beside PR 25's: inside the one layer
    scan no `pallas_call` takes an array of a layer's `wo` / `w_up` /
    `w_down` shape. There those are the scan's slices of the stacked
    blocks, and in front of a Mosaic call a slice is a copy: on the chip
    24 x 75 MB a decode step, the weight stream itself, with the kernel
    serial behind it (1.8 ms of 12.2). The paged kernel, which takes the
    WHOLE pool and the layer's index, is the only kernel allowed there.
    The opt-in makes `can_use_*` and `*_wins` say yes off the chip, as the
    gate may on it, at widths the fused tail accepts (and that no block
    of rows, padded to 128, has)."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    cfg = GPTConfig.tiny(num_layers=3, hidden_size=256)
    assert cfg.fused_blocks         # the tail's kernels were asked for
    model = GPTDecodeModel(cfg, seed=0, attn_impl="pallas")
    jaxpr = _gpt_body_jaxpr(model, entry)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    calls = _weight_operands(_eqns(scans[0].params["jaxpr"].jaxpr), cfg)
    assert not [shapes for _e, shapes in calls if shapes], calls
    pool = model.init_cache(10, 8)["k"].shape
    for e, _shapes in calls:
        assert pool in [v.aval.shape for v in e.invars], e
    assert bool(calls) == (entry != "prefill")      # the paged kernel ran


def test_training_block_keeps_the_fused_tail(monkeypatch):
    """Serving's choice is not training's: under the same opt-in
    `gpt_block_fn`, whose weights are arrays of their own (the trainer's
    layer loop hands each block its leaves), still reaches `fused_out_ln`
    and `fused_ffn_ln`."""
    from paddle_tpu.models.gpt import gpt_block_fn, init_gpt_params
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    cfg = GPTConfig.tiny(num_layers=1, hidden_size=256)
    D, F = cfg.hidden_size, cfg.intermediate_size
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a[0]),
                               init_gpt_params(cfg, 0)["blocks"])
    jaxpr = jax.make_jaxpr(lambda p, x: gpt_block_fn(p, x, cfg))(
        p, jnp.zeros((2, 16, D), jnp.float32))
    took = sorted(tuple(shapes) for _e, shapes
                  in _weight_operands(_eqns(jaxpr.jaxpr), cfg))
    assert took == [((D, D),), ((D, F), (F, D))], took


def _engine_programs(family):
    """A small engine of one model family and, for each of its jitted
    bodies, the arguments `Engine` calls it with (slot-wide sampling
    arrays for `decode`, shape-[1] ones for the prefill programs). The
    vocabulary is a prime, so that no other array has its size."""
    S, P, ps, T, V = 4, 24, 4, 16, 499
    if family == "gpt":
        model = GPTDecodeModel(GPTConfig.tiny(num_layers=2, vocab_size=V),
                               seed=0)
    else:
        from paddle_tpu.models import lfm2
        from paddle_tpu.serving import HybridDecodeModel
        model = HybridDecodeModel(lfm2.LFM2Config.tiny(vocab_size=V), seed=0)
    eng = Engine(model, num_slots=S, num_pages=P, page_size=ps,
                 max_seq_len=32)
    M = eng.max_pages_per_req

    def samp(n):
        return (jnp.full((n,), 0.8, jnp.float32), jnp.zeros((n,), jnp.int32),
                jnp.full((n,), 0.9, jnp.float32),
                jnp.zeros((n, 2), jnp.uint32), jnp.zeros((n,), jnp.int32))
    row = jnp.full((M,), eng.trash_page, jnp.int32)
    toks = jnp.zeros((T,), jnp.int32)
    head = (model.params, eng.cache)
    programs = {
        "decode": (eng._decode, (
            *head, jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32),
            jnp.zeros((S,), jnp.int32),
            jnp.full((S, M), eng.trash_page, jnp.int32), *samp(S)), S),
        "prefill": (eng._prefill, (
            *head, toks, np.int32(T - 3), row, np.int32(1),
            jnp.zeros((S,), jnp.int32), *samp(1)), 1),
        "prefill_tail": (eng._prefill_tail, (
            *head, toks, np.int32(ps), np.int32(T - 3), row, np.int32(1),
            jnp.zeros((S,), jnp.int32), *samp(1)), 1),
    }
    return programs, V


@pytest.mark.parametrize("family,entry", [
    ("gpt", "decode"), ("gpt", "prefill"), ("gpt", "prefill_tail"),
    ("hybrid", "decode"), ("hybrid", "prefill")])
def test_sampler_neither_sorts_nor_scans_a_vocabulary(family, entry):
    """The structural guard of PR 33 (PR 27's went with the sort it
    guarded): no jitted body of the engine sorts, prefix-sums or gathers
    an array of a vocabulary row a slot, operand or result. On a v5e the
    sort of `[64, 128256]` was 10 ms of a 27 ms decode step, the two
    prefix sums 1.6, and `take_along_axis(scaled, order)` before them 42.8
    of 68 at `[64, 65536]`. What the sampler does instead is loop over
    ONE such array, the one its docstring names, and write no other: its
    loops take `scaled` and hand back a row's scalars."""
    from paddle_tpu.serving.sampling import sample_tokens
    programs, V = _engine_programs(family)
    fn, args, S = programs[entry]
    eqns = list(_eqns(jax.make_jaxpr(fn)(*args).jaxpr))

    def wide(v):
        shape = getattr(v.aval, "shape", ())
        return shape[-1:] == (V,) or v.aval.size == S * V

    def ordering(name):
        return name in ("sort", "gather") or name.startswith("cum") \
            or name.startswith("reduce_window")
    banned = [(e.primitive.name, [v.aval.shape for v in e.invars],
               [v.aval.shape for v in e.outvars]) for e in eqns
              if ordering(e.primitive.name)
              and any(wide(v) for v in (*e.invars, *e.outvars))]
    assert not banned, banned
    loops = [e for e in eqns if e.primitive.name in ("scan", "while")
             and any(wide(v) for v in e.invars)]
    # the cut, the draw, the index among equals
    assert len(loops) == 3, [e.primitive.name for e in loops]
    read = {v for e in loops for v in e.invars if wide(v)}
    assert len(read) == 1 and "`scaled`" in sample_tokens.__doc__
    assert next(iter(read)).aval.shape == (S, V)
    assert not [v.aval.shape for e in loops for v in e.outvars if wide(v)]
    # a pass is compiled once, not once a bit of the key
    assert all(e.params.get("unroll", 1) == 1 for e in loops)


def test_paged_attention_op_registered_with_infer_shape():
    from paddle_tpu.fluid import registry
    opdef = registry.lookup("paged_attention")
    assert opdef is not None and opdef.infer_shape is not None


# ---------------------------------------------------------------------------
# autobench gate (injected timings — no real kernels)
# ---------------------------------------------------------------------------

def test_autobench_measures_once_and_caches(monkeypatch):
    from paddle_tpu.ops import autobench
    autobench.clear()
    calls = []

    def fake_measure(fn, make_args, reps):
        calls.append(fn)
        return fn(), 0.0     # candidates below return their "time"

    monkeypatch.setattr(autobench, "_measure", fake_measure)
    cands = {"pallas": lambda: 2.0, "xla": lambda: 1.0}
    assert autobench.prefer(("k", 1), cands, tuple) == "xla"
    assert len(calls) == 2
    # cached: no re-measurement for the same key
    assert autobench.prefer(("k", 1), cands, tuple) == "xla"
    assert len(calls) == 2
    # a different shape measures again and can pick the other winner
    cands2 = {"pallas": lambda: 0.5, "xla": lambda: 1.0}
    assert autobench.prefer(("k", 2), cands2, tuple) == "pallas"
    assert autobench.decisions() == {("k", 1): "xla", ("k", 2): "pallas"}
    autobench.clear()


def test_autobench_env_knobs(monkeypatch):
    from paddle_tpu.ops import autobench
    autobench.clear()
    monkeypatch.setattr(autobench, "_measure",
                        lambda fn, make_args, reps: (fn(), 0.0))
    cands = {"pallas": lambda: 2.0, "xla": lambda: 1.0}
    monkeypatch.setenv("PADDLE_TPU_AUTOBENCH_FORCE", "pallas")
    assert autobench.prefer(("e", 1), cands, tuple) == "pallas"
    monkeypatch.delenv("PADDLE_TPU_AUTOBENCH_FORCE")
    monkeypatch.setenv("PADDLE_TPU_AUTOBENCH", "0")
    assert autobench.prefer(("e", 2), cands, tuple) == "pallas"  # default
    monkeypatch.delenv("PADDLE_TPU_AUTOBENCH")
    # a crashing candidate never wins
    cands3 = {"pallas": lambda: 1 / 0, "xla": lambda: 1.0}

    def m3(fn, make_args, reps):
        return fn(), 0.0

    monkeypatch.setattr(autobench, "_measure", m3)
    # prefer() shields candidate exceptions itself
    assert autobench.prefer(("e", 3), cands3, tuple) == "xla"
    autobench.clear()


# ---------------------------------------------------------------------------
# end-to-end engine (acceptance criteria)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_engine():
    cfg = GPTConfig.tiny(num_layers=2)      # hidden 64, 4 heads, hd 16
    model = GPTDecodeModel(cfg, seed=0)
    eng = Engine(model, num_slots=8, num_pages=64, page_size=8,
                 max_seq_len=96)
    return cfg, model, eng


def test_engine_concurrent_matches_sequential_greedy(tiny_engine):
    """>= 8 concurrent requests of DIFFERENT prompt/output lengths:
    token-for-token parity with sequential batch-1 full-recompute greedy
    decode, one compile per bucket, pool drained afterwards."""
    cfg, model, eng = tiny_engine
    rng = np.random.RandomState(7)
    reqs = []
    for _ in range(9):
        plen = int(rng.randint(1, 24))
        prompt = rng.randint(0, cfg.vocab_size, (plen,))
        mnt = int(rng.randint(1, 12))
        reqs.append((prompt, mnt, eng.submit(prompt, mnt)))
    assert eng.stats()["queue_depth"] > 0
    eng.run_until_idle()
    for prompt, mnt, h in reqs:
        got = h.result(1.0).tolist()
        ref = greedy_decode(
            lambda ids: gpt_forward(model.params, ids, cfg), prompt, mnt)
        assert got == ref, (prompt[:4], mnt, got, ref)
    st = eng.stats()
    # at most one compile per bucket, asserted via the trace counters
    assert st["compiles"] and all(v == 1 for v in st["compiles"].values()), \
        st["compiles"]
    assert sum(1 for kk in st["compiles"] if kk.startswith("decode")) == 1
    assert st["pool"]["used_pages"] == 0
    assert st["completed"] == 9 and st["preemptions"] == 0
    assert st["latency_ms_p50"] is not None \
        and st["latency_ms_p99"] >= st["latency_ms_p50"]


def test_engine_deadline_preemption_returns_pages(tiny_engine):
    cfg, model, eng = tiny_engine
    rng = np.random.RandomState(3)
    long_req = eng.submit(rng.randint(0, cfg.vocab_size, (8,)), 64,
                          deadline=3600.0)
    short = eng.submit(rng.randint(0, cfg.vocab_size, (4,)), 4)
    for _ in range(4):
        eng.step()
    assert long_req.status == "running" and len(long_req.generated) >= 1
    used_before = eng.pool.used_pages
    assert used_before > 0
    long_req.deadline = -1.0                 # force the deadline past
    eng.run_until_idle()
    assert long_req.status == "deadline"
    assert len(long_req.result()) >= 1       # partial output stands
    assert short.status == "done"
    assert eng.pool.used_pages == 0          # every page back in the pool
    assert eng.stats()["preemptions"] == 1


def test_engine_eos_stops_decode(tiny_engine):
    cfg, model, eng = tiny_engine
    prompt = np.asarray([5, 9, 2])
    ref = greedy_decode(lambda ids: gpt_forward(model.params, ids, cfg),
                        prompt, 10)
    eos = ref[2]
    cut = ref.index(eos)                     # decode stops at FIRST hit
    h = eng.submit(prompt, 10, eos_id=int(eos))
    eng.run_until_idle()
    assert h.result().tolist() == ref[:cut + 1]
    assert len(h.generated) < 10
    # compile counters unchanged: same buckets as earlier tests
    assert all(v == 1 for v in eng.stats()["compiles"].values())


def test_engine_backpressure_queue_full(tiny_engine):
    cfg, model, eng = tiny_engine
    eng.scheduler.max_queue = 1
    try:
        eng.submit([1, 2], 2)
        with pytest.raises(QueueFull):
            eng.submit([3, 4], 2)
    finally:
        eng.run_until_idle()
        eng.scheduler.max_queue = 256


def test_engine_defrag_midflight(tiny_engine):
    """Defrag between steps: live pages compact, decode stays correct."""
    cfg, model, eng = tiny_engine
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, cfg.vocab_size, (int(rng.randint(2, 20)),))
               for _ in range(4)]
    handles = [eng.submit(p, 8) for p in prompts]
    for _ in range(3):
        eng.step()
    mapping = eng.defrag()
    live = sorted(p for r in eng.scheduler.active_requests()
                  for p in r.table.pages)
    assert live == list(range(len(live)))    # compacted to the low end
    assert isinstance(mapping, dict)
    eng.run_until_idle()
    for p, h in zip(prompts, handles):
        ref = greedy_decode(
            lambda ids: gpt_forward(model.params, ids, cfg), p, 8)
        assert h.result().tolist() == ref


def test_engine_decode_model_pallas_impl_parity():
    """The whole engine with the Pallas ragged kernel (interpret mode on
    CPU) decodes identically to the XLA gather path."""
    cfg = GPTConfig.tiny(num_layers=1)
    model_x = GPTDecodeModel(cfg, seed=1, attn_impl="xla")
    model_p = GPTDecodeModel(cfg, seed=1, attn_impl="pallas")
    out = []
    for model in (model_x, model_p):
        eng = Engine(model, num_slots=2, num_pages=16, page_size=8,
                     max_seq_len=32)
        h = eng.submit([3, 1, 4, 1, 5], 6)
        eng.run_until_idle()
        out.append(h.result().tolist())
    assert out[0] == out[1]


def test_engine_threaded_submit_and_stats(tiny_engine):
    cfg, model, eng = tiny_engine
    with eng:
        toks = eng.generate([2, 7, 1], max_new_tokens=5, timeout=60)
        assert len(toks) == 5
        st = eng.stats()
        assert st["tokens_generated"] > 0
        assert set(st["pool"]) >= {"occupancy", "free_pages"}
    assert eng._thread is None


def test_engine_caps_sequence_at_model_positions():
    """The engine ceiling folds in the MODEL's position limit — without
    it a request could decode past wpe and jnp.take would silently
    clip (garbage tokens with status 'done')."""
    cfg = GPTConfig.tiny(num_layers=1)         # max_position_embeddings=128
    model = GPTDecodeModel(cfg, seed=0)
    eng = Engine(model, num_slots=2, num_pages=64, page_size=8)  # pool: 512
    assert eng.max_seq_len == 128
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.submit([1] * 100, 40)              # 140 > 128
    with pytest.raises(ValueError, match="sequence ceiling"):
        Engine(model, num_slots=1, num_pages=4, page_size=256)


def test_engine_refuses_what_is_not_a_decode_model():
    """What `Engine` reads of a model is `DecodeModel`'s to declare: an
    object with the right method names and no such base is refused when
    the engine is built, by name, not at the first missing attribute."""
    class Lookalike:
        max_positions = 128
        params = {}

        def init_cache(self, *a):
            return {}

        prefill = decode = init_cache

    with pytest.raises(TypeError, match="DecodeModel.*Lookalike"):
        Engine(Lookalike(), num_slots=1, num_pages=4, page_size=4)


def test_engine_poison_request_fails_alone(tiny_engine):
    """A request whose prefill raises is failed with status 'error' and
    its pages freed; the engine keeps serving everyone else."""
    cfg, model, eng = tiny_engine
    orig = eng._prefill

    def boom(*a, **k):
        raise RuntimeError("poison prompt")

    eng._prefill = boom
    bad = eng.submit([1, 2, 3], 4)
    try:
        eng.step()
        assert bad.status == "error"
        with pytest.raises(RuntimeError, match="poison"):
            bad.result(1.0)
    finally:
        eng._prefill = orig
    assert eng.pool.used_pages == 0
    good = eng.submit([4, 5], 3)
    eng.run_until_idle()
    assert good.status == "done" and len(good.result()) == 3


def test_engine_cancel_queued_and_running(tiny_engine):
    cfg, model, eng = tiny_engine
    running = eng.submit([2, 4, 6], 32)
    queued = eng.submit([1, 3], 8)
    for _ in range(2):
        eng.step()
    assert running.status == "running"
    assert eng.cancel(queued) and queued.status == "cancelled"
    got = len(running.generated)
    assert eng.cancel(running) and running.status == "cancelled"
    assert len(running.result()) == got      # partial output stands
    assert eng.pool.used_pages == 0
    assert not eng.cancel(running)           # already finished
    eng.run_until_idle()


# ---------------------------------------------------------------------------
# a decode in flight behind the host (ISSUE 31): `step()` dispatches decode
# k before it reads decode k-1, whose tokens feed it on the device
# ---------------------------------------------------------------------------

FAMILIES = ["gpt", "hybrid", "looped"]
AHEAD_KW = dict(num_slots=3, num_pages=40, page_size=4, max_seq_len=48)
SAMPLED = dict(temperature=0.8, top_k=12, top_p=0.9)


@functools.lru_cache(maxsize=None)
def _family_model(family):
    if family == "gpt":
        return GPTDecodeModel(GPTConfig.tiny(num_layers=2), seed=0)
    if family == "hybrid":
        from paddle_tpu.models import lfm2
        from paddle_tpu.serving import HybridDecodeModel
        return HybridDecodeModel(lfm2.LFM2Config.tiny(), seed=0)
    from paddle_tpu.models import ouro
    from paddle_tpu.serving import LoopedDecodeModel
    return LoopedDecodeModel(ouro.OuroConfig.tiny(), seed=0)


@functools.lru_cache(maxsize=None)
def _plain_programs(family):
    """The model's two bodies with the sampler behind them, as a loop
    that reads every token before it makes the next one calls them."""
    from paddle_tpu.serving.sampling import sample_tokens
    model = _family_model(family)

    def prefill(params, cache, tokens, true_len, row, *samp):
        cache, lg = model.prefill(params, cache, tokens, true_len, row,
                                  np.int32(0))
        return cache, sample_tokens(lg[None, :], *samp)[0]

    def decode(params, cache, tokens, positions, tables, *samp):
        cache, lg = model.decode(params, cache, tokens, positions, tables)
        return cache, sample_tokens(lg, *samp)

    return jax.jit(prefill), jax.jit(decode)


def _step_by_step(family, prompt, max_new, seed, temperature=0.0, top_k=0,
                  top_p=1.0, eos_id=None):
    """The reference: one request alone in slot 0, its prefill and then one
    decode a token, each token on the host before the next call is made."""
    from paddle_tpu.serving.engine import _bucket_len
    from paddle_tpu.serving.sampling import seed_to_key
    model = _family_model(family)
    S, P, ps = (AHEAD_KW[k] for k in ("num_slots", "num_pages", "page_size"))
    M = AHEAD_KW["max_seq_len"] // ps
    prefill, decode = _plain_programs(family)
    prompt = np.asarray(prompt, np.int32)
    cache = model.init_cache(P, ps, S)
    n_pages = pages_needed(prompt.size + max_new, ps)
    row = np.full((M,), P, np.int32)
    row[:n_pages] = np.arange(n_pages)

    def samp(n, step):
        key = np.zeros((n, 2), np.uint32)
        key[0] = seed_to_key(seed)
        first = lambda v, dt, rest: np.asarray(     # noqa: E731
            [v] + [rest] * (n - 1), dt)
        return (first(temperature, np.float32, 0.0),
                first(top_k, np.int32, 0), first(top_p, np.float32, 1.0),
                key, first(step, np.int32, 0))

    T = min(_bucket_len(prompt.size, ps), M * ps)
    toks = np.zeros((T,), np.int32)
    toks[:prompt.size] = prompt
    cache, tok = prefill(model.params, cache, toks, np.int32(prompt.size),
                         row, *samp(1, 0))
    out = [int(tok)]
    tables = np.full((S, M), P, np.int32)
    tables[0] = row
    while len(out) < max_new and out[-1] != eos_id:
        tokens = np.zeros((S,), np.int32)
        positions = np.zeros((S,), np.int32)
        tokens[0] = out[-1]
        positions[0] = prompt.size + len(out) - 1
        cache, nxt = decode(model.params, cache, tokens, positions, tables,
                            *samp(S, len(out)))
        out.append(int(np.asarray(nxt)[0]))
    return out


def _jobs(family, n, seed, max_new=(3, 12)):
    """`n` requests of mixed lengths, every other one sampled."""
    cfg = _family_model(family).cfg
    rng = np.random.RandomState(seed)
    jobs = []
    for i in range(n):
        prompt = rng.randint(0, cfg.vocab_size, int(rng.randint(1, 20)))
        kw = dict(SAMPLED, seed=900 + i) if i % 2 else dict(seed=900 + i)
        jobs.append((prompt, int(rng.randint(*max_new)), kw))
    return jobs


def _ahead_engine(family, **kw):
    return Engine(_family_model(family), **{**AHEAD_KW, **kw})


def _held(eng, req):
    """Whether a decode the host has not read holds `req`'s slot."""
    fl = eng._inflight
    return fl is not None and fl.reqs.get(req.slot) is req


@pytest.mark.parametrize("family", FAMILIES)
def test_served_tokens_equal_a_step_by_step_decode(family):
    """Greedy and sampled requests mixed in one batch, more of them than
    slots so that every slot is reused: token for token what a loop that
    reads each token before it makes the next one produces."""
    eng = _ahead_engine(family)
    jobs = _jobs(family, 8, seed=5)
    reqs = [eng.submit(p, n, **kw) for p, n, kw in jobs]
    eng.run_until_idle()
    for (p, n, kw), r in zip(jobs, reqs):
        assert r.status == "done" and len(r.generated) == n
        assert list(r.generated) == _step_by_step(family, p, n, **kw)
    st = eng.stats()
    assert st["tokens_discarded"] == 0 and st["pool"]["used_pages"] == 0
    # every decode was dispatched with something unread in front of it
    # (the decode before, or since ISSUE 44 its own step's prefills), and
    # every token but the prefills' came out of one
    assert 0 < st["decodes_ahead"] == st["steps"]
    assert eng._inflight is None
    assert all(v == 1 for v in st["compiles"].values()), st["compiles"]


@pytest.mark.parametrize("beside", [False, True])
@pytest.mark.parametrize("family", FAMILIES)
def test_eos_midstream_discards_the_token_in_flight(family, beside):
    """The EOS is read while the next decode holds the slot: that decode's
    token is thrown away, nothing follows the EOS, the pages are back."""
    # the sampled one: a tiny greedy model soon repeats its first tokens
    (p2, _n2, kw2), (p, _n, kw) = _jobs(family, 2, seed=6)
    ref = _step_by_step(family, p, 12, **kw)
    cut = next(k for k in range(2, 11) if ref.index(ref[k]) == k)
    eng = _ahead_engine(family)
    other = eng.submit(p2, 12, **kw2) if beside else None
    req = eng.submit(p, 12, eos_id=ref[cut], **kw)
    seen_held = False
    while not req.done():
        seen_held |= _held(eng, req)
        eng.step()
    assert seen_held and req.status == "done"
    assert list(req.generated) == ref[:cut + 1]
    assert req.table is None
    if beside:
        # its pages went when the EOS was read; the stray token goes when
        # the decode that held the slot is read, a step later
        assert eng.pool.used_pages == len(other.table.pages)
        assert eng.stats()["tokens_discarded"] == 0
        eng.step()
        assert eng.stats()["tokens_discarded"] == 1
        eng.run_until_idle()
        assert list(other.generated) == _step_by_step(family, p2, 12, **kw2)
    # the orphaned decode was forgotten with its last request
    assert eng.scheduler.idle and eng._inflight is None
    assert eng.pool.used_pages == 0
    assert eng.stats()["tokens_discarded"] == 1


@pytest.mark.parametrize("how", ["cancel", "deadline", "evict"])
@pytest.mark.parametrize("family", FAMILIES)
def test_a_request_that_ends_under_a_decode_in_flight(family, how):
    """Its partial output stands as it was, the token of the decode that
    held its slot is discarded, its neighbour is not disturbed, and the
    next tenant of the slot and of the pages decodes as if alone (the
    stray K/V write landed before the tenant's prefill)."""
    (pa, _, kwa), (pb, _, kwb), (pc, _, kwc) = _jobs(family, 3, seed=7)
    eng = _ahead_engine(family)
    a = eng.submit(pa, 12, **kwa)
    b = eng.submit(pb, 12, **kwb)
    for _ in range(4):
        eng.step()
    assert _held(eng, a) and _held(eng, b)
    got, slot = list(a.generated), a.slot
    if how == "cancel":
        assert eng.cancel(a) and a.status == "cancelled"
    elif how == "evict":
        with eng._lock:
            a.error = "evicted by the test"
            assert eng.scheduler.evict(a, "error")
    else:
        a.deadline = -1.0
    c = eng.submit(pc, 9, **kwc)
    if how != "deadline":
        assert eng.pool.used_pages == len(b.table.pages)
    eng.step()                  # expires `a`; discards its token either way
    assert a.done() and list(a.generated) == got
    assert eng.stats()["tokens_discarded"] == 1
    eng.step()
    assert c.slot == slot       # the freed slot's next tenant
    eng.run_until_idle()
    assert a.status == {"cancel": "cancelled", "evict": "error",
                        "deadline": "deadline"}[how]
    assert list(a.generated) == got == _step_by_step(
        family, pa, 12, **kwa)[:len(got)]
    assert list(b.generated) == _step_by_step(family, pb, 12, **kwb)
    assert list(c.generated) == _step_by_step(family, pc, 9, **kwc)
    assert eng.stats()["tokens_discarded"] == 1 and eng.pool.used_pages == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_defrag_between_two_steps_with_a_decode_in_flight(family):
    """`defrag` moves the pages of the cache the decode in flight will
    hand back (a future of it): it orders itself behind that decode."""
    jobs = _jobs(family, 3, seed=8, max_new=(10, 12))
    eng = _ahead_engine(family)
    first = eng.submit(jobs[0][0], 2, **jobs[0][2])
    reqs = [eng.submit(p, n, **kw) for p, n, kw in jobs[1:]]
    for _ in range(4):
        eng.step()
    assert first.done() and all(_held(eng, r) for r in reqs)
    assert eng.defrag()                     # the first request left a hole
    live = sorted(pg for r in reqs for pg in r.table.pages)
    assert live == list(range(len(live)))
    eng.run_until_idle()
    for (p, n, kw), r in zip(jobs[1:], reqs):
        assert list(r.generated) == _step_by_step(family, p, n, **kw)


def test_warm_start_between_two_steps_with_a_decode_in_flight(tmp_path):
    """The flip lands between two dispatches: what was dispatched before
    it is the old weights', the request goes on under the new ones."""
    _greedy, (p, _n, kw) = _jobs("gpt", 2, seed=9)     # the sampled one
    GPTDecodeModel(GPTConfig.tiny(num_layers=2), seed=1).save_checkpoint(
        str(tmp_path), step=3)
    eng = Engine(GPTDecodeModel(GPTConfig.tiny(num_layers=2), seed=0),
                 **AHEAD_KW)
    req = eng.submit(p, 12, **kw)
    for _ in range(4):
        eng.step()
    assert _held(eng, req)
    dispatched = len(req.generated) + 1
    eng.warm_start(str(tmp_path), version=7)
    assert eng.model_version == 7 and _held(eng, req)
    eng.run_until_idle()
    old = _step_by_step("gpt", p, 12, **kw)
    assert req.status == "done" and len(req.generated) == 12
    assert list(req.generated)[:dispatched] == old[:dispatched]
    assert list(req.generated) != old       # the new weights took over


def test_a_bootstrap_admission_feeds_a_host_token_beside_device_tokens():
    """Whole prompt cached: no prefill and no first token to read, and
    the decode takes this slot's token (the prompt's last) from the host
    and its neighbour's from the decode before."""
    from paddle_tpu.observability.tracing import TRACER
    (pa, _, kwa), (pb, _, kwb) = _jobs("gpt", 2, seed=10)
    pb = np.resize(pb, 8)                   # two whole pages
    eng = _ahead_engine("gpt", prefix_cache_pages=16)
    warm = eng.submit(pb, 4, **kwb)
    eng.run_until_idle()
    a = eng.submit(pa, 12, **kwa)
    for _ in range(3):
        eng.step()
    assert _held(eng, a)
    TRACER.clear()
    b = eng.submit(pb, 6, **kwb)
    ahead0 = eng.stats()["decodes_ahead"]
    eng.step()
    assert b.status == "running" and b.prefix_match.full
    assert not [s for s in TRACER.spans() if s.name == "engine.prefill"]
    dec = next(s for s in TRACER.spans() if s.name == "engine.decode")
    assert dec.attrs["ahead"] is True and dec.attrs["active"] == 2
    assert eng.stats()["decodes_ahead"] == ahead0 + 1
    eng.run_until_idle()
    assert eng.stats()["prefix_cache"]["cow_copies"] == 1
    assert list(a.generated) == _step_by_step("gpt", pa, 12, **kwa)
    assert list(b.generated) == _step_by_step("gpt", pb, 6, **kwb)
    assert list(b.generated)[:4] == list(warm.generated)


@pytest.mark.parametrize("at", [1, 3])
@pytest.mark.parametrize("family", FAMILIES)
def test_a_failed_decode_fails_its_steps_requests_and_the_engine_serves_on(
        family, at):
    """The first decode of a batch (its step's prefills unread) or a later
    one (its predecessor unread): the step's requests end in error with
    their pages freed, the unread tokens are discarded, and the next
    request is served as if nothing had happened."""
    (pa, _, kwa), (pb, _, kwb), (pc, _, kwc) = _jobs(family, 3, seed=11)
    eng = _ahead_engine(family)
    real, calls = eng._decode, []

    def decode(*args):
        calls.append(1)
        if len(calls) == at:
            raise RuntimeError("the device said no")
        return real(*args)
    eng._decode = decode
    a = eng.submit(pa, 12, **kwa)
    b = eng.submit(pb, 12, **kwb)
    with pytest.raises(RuntimeError, match="said no"):
        for _ in range(at):
            eng.step()
    for r in (a, b):
        assert r.status == "error" and "decode failed" in r.error
        # what was read: nothing of the step that admitted them (their
        # first tokens went with the decode dispatched behind them)
        assert len(r.generated) == at - 1
    assert eng._inflight is None and eng.pool.used_pages == 0
    # the two first tokens, or the two of the decode before
    assert eng.stats()["tokens_discarded"] == 2
    c = eng.submit(pc, 9, **kwc)
    eng.run_until_idle()
    assert list(c.generated) == _step_by_step(family, pc, 9, **kwc)


@pytest.mark.parametrize("family", FAMILIES)
def test_every_decode_is_dispatched_before_the_one_before_is_read(family):
    """The structural guard of ISSUE 31, beside PR 25 / 27 / 29's: in a run
    of N decode steps of which only the first admits, every
    `engine.dispatch` but the first starts before the `engine.wait` that
    reads the step before ends, that wait names the step before, and every
    decode counts as ahead: the first follows its step's unread prefills
    (ISSUE 44, whose own guard is below). An edit that reads before it
    dispatches fails here, not only in a cell."""
    from paddle_tpu.observability.tracing import TRACER
    N = 9
    eng = _ahead_engine(family)
    jobs = _jobs(family, 2, seed=12)
    TRACER.clear()
    for p, _n, kw in jobs:
        eng.submit(p, N + 1, **kw)      # the prefill's token, then N more
    eng.run_until_idle()
    spans = TRACER.spans()
    steps = [s for s in spans if s.name == "engine.step"]
    assert len(steps) == N + 1          # the last one only reads
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s.parent_id, []).append(s)
    rows = []
    for st in steps:
        dec = next(k for k in by_parent[st.span_id]
                   if k.name == "engine.decode")
        dispatch, wait = sorted(by_parent[dec.span_id],
                                key=lambda s: s.start)
        assert (dispatch.name, wait.name) == ("engine.dispatch",
                                              "engine.wait")
        rows.append((st.attrs["step"], dec, dispatch, wait))
    assert [w.attrs["of_step"] for _n, _d, _dp, w in rows] == \
        [None] + [n for n, *_ in rows[:-1]]
    for (_n, dec, dispatch, wait), (n0, *_rest) in zip(rows[1:], rows):
        assert wait.attrs["of_step"] == n0 == _n - 1
        assert dispatch.start <= dispatch.end <= wait.start <= wait.end
    # the first decode follows two prefills nobody has read, the last
    # step has none to dispatch
    assert [d.attrs["ahead"] for _n, d, *_ in rows] == [True] * N + [False]
    assert [d.attrs["active"] for _n, d, *_ in rows] == [2] * N + [0]
    assert [w.attrs["first_tokens"] for _n, _d, _dp, w in rows] == \
        [2] + [0] * N
    st = eng.stats()
    assert st["steps"] == N and st["decodes_ahead"] == N


# ---------------------------------------------------------------------------
# a prefill's first token behind the step's decode (ISSUE 44): `engine.admit`
# dispatches the prefills and reads nothing, the decode takes a fresh slot's
# token on the device, `engine.wait` reads it after the tokens of decode k-1
# ---------------------------------------------------------------------------

def _phase(spans, step, name):
    """The span called `name` of the `engine.step` span `step`: a phase of
    it, or a child of its `engine.decode`."""
    kids = [s for s in spans if s.parent_id == step.span_id]
    dec = next((k for k in kids if k.name == "engine.decode"), None)
    kids += [s for s in spans if dec and s.parent_id == dec.span_id]
    return next((k for k in kids if k.name == name), None)


def _last_step(spans):
    return [s for s in spans if s.name == "engine.step"][-1]


@pytest.mark.parametrize("family", FAMILIES)
def test_requests_admitted_beside_running_ones_decode_as_if_alone(family):
    """Greedy and sampled requests that arrive while others decode, one or
    two a step, into slots that others have left: every admission's first
    token reaches the decode on the device beside the running slots', and
    every request's tokens are the step-by-step loop's."""
    eng = _ahead_engine(family)
    jobs = _jobs(family, 9, seed=21, max_new=(2, 10))
    reqs, later = [], list(jobs)
    for k in range(400):
        # two at once, then one every other step
        for _ in range(2 if k == 0 else (k % 2 if later else 0)):
            if later:
                p, n, kw = later.pop(0)
                reqs.append(eng.submit(p, n, **kw))
        eng.step()
        if not later and eng.scheduler.idle:
            break
    assert eng.scheduler.idle and len(reqs) == len(jobs)
    for (p, n, kw), r in zip(jobs, reqs):
        assert r.status == "done"
        assert list(r.generated) == _step_by_step(family, p, n, **kw)
    st = eng.stats()
    assert st["tokens_discarded"] == 0 and st["pool"]["used_pages"] == 0
    assert st["decodes_ahead"] == st["steps"] and eng._inflight is None
    assert all(v == 1 for v in st["compiles"].values()), st["compiles"]


@pytest.mark.parametrize("family", FAMILIES)
def test_several_admissions_in_one_step_are_read_in_one_wait(family):
    """Three prefills dispatched one after the other in one `engine.admit`,
    their first tokens read together behind the decode that already
    holds all three slots, and recorded in the call that admitted them."""
    from paddle_tpu.observability.tracing import TRACER
    eng = _ahead_engine(family)
    jobs = _jobs(family, 3, seed=22, max_new=(4, 9))
    TRACER.clear()
    reqs = [eng.submit(p, n, **kw) for p, n, kw in jobs]
    eng.step()
    spans = TRACER.spans()
    step = _last_step(spans)
    assert step.attrs["admitted"] == 3
    assert len([s for s in spans if s.name == "engine.prefill"]) == 3
    assert _phase(spans, step, "engine.wait").attrs["first_tokens"] == 3
    dec = _phase(spans, step, "engine.decode")
    assert dec.attrs["active"] == 3 and dec.attrs["ahead"] is True
    assert all(len(r.generated) == 1 and r.first_token_at is not None
               and _held(eng, r) for r in reqs)
    eng.run_until_idle()
    for (p, n, kw), r in zip(jobs, reqs):
        assert list(r.generated) == _step_by_step(family, p, n, **kw)
    assert eng.stats()["tokens_discarded"] == 0


@pytest.mark.parametrize("beside", [False, True])
@pytest.mark.parametrize("family", FAMILIES)
def test_a_request_of_one_token_never_enters_a_decode(family, beside):
    """`max_new_tokens == 1`: its only token is its prefill's, read in the
    step that admitted it; no decode is dispatched for it, so nothing of
    it is discarded, and its slot and pages are free when the call ends."""
    from paddle_tpu.observability.tracing import TRACER
    (p, _n, kw), (pb, _nb, kwb) = _jobs(family, 2, seed=23)
    eng = _ahead_engine(family)
    other = eng.submit(pb, 9, **kwb) if beside else None
    for _ in range(2 if beside else 0):
        eng.step()
    TRACER.clear()
    req = eng.submit(p, 1, **kw)
    eng.step()
    spans = TRACER.spans()
    step = _last_step(spans)
    assert req.status == "done" and req.table is None
    assert list(req.generated) == _step_by_step(family, p, 1, **kw)
    assert _phase(spans, step, "engine.wait").attrs["first_tokens"] == 1
    assert _phase(spans, step, "engine.decode").attrs["active"] == beside
    assert not _held(eng, req)
    assert eng.pool.used_pages == (len(other.table.pages) if beside else 0)
    eng.run_until_idle()
    if beside:
        assert list(other.generated) == _step_by_step(family, pb, 9, **kwb)
    assert eng.stats()["tokens_discarded"] == 0
    assert eng.stats()["steps"] == (8 if beside else 0)


@pytest.mark.parametrize("beside", [False, True])
@pytest.mark.parametrize("family", FAMILIES)
def test_a_first_token_that_is_the_eos_discards_the_decode_behind_it(
        family, beside):
    """The first token ends the request while decode k, dispatched before
    the token was read, holds its slot: that decode's token is discarded
    by PR 31's rule, the pages go back once, and the slot's next tenant
    decodes as if alone."""
    (p, _n, kw), (pb, _nb, kwb), (pc, _nc, kwc) = _jobs(family, 3, seed=24)
    first = _step_by_step(family, p, 1, **kw)[0]
    eng = _ahead_engine(family)
    other = eng.submit(pb, 12, **kwb) if beside else None
    for _ in range(3 if beside else 0):
        eng.step()
    req = eng.submit(p, 12, eos_id=first, **kw)
    eng.step()
    slot = req.slot
    assert req.status == "done" and list(req.generated) == [first]
    assert req.table is None
    if beside:
        # the decode behind the prefill still holds the slot for it
        assert eng._inflight.reqs[slot] is req
        assert eng.pool.used_pages == len(other.table.pages)
        assert eng.stats()["tokens_discarded"] == 0
    else:
        # nobody else in that decode: forgotten with its only request
        assert eng.scheduler.idle and eng._inflight is None
    c = eng.submit(pc, 7, **kwc)
    eng.step()
    assert c.slot == slot and eng.stats()["tokens_discarded"] == 1
    eng.run_until_idle()
    assert list(c.generated) == _step_by_step(family, pc, 7, **kwc)
    if beside:
        assert list(other.generated) == _step_by_step(family, pb, 12, **kwb)
    assert eng.stats()["tokens_discarded"] == 1 and eng.pool.used_pages == 0


@pytest.mark.parametrize("how", ["cancel", "deadline"])
@pytest.mark.parametrize("family", FAMILIES)
def test_a_request_that_ends_between_its_prefills_dispatch_and_its_read(
        family, how):
    """Cancelled while its prefill and the decode behind it are on the
    device: neither token is its own any more (two discarded), nothing is
    in `generated`. A deadline that passes there is seen by the next
    call's `expire_deadlines`: the first token was read and stands, the
    decode's is discarded. Either way the pages go back once, the
    neighbour is not disturbed, and the slot's next tenant decodes as if
    alone."""
    (pa, _, kwa), (pb, _, kwb), (pc, _, kwc) = _jobs(family, 3, seed=25)
    eng = _ahead_engine(family)
    b = eng.submit(pb, 12, **kwb)
    for _ in range(3):
        eng.step()
    a = eng.submit(pa, 12, **kwa)
    real = eng._decode

    def decode(*args):
        # the step holds the engine's lock, as `Scheduler.cancel` asks
        if how == "cancel":
            assert eng.scheduler.cancel(a)
        else:
            a.deadline = -1.0
        eng._decode = real
        return real(*args)
    eng._decode = decode
    eng.step()
    slot = a.slot
    assert eng._decode is real and eng._inflight.reqs[slot] is a
    if how == "cancel":
        assert a.status == "cancelled" and a.generated == []
        assert eng.stats()["tokens_discarded"] == 1     # the first token
    else:
        assert a.status == "running" and len(a.generated) == 1
    c = eng.submit(pc, 8, **kwc)
    eng.step()                  # expires `a`; its decode's token goes
    assert a.done() and a.table is None and c.slot == slot
    assert eng.stats()["tokens_discarded"] == (2 if how == "cancel" else 1)
    eng.run_until_idle()
    assert a.status == {"cancel": "cancelled", "deadline": "deadline"}[how]
    assert list(a.generated) == _step_by_step(
        family, pa, 12, **kwa)[:how == "deadline"]
    assert list(b.generated) == _step_by_step(family, pb, 12, **kwb)
    assert list(c.generated) == _step_by_step(family, pc, 8, **kwc)
    assert eng.stats()["tokens_discarded"] == (2 if how == "cancel" else 1)
    assert eng.pool.used_pages == 0


@pytest.mark.parametrize("donating", [False, True])
@pytest.mark.parametrize("family", FAMILIES)
def test_a_prefill_that_fails_at_dispatch_fails_alone(family, donating):
    """Raised where the program is dispatched (trace, compile, shapes):
    that request ends in error with its pages freed and the step goes on;
    where nothing is donated the running request, the prefill dispatched
    before it in the same step and the one after it are untouched. On a
    backend that donates the cache is lost with everything on it: those
    end `kv cache lost`, what was unread is discarded, the next request is
    served."""
    (pa, _, kwa), (pb, _, kwb), (pc, _, kwc), (pd, _, kwd) = _jobs(
        family, 4, seed=26)
    eng = _ahead_engine(family, num_slots=4)
    eng._donate = donating      # the CPU never donates: force recovery
    a = eng.submit(pa, 12, **kwa)
    for _ in range(3):
        eng.step()
    real, calls = eng._prefill, []

    def prefill(*args):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("poison prompt")
        return real(*args)
    eng._prefill = prefill
    b = eng.submit(pb, 9, **kwb)
    bad = eng.submit(pc, 9, **kwc)
    d = eng.submit(pd, 9, **kwd)
    eng.step()
    eng._prefill = real
    assert bad.status == "error" and "prefill failed" in bad.error
    assert "poison" in bad.error and bad.table is None
    if donating:
        for r in (a, b, d):
            assert r.status == "error" and "kv cache lost" in r.error
        assert len(calls) == 2 and b.generated == [] == d.generated
        # decode k-1's token for `a`, and `b`'s unread first token
        assert eng.stats()["tokens_discarded"] == 2
        assert eng._inflight is None and eng.pool.used_pages == 0
        d = eng.submit(pd, 9, **kwd)
    else:
        assert len(calls) == 3
        assert [len(r.generated) for r in (b, d)] == [1, 1]
    eng.run_until_idle()
    assert list(d.generated) == _step_by_step(family, pd, 9, **kwd)
    if not donating:
        assert list(a.generated) == _step_by_step(family, pa, 12, **kwa)
        assert list(b.generated) == _step_by_step(family, pb, 9, **kwb)
        assert eng.stats()["tokens_discarded"] == 0
    assert eng.pool.used_pages == 0


def test_a_tail_prefill_and_a_bootstrap_admitted_beside_device_tokens():
    """One step admits a prompt that resumes behind a cached prefix
    (`prefill_tail`: its first token stays on the device) and one whose
    whole prompt is cached (no program: the host feeds the prompt's last
    token), beside a slot that takes the decode before's token."""
    from paddle_tpu.observability.tracing import TRACER
    (pa, _, kwa), (pb, _, kwb), (pc, _, kwc) = _jobs("gpt", 3, seed=27)
    pb = np.resize(pb, 8)                   # two whole pages
    pc = np.concatenate([pb[:4], np.resize(pc, 7)])    # one of them shared
    eng = _ahead_engine("gpt", prefix_cache_pages=16)
    warm = eng.submit(pb, 3, **kwb)
    eng.run_until_idle()
    a = eng.submit(pa, 12, **kwa)
    for _ in range(3):
        eng.step()
    assert _held(eng, a)
    TRACER.clear()
    b = eng.submit(pb, 6, **kwb)
    c = eng.submit(pc, 6, **kwc)
    eng.step()
    spans = TRACER.spans()
    step = _last_step(spans)
    assert b.prefix_match.full and not c.prefix_match.full
    [tail] = [s for s in spans if s.name == "engine.prefill"]
    assert tail.attrs["cached_tokens"] == 4 and tail.attrs["slot"] == c.slot
    assert step.attrs["admitted"] == 2
    assert _phase(spans, step, "engine.wait").attrs["first_tokens"] == 1
    dec = _phase(spans, step, "engine.decode")
    assert dec.attrs["active"] == 3 and dec.attrs["ahead"] is True
    assert len(c.generated) == 1 and b.generated == []
    eng.run_until_idle()
    assert list(a.generated) == _step_by_step("gpt", pa, 12, **kwa)
    assert list(b.generated) == _step_by_step("gpt", pb, 6, **kwb)
    assert list(c.generated) == _step_by_step("gpt", pc, 6, **kwc)
    assert list(b.generated)[:3] == list(warm.generated)
    assert any(k.startswith("prefill_tail[")
               for k in eng.stats()["compiles"])
    assert eng.stats()["tokens_discarded"] == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_defrag_behind_a_step_that_admitted(family):
    """`defrag` right after the call that admitted: the decode in flight
    took the fresh slot's token on the device and wrote its K/V through
    the old page numbers; the move is ordered behind it."""
    jobs = _jobs(family, 3, seed=28, max_new=(9, 12))
    eng = _ahead_engine(family)
    first = eng.submit(jobs[0][0], 2, **jobs[0][2])
    a = eng.submit(jobs[1][0], jobs[1][1], **jobs[1][2])
    for _ in range(3):
        eng.step()
    assert first.done()
    b = eng.submit(jobs[2][0], jobs[2][1], **jobs[2][2])
    eng.step()
    assert len(b.generated) == 1 and _held(eng, a) and _held(eng, b)
    assert eng.defrag()                     # the first request left a hole
    live = sorted(pg for r in (a, b) for pg in r.table.pages)
    assert live == list(range(len(live)))
    eng.run_until_idle()
    for (p, n, kw), r in zip(jobs[1:], (a, b)):
        assert list(r.generated) == _step_by_step(family, p, n, **kw)


def test_warm_start_behind_a_step_that_admitted(tmp_path):
    """The flip lands behind the admitting call: the first token and the
    decode dispatched behind it are the old weights', the rest the new."""
    _greedy, (p, _n, kw) = _jobs("gpt", 2, seed=9)     # the sampled one
    GPTDecodeModel(GPTConfig.tiny(num_layers=2), seed=1).save_checkpoint(
        str(tmp_path), step=3)
    eng = Engine(GPTDecodeModel(GPTConfig.tiny(num_layers=2), seed=0),
                 **AHEAD_KW)
    req = eng.submit(p, 12, **kw)
    eng.step()
    assert len(req.generated) == 1 and _held(eng, req)
    eng.warm_start(str(tmp_path), version=7)
    eng.run_until_idle()
    old = _step_by_step("gpt", p, 12, **kw)
    assert req.status == "done" and len(req.generated) == 12
    assert list(req.generated)[:2] == old[:2]
    assert list(req.generated) != old       # the new weights took over


class _Unread:
    """Stands in for a device value the engine has dispatched and not
    read: notes the tracer's clock whenever the host waits for it or
    copies it."""

    def __init__(self, value, reads):
        self.value, self.reads = value, reads

    def _read(self):
        from paddle_tpu.observability.tracing import TRACER
        self.reads.append(TRACER.clock())
        return self.value

    def block_until_ready(self):
        self._read().block_until_ready()
        return self

    def __array__(self, *a, **kw):
        return np.asarray(self._read(), *a, **kw)

    def __int__(self):
        return int(self._read())
    __index__ = __int__


@pytest.mark.parametrize("family", FAMILIES)
def test_no_prefill_token_is_read_before_the_steps_decode_is_dispatched(
        family):
    """The structural guard of ISSUE 44, beside PR 31's: in a run that
    admits on several steps, beside running requests and alone, every
    `engine.prefill` span ends before its step's `engine.dispatch`
    starts, the step's `engine.wait` says it read as many first tokens as
    the step prefilled, its decode counts as ahead, and the host neither
    waits for nor copies anything a prefill or a decode returned between
    the start of `engine.admit` and the end of `engine.dispatch`. An edit
    that reads a first token inside the admission fails here, not only in
    a cell."""
    from paddle_tpu.observability.tracing import TRACER
    eng = _ahead_engine(family)
    reads = []
    unwrap = lambda a: a.value if isinstance(a, _Unread) else a  # noqa: E731

    def spied(fn):
        def call(*args):
            cache, out = fn(*map(unwrap, args))
            return cache, _Unread(out, reads)
        return call
    eng._prefill, eng._decode = spied(eng._prefill), spied(eng._decode)
    jobs = _jobs(family, 7, seed=29, max_new=(3, 9))
    TRACER.clear()
    reqs, later = [], list(jobs)
    for k in range(200):
        for _ in range(2 if k in (0, 5) else k % 3 == 0):
            if later:
                p, n, kw = later.pop(0)
                reqs.append(eng.submit(p, n, **kw))
        eng.step()
        if not later and eng.scheduler.idle:
            break
    assert eng.scheduler.idle
    spans = TRACER.spans()
    steps = [s for s in spans if s.name == "engine.step"]
    prefills = [s for s in spans if s.name == "engine.prefill"]
    assert len(prefills) == len(jobs)
    admitting = 0
    for st in steps:
        admit = _phase(spans, st, "engine.admit")
        dispatch = _phase(spans, st, "engine.dispatch")
        wait = _phase(spans, st, "engine.wait")
        dec = _phase(spans, st, "engine.decode")
        mine = [p for p in prefills if p.caused_by == admit.span_id]
        assert st.attrs["admitted"] == len(mine)    # no bootstrap here
        assert wait.attrs["first_tokens"] == len(mine)
        for p in mine:
            assert admit.start <= p.start <= p.end <= admit.end \
                <= dispatch.start
        if mine:
            admitting += 1
            assert dec.attrs["ahead"] is (dec.attrs["active"] > 0)
        # nothing the device made is read in front of the dispatch
        early = [t for t in reads if admit.start <= t <= dispatch.end]
        assert not early, (st.attrs["step"], early)
        assert all(wait.start <= t <= wait.end for t in reads
                   if st.start <= t <= st.end)
    assert admitting >= 4 and len(reads) >= 2 * len(steps) - 2
    for (p, n, kw), r in zip(jobs, reqs):
        assert list(r.generated) == _step_by_step(family, p, n, **kw)
    st = eng.stats()
    assert st["decodes_ahead"] == st["steps"] and st["tokens_discarded"] == 0
