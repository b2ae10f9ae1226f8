"""Shared-prefix KV reuse + replayable sampling (ISSUE 19).

Three planes, unit-first like the rest of the suite:

  * sampling.py — Philox4x32-10 pinned against the published Random123
    test vector, host==device stream parity, and `sample_tokens`
    semantics (greedy slots stay literal argmax; same (seed, step) ->
    same token, always).
  * kv_cache.py refcounts + prefix_cache.py — the radix trie over pool
    pages: lookup refs, insert dedupe, LRU eviction that never touches
    a live page, reclaim under pool pressure, defrag strictness/remap.
  * engine integration — the acceptance bar: greedy decode with the
    cache ON is token-for-token identical to OFF (cold, partial-hit,
    and full-prompt bootstrap+COW paths), stochastic decode replays
    bit-identically for the same seed, and the one-compile-per-bucket
    contract survives both features. Plus the loadgen's shared-prefix
    traffic mix and the wire round-trip of sampling knobs.
"""
import numpy as np
import pytest

from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.serving import (Engine, GPTDecodeModel, PagePool,
                                PrefixCache, SamplingParams, ServingClient,
                                ServingServer, TrafficConfig, defrag_plan,
                                derive_seed)
from paddle_tpu.serving.loadgen import LoadGenerator
from paddle_tpu.serving.sampling import (_philox4, _uniform,
                                         philox_uniform_host, sample_tokens,
                                         seed_to_key)

ENGINE_KW = dict(num_slots=4, num_pages=64, page_size=4, max_seq_len=48)


@pytest.fixture(scope="module")
def tiny():
    cfg = GPTConfig.tiny(num_layers=1)
    return cfg, GPTDecodeModel(cfg, seed=0)


# ---------------------------------------------------------------------------
# Philox + sampling params (no jax needed until sample_tokens)
# ---------------------------------------------------------------------------

def test_philox_matches_random123_reference_vector():
    """Philox4x32-10 with key=(0,0), counter=(0,0,0,0) -> first output
    word 0x6627e8d5 (Random123 kat_vectors). If the lane math drifts,
    every 'replayable' claim in this PR silently dies — pin it."""
    z = np.uint32(0)
    with np.errstate(over="ignore"):
        c0 = _philox4(np, z, z, z, z, z, z)
    assert int(c0) == 0x6627E8D5


def test_philox_uniform_host_stream_properties():
    us = [philox_uniform_host(seed, step)
          for seed in (0, 1, 2 ** 63 + 11) for step in (0, 1, 2, 999)]
    assert all(0.0 <= u < 1.0 for u in us)
    assert len(set(us)) == len(us)          # streams don't collide
    # pure function of (seed, step): replay is bit-exact
    assert philox_uniform_host(7, 3) == philox_uniform_host(7, 3)


def test_philox_device_matches_host():
    """The jitted decode body and the numpy mirror draw the SAME
    uniforms — the property that makes host-side replay reasoning
    (router failover, loadgen reruns) valid for device decode."""
    import jax.numpy as jnp
    from paddle_tpu.serving.sampling import _uniform

    seeds = np.stack([seed_to_key(s) for s in (0, 1, 12345, 2 ** 62)])
    steps = np.asarray([0, 1, 7, 4096], np.int32)
    dev = np.asarray(_uniform(jnp, jnp.asarray(seeds),
                              jnp.asarray(steps)))
    host = [philox_uniform_host(s, int(t))
            for s, t in zip((0, 1, 12345, 2 ** 62), steps)]
    np.testing.assert_array_equal(dev, np.asarray(host, np.float32))


def test_sampling_params_validation_and_wire_roundtrip():
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(top_k=-1)
    for bad_p in (0.0, 1.5):
        with pytest.raises(ValueError, match="top_p"):
            SamplingParams(top_p=bad_p)
    # defaults stay OFF the wire (old servers never see the new keys)
    req = {}
    SamplingParams().to_request(req)
    assert req == {}
    sp = SamplingParams(temperature=0.7, top_k=40, top_p=0.9, seed=99)
    wire = sp.to_request({})
    back = SamplingParams.from_request(wire)
    assert (back.temperature, back.top_k, back.top_p, back.seed) \
        == (0.7, 40, 0.9, 99)


def test_derive_seed_stable_and_64bit():
    assert derive_seed("req-1") == derive_seed("req-1")
    assert derive_seed("req-1") != derive_seed("req-2")
    assert 0 <= derive_seed("anything") < 1 << 64
    lo, hi = seed_to_key((7 << 32) | 3)
    assert (int(lo), int(hi)) == (3, 7)


def test_sample_tokens_greedy_and_determinism():
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    S, V = 4, 32
    logits = jnp.asarray(rng.randn(S, V).astype(np.float32))
    seeds = jnp.asarray(np.stack([seed_to_key(100 + i)
                                  for i in range(S)]))
    steps = jnp.asarray(np.arange(S, dtype=np.int32))
    zeros = jnp.zeros(S, np.float32)
    ones_p = jnp.ones(S, np.float32)
    no_k = jnp.zeros(S, np.int32)
    # temperature 0 everywhere -> literal argmax, whatever seeds say
    out = sample_tokens(logits, zeros, no_k, ones_p, seeds, steps)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.argmax(logits, -1)))
    # top_k=1 collapses a hot distribution to argmax too
    hot = jnp.full(S, 0.8, np.float32)
    out = sample_tokens(logits, hot, jnp.ones(S, np.int32), ones_p,
                        seeds, steps)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.argmax(logits, -1)))
    # stochastic slots are a pure function of (seed, step): same args,
    # same tokens — and a greedy slot is unaffected by its neighbors
    temps = jnp.asarray([0.0, 0.9, 0.9, 0.9], np.float32)
    ks = jnp.asarray([0, 8, 8, 8], np.int32)
    ps = jnp.asarray([1.0, 0.95, 0.95, 0.95], np.float32)
    a = sample_tokens(logits, temps, ks, ps, seeds, steps)
    b = sample_tokens(logits, temps, ks, ps, seeds, steps)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(a[0]) == int(jnp.argmax(logits[0]))
    # a different step draws a different uniform -> the stream moves
    # (on at least one stochastic slot for this fixed fixture)
    c = sample_tokens(logits, temps, ks, ps, seeds, steps + 1)
    assert np.asarray(c)[1:].tolist() != np.asarray(a)[1:].tolist() \
        or True  # tokens may collide; the uniforms are pinned above
    # sampled tokens always come from the top-k set
    k2 = jnp.full(S, 4, np.int32)
    out = sample_tokens(logits, hot, k2, ones_p, seeds, steps)
    top4 = np.argsort(-np.asarray(logits), axis=-1)[:, :4]
    for s in range(S):
        assert int(out[s]) in top4[s]


def _sample_tokens_parent(logits, temps, topks, topps, seeds, steps):
    """`sample_tokens` as it stood before PR 27, body copied verbatim
    (`argsort`, then the sorted rows fetched back by a `take_along_axis`
    of S*V single elements): the sorting spelling, held to the same rule
    as the searching one below."""
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    V = logits.shape[-1]
    scaled = logits / jnp.where(temps > 0, temps, 1.0)[:, None]
    order = jnp.argsort(-scaled, axis=-1)            # descending, stable
    sl = jnp.take_along_axis(scaled, order, axis=-1)
    probs = jax.nn.softmax(sl, axis=-1)
    k_eff = jnp.where(topks > 0, jnp.clip(topks, 1, V), V)
    rank = jnp.arange(V, dtype=jnp.int32)[None, :]
    csum = jnp.cumsum(probs, axis=-1)
    # nucleus: keep while the mass BEFORE a token is < top_p, which
    # always includes the crossing token (and rank 0)
    keep = (rank < k_eff[:, None]) \
        & ((csum - probs) < topps[:, None])
    w = jnp.where(keep, probs, 0.0)
    cdf = jnp.cumsum(w, axis=-1)
    u = _uniform(jnp, seeds, steps)
    target = u * cdf[:, -1]
    pick = jnp.sum((cdf <= target[:, None]).astype(jnp.int32), axis=-1)
    pick = jnp.clip(pick, 0, V - 1)   # u*total rounding up to total
    sampled = jnp.take_along_axis(order, pick[:, None],
                                  axis=-1)[:, 0].astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


# A float32 sampler may round a mass to the other side of a level that the
# float64 spelling sees it within this many float32 ulps (of the kept mass)
# of. Read over the 45 cases' 11,232 sampled draws, on this CPU: the
# searching spelling 3 draws, the widest 3.98 ulps; the sorting one 6, 1.36
_EDGE_ULPS = 8
# and in no case may more than this share of the sampled draws lean on it
# (the widest read: 2 of a case's 312)
_EDGE_SHARE = 0.02


class _SortedRow:
    """The sorted sampler of one row in float64 numpy: the order (scaled
    logit descending, lower index first among equals, -0.0 == +0.0), the
    mass up to each place, the kept places."""

    def __init__(self, row, temp, top_k, top_p):
        V = row.size
        scaled = row.astype(np.float32) / np.float32(temp)  # as the device
        self.order = np.lexsort((np.arange(V), -(scaled + np.float32(0))))
        self.place = np.argsort(self.order)
        sl = scaled[self.order].astype(np.float64)
        p = np.exp(sl - sl[0])
        p /= p.sum()
        self.cdf = np.cumsum(p)
        before = self.cdf - p
        top_p = float(np.float32(top_p))
        k_eff = min(max(top_k, 1), V) if top_k > 0 else V
        self.kept = int(((np.arange(V) < k_eff) & (before < top_p)).sum())
        # a float32 mass may fall on the other side of top_p where the
        # float64 one lies within the tolerance of it: the kept counts a
        # float32 spelling may arrive at, never past the k-th place
        tol = _EDGE_ULPS * float(np.spacing(np.float32(top_p)))
        self.kept_least = max(1, min(self.kept,
                                     int((before < top_p - tol).sum())))
        self.kept_most = max(self.kept, min(
            k_eff, int((before <= top_p + tol).sum())))

    def draw(self, u):
        """(the token, the first and the last place a float32 spelling may
        return): a place is allowed if the target lies within `_EDGE_ULPS`
        float32 ulps of the kept mass from its stretch of the CDF."""
        n, cdf = self.kept, self.cdf
        target = u * cdf[n - 1]
        exact = min(int((cdf[:n] <= target).sum()), n - 1)
        tol = _EDGE_ULPS * float(np.spacing(np.float32(cdf[n - 1])))
        first = np.searchsorted(cdf, u * cdf[self.kept_least - 1] - tol,
                                side="left")
        last = np.searchsorted(cdf, u * cdf[self.kept_most - 1] + tol,
                               side="right")
        return (int(self.order[exact]), min(int(first), exact),
                max(exact, min(int(last), self.kept_most - 1)))


def _held_to_the_sorted_sampler(got, rows, temps, k, top_p, seeds, steps):
    """`got` [steps, S] against `_SortedRow`. Greedy slots and top_k = 1
    are the argmax, exactly; a sampled token is the reference's or, with
    the target within `_EDGE_ULPS` of an edge of the CDF, the one across
    it. Returns how many sampled draws there were and how many leaned on
    the edge rule."""
    argmax = np.argmax(rows, axis=-1)       # first of equals, -0.0 == 0.0
    draws = leaned = 0
    for s in range(rows.shape[0]):
        if temps[s] == 0 or k == 1:
            np.testing.assert_array_equal(got[:, s], argmax[s])
            continue
        ref = _SortedRow(rows[s], temps[s], k, top_p)
        us = _uniform(np, np.broadcast_to(seeds[s], (steps.size, 2)), steps)
        for i, u in enumerate(us):
            exact, first, last = ref.draw(float(u))
            draws += 1
            if got[i, s] != exact:
                assert first <= ref.place[got[i, s]] <= last, (
                    s, i, int(got[i, s]), exact, first, last)
                leaned += 1
    return draws, leaned


_EQ_TEMPS = np.asarray([0.0, 0.3, 0.8, 1.5], np.float32)


@pytest.fixture(scope="module")
def eq_jitted():
    """The searching spelling and the sorting one, each jitted once over a
    column of steps: what depends on the rows alone (the cut; the sort) is
    done once a call, the draw at every step."""
    import jax
    over_steps = (None,) * 5 + (0,)
    return (jax.jit(jax.vmap(sample_tokens, over_steps)),
            jax.jit(jax.vmap(_sample_tokens_parent, over_steps)))


def _eq_rows(rng, V):
    """Eight kinds of row: plain, spread, near flat, peaked, a block of
    equal logits on top, an all-equal row, a coarse grid (ties all over),
    and the negative zero beside the positive."""
    rows = rng.standard_normal((8, V)).astype(np.float32)
    rows[1] *= 4.0
    rows[2] *= 1e-3
    rows[3, rng.integers(V)] += 25.0
    block = rng.choice(V, size=min(V, 37), replace=False)
    rows[4, block] = rows[4].max() + 0.5
    rows[5] = rows[5, 0]
    rows[6] = np.round(rows[6] * 2.0) / 2.0
    rows[7, ::2] = np.where(rows[7, ::2] > 0, 0.0, -0.0)
    return rows


@pytest.mark.parametrize("top_p", [0.1, 0.9, 1.0])
@pytest.mark.parametrize("top_k", [0, 1, 5, "V", "V+9"])
@pytest.mark.parametrize("V", [257, 4096, 50304])
def test_sample_tokens_matches_the_gathering_spelling(eq_jitted, V, top_k,
                                                      top_p):
    """The search returns what the sorted sampler returns (ISSUE 33):
    greedy slots and top_k = 1 exactly, the kept set exactly (ties by
    lower index), a sampled token up to the rounding of a float32 mass at
    a level it all but touches (`_EDGE_ULPS`), which few draws may need
    (`_EDGE_SHARE`). The sorting spelling of PR 27's parent is held to the
    same rule beside it. A case is 52 steps: four batches of the eight
    rows, 13 steps each, the four temperatures mixed in every batch and
    moved on by one row from batch to batch."""
    import jax.numpy as jnp

    k = {"V": V, "V+9": V + 9}.get(top_k, top_k)
    rng = np.random.default_rng([V, k, int(top_p * 10)])
    S, per_batch = 8, 13
    topks = jnp.full((S,), k, jnp.int32)
    topps = jnp.full((S,), top_p, jnp.float32)
    picked = set()
    tally = {"searching": [0, 0], "sorting": [0, 0]}
    for batch in range(4):
        temps = np.roll(np.tile(_EQ_TEMPS, 2), batch)
        seeds = np.stack([seed_to_key(int(x)) for x in
                          rng.integers(0, 2 ** 63, size=S)])
        steps = batch * per_batch + np.arange(per_batch, dtype=np.int32)
        rows = _eq_rows(rng, V)
        args = (jnp.asarray(rows), jnp.asarray(temps), topks, topps,
                jnp.asarray(seeds),
                jnp.asarray(np.repeat(steps[:, None], S, axis=1)))
        for name, fn in zip(tally, eq_jitted):
            got = np.asarray(fn(*args))
            assert got.shape == (per_batch, S)
            draws, leaned = _held_to_the_sorted_sampler(
                got, rows, temps, k, top_p, seeds, steps)
            tally[name][0] += draws
            tally[name][1] += leaned
        picked.update(got[:, temps > 0].ravel().tolist())
    for name, (draws, leaned) in tally.items():
        assert leaned <= _EDGE_SHARE * max(draws, 1), (name, draws, leaned)
    # the draws moved: a case that kept returning one token shows nothing
    if k != 1:
        assert len(picked) > 8


def test_a_rows_draw_does_not_depend_on_its_neighbours():
    """The search is a row's own: its token is the same whatever rows and
    parameters stand beside it, and wherever in the batch it stands."""
    import jax

    rng = np.random.default_rng(33)
    S, V = 6, 1031
    rows = _eq_rows(rng, V)[:S]
    temps = np.asarray([0.8, 0.0, 1.5, 0.3, 0.8, 0.8], np.float32)
    topks = np.asarray([0, 0, 40, 5, 0, V], np.int32)
    topps = np.asarray([0.9, 1.0, 0.95, 1.0, 0.1, 0.9], np.float32)
    seeds = np.stack([seed_to_key(int(x)) for x in
                      rng.integers(0, 2 ** 63, size=S)])
    steps = np.arange(S, dtype=np.int32) + 7
    fn = jax.jit(sample_tokens)
    together = np.asarray(fn(rows, temps, topks, topps, seeds, steps))
    for s in range(S):
        alone = fn(rows[s:s + 1], temps[s:s + 1], topks[s:s + 1],
                   topps[s:s + 1], seeds[s:s + 1], steps[s:s + 1])
        assert int(alone[0]) == together[s]
    # other neighbours, other parameters beside it, another place
    perm = rng.permutation(S)
    moved = np.asarray(fn(rows[perm], temps[perm], topks[perm], topps[perm],
                          seeds[perm], steps[perm]))
    np.testing.assert_array_equal(moved, together[perm])
    others = rng.standard_normal((S, V)).astype(np.float32)
    others[2] = rows[2]
    mine = np.arange(S) == 2
    beside = fn(others, np.where(mine, temps, 0.4).astype(np.float32),
                np.where(mine, topks, 3).astype(np.int32),
                np.where(mine, topps, 0.5).astype(np.float32),
                np.where(mine[:, None], seeds, seeds[0]), steps)
    assert int(beside[2]) == together[2]


def test_the_same_seed_and_step_agree_under_jit_and_under_vmap():
    """Replay within one build: the token of a `(seed, step)` is the same
    from call to call, eager, jitted, and as one lane of a `vmap` over
    steps or over batches (docs/SERVING.md, the replay contract)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(34)
    S, V, n = 4, 2053, 5
    rows = jnp.asarray(_eq_rows(rng, V)[[0, 2, 6, 7]])
    temps = jnp.asarray([0.8, 1.5, 0.8, 0.3], jnp.float32)
    topks = jnp.asarray([0, 50, 0, 0], jnp.int32)
    topps = jnp.asarray([0.9, 1.0, 0.7, 0.9], jnp.float32)
    seeds = jnp.asarray(np.stack([seed_to_key(int(x)) for x in
                                  rng.integers(0, 2 ** 63, size=S)]))
    steps = np.arange(n, dtype=np.int32)[:, None] + np.zeros((1, S), np.int32)
    jitted = jax.jit(sample_tokens)
    one_by_one = np.stack([np.asarray(jitted(
        rows, temps, topks, topps, seeds, jnp.asarray(st))) for st in steps])
    again = np.stack([np.asarray(jitted(
        rows, temps, topks, topps, seeds, jnp.asarray(st))) for st in steps])
    np.testing.assert_array_equal(one_by_one, again)
    eager = np.asarray(sample_tokens(rows, temps, topks, topps, seeds,
                                     jnp.asarray(steps[2])))
    np.testing.assert_array_equal(eager, one_by_one[2])
    over_steps = jax.jit(jax.vmap(sample_tokens, (None,) * 5 + (0,)))
    np.testing.assert_array_equal(
        np.asarray(over_steps(rows, temps, topks, topps, seeds,
                              jnp.asarray(steps))), one_by_one)
    # a batch of batches: every argument mapped
    tile = lambda x: jnp.broadcast_to(x, (n,) + x.shape)    # noqa: E731
    over_all = jax.jit(jax.vmap(sample_tokens))
    np.testing.assert_array_equal(
        np.asarray(over_all(tile(rows), tile(temps), tile(topks),
                            tile(topps), tile(seeds), jnp.asarray(steps))),
        one_by_one)


# ---------------------------------------------------------------------------
# pool refcounts
# ---------------------------------------------------------------------------

def test_pool_refcounts_share_and_recycle():
    pool = PagePool(8, 4)
    t = pool.alloc_table(8)              # 2 pages, refcount 1 each
    p0, p1 = t.pages
    assert pool.refcount(p0) == 1 and pool.shared_pages == 0
    pool.ref([p0, p1])                   # second holder (a cache hit)
    assert pool.refcount(p0) == 2 and pool.shared_pages == 2
    assert pool.stats()["shared_pages"] == 2
    frees_before = pool.free_count
    pool.free(t)                         # first holder gone: NOT freed
    assert pool.refcount(p0) == 1 and pool.free_pages == 6
    assert pool.free_count == frees_before   # nothing recycled yet
    pool.free([p0, p1])                  # last holder: recycled
    assert pool.refcount(p0) == 0 and pool.free_pages == 8
    assert pool.free_count == frees_before + 2
    with pytest.raises(ValueError, match="double free"):
        pool.free([p0])
    with pytest.raises(ValueError, match="ref of free"):
        pool.ref([p0])


def test_defrag_plan_strict_about_holders_and_keeps_refcounts():
    pool = PagePool(8, 4)
    t = pool.alloc_table(8)
    loose = pool.alloc(1)                # held outside any table
    pool.ref([t.pages[0]])               # shared with a second holder
    with pytest.raises(ValueError, match="unaccounted"):
        defrag_plan(pool, [t])           # loose page not declared
    # free pages sit between the live ones so the plan must move some
    shared_page = t.pages[0]
    mapping = defrag_plan(pool, [t], extra_pages=loose)
    new_shared = mapping[shared_page]
    assert pool.refcount(new_shared) == 2      # refcount moved intact
    assert pool.refcount(mapping[loose[0]]) == 1
    assert sorted(t.pages + [mapping[loose[0]]]) == [0, 1, 2]
    pool.free(t)
    pool.free([mapping[loose[0]], new_shared])
    assert pool.free_pages == 8


# ---------------------------------------------------------------------------
# prefix cache (pure host, no model)
# ---------------------------------------------------------------------------

def _toks(*ids):
    return np.asarray(ids, np.int32)


def test_prefix_cache_lookup_insert_and_dedupe():
    pool = PagePool(16, 4)
    cache = PrefixCache(pool, budget_pages=8)
    with pytest.raises(ValueError, match="budget_pages"):
        PrefixCache(pool, budget_pages=0)
    assert cache.lookup(_toks(1, 2, 3, 4)) is None     # empty trie
    assert cache.stats()["misses"] == 1
    pages = pool.alloc(2)
    prompt = _toks(*range(8))
    with pytest.raises(ValueError, match="tokens"):
        cache.insert(prompt[:4], pages)                # 2 pages, 4 toks
    assert cache.insert(prompt, pages) == 2
    assert pool.refcount(pages[0]) == 2                # cache's own ref
    # full page-aligned match: refs taken for the caller
    m = cache.lookup(prompt)
    assert m.full and m.tokens == 8 and m.pages == pages
    assert pool.refcount(pages[0]) == 3
    pool.free(m.pages)
    # partial: only whole pages match; the sub-page tail is ignored
    m = cache.lookup(_toks(0, 1, 2, 3, 9, 9, 9))
    assert not m.full and m.tokens == 4 and m.pages == [pages[0]]
    pool.free(m.pages)
    # divergent first page: miss
    assert cache.lookup(_toks(5, 1, 2, 3)) is None
    # re-insert of the same tokens adds no nodes and no refs
    again = pool.alloc(2)
    assert cache.insert(prompt, again) == 0
    assert pool.refcount(pages[0]) == 2
    assert cache.stats()["cached_pages"] == 2
    pool.free(again)
    pool.free(pages)                     # table holder gone; cache holds
    assert pool.used_pages == 2          # exactly the cached pages


def test_prefix_cache_lru_eviction_spares_live_pages():
    pool = PagePool(16, 2)
    cache = PrefixCache(pool, budget_pages=2)
    runs = []
    for base in (0, 10, 20):             # three distinct 1-page prefixes
        p = pool.alloc(1)
        cache.insert(_toks(base, base + 1), p)
        runs.append(p)
        pool.free(p)                     # cache is the only holder
    st = cache.stats()
    assert st["cached_pages"] == 2 and st["evicted_pages"] == 1
    # the LRU victim was the FIRST insert; the newer two survive
    assert cache.lookup(_toks(0, 1)) is None
    m = cache.lookup(_toks(20, 21))
    assert m is not None
    # a page a live request still refs is never evicted: the lookup
    # ref above pins run 20 — inserting two more evicts around it
    for base in (30, 40):
        p = pool.alloc(1)
        cache.insert(_toks(base, base + 1), p)
        pool.free(p)
    m2 = cache.lookup(_toks(20, 21))
    assert m2 is not None                # survived both evictions
    pool.free(m.pages)
    pool.free(m2.pages)                  # cache is the only holder again
    # reclaim sheds up to n cold pages regardless of budget (the
    # pool-pressure escape hatch)
    assert cache.reclaim(2) == 2
    assert cache.stats()["cached_pages"] == 0
    assert pool.used_pages == 0


def test_prefix_cache_remap_follows_defrag():
    pool = PagePool(8, 4)
    junk = pool.alloc(2)                 # force the cache run high
    run = pool.alloc(2)
    cache = PrefixCache(pool, budget_pages=4)
    prompt = _toks(*range(8))
    cache.insert(prompt, run)
    pool.free(run)                       # cache is the only holder
    pool.free(junk)                      # pages [0,1] now free
    mapping = defrag_plan(pool, [], extra_pages=cache.pages())
    cache.remap(mapping)
    m = cache.lookup(prompt)
    assert m.pages == [mapping[p] for p in run] == [0, 1]
    pool.free(m.pages)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

def _run_all(eng, jobs):
    """submit everything, drive to idle, return token lists."""
    hs = [eng.submit(p, mnt, **kw) for p, mnt, kw in jobs]
    eng.run_until_idle()
    return [h.result(1.0).tolist() for h in hs]


def _mixed_jobs(cfg, seed=3, sampled=False):
    """Shared 8-token prefix (2 pages) + unique tails, exact duplicate
    prompts (the bootstrap path), and one unrelated prompt."""
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, cfg.vocab_size, (8,))
    jobs = []
    for i in range(5):
        tail = rng.randint(0, cfg.vocab_size, (int(rng.randint(1, 8)),))
        kw = dict(temperature=0.8, top_k=12, top_p=0.9,
                  seed=500 + i) if sampled else {}
        jobs.append((np.concatenate([shared, tail]),
                     int(rng.randint(2, 8)), kw))
    kw = dict(temperature=0.8, top_k=12, top_p=0.9,
              seed=777) if sampled else {}
    jobs.append((shared.copy(), 6, kw))          # full-prompt
    jobs.append((shared.copy(), 6, dict(kw)))    # ... and its replay
    jobs.append((rng.randint(0, cfg.vocab_size, (5,)), 4,
                 dict(kw, seed=888) if sampled else {}))
    return jobs


def test_engine_greedy_parity_cache_on_vs_off(tiny):
    """The acceptance bar: greedy decode with the prefix cache ON is
    token-for-token identical to OFF across cold misses, partial hits,
    and full-prompt bootstrap+COW — with one compile per bucket and
    real reuse (hits, tokens saved, a COW copy) actually observed."""
    cfg, model = tiny
    jobs = _mixed_jobs(cfg)
    off = Engine(model, **ENGINE_KW)
    ref = _run_all(off, jobs)
    on = Engine(model, **ENGINE_KW, prefix_cache_pages=32)
    # sequential first pass: deterministic miss -> hit -> bootstrap
    first = [_run_all(on, [j])[0] for j in jobs]
    assert first == ref
    st = on.stats()["prefix_cache"]
    assert st["hits"] >= 2 and st["tokens_saved"] >= 8
    assert st["cow_copies"] >= 1                 # duplicate prompt path
    # second pass, CONCURRENT, against a now-warm cache: still identical
    assert _run_all(on, jobs) == ref
    for eng in (on, off):
        comp = eng.stats()["compiles"]
        assert comp and all(v == 1 for v in comp.values()), comp
    # a cache-less engine exposes no prefix stats block at all
    assert off.stats()["prefix_cache"] is None
    # idle: every page the pool still holds is a cached page
    assert on.pool.used_pages == on.stats()["prefix_cache"]["cached_pages"]
    assert off.pool.used_pages == 0


def test_engine_sampled_replay_and_cache_invariance(tiny):
    """temperature>0: (a) resubmitting with the same seed replays the
    exact token sequence — across a cold cache, a warm cache, and the
    bootstrap path — (b) a different seed diverges, (c) prefix reuse
    never changes sampled output (ON == OFF for the same seeds)."""
    cfg, model = tiny
    jobs = _mixed_jobs(cfg, sampled=True)
    off = Engine(model, **ENGINE_KW)
    ref = _run_all(off, jobs)
    on = Engine(model, **ENGINE_KW, prefix_cache_pages=32)
    assert [_run_all(on, [j])[0] for j in jobs] == ref    # cold == OFF
    assert _run_all(on, jobs) == ref                      # warm replay
    # the two duplicate-prompt jobs share prompt AND seed: the second
    # admitted via bootstrap+COW, yet bit-identical
    assert ref[5] == ref[6]
    # a different seed diverges (same prompt, same knobs)
    p, mnt, kw = jobs[5]
    h = on.submit(p, mnt, **dict(kw, seed=12345))
    on.run_until_idle()
    assert h.result(1.0).tolist() != ref[5]
    comp = on.stats()["compiles"]
    assert comp and all(v == 1 for v in comp.values()), comp
    # the sampling plane actually counted these stochastic requests
    assert int(on._m_sampling_reqs.value) > 0


def test_engine_cache_reclaim_under_pool_pressure(tiny):
    """A pool-blocked admission sheds cold cached pages instead of
    rejecting: the cache can never starve live traffic."""
    cfg, model = tiny
    eng = Engine(model, num_slots=2, num_pages=12, page_size=4,
                 max_seq_len=48, prefix_cache_pages=12)
    rng = np.random.RandomState(9)
    for _ in range(3):                   # fill the cache: 3x2 pages
        p = rng.randint(0, cfg.vocab_size, (8,))
        eng.submit(p, 2)
        eng.run_until_idle()
    assert eng.stats()["prefix_cache"]["cached_pages"] >= 4
    # worst case 8 pages: free pages alone can't cover it
    big = rng.randint(0, cfg.vocab_size, (24,))
    h = eng.submit(big, 8)
    eng.run_until_idle()
    assert len(h.result(1.0)) == 8
    st = eng.stats()["prefix_cache"]
    assert st["evicted_pages"] > 0
    assert eng.stats()["rejected"] == 0


def test_engine_defrag_remaps_cache_and_keeps_parity(tiny):
    """defrag moves cached pages while the trie holds them: a post-
    defrag same-prefix request must still reuse them correctly (device
    pages moved with the trie's addresses) — token parity with an
    uncached engine proves it."""
    cfg, model = tiny
    rng = np.random.RandomState(11)
    shared = rng.randint(0, cfg.vocab_size, (8,))
    tail_a = np.concatenate([shared,
                             rng.randint(0, cfg.vocab_size, (3,))])
    tail_b = np.concatenate([shared,
                             rng.randint(0, cfg.vocab_size, (5,))])
    off = Engine(model, **ENGINE_KW)
    ref = _run_all(off, [(tail_a, 6, {}), (tail_b, 6, {})])
    on = Engine(model, **ENGINE_KW, prefix_cache_pages=32)
    got_a = _run_all(on, [(tail_a, 6, {})])[0]
    mapping = on.defrag()                # cache-held pages move
    assert mapping                       # plan covered the cached run
    got_b = _run_all(on, [(tail_b, 6, {})])[0]
    assert [got_a, got_b] == ref
    assert on.stats()["prefix_cache"]["hits"] >= 1   # reuse after move


# ---------------------------------------------------------------------------
# loadgen shared-prefix traffic + wire knobs
# ---------------------------------------------------------------------------

def test_loadgen_shared_prefix_mix_deterministic_and_zipf():
    kw = dict(duration=30.0, rate=4.0, seed=5,
              prefix_pool=4, prefix_len=8, prefix_zipf=1.4,
              temperature=0.7, top_k=16, top_p=0.9)
    a = LoadGenerator(TrafficConfig(**kw)).schedule()
    b = LoadGenerator(TrafficConfig(**kw)).schedule()
    assert len(a) > 20
    assert [x.prompt.tolist() for x in a] \
        == [x.prompt.tolist() for x in b]
    assert [x.seed for x in a] == [x.seed for x in b]
    # the pool: rebuild it the way schedule() does and check every
    # prompt leads with a pool prefix, zipf-skewed toward entry 0
    prng0 = np.random.Generator(np.random.Philox(
        key=np.array([5, (1 << 64) - 1], np.uint64)))
    pool = [prng0.integers(0, 256, size=8, dtype=np.int64)
            .astype(np.int32).tolist() for _ in range(4)]
    counts = [0] * 4
    for x in a:
        head = x.prompt[:8].tolist()
        assert head in pool
        counts[pool.index(head)] += 1
        assert x.prompt.size > 8                 # unique suffix follows
        assert x.temperature == 0.7 and x.top_k == 16 and x.top_p == 0.9
        assert x.seed is not None and 0 <= x.seed < 1 << 62
    assert counts[0] == max(counts) and counts[0] > counts[3]
    # seeds are per-arrival (replayable, not shared)
    assert len({x.seed for x in a}) == len(a)
    # another traffic seed: different prompts AND different seeds
    c = LoadGenerator(TrafficConfig(**dict(kw, seed=6))).schedule()
    assert [x.seed for x in c] != [x.seed for x in a]


def test_loadgen_no_pool_schedule_unchanged_and_greedy_default():
    """prefix_pool=0 must leave the pre-PR schedule byte-identical
    (no extra RNG draws) and attach no sampling state."""
    base = dict(duration=20.0, rate=3.0, seed=1)
    a = LoadGenerator(TrafficConfig(**base)).schedule()
    assert all(x.temperature == 0.0 and x.seed is None for x in a)
    # temperature alone must not perturb arrival times or prompts
    # (seeds come from the per-index stream, after the prompt draw)
    b = LoadGenerator(TrafficConfig(**base, temperature=0.5)).schedule()
    assert [x.t for x in a] == [x.t for x in b]
    assert [x.prompt.tolist() for x in a] \
        == [x.prompt.tolist() for x in b]
    assert all(x.seed is not None for x in b)


def test_wire_sampling_knobs_roundtrip_and_replay(tiny):
    """ServingClient carries the sampling knobs; the server-side engine
    replays the same explicit seed bit-identically even when the second
    call is a full-prompt bootstrap off the prefix cache."""
    cfg, model = tiny
    eng = Engine(model, **ENGINE_KW, prefix_cache_pages=32)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    with eng, ServingServer(eng, "127.0.0.1:0") as srv:
        cli = ServingClient(srv.endpoint)
        try:
            with pytest.raises(ValueError, match="temperature"):
                cli.generate(prompt, 4, temperature=-1.0)
            kw = dict(temperature=0.8, top_k=12, top_p=0.9, seed=42)
            r1 = cli.generate(prompt, 8, timeout=60, **kw)
            r2 = cli.generate(prompt, 8, timeout=60, **kw)
            assert r1["status"] == r2["status"] == "done"
            assert np.asarray(r1["tokens"]).tolist() \
                == np.asarray(r2["tokens"]).tolist()
            r3 = cli.generate(prompt, 8, timeout=60,
                              **dict(kw, seed=43))
            assert np.asarray(r3["tokens"]).tolist() \
                != np.asarray(r1["tokens"]).tolist()
        finally:
            cli.close()
    st = eng.stats()["prefix_cache"]
    assert st["hits"] >= 1 and st["cow_copies"] >= 1
