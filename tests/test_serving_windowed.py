"""`WindowedDecodeModel` through `Engine`: prefill (banded attention) then
decode through BOTH caches (the full layer's pages under the request's
table, the sliding layers' ring of pages a slot) against the plain reference's full forward
(benchmark/reference/afmoe_window_moe.py: no cache, a mask), logits and not
tokens, on seeded weights at a small size: a window of 8 positions in pages
of 4, so a ring of 3 pages; contexts to 40, so the ring wraps several
times, and requests that never leave the window. float32 on the CPU with
products at `highest` on both sides; the tolerance on logits of size ~1 is
1e-4 (read: 4e-6). Six broken programs must fail the same comparison
(benchmark/tools/window_faults.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import afmoe_window_moe as ref
from benchmark.tools import window_faults
from paddle_tpu.models import afmoe
from paddle_tpu.observability import tracing
from paddle_tpu.serving import Engine, GPTDecodeModel, WindowedDecodeModel
from tests.test_afmoe_model import sizes_of
from tests.test_serving_hybrid import logits_behind

ATOL = 1e-4
# round a page (4), the window (8) and a ring (12); 33 + 7 = 40 positions
LENGTHS = [1, 3, 4, 5, 8, 9, 12, 13, 17, 7, 33]


def _engine(model, **kw):
    kw = {"num_slots": 3, "num_pages": 40, "page_size": 4,
          "max_seq_len": 48, **kw}
    return Engine(model, **kw)


def _serve(model, lengths=LENGTHS, new=7, seed=3, **engine_kw):
    """Run `lengths` prompts, `new` tokens each, over 3 slots (so every
    slot is reused); returns (engine, [(request, [(position fed, logits
    row)])])."""
    log = []

    class Spy(type(model)):
        """Hands every program's logits to the host, in order (a decode's
        with the positions it fed: tests/test_serving_hybrid.py)."""

        def prefill(self, params, cache, *a):
            cache, lg = super().prefill(params, cache, *a)
            jax.debug.callback(
                lambda s, x: log.append((int(s), np.asarray(x)[None])),
                a[-1], lg, ordered=True)
            return cache, lg

        def decode(self, params, cache, tokens, positions, tables):
            cache, lg = super().decode(params, cache, tokens, positions,
                                       tables)
            jax.debug.callback(
                lambda p, x: log.append((np.asarray(p), np.asarray(x))),
                positions, lg, ordered=True)
            return cache, lg

    eng = _engine(Spy(model.cfg, params=model.params,
                      attn_impl=model.attn_impl), **engine_kw)
    seen = {}
    inner = eng.scheduler.record_token

    def record_token(req, token):
        jax.effects_barrier()
        seen.setdefault(req.id, []).append(logits_behind(log, req))
        return inner(req, token)
    eng.scheduler.record_token = record_token
    rng = np.random.RandomState(seed)
    reqs = [eng.submit(rng.randint(0, model.cfg.vocab_size, n), new,
                       return_routing=True) for n in lengths]
    eng.run_until_idle()
    for r in reqs:
        assert r.status == "done" and len(r.generated) == new, r.error
    return eng, [(r, seen[r.id]) for r in reqs]


def _widest(params, sizes, served, T=48):
    """Widest |served logit - reference logit| over every served position
    (prefill's last and every decode's), and that over decode's alone."""
    worst = worst_decode = 0.0
    for r, got in served:
        ids = np.zeros((1, T), np.int32)
        full = np.concatenate([r.prompt, r.generated])
        ids[0, :full.size] = full
        want = np.asarray(ref.logits(params, jnp.asarray(ids), sizes))[0]
        p = int(r.prompt.size)
        assert [pos for pos, _ in got] == list(range(p - 1, p - 1 + len(got)))
        for pos, row in got:
            err = float(np.max(np.abs(row - want[pos])))
            worst = max(worst, err)
            if pos >= p:
                worst_decode = max(worst_decode, err)
    return worst, worst_decode


@pytest.fixture(scope="module")
def tiny():
    cfg = afmoe.AfmoeConfig.tiny()
    sizes = sizes_of(cfg)
    return cfg, sizes, ref.make_weights(sizes, 11, jnp.float32)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_every_served_position_agrees_with_the_full_forward(tiny, impl):
    """Slots of different lengths, every slot reused by later requests, a
    short one after a long one (what the last tenant left in a slot's ring
    is masked by its position). The full layer through the Pallas kernel in
    interpret mode and through the XLA path; the sliding layers' ring walk
    has one implementation."""
    cfg, sizes, params = tiny
    eng, served = _serve(WindowedDecodeModel(cfg, params=params,
                                             attn_impl=impl))
    worst, worst_decode = _widest(params, sizes, served)
    assert worst < ATOL, worst
    assert 0 < worst_decode < ATOL
    # the full layer's K/V under the request's table; the four sliding
    # layers' in a ring a slot: 3 slots x 3 pages, and the trash page
    assert eng.cache["k_full"].shape == (1, 41, 4, 2, 16)
    assert eng.cache["v_win"].shape == (4, 10, 4, 2, 16)
    assert eng.ring_pages == 3
    assert eng.pool.used_pages == 0
    # the routing of every cached position lies under the request's table
    r = served[-1][0]
    assert r.routing.shape == (33 + 6, cfg.num_moe_layers,
                               cfg.num_experts_per_tok)
    assert eng.stats()["pool"]["window"] == {"window": 8, "ring_pages": 3}


@pytest.mark.parametrize("name", window_faults.FAULTS)
def test_a_broken_program_fails_the_comparison(tiny, name):
    cfg, sizes, params = tiny
    with window_faults.fault(name):
        _eng, served = _serve(WindowedDecodeModel(cfg, params=params),
                              lengths=[5, 9, 17, 33])
    worst, _ = _widest(params, sizes, served)
    assert worst > 100 * ATOL, (name, worst)


def test_the_faults_leave_the_program_as_they_found_it():
    from paddle_tpu.serving import model
    sound = lambda: (afmoe.window_of, afmoe.rotates, afmoe.output_gate,
                     afmoe.routed_ffn, model.paged_attention_decode,
                     model.paged_attention_xla)
    before = sound()
    for name in window_faults.FAULTS:
        with window_faults.fault(name):
            assert sound() != before
    assert before == sound()
    with pytest.raises(ValueError, match="unknown fault"):
        with window_faults.fault("no_such"):
            pass


# -- the engine round a ring a slot ----------------------------------------

def test_a_ring_is_its_slots_and_nothing_is_allocated_for_it(tiny):
    """The scheduler and the pool know one table a request, as for every
    model; a slot's ring lies where its slot does, and an idle slot's
    decode rows go to the ring's trash page."""
    cfg, _sizes, params = tiny
    model = WindowedDecodeModel(cfg, params=params, attn_impl="xla")
    assert model.slot_state and not model.has_prefill_tail
    assert model.parts_of("slot") == ("k_win", "v_win")
    eng = _engine(model)
    req = eng.submit(np.arange(30, dtype=np.int32), 4)
    eng.run_until_idle()
    assert req.status == "done" and eng.pool.used_pages == 0
    k_win = np.asarray(eng.cache["k_win"])
    # the ring of the slot that served it holds rows, the other slots' none
    mine = np.zeros(10, bool)
    mine[req.slot * 3:req.slot * 3 + 3] = True
    assert (np.abs(k_win[:, mine]).max(axis=(0, 2, 3, 4)) > 0).all()
    assert np.abs(k_win[:, ~mine][:, :-1]).max() == 0     # (but the trash)


def test_a_model_without_a_window_runs_what_it_ran():
    from paddle_tpu.models.gpt import GPTConfig
    model = GPTDecodeModel(GPTConfig.tiny())
    assert model.window is None
    eng = _engine(model)
    assert eng.ring_pages == 0
    req = eng.submit(np.arange(5, dtype=np.int32), 3)
    eng.run_until_idle()
    assert req.status == "done"
    assert "window" not in eng.stats()["pool"]


def test_the_windowed_model_refuses_the_prefix_cache(tiny):
    """A ring keeps no page of a prefix older than its window: per-slot
    state, no `prefill_tail`."""
    cfg, _sizes, params = tiny
    with pytest.raises(ValueError, match="prefill_tail"):
        _engine(WindowedDecodeModel(cfg, params=params),
                prefix_cache_pages=8)


def test_defrag_and_recovery_leave_the_rings_where_they_are(tiny):
    """Defrag compacts the pages under the requests' tables; the rings lie
    with their slots and the served tokens stay the reference's. A lost
    cache is rebuilt with both kinds of part."""
    cfg, sizes, params = tiny
    model = WindowedDecodeModel(cfg, params=params, attn_impl="xla")
    eng = _engine(model)
    rng = np.random.RandomState(0)
    first = [eng.submit(rng.randint(0, cfg.vocab_size, n), 30)
             for n in (6, 14)]
    for _ in range(4):
        eng.step()
    eng.cancel(first[0])            # a hole at the pool's low end
    late = eng.submit(rng.randint(0, cfg.vocab_size, 9), 12)
    for _ in range(3):
        eng.step()
    rings = np.asarray(eng.cache["k_win"])
    assert eng.defrag()             # something moved
    np.testing.assert_array_equal(np.asarray(eng.cache["k_win"]), rings)
    live = [r for r in eng.scheduler.active_requests()]
    assert sorted(p for r in live for p in r.table.pages) \
        == list(range(eng.pool.used_pages))
    eng.run_until_idle()
    for r in (first[1], late):
        assert r.status == "done"
        ids = np.zeros((1, 48), np.int32)
        full = np.concatenate([r.prompt, r.generated])
        ids[0, :full.size] = full
        want = np.asarray(ref.logits(params, jnp.asarray(ids), sizes))[0]
        p = int(r.prompt.size)
        # greedy tokens: the reference's own argmax at every served position
        assert list(np.argmax(want[p - 1:full.size - 1], -1)) \
            == list(r.generated)
    shapes = {k: v.shape for k, v in eng.cache.items()}
    eng._donate = True              # as on a device backend
    eng._recover_cache("test")
    assert {k: v.shape for k, v in eng.cache.items()} == shapes


# -- spans ------------------------------------------------------------------

def test_the_spans_say_what_the_rings_held(tiny):
    cfg, _sizes, params = tiny
    eng = _engine(WindowedDecodeModel(cfg, params=params, attn_impl="xla"))
    tracing.TRACER.clear()
    a = eng.submit(np.arange(13, dtype=np.int32), 4)    # 5 past the window
    b = eng.submit(np.arange(3, dtype=np.int32), 4)
    eng.step()
    eng.step()
    spans = tracing.TRACER.spans()
    prefill = {s.attrs["request"]: s.attrs for s in spans
               if s.name == "engine.prefill"}
    assert prefill[a.id]["past_window"] == 5
    assert prefill[b.id]["past_window"] == 0
    steps = [s.attrs for s in spans if s.name == "engine.step"]
    # reserved: 17 and 7 tokens = 5 + 2 pages under the tables, and two
    # slots' rings of 3 pages; live once the first decode has written: 14
    # and 4 tokens = 4 + 1 pages, of which the rings hold 3 + 1
    assert steps[0]["pages_reserved"] == 7
    assert steps[0]["window_pages_reserved"] == 6
    assert steps[0]["pages_live"] == 5
    assert steps[0]["window_pages_live"] == 4
    decode = [s.attrs for s in spans if s.name == "engine.decode"]
    # the sliding layers read min(context, window) rows a slot: 8 + 4
    assert decode[0]["window_rows"] == 12 and decode[1]["window_rows"] == 13
    eng.run_until_idle()
    # the spans of a model without window layers carry none of these
    from paddle_tpu.models.gpt import GPTConfig
    tracing.TRACER.clear()
    eng = _engine(GPTDecodeModel(GPTConfig.tiny()))
    eng.submit(np.arange(5, dtype=np.int32), 2)
    eng.run_until_idle()
    for s in tracing.TRACER.spans():
        assert not any(k.startswith(("window_", "past_")) for k in s.attrs)


def test_the_gauges_split_the_bytes_by_kind(tiny):
    from paddle_tpu.observability import registry
    cfg, _sizes, params = tiny
    eng = _engine(WindowedDecodeModel(cfg, params=params))
    gauge = lambda name: registry.REGISTRY.get(name).labels(
        engine=eng.engine_id).value
    # the full layer's K and V (2 x 2 heads x 16 x 4 B) and the routing
    # part (3 expert layers x 2 experts, int8)
    assert gauge("paddle_tpu_serving_paged_bytes_per_token") \
        == 2 * 2 * 16 * 4 + 6
    # 4 sliding layers x (3 slots x 3 pages + trash) x 4 positions x K and V
    assert gauge("paddle_tpu_serving_slot_state_bytes") \
        == 4 * 10 * 4 * 2 * 2 * 16 * 4
