"""`LoopedDecodeModel` through `Engine`: prefill then decode through the
pages of every pass against the plain reference's full forward
(benchmark/reference/ouro_looped.py), logits and not tokens, on seeded
weights at a small size (3 layers x 4 passes, 4 heads of 16). float32 on
the CPU with products at `highest` on both sides; the tolerance on logits
of size ~1 is 1e-4: another summation order over 12 layer applications,
and attention over pages instead of over the sequence (read: 4e-6). Two
broken programs must fail the same comparison: one that runs 3 passes, and
one whose pass t attends over pass 0's K/V."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ouro_looped as ref
from paddle_tpu.models import ouro
from paddle_tpu.serving import Engine, LoopedDecodeModel
from paddle_tpu.serving import model as serving_model
from tests.test_ouro_model import sizes_of
from tests.test_serving_hybrid import logits_behind

ATOL = 1e-4
LENGTHS = [1, 3, 4, 5, 8, 9, 17, 7, 31]      # round a page (4) and a bucket


def _serve(model, lengths=LENGTHS, new=6, seed=3):
    """Run `lengths` prompts, `new` tokens each, over 3 slots (so every
    slot is reused); returns [(request, [(position fed, logits row)])]."""
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

    eng = Engine(Spy(model.cfg, params=model.params), num_slots=3,
                 num_pages=40, page_size=4, max_seq_len=48)
    seen = {}
    inner = eng.scheduler.record_token

    def record_token(req, token):
        jax.effects_barrier()
        seen.setdefault(req.id, []).append(logits_behind(log, req))
        return inner(req, token)
    eng.scheduler.record_token = record_token
    rng = np.random.RandomState(seed)
    reqs = [eng.submit(rng.randint(0, model.cfg.vocab_size, n), new)
            for n in lengths]
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
    cfg = ouro.OuroConfig.tiny()
    sizes = sizes_of(cfg)
    return cfg, sizes, ref.make_weights(sizes, 11, jnp.float32)


def test_every_served_position_agrees_with_the_full_forward(tiny):
    cfg, sizes, params = tiny
    eng, served = _serve(LoopedDecodeModel(cfg, params=params))
    worst, _ = _widest(params, sizes, served)
    assert worst < ATOL, worst
    # nine requests over three slots: every slot had a second tenant
    assert {r.slot for r, _ in served} <= {0, 1, 2, None}
    # the pool holds a row for every layer of every pass, one page table
    assert eng.cache["k"].shape == (12, 41, 4, 4, 16)
    st = eng.stats()
    fed = sum(int(r.prompt.size) + len(r.generated) - 1 for r, _ in served)
    assert st["loop_passes"] == [fed] * 4
    assert st["loop_passes_per_token"] == 4.0
    share = st["exit_mass_share"]
    assert len(share) == 4 and abs(sum(share) - 1) < 1e-6
    assert all(0.0 < s < 1.0 for s in share)
    # nothing since the last read: the ratios have nothing to report
    again = eng.stats()
    assert again["loop_passes"] == st["loop_passes"]
    assert again["loop_passes_per_token"] is None
    assert again["exit_mass_share"] is None


def test_a_program_of_three_passes_fails_the_comparison(tiny):
    cfg, sizes, params = tiny
    three = dataclasses.replace(cfg, total_ut_steps=3)
    eng, served = _serve(LoopedDecodeModel(three, params=params),
                         lengths=[5, 9, 3, 17])
    worst, _ = _widest(params, sizes, served)
    assert worst > 1000 * ATOL, worst
    assert eng.stats()["loop_passes_per_token"] == 3.0


def test_a_pass_that_attends_over_pass_0s_cache_fails_it(tiny, monkeypatch):
    """Writes go to row t L + l, reads to row l: prefill (dense within each
    pass) is untouched, every decode position is wrong."""
    cfg, sizes, params = tiny
    sound = serving_model.paged_attention_decode

    def pass_0(q, k_pages, v_pages, tables, ctx, layer=None, **kw):
        return sound(q, k_pages, v_pages, tables, ctx,
                     layer=layer % cfg.num_hidden_layers, **kw)
    monkeypatch.setattr(serving_model, "paged_attention_decode", pass_0)
    _eng, served = _serve(LoopedDecodeModel(cfg, params=params),
                          lengths=[5, 9, 3, 17])
    worst, worst_decode = _widest(params, sizes, served)
    assert worst_decode > 1000 * ATOL, worst_decode
    assert worst == worst_decode


def test_the_prefix_cache_is_refused_without_a_prefill_tail(tiny):
    cfg, _sizes, params = tiny
    model = LoopedDecodeModel(cfg, params=params)
    assert not model.has_prefill_tail and not model.slot_state
    with pytest.raises(ValueError, match="prefill_tail"):
        Engine(model, num_slots=2, num_pages=16, page_size=4,
               prefix_cache_pages=4)


def test_defrag_moves_every_pass_of_a_page(tiny):
    cfg, _sizes, params = tiny

    def run(defrag):
        eng = Engine(LoopedDecodeModel(cfg, params=params), num_slots=3,
                     num_pages=40, page_size=4, max_seq_len=48)
        rng = np.random.RandomState(8)
        first = eng.submit(rng.randint(0, cfg.vocab_size, 9), 2)
        reqs = [eng.submit(rng.randint(0, cfg.vocab_size, n), 10)
                for n in (5, 13)]
        for _ in range(4):
            eng.step()
        assert first.done() and not any(r.done() for r in reqs)
        if defrag:
            k = np.asarray(eng.cache["k"])
            moved = eng.defrag()
            assert moved                    # the first request left a hole
            for old, new in moved.items():
                assert np.array_equal(np.asarray(eng.cache["k"])[:, new],
                                      k[:, old])
        eng.run_until_idle()
        return [list(r.generated) for r in reqs]

    assert run(defrag=True) == run(defrag=False)
