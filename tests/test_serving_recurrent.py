"""`RecurrentDecodeModel` through `Engine`: prefill (the chunked scan from a
zero state, the slot's rows written from the state at `true_len`) then
decode through the slot's state and the pages (the shared [v | k] row,
attended through the latent path) against the plain reference's full
forward (benchmark/reference/jamba_ssm.py: no cache, the recurrence a scan
over the positions), logits and not tokens, on seeded weights at a small
size: several slots admitted at different steps, every slot reused, a short
request after a longer tenant, an idle slot beside live ones. float32 on
the CPU with products at `highest` on both sides; the tolerance on logits
of size ~1 is 1e-4 (read: 5e-6). Eight broken programs must fail the same
comparison or the state's byte count (benchmark/tools/
recurrent_faults.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import jamba_ssm as ref
from benchmark.tools import recurrent_faults
from paddle_tpu.models import jamba
from paddle_tpu.observability import registry, tracing
from paddle_tpu.serving import Engine, GPTDecodeModel, RecurrentDecodeModel
from tests.test_jamba_model import sizes_of, small_chunks  # noqa: F401
from tests.test_serving_hybrid import logits_behind

ATOL = 1e-4
# round a page (4), a chunk (16) and the convolution's reach (3); the last
# is short after long tenants; 33 + 7 = 40 positions
LENGTHS = [1, 2, 3, 4, 5, 15, 16, 17, 33, 7, 2]


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
    cfg = jamba.JambaConfig.tiny()
    sizes = sizes_of(cfg)
    return cfg, sizes, ref.make_weights(sizes, 11, jnp.float32)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_every_served_position_agrees_with_the_full_forward(
        tiny, small_chunks, impl):   # noqa: F811
    """Eleven requests over three slots: slots admitted at different steps,
    every slot reused, a prompt of 2 after tenants of 33 and 17 (a slot's
    rows are written whole at admission), and at the end slots idle beside
    live ones. The attention layer through the latent kernel in interpret
    mode and through the XLA gather."""
    cfg, sizes, params = tiny
    with jax.default_matmul_precision("highest"):
        eng, served = _serve(RecurrentDecodeModel(cfg, params=params,
                                                  attn_impl=impl))
        worst, worst_decode = _widest(params, sizes, served)
    assert worst < ATOL, worst
    assert 0 < worst_decode < ATOL
    # three Mamba layers' state a slot, the taps time-major; one attention
    # layer's [v | k] row a token under the request's table
    assert eng.cache["ssm"].shape == (3, 3, 4, 128)
    assert eng.cache["ssm"].dtype == jnp.float32
    assert eng.cache["conv"].shape == (3, 3, 3, 128)
    assert eng.cache["kv"].shape == (1, 41, 4, 32)
    assert eng.pool.used_pages == 0
    # a dead slot's rows stay finite
    assert bool(jnp.all(jnp.isfinite(eng.cache["ssm"])))


@pytest.mark.parametrize("name", recurrent_faults.FAULTS)
def test_a_broken_program_fails_the_comparison(
        tiny, small_chunks, name):   # noqa: F811
    cfg, sizes, params = tiny
    with recurrent_faults.fault(name), \
            jax.default_matmul_precision("highest"):
        eng, served = _serve(RecurrentDecodeModel(cfg, params=params),
                             lengths=[5, 17, 33, 7, 2, 3])
        worst, _ = _widest(params, sizes, served)
    if name == "state_bf16":    # its rounding is small; its bytes are not
        assert eng.cache["ssm"].dtype == jnp.bfloat16
        assert 1e-4 < worst < 0.05
    else:
        assert worst > 0.01, worst


def test_the_faults_leave_the_program_as_they_found_it():
    before = (jamba.mamba_mixer, jamba.selective_scan, jamba.selective_step,
              jamba.rmsnorm, jamba.zero_state, RecurrentDecodeModel.prefill)
    for name in recurrent_faults.FAULTS:
        with recurrent_faults.fault(name):
            pass
    assert before == (jamba.mamba_mixer, jamba.selective_scan,
                      jamba.selective_step, jamba.rmsnorm, jamba.zero_state,
                      RecurrentDecodeModel.prefill)
    with pytest.raises(ValueError):
        with recurrent_faults.fault("no_such_fault"):
            pass


def test_the_recurrent_model_refuses_the_prefix_cache_and_more_kv_heads(tiny):
    cfg, _sizes, params = tiny
    with pytest.raises(ValueError, match="prefill_tail"):
        _engine(RecurrentDecodeModel(cfg, params=params),
                prefix_cache_pages=8)
    with pytest.raises(NotImplementedError, match="num_key_value_heads"):
        RecurrentDecodeModel(jamba.JambaConfig.tiny(num_key_value_heads=2))


def test_the_spans_say_what_the_recurrence_ran_over(tiny):
    cfg, _sizes, params = tiny
    eng = _engine(RecurrentDecodeModel(cfg, params=params), num_pages=100,
                  max_seq_len=320)
    tracing.TRACER.clear()
    a = eng.submit(np.arange(290, dtype=np.int32) % 256, 4)  # bucket 320: the cap
    b = eng.submit(np.arange(3, dtype=np.int32), 2)          # bucket 4
    eng.run_until_idle()
    spans = tracing.TRACER.spans()
    prefill = {s.attrs["request"]: s.attrs for s in spans
               if s.name == "engine.prefill"}
    # chunks of 256: a bucket of 320 is two (the second's tail is padding),
    # of 4 one
    assert (prefill[a.id]["scan_len"], prefill[a.id]["scan_chunks"]) == (320, 2)
    assert (prefill[b.id]["scan_len"], prefill[b.id]["scan_chunks"]) == (4, 1)
    rows = [s.attrs["state_rows"] for s in spans if s.name == "engine.decode"]
    # both slots live, then the longer request alone, then a step that
    # only reads the last tokens
    assert rows[0] == 2 and rows[-2] == 1 and rows[-1] == 0
    assert all(s.attrs["state_rows"] == s.attrs["active"] for s in spans
               if s.name == "engine.decode")
    # the spans of a model without a recurrence carry none of these
    from paddle_tpu.models.gpt import GPTConfig
    tracing.TRACER.clear()
    eng = _engine(GPTDecodeModel(GPTConfig.tiny()))
    eng.submit(np.arange(5, dtype=np.int32), 2)
    eng.run_until_idle()
    for s in tracing.TRACER.spans():
        assert not any(k.startswith(("scan_", "state_")) for k in s.attrs)


def test_the_gauges_split_the_bytes_by_kind(tiny):
    cfg, _sizes, params = tiny
    eng = _engine(RecurrentDecodeModel(cfg, params=params))
    gauge = lambda name: registry.REGISTRY.get(name).labels(    # noqa: E731
        engine=eng.engine_id).value
    # one attention layer's [v | k]: 2 x 16 x 4 B a token
    assert gauge("paddle_tpu_serving_paged_bytes_per_token") == 2 * 16 * 4
    # three Mamba layers x 3 slots x (h [4, 128] float32 + 3 taps [128])
    assert gauge("paddle_tpu_serving_slot_state_bytes") \
        == 3 * 3 * (4 * 128 * 4 + 3 * 128 * 4)
    assert eng._kv_cache_bytes()["slot"] \
        == gauge("paddle_tpu_serving_slot_state_bytes")
