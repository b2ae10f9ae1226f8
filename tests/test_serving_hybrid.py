"""`HybridDecodeModel` through `Engine`: prefill then decode through both
kinds of cache (paged K/V, per-slot convolution state) against the plain
reference's full forward (benchmark/reference/lfm2_moe.py), on seeded
weights at a small size with every kind of layer. float32 on the CPU with
products at `highest` on both sides; the tolerance on logits of size ~1 is
1e-4: another summation order, and attention over pages instead of over
the sequence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as ref
from paddle_tpu.models import lfm2
from paddle_tpu.models.deepseek_v3 import DeepseekV3Config
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.ouro import OuroConfig
from paddle_tpu.serving import (DecodeModel, Engine, GPTDecodeModel,
                                HybridDecodeModel, LatentDecodeModel,
                                LoopedDecodeModel)
from tests.test_lfm2_model import sizes_of

LOG = []


class Spy(HybridDecodeModel):
    """Hands every program's logits to the host, in order: a prefill's
    with its slot, a decode's with the positions it fed, since a token is
    read when later programs' logits are already the newest (a decode's a
    `step()` later, a prefill's behind its step's decode)."""

    def prefill(self, params, cache, tokens, true_len, page_row, slot):
        cache, lg = super().prefill(params, cache, tokens, true_len,
                                    page_row, slot)
        jax.debug.callback(
            lambda s, x: LOG.append((int(s), np.asarray(x)[None])), slot, lg,
            ordered=True)
        return cache, lg

    def decode(self, params, cache, tokens, positions, tables):
        cache, lg = super().decode(params, cache, tokens, positions, tables)
        jax.debug.callback(
            lambda p, x: LOG.append((np.asarray(p), np.asarray(x))),
            positions, lg, ordered=True)
        return cache, lg


def logits_behind(log, req):
    """The logits row the token about to be recorded for `req` was drawn
    from: those of the newest prefill into its slot, or of the newest decode
    that fed its slot the position before the token's."""
    pos = int(req.prompt.size) + len(req.generated) - 1
    if not req.generated:
        return pos, next(lg[0] for fed, lg in reversed(log)
                         if isinstance(fed, int) and fed == req.slot)
    return pos, next(lg[req.slot] for fed, lg in reversed(log)
                     if not isinstance(fed, int) and fed[req.slot] == pos)


@pytest.fixture(scope="module")
def served():
    cfg = lfm2.LFM2Config.tiny()
    sizes = sizes_of(cfg)
    params = ref.make_weights(sizes, 11, jnp.float32)
    eng = Engine(Spy(cfg, params=params), num_slots=3, num_pages=40,
                 page_size=4, max_seq_len=48)
    seen = {}               # request id -> [(position fed, logits row)]
    inner = eng.scheduler.record_token

    def record_token(req, token):
        jax.effects_barrier()
        seen.setdefault(req.id, []).append(logits_behind(LOG, req))
        return inner(req, token)
    eng.scheduler.record_token = record_token
    return cfg, sizes, params, eng, seen


def _ref_logits(params, sizes, req, T=48):
    ids = np.zeros((1, T), np.int32)
    full = np.concatenate([req.prompt, req.generated])
    ids[0, :full.size] = full
    return np.asarray(ref.logits(params, jnp.asarray(ids), sizes))[0]


def test_every_served_position_agrees_with_the_full_forward(served):
    """Two waves over 3 slots, so that every slot is reused; prompt
    lengths of 1 and 2 (less than the convolution's reach), on and either
    side of a page (4) and of a bucket (4, 8, 16, 32)."""
    cfg, sizes, params, eng, seen = served
    rng = np.random.RandomState(3)
    lengths = [1, 2, 3, 4, 5, 8, 9, 16, 17, 7, 31]
    reqs = [eng.submit(rng.randint(0, cfg.vocab_size, n), 6,
                       return_routing=True) for n in lengths]
    eng.run_until_idle()
    used = set()
    for r in reqs:
        assert r.status == "done" and len(r.generated) == 6, r.error
        want = _ref_logits(params, sizes, r)
        got = seen[r.id]
        p = int(r.prompt.size)
        assert [pos for pos, _ in got] == list(range(p - 1, p + 5))
        for pos, row in got:
            np.testing.assert_allclose(row, want[pos], atol=1e-4)
        # the routing handed back is the reference's own, position by
        # position: [prompt + generated - 1, expert layers, k]
        assert r.routing.shape == (p + 5, cfg.num_moe_layers,
                                   cfg.num_experts_per_tok)
        short = np.asarray(ref.replay(
            params, np.pad(np.concatenate([r.prompt, r.generated]),
                           (0, 48 - p - 6)).astype(np.int32),
            sizes, routing=r.routing)[3])
        assert float(short.max()) < 1e-5
    assert eng.stats()["completed"] >= len(lengths)


def test_stats_tally_every_token_expert_pair(served):
    cfg, _sizes, _params, eng, _seen = served
    before = np.asarray(eng.stats()["expert_tokens"])
    rng = np.random.RandomState(4)
    reqs = [eng.submit(rng.randint(0, cfg.vocab_size, n), 5)
            for n in (6, 11)]
    eng.run_until_idle()
    st = eng.stats()
    pairs = np.asarray(st["expert_tokens"]) - before
    fed = sum(int(r.prompt.size) + len(r.generated) - 1 for r in reqs)
    assert pairs.shape == (cfg.num_moe_layers, cfg.num_experts)
    assert (pairs.sum(1) == fed * cfg.num_experts_per_tok).all()
    assert st["expert_load_max_over_mean"] >= 1.0
    assert 0.0 < st["experts_touched_share"] <= 1.0
    # nothing since the last read: the ratios have nothing to report
    again = eng.stats()
    assert again["experts_touched_share"] is None
    assert again["expert_tokens"] == st["expert_tokens"]


def test_the_prefix_cache_is_refused_with_per_slot_state(served):
    cfg, _sizes, params, _eng, _seen = served
    model = HybridDecodeModel(cfg, params=params)
    assert model.slot_state
    with pytest.raises(ValueError, match="per-slot state"):
        Engine(model, num_slots=2, num_pages=16, page_size=4,
               prefix_cache_pages=4)
    Engine(model, num_slots=2, num_pages=16, page_size=4,
           prefix_cache_pages=0)


@pytest.mark.parametrize("ending", ["cancelled", "eos"])
def test_routing_is_handed_back_however_the_request_ends(served, ending):
    """The scheduler calls the engine before it frees the pages: the rule
    of what ends a request stays the scheduler's alone."""
    cfg, sizes, params, eng, _seen = served
    prompt = np.random.RandomState(6).randint(0, cfg.vocab_size, 7)
    if ending == "eos":
        first = eng.submit(prompt, 3)
        eng.run_until_idle()
        eos = first.generated[-1]
        req = eng.submit(prompt, 9, eos_id=eos, return_routing=True)
        eng.run_until_idle()
        assert req.status == "done"
        assert len(req.generated) == first.generated.index(eos) + 1
    else:
        req = eng.submit(prompt, 9, return_routing=True)
        for _ in range(3):
            eng.step()
        assert eng.cancel(req) and req.status == "cancelled"
    fed = prompt.size + len(req.generated) - 1
    assert req.routing.shape == (fed, cfg.num_moe_layers,
                                 cfg.num_experts_per_tok)
    ids = np.zeros(48, np.int32)
    ids[:fed + 1] = np.concatenate([prompt, req.generated])
    short = np.asarray(ref.replay(params, ids, sizes,
                                  routing=req.routing)[3])
    assert float(short.max()) < 1e-5


def test_cache_bytes_of_a_model_that_wraps_another():
    """A model that wraps one (tests do) subclasses it, so it answers the
    engine's questions as its parent does: the gauge counts its cache by
    kind of part. (A duck-typed look-alike is refused when the engine is
    built: tests/test_serving.py.)"""
    class Wrapped(GPTDecodeModel):
        def decode(self, *a):
            return super().decode(*a)

    eng = Engine(Wrapped(GPTConfig.tiny(num_layers=1)), num_slots=2,
                 num_pages=8, page_size=8)
    want = sum(x.nbytes for x in jax.tree_util.tree_leaves(eng.cache))
    assert eng._kv_cache_bytes() == {"paged": float(want), "slot": 0.0,
                                     "tally": 0.0}
    req = eng.submit([1, 2, 3], 2)
    eng.run_until_idle()
    assert req.status == "done" and len(req.generated) == 2


def test_return_routing_needs_routed_experts():
    eng = Engine(GPTDecodeModel(GPTConfig.tiny(num_layers=1)), num_slots=2,
                 num_pages=8, page_size=8)
    with pytest.raises(ValueError, match="routed experts"):
        eng.submit([1, 2, 3], 2, return_routing=True)


def test_defrag_moves_the_pages_and_leaves_the_slots(served):
    cfg, _sizes, params, _eng, _seen = served

    def run(defrag):
        eng = Engine(HybridDecodeModel(cfg, params=params), num_slots=3,
                     num_pages=40, page_size=4, max_seq_len=48)
        rng = np.random.RandomState(8)
        first = eng.submit(rng.randint(0, cfg.vocab_size, 9), 2)
        reqs = [eng.submit(rng.randint(0, cfg.vocab_size, n), 10)
                for n in (5, 13)]
        for _ in range(4):
            eng.step()
        assert first.done() and not any(r.done() for r in reqs)
        moved = None
        if defrag:
            conv = np.asarray(eng.cache["conv"])
            kv = np.asarray(eng.cache["kv"])
            moved = eng.defrag()
            assert moved                    # the first request left a hole
            assert np.array_equal(np.asarray(eng.cache["conv"]), conv)
            for old, new in moved.items():
                for part in eng.model.parts_of("paged"):
                    assert part in ("kv", "routing")
                assert np.array_equal(np.asarray(eng.cache["kv"])[:, new],
                                      kv[:, old])
        eng.run_until_idle()
        return [list(r.generated) for r in reqs]

    assert run(defrag=True) == run(defrag=False)


@pytest.mark.parametrize("which", ["gpt", "hybrid", "looped", "latent",
                                   "latent_streams"])
def test_both_decode_models_answer_one_cache_interface(served, which):
    """Everything `Engine` reads of a model, `DecodeModel` declares and
    every model answers; the bodies hand back the cache they were given
    (keys, shapes, dtypes), which donation relies on."""
    cfg, _sizes, params, _eng, _seen = served
    model = {"gpt": lambda: GPTDecodeModel(GPTConfig.tiny(num_layers=1)),
             "hybrid": lambda: HybridDecodeModel(cfg, params=params),
             "looped": lambda: LoopedDecodeModel(OuroConfig.tiny()),
             "latent": lambda: LatentDecodeModel(DeepseekV3Config.tiny()),
             # four residual streams, a low-rank query: the same parts
             "latent_streams": lambda: LatentDecodeModel(
                 DeepseekV3Config.tiny(hc_mult=4, q_lora_rank=12))
             }[which]()
    slot = which == "hybrid"
    latent = which.startswith("latent")
    routed = which == "hybrid" or latent
    assert isinstance(model, DecodeModel)
    assert model.cfg is not None and model.params
    assert model.max_positions == model.cfg.max_position_embeddings
    assert model.passes == (4 if which == "looped" else 1)
    # the two optional capabilities, and the methods behind them
    assert model.has_prefill_tail is (which == "gpt") \
        is hasattr(model, "prefill_tail")
    assert model.has_routing is routed is hasattr(model, "routing_of")
    # two forms of attention are named, one is not
    assert model.attn_forms == ({"prefill": "expanded", "decode": "absorbed"}
                                if latent else {})
    # a residual that is not a sum is named, a sum is not
    assert (model.residual_form, model.residual_streams) == (
        ("mhc4x20", 4) if which == "latent_streams" else ("", 1))
    assert not (model.has_prefill_tail and model.slot_state)
    # a tally has a meaning only through the model
    assert bool(model.parts_of("tally")) is (which != "gpt")
    assert DecodeModel.tally_stats(model, {}, {}, 0) == {}
    cache = model.init_cache(8, 4, 3)
    assert set(cache) == set(model.cache_kinds)
    assert set(model.cache_kinds.values()) <= {"paged", "slot", "tally"}
    assert model.slot_state is slot
    size = model.cache_bytes(cache)
    assert size["paged"] > 0 and (size["slot"] > 0) is slot
    for name in model.parts_of("paged"):
        assert cache[name].shape[1] == 9            # P+1 pages
    for name in model.parts_of("slot"):
        assert cache[name].shape[1] == 3            # one row a slot
    copied = model.copy_pages(
        {k: v + 1 if k in model.parts_of("paged") else v
         for k, v in cache.items()}, [0], [5])
    assert set(copied) == set(cache)

    def like(tree):
        return jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), tree)

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    V = model.cfg.vocab_size
    bodies = {"prefill": (i32(8), i32(), i32(4), i32()),
              "decode": (i32(3), i32(3), i32(3, 4))}
    if model.has_prefill_tail:
        bodies["prefill_tail"] = (i32(8), i32(), i32(), i32(4))
    for name, targs in bodies.items():
        out, logits = jax.eval_shape(getattr(model, name), model.params,
                                     cache, *targs)
        assert like(out) == like(cache), name
        assert logits.shape == ((3, V) if name == "decode" else (V,))
        assert logits.dtype == jnp.float32


def test_the_protocol_base_answers_for_no_model():
    base = DecodeModel(GPTConfig.tiny(), params={})
    assert not base.has_prefill_tail and not base.has_routing
    assert base.cache_kinds == {} and not base.slot_state
    for call in (lambda: base.init_cache(8, 4, 3),
                 lambda: base.prefill({}, {}, None, None, None, None),
                 lambda: base.decode({}, {}, None, None, None)):
        with pytest.raises(NotImplementedError):
            call()


def test_gauges_report_paged_and_per_slot_bytes_apart(served):
    _cfg, _sizes, _params, eng, _seen = served
    from paddle_tpu.observability import registry
    text = registry.REGISTRY.expose_text() if hasattr(
        registry.REGISTRY, "expose_text") else ""
    size = eng._kv_cache_bytes()
    assert size["slot"] == eng.cache["conv"].nbytes
    assert size["paged"] == eng.cache["kv"].nbytes \
        + eng.cache["routing"].nbytes
    m = registry.REGISTRY.get("paddle_tpu_serving_slot_state_bytes")
    assert m is not None
    if text:
        assert "paddle_tpu_serving_slot_state_bytes" in text
