"""`LatentDecodeModel` through `Engine`: prefill in the EXPANDED form, then
decode in the ABSORBED form through the latent pages, against the plain
reference's full forward (benchmark/reference/deepseek_mla_moe.py, which
has the expanded form only), logits and not tokens, on seeded weights at a
small size (a dense layer and two expert layers, 4 heads on a latent row
of 24 + 8). float32 on the CPU with products at `highest` on both sides;
the tolerance on logits of size ~1 is 1e-4: another summation order over 3
layers, the absorbed product's other association, and attention over pages
instead of over the sequence (read: 3e-6). Top-2 of 8 does not flip at
that distance on these seeds (a flip would read ~0.1). Three broken
programs must fail the same comparison (benchmark/tools/latent_faults.py):
the shared experts left out, decode's softmax scaled by the absorbed width,
and kr cached without its rotation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_mla_moe as ref
from benchmark.tools import latent_faults
from paddle_tpu.models import deepseek_v3 as ds
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.serving import Engine, LatentDecodeModel
from tests.test_deepseek_v3_model import sizes_of
from tests.test_serving_hybrid import logits_behind

ATOL = 1e-4
LENGTHS = [1, 3, 4, 5, 8, 9, 17, 7, 31]      # round a page (4) and a bucket


def _serve(model, lengths=LENGTHS, new=6, seed=3):
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

    eng = Engine(Spy(model.cfg, params=model.params,
                     attn_impl=model.attn_impl), num_slots=3,
                 num_pages=40, page_size=4, max_seq_len=48)
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


def _widest(params, sizes, served, T=48, ref=ref):
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
    cfg = ds.DeepseekV3Config.tiny()
    sizes = sizes_of(cfg)
    return cfg, sizes, ref.make_weights(sizes, 11, jnp.float32)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_every_served_position_agrees_with_the_full_forward(tiny, impl):
    """Slots of different lengths, prompts that span several pages (31 is
    eight), every slot reused by a later request; the Pallas kernel in
    interpret mode and the XLA path."""
    cfg, sizes, params = tiny
    eng, served = _serve(LatentDecodeModel(cfg, params=params,
                                           attn_impl=impl))
    worst, worst_decode = _widest(params, sizes, served)
    assert worst < ATOL, worst
    assert 0 < worst_decode < ATOL
    # nine requests over three slots: every slot had a second tenant
    assert {r.slot for r, _ in served} <= {0, 1, 2, None}
    # ONE row a token a layer, no head axis and no expanded key or value:
    # 24 + 8 numbers in a row padded to 128 lanes
    assert eng.cache["latent"].shape == (3, 41, 4, 128)
    assert eng.cache["routing"].dtype == jnp.int8       # 8 experts
    assert not np.asarray(eng.cache["latent"])[..., cfg.latent_width:].any()
    # the routing of every cached position, as the hybrid model hands it
    r = served[6][0]
    assert r.routing.shape == (17 + 5, cfg.num_moe_layers,
                               cfg.num_experts_per_tok)
    st = eng.stats()
    fed = sum(int(q.prompt.size) + len(q.generated) - 1 for q, _ in served)
    assert np.sum(st["expert_tokens"]) == fed * cfg.num_moe_layers \
        * cfg.num_experts_per_tok
    assert st["expert_load_max_over_mean"] >= 1.0


@pytest.mark.parametrize("name", latent_faults.FAULTS)
def test_a_broken_program_fails_the_comparison(tiny, name):
    cfg, sizes, params = tiny
    with latent_faults.fault(name):
        _eng, served = _serve(LatentDecodeModel(cfg, params=params),
                              lengths=[5, 9, 3, 17])
    worst, worst_decode = _widest(params, sizes, served)
    assert worst > 100 * ATOL, (name, worst)
    if name == "scale_576":     # prefill runs the expanded form: sound
        assert worst == worst_decode


def test_more_than_127_experts_are_recorded_in_int16():
    cfg = ds.DeepseekV3Config.tiny(n_routed_experts=130,
                                   moe_intermediate_size=8)
    model = LatentDecodeModel(cfg, seed=0)
    eng = Engine(model, num_slots=2, num_pages=8, page_size=4,
                 max_seq_len=16)
    assert eng.cache["routing"].dtype == jnp.int16
    r = eng.submit(np.arange(5), 3, return_routing=True)
    eng.run_until_idle()
    assert r.routing.shape == (7, 2, 2) and r.routing.max() < 130


def test_the_prefix_cache_is_refused_without_a_prefill_tail(tiny):
    cfg, _sizes, params = tiny
    model = LatentDecodeModel(cfg, params=params)
    assert not model.has_prefill_tail and not model.slot_state
    assert model.attn_forms == {"prefill": "expanded", "decode": "absorbed"}
    with pytest.raises(ValueError, match="prefill_tail"):
        Engine(model, num_slots=2, num_pages=16, page_size=4,
               prefix_cache_pages=4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)])
def test_the_latent_kernel_agrees_with_the_xla_path(dtype, tol, monkeypatch):
    """The kernel alone, interpret mode: ragged contexts (one token, a
    whole page, a page and one, every page of the table, a context that
    ends mid-block), several blocks a slot, a layer that is not the first,
    and a trash page full of NaN that no live token reads."""
    rng = np.random.RandomState(0)
    S, H, w, vw, P, ps, M, W = 5, 4, 48, 32, 200, 8, 80, 128
    q = jnp.asarray(rng.randn(S, H, w), dtype)
    rows = jnp.asarray(rng.randn(3, P, ps, W), dtype).at[..., w:].set(0)
    rows = rows.at[:, -1].set(jnp.nan)
    pt = jnp.asarray(rng.randint(0, P - 1, (S, M)), jnp.int32)
    ctx = jnp.asarray([1, 8, 9, M * ps, 300], jnp.int32)
    # blocks of three pages: the longest slot walks 27 of them
    monkeypatch.setattr(pa, "_PAGE_BUFFER_BYTES",
                        6 * ps * W * rows.dtype.itemsize)
    assert pa._block_pages(ps * W * rows.dtype.itemsize, 1, M) == 3
    want = pa.paged_latent_attention_xla(q, rows, pt, ctx, vw, 0.2, 1)
    got = pa.paged_latent_attention_pallas(q, rows, pt, ctx, vw, 0.2,
                                           jnp.int32(1), interpret=True)
    assert got.shape == (S, H, vw) and got.dtype == dtype
    err = jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))
    assert float(err) < tol, float(err)
    assert pa.latent_row_width(576) == 640 and pa.latent_row_width(48) == 128
