"""`LatentDecodeModel` on hyper-connected residual streams through
`Engine`: prefill (expanded attention) then decode (absorbed, through the
latent pages), the four streams activations of both programs, against the
plain reference's full forward (benchmark/reference/xing_mhc_mla_moe.py),
logits and not tokens, on seeded weights at a small size: a dense layer
and two expert layers, a low-rank query, YaRN past its original context of
16. float32 on the CPU with products at `highest` on both sides; 1e-4 on
logits of size ~1 (read: 6e-6). Five broken programs must fail the same
comparison (benchmark/tools/hyper_faults.py), and kanana's programs must
lower to the text they lowered to before these keys existed."""
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import xing_mhc_mla_moe as ref
from benchmark.tools import hyper_faults
from paddle_tpu.models import deepseek_v3 as ds
from paddle_tpu.observability import registry, tracing
from paddle_tpu.serving import Engine, LatentDecodeModel
from tests.test_deepseek_v3_hyper import tiny
from tests.test_serving_latent import ATOL, _serve, _widest


@pytest.fixture(scope="module")
def served_model():
    return tiny("all")


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_every_served_position_agrees_with_the_full_forward(served_model,
                                                            impl):
    cfg, sizes, params = served_model
    model = LatentDecodeModel(cfg, params=params, attn_impl=impl)
    assert model.residual_form == "mhc4x20" and model.residual_streams == 4
    eng, served = _serve(model)
    worst, worst_decode = _widest(params, sizes, served, ref=ref)
    assert worst < ATOL, worst
    assert 0 < worst_decode < ATOL
    # the streams leave nothing in the cache: kanana's one latent row a
    # token a layer, and the routing part
    assert set(eng.cache) == {"latent", "routing", "expert_tokens",
                              "expert_touched"} == set(model.cache_kinds)
    assert eng.cache["latent"].shape == (3, 41, 4, 128)
    # what the engine says of the residual: the spans' attribute, a gauge
    for name in ("engine.prefill", "engine.decode"):
        spans = [s for s in tracing.TRACER.spans() if s.name == name
                 and s.attrs.get("engine") == eng.engine_id]
        assert spans and all(s.attrs["residual"] == "mhc4x20"
                             and "attn" in s.attrs for s in spans)
    gauge = registry.REGISTRY.get("paddle_tpu_serving_residual_streams")
    assert gauge.labels(engine=eng.engine_id).value == 4


def test_a_model_of_one_stream_says_nothing_of_its_residual():
    cfg = ds.DeepseekV3Config.tiny()
    model = LatentDecodeModel(cfg, seed=0)
    assert model.residual_form == "" and model.residual_streams == 1
    eng = Engine(model, num_slots=2, num_pages=8, page_size=4,
                 max_seq_len=16)
    eng.submit(np.arange(5), 2)
    eng.run_until_idle()
    spans = [s for s in tracing.TRACER.spans()
             if s.attrs.get("engine") == eng.engine_id
             and s.name in ("engine.prefill", "engine.decode")]
    assert spans and not any("residual" in s.attrs for s in spans)
    gauge = registry.REGISTRY.get("paddle_tpu_serving_residual_streams")
    assert gauge.labels(engine=eng.engine_id).value == 1


@pytest.mark.parametrize("name", hyper_faults.FAULTS)
def test_a_broken_program_fails_the_comparison(served_model, name):
    cfg, sizes, params = served_model
    with hyper_faults.fault(name):
        _eng, served = _serve(LatentDecodeModel(cfg, params=params),
                              lengths=[5, 9, 3, 17])
    worst, worst_decode = _widest(params, sizes, served, ref=ref)
    assert worst > 100 * ATOL, (name, worst)
    if name == "mscale_dropped":    # prefill runs the expanded form: sound
        assert worst == worst_decode


def test_the_faults_leave_the_program_as_they_found_it():
    from paddle_tpu.serving import model
    sound = lambda: (ds.hc_coefficients, ds.hc_read, ds.low_rank_query,
                     model.LatentDecodeModel.decode)
    before = sound()
    for name in hyper_faults.FAULTS:
        with hyper_faults.fault(name):
            assert sound() != before
    assert before == sound()
    with pytest.raises(ValueError, match="unknown fault"):
        with hyper_faults.fault("no_such"):
            pass


# sha256 (16 hex digits) of the lowered text, without locations, of
# kanana's serving programs at the parent of the PR that taught the core
# `q_lora_rank`, `rope_scaling` and `hc_mult` (scripts/pr49_lowered_texts.py
# prints the same, and the published shapes', from any tree): with all
# three None the programs are the parent's byte for byte. Read under this
# suite's `jax_default_matmul_precision` = highest (tests/conftest.py),
# which is part of the text
KANANA_TINY = {("xla", "prefill 16"): "182aaac28030abe4",
               ("xla", "prefill 64"): "8d738ed9a8a803c5",
               ("xla", "decode 4"): "c70a72efc54905f3",
               ("pallas", "decode 4"): "aeb939f1da9679cc"}


@pytest.mark.parametrize("impl,program", list(KANANA_TINY))
def test_kananas_lowered_programs_are_the_parents(impl, program,
                                                  monkeypatch):
    import functools
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    # two kernel test modules set this on import, in every worker that
    # collects them: with it a 64-position prefill takes the flash kernel
    # interpreted, another text than the one recorded
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    cfg = ds.DeepseekV3Config.tiny()
    assert (cfg.hc_mult, cfg.q_lora_rank, cfg.rope_scaling) == (None,) * 3
    model = LatentDecodeModel(cfg, params={}, attn_impl=impl)
    params = jax.eval_shape(lambda: ds.init_params(cfg, 0))
    cache = jax.eval_shape(functools.partial(model.init_cache, 64, 8, 4))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    kind, n = program.split()
    if kind == "prefill":
        low = jax.jit(model.prefill).lower(params, cache, i32(int(n)), i32(),
                                           i32(16), i32())
    else:
        low = jax.jit(model.decode).lower(params, cache, i32(4), i32(4),
                                          i32(4, 16))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    text = re.sub(r"loc\([^)]*\)", "", low.as_text()).replace(root, "")
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == KANANA_TINY[impl, program]
