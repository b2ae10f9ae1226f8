"""`WindowedDecodeModel.prefill` through the flash kernel (interpret mode)
against the same prefill through XLA's row-blocked bands
(`afmoe.banded_causal_attention`), on seeded float32 weights at a small
size with the published grouping (8 query heads a KV head): the logits of
the last real position and all four K/V parts. The kernel is taken with
`attn_impl="pallas"` wherever its blocks divide the bucket
(`layers.gated_causal_attention`; off a TPU there is no clock to ask), and
the window still comes from `afmoe.window_of` at trace time, which is
where benchmark/tools/window_faults.py breaks it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.tools import window_faults
from paddle_tpu.models import afmoe
from paddle_tpu.serving import WindowedDecodeModel

PS, SLOTS, PAGES = 16, 2, 12
ATOL = 2e-5     # read 2e-6 on logits of size ~1


def _cfg(window):
    return afmoe.AfmoeConfig.tiny(num_attention_heads=16,
                                  num_key_value_heads=2,
                                  sliding_window=window)


@pytest.fixture(scope="module")
def params():
    return afmoe.init_params(_cfg(48), seed=5)


def _prefill(params, window, T, true_len, impl, slot=1):
    """(program text, cache, logits) of one bucket of T, the prompt's
    pages out of order under its table, into slot `slot`'s ring."""
    model = WindowedDecodeModel(_cfg(window), params=params, attn_impl=impl)
    cache = model.init_cache(PAGES, PS, SLOTS)
    tokens = jax.random.randint(jax.random.PRNGKey(T), (T,), 0, 256)
    row = jnp.asarray(np.r_[np.arange(T // PS)[::-1] + 2,
                            np.full(PAGES - T // PS, PAGES)], jnp.int32)
    args = (model.params, cache, tokens, jnp.int32(true_len), row,
            jnp.int32(slot))
    return str(jax.make_jaxpr(model.prefill)(*args)), \
        jax.jit(model.prefill)(*args)


@pytest.mark.parametrize("window,T,true_len,bands,fulls", [
    (96, 64, 64, 0, 5),     # a bucket shorter than the window: no band
    (48, 128, 128, 4, 1),   # longer: the band and the triangle both live
    (48, 128, 117, 4, 1),   # the prompt ends inside the bucket's last page
    (48, 32, 20, 0, 0),     # a bucket no block divides: XLA's, as before
])
def test_prefill_through_the_kernel_agrees_with_the_bands(
        params, window, T, true_len, bands, fulls):
    text, (got, lg) = _prefill(params, window, T, true_len, "pallas")
    assert text.count("flash_band_fwd") == bands
    assert text.count("flash_full_fwd") == fulls
    text, (want, lw) = _prefill(params, window, T, true_len, "xla")
    assert "pallas_call" not in text
    assert lg.shape == (256,) and float(jnp.max(jnp.abs(lw))) > 0.1
    assert float(jnp.max(jnp.abs(lg - lw))) < ATOL
    for part in ("k_full", "v_full", "k_win", "v_win"):
        assert got[part].shape == want[part].shape
        assert float(jnp.max(jnp.abs(want[part]))) > 0.1
        assert float(jnp.max(jnp.abs(got[part] - want[part]))) < ATOL, part
    for part in set(got) - {"k_full", "v_full", "k_win", "v_win"}:
        np.testing.assert_array_equal(got[part], want[part])


@pytest.mark.parametrize("name", ["window_whole", "full_windowed"])
def test_a_window_broken_where_the_fault_tool_breaks_it_changes_the_result(
        params, name):
    """The prefill reads its window through `afmoe.window_of` when it is
    traced, on the kernel's path too."""
    _, (_, sound) = _prefill(params, 48, 128, 117, "pallas")
    with window_faults.fault(name):
        text, (_, broken) = _prefill(params, 48, 128, 117, "pallas")
    assert text.count("flash_band_fwd") == (0 if name == "window_whole"
                                            else 5)
    assert float(jnp.max(jnp.abs(broken - sound))) > 1000 * ATOL
