"""The serving kernels this repo added for the latent model, compiled at
the benchmark's real widths for a DESCRIBED TPU v5e (no chip; the chip's
compiler is installed here): what Mosaic refuses shows here and in no
interpret-mode test (a page row of 576 lanes, more VMEM than a kernel may
use). Nothing runs: no result, no time. The topology is described inside a
fixture, and only in this file: one process may load the TPU's library,
and a worker that is not given this file must not try."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *specs):
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return jax.jit(fn).lower(*specs).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()


def test_the_latent_paged_kernel_compiles_at_the_cells_shapes(one_chip):
    """64 slots, 32 heads on a row of 576 padded to 640 lanes, pages of 64,
    6,400 pages and the trash page, 160 pages a slot, a layer of 7."""
    from paddle_tpu.ops import paged_attention as pa
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    W = pa.latent_row_width(576)
    assert W == 640

    def call(q, rows, pt, ctx, layer):
        return pa.paged_latent_attention_pallas(
            q, rows, pt, ctx, 512, 192 ** -0.5, layer, interpret=False)

    c = _compile(call, sds((64, 32, 576), jnp.bfloat16),
                 sds((7, 6401, 64, W), jnp.bfloat16),
                 sds((64, 160), jnp.int32), sds((64,), jnp.int32),
                 sds((), jnp.int32))
    assert "tpu_custom_call" in c.as_text()
    # the pool is read where it lies: no copy of it among the temporaries
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 20
    # a row of 576 as it is, Mosaic's page copy refuses
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(call, sds((64, 32, 576), jnp.bfloat16),
                 sds((7, 6401, 64, 576), jnp.bfloat16),
                 sds((64, 160), jnp.int32), sds((64,), jnp.int32),
                 sds((), jnp.int32))


def test_the_flash_forward_compiles_at_the_longest_prefill_bucket(
        one_chip, monkeypatch):
    """Prefill's expanded latent attention at 8,192 positions: 32 heads,
    products 192 wide, values 128, a query block of 256 (512 beside whole
    K and V of a head passes Mosaic's 16 MiB of VMEM)."""
    from paddle_tpu.ops import pallas_attention
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    sds = lambda d: jax.ShapeDtypeStruct((1, 32, 8192, d), jnp.bfloat16,
                                         sharding=one_chip)

    def call(q, k, v, block_q=256):
        return pallas_attention.flash_attention(
            q, k, v, scale=192 ** -0.5, causal=True, block_q=block_q)

    # the tests' `highest` default is not the chip's: Mosaic refuses
    # bf16 operands at it
    with jax.default_matmul_precision("default"):
        c = _compile(call, sds(192), sds(192), sds(128))
        assert "tpu_custom_call" in c.as_text()
        with pytest.raises(Exception, match="vmem"):
            _compile(lambda q, k, v: call(q, k, v, 512), sds(192), sds(192),
                     sds(128))
