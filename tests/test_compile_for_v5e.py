"""The serving kernels this repo added (latent rows, grouped query heads),
compiled at the benchmark's real widths for a DESCRIBED TPU v5e (no chip;
the chip's compiler is installed here): what Mosaic refuses shows here and
in no interpret-mode test (a page row of 576 lanes, more VMEM than a
kernel may use, a read the interpreters spell another way). Nothing runs:
no result, no time. The topology is described inside a fixture, and only
in this file: one process may load the TPU's library, and a worker that is
not given this file must not try."""
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


@pytest.mark.parametrize("S,H,Hkv,d,L,P,ps,M,fused", [
    (64, 32, 4, 128, 1, 7681, 64, 288, False),      # Trinity-Mini's full layer
    (64, 32, 8, 64, 3, 16385, 16, 256, True),       # LFM2's three
], ids=["trinity_full", "lfm2_fused"])
def test_the_grouped_paged_kernel_compiles_at_the_cells_shapes(
        one_chip, S, H, Hkv, d, L, P, ps, M, fused):
    """`_grouped_kernel` as the chip runs it: a KV head of a block read as
    32-bit words with a stride through a folded, bitcast ref
    (`_head_rows(words=True)`, which no interpreted test of the kernel
    takes), two MXU products a head, the output stored a head at a time
    into bf16[S, G, Hkv, 128]; the pools read where they lie."""
    from paddle_tpu.ops import paged_attention as pa
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    pool = sds((L, P, ps, Hkv, 2 * d if fused else d), jnp.bfloat16)

    def call(q, k, v, pt, ctx, layer):
        return pa.paged_attention_pallas(q, k, v, pt, ctx, layer=layer,
                                         interpret=False)

    c = _compile(call, sds((S, H, d), jnp.bfloat16), pool,
                 None if fused else pool, sds((S, M), jnp.int32),
                 sds((S,), jnp.int32), sds((), jnp.int32))
    assert f"bf16[{S},{H // Hkv},{Hkv},128]" in c.as_text()
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 20


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
