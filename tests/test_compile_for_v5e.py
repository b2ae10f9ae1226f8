"""The serving kernels this repo added (latent rows, grouped query heads,
the one-step update of a recurrent state), compiled at the benchmark's
real widths for a DESCRIBED TPU v5e (no chip;
the chip's compiler is installed here): what Mosaic refuses shows here and
in no interpret-mode test (a page row of 576 lanes, more VMEM than a
kernel may use, a read the interpreters spell another way). Nothing runs:
no result, no time. The topology is described inside a fixture, and only
in this file: one process may load the TPU's library, and a worker that is
not given this file must not try."""
import os
import re

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


def _compile(fn, *specs, donate=()):
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return jax.jit(fn, donate_argnums=donate).lower(*specs).compile()
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
        # the shortest bucket, 1,024: its four q blocks one grid step
        short = lambda d: jax.ShapeDtypeStruct((1, 32, 1024, d),
                                               jnp.bfloat16,
                                               sharding=one_chip)
        assert "tpu_custom_call" in _compile(
            call, short(192), short(192), short(128)).as_text()
        with pytest.raises(Exception, match="vmem"):
            _compile(lambda q, k, v: call(q, k, v, 512), sds(192), sds(192),
                     sds(128))


def test_the_flash_forward_compiles_at_sixteen_thousand_positions(
        one_chip, monkeypatch):
    """The Xing4.0 cell's longest prefill bucket: a head's whole K and V,
    double-buffered, are 20 MiB at 16,384 positions of 192 + 128, past the
    compiler's default 16 MiB of scoped VMEM, which refused the plain call
    (24.6 MiB asked) until it was given room past `_PLAIN_HELD_BYTES`."""
    from paddle_tpu.ops import pallas_attention
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    sds = lambda d: jax.ShapeDtypeStruct((1, 32, 16384, d), jnp.bfloat16,
                                         sharding=one_chip)

    def call(q, k, v):
        return pallas_attention.flash_attention(
            q, k, v, scale=192 ** -0.5, causal=True, block_q=256)

    with jax.default_matmul_precision("default"):
        assert "tpu_custom_call" in _compile(
            call, sds(192), sds(192), sds(128)).as_text()
        monkeypatch.setattr(pallas_attention, "_PLAIN_HELD_BYTES", 2 ** 40)
        with pytest.raises(Exception, match="vmem"):    # traced anew
            _compile(lambda q, k, v: call(q, k, v), sds(192), sds(192),
                     sds(128))


@pytest.mark.parametrize("T,launches", [(16384, 30), (32, 30)])
def test_the_residual_streams_steps_hold_no_copy_of_the_streams(
        one_chip, T, launches):
    """One sub-layer's hyper-connection steps at the Xing4.0 cell's sizes
    (four streams of 3,584, a prefill bucket's rows and a decode batch's):
    the streams are a tuple of arrays, so nothing concatenates them (no
    temporary the size of a stream), and the Sinkhorn loop is a `while`
    whose body is a few fusions, not 20 copies of it."""
    from paddle_tpu.models import layers
    n, C = 4, 3584
    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    p = {"phi": sds((n * C, n * (n + 2))), "hc_bias": sds((n * (n + 2),)),
         "hc_scale": sds((3,))}

    def both(p, X, f):
        pre, post, res = layers.hc_coefficients(p, X, 20, 1e-6, (-30., 30.))
        return layers.hc_write(X, res, post, layers.hc_read(X, pre) + f)

    with jax.default_matmul_precision("default"):
        c = _compile(both, p, (sds((T, C)),) * n, sds((T, C)))
    text = c.as_text()
    entry = text[text.index("ENTRY"):]
    assert len(re.findall(r" (?:fusion|custom-call|while)\(", entry)) \
        < launches
    assert entry.count(" while(") == 1
    assert c.memory_analysis().temp_size_in_bytes < T * C * 2


@pytest.mark.parametrize("B,H,Hkv,T,d,window", [
    (16, 16, 16, 1024, 64, None),       # gpt_350m_train.b16s1024
    (2, 8, 8, 1024, 128, None),         # gpt_1p3b_train_pp2tp2, a shard
    (2, 32, 4, 8192, 128, 1024),        # Mellum's three banded layers of four
    (2, 32, 4, 8192, 128, None),        # Mellum's full layer
], ids=["gpt_350m", "gpt_1p3b_shard", "mellum_band", "mellum_full"])
def test_the_flash_calls_compile_at_the_training_cells_shapes(
        one_chip, monkeypatch, B, H, Hkv, T, d, window):
    """Forward, dq and dk/dv as the training step calls them, tiles of
    512: three Mosaic calls. At 1,024 positions a head's two q blocks
    (K blocks) are one grid step with Python's block indices, no loop
    left; at 8,192 the tiles no edge crosses are a `fori_loop` and a
    band's three tiles a row block are spelt out, the one before the
    sequence's start under a traced flag; VMEM within its limit."""
    from paddle_tpu.ops import pallas_attention
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    assert pallas_attention._per_step(T, 512, True) == (2 if T == 1024 else 1)
    sds = lambda h: jax.ShapeDtypeStruct((B, h, T, d), jnp.bfloat16,
                                         sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(pallas_attention.flash_attention(
            *a, causal=True, window=window).astype(jnp.float32)),
            (0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("default"):
        c = _compile(grads, sds(H), sds(Hkv), sds(Hkv))
    assert c.as_text().count("tpu_custom_call") == 3


@pytest.mark.parametrize("T,window,name", [
    (16384, 2048, "flash_band_fwd"),    # a sliding layer, the longest bucket
    (16384, None, "flash_full_fwd"),    # the full layer, the longest bucket
    (1024, 2048, "flash_full_fwd"),     # a bucket inside the window: no band
], ids=["band_16384", "full_16384", "band_inside_window"])
def test_the_windowed_prefills_attention_compiles_at_the_cells_buckets(
        one_chip, monkeypatch, T, window, name):
    """`layers.gated_causal_attention` as `WindowedDecodeModel.prefill`
    calls it on a TPU (B 1, 32 query heads over 4 KV heads of 128): ONE
    Mosaic call under the mask's name, the whole K and V of a KV head in
    VMEM with no `block_q` handed in, and three times q's bytes kept (a
    transposed copy and the forward's float32 lse). The gate is off:
    nothing can be timed on a described chip, and its default, the
    kernel, holds; without `kernel` the same call is XLA's row blocks."""
    from paddle_tpu.models import afmoe, layers
    from paddle_tpu.ops import pallas_attention
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_attention, "on_tpu", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_AUTOBENCH", "0")
    sds = lambda h: jax.ShapeDtypeStruct((1, T, h, 128), jnp.bfloat16,
                                         sharding=one_chip)

    def attend(kernel):
        return lambda q, k, v: layers.gated_causal_attention(
            q, k, v, 128 ** -0.5, window, kernel,
            xla=afmoe.banded_causal_attention)

    with jax.default_matmul_precision("default"):
        c = _compile(attend(True), sds(32), sds(4), sds(4))
        x = _compile(attend(False), sds(32), sds(4), sds(4))
    text = c.as_text()
    assert text.count("tpu_custom_call") == 1 and name in text
    assert "tpu_custom_call" not in x.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 3.1 * 32 * T * 128 * 2


def _ops_naming(text, pattern):
    """The fusions, custom calls and copies of a compiled module whose
    result, or whose fused computation's parameter, is of a type that
    `pattern` finds."""
    reads = {m.group(1) for m in re.finditer(
        r"^(%fused_computation[\w.\-]*) \(([^)]*)\) ->", text, re.M)
        if re.search(pattern, m.group(2))}
    found = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*?) "
                     r"(fusion|custom-call|copy)\(", ln)
        called = re.search(r"calls=(%[\w.\-]+)", ln)
        if m and (re.search(pattern, m.group(2))
                  or (called and called.group(1) in reads)):
            found.append(m.group(1))
    return found


STATE = (26, 256, 16, 5120)     # Jamba2-3B's Mamba layers at 256 slots


def test_the_step_kernel_compiles_at_the_cells_shapes(one_chip):
    """The whole stacked part f32[26,256,16,5120] and a traced layer: the
    stack one aliased buffer (2.18 GB in, the same 2.18 GB out), no copy
    of it or of a row among the temporaries."""
    from paddle_tpu.ops import selective_scan as ss
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    L, S, N, E = STATE

    def call(stack, m, u, dt, A, B, C, D):
        return ss.selective_step_pallas(u, dt, A, B, C, D, stack, m,
                                        interpret=False)

    c = _compile(call, sds(STATE), sds((), jnp.int32),
                 sds((S, E), jnp.bfloat16), sds((S, E)), sds((N, E)),
                 sds((S, N)), sds((S, N)), sds((E,), jnp.bfloat16),
                 donate=(0,))
    m = c.memory_analysis()
    assert "tpu_custom_call" in c.as_text()
    assert m.alias_size_in_bytes == 4 * L * S * N * E
    assert m.temp_size_in_bytes < 32 * 2 ** 20     # streams, B and C


@pytest.mark.parametrize("step,ops_a_layer", [("pallas", 1), ("xla", 2)])
def test_a_jamba_decode_program_touches_a_layers_state_in_one_operation(
        one_chip, monkeypatch, step, ops_a_layer):
    """The finding of PR 42 and its guard (PR 43): in the decode program at
    the cell's size (28 layers, 256 slots, every part donated) the XLA
    step is TWO fusions a Mamba layer that each read the layer's state
    out of the stack (y; the update written in place), the kernel ONE
    custom call; neither copies the stack or a row (temporaries 65 MB,
    the logits). The three runs of Mamba layers are three loops. If the
    `xla` case fails because the compiler now fuses the two, the kernel
    and its gate key can go."""
    from paddle_tpu.models import jamba
    from paddle_tpu.ops import paged_attention, selective_scan
    from paddle_tpu.serving import RecurrentDecodeModel
    monkeypatch.setattr(paged_attention, "on_tpu", lambda: True)
    monkeypatch.setattr(selective_scan, "on_tpu", lambda: True)
    monkeypatch.setattr(selective_scan, "_auto_step_impl", lambda *a: step)
    cfg = jamba.JambaConfig(dtype="bfloat16")
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda s: sds(s, jnp.bfloat16), jamba.weight_shapes(cfg),
        is_leaf=lambda s: isinstance(s, tuple))
    model = RecurrentDecodeModel.__new__(RecurrentDecodeModel)
    model.cfg, model.attn_impl = cfg, "pallas"
    S = STATE[1]
    cache = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init_cache(6144, 64, S)))
    assert cache["ssm"].shape == STATE

    def decode(cache, params, tokens, positions, tables):
        return model.decode(params, cache, tokens, positions, tables)

    c = _compile(decode, cache, params, sds((S,), jnp.int32),
                 sds((S,), jnp.int32), sds((S, 64), jnp.int32), donate=(0,))
    m = c.memory_analysis()
    assert m.alias_size_in_bytes >= 4 * 26 * S * 16 * 5120
    assert m.temp_size_in_bytes < 100e6
    ops = _ops_naming(c.as_text(), r"f32\[(26,)?256,16,5120\]")
    assert len(ops) == 3 * ops_a_layer, ops
    if step == "pallas":
        assert all(o.startswith("%selective_step") for o in ops), ops


def _computation(text, name):
    """The instructions of the computation `name` of a compiled module."""
    m = re.search(r"\n(?:ENTRY )?" + re.escape(name) + r" \(.*?\n\}\n", text,
                  re.S)
    return m.group(0).split("\n")[2:-2]


def test_a_ranks_expert_layer_keeps_its_sorted_arrays_at_the_bound(
        one_chip, monkeypatch):
    """One expert layer of the Mellum cell (16,384 tokens, 8 of 64 experts
    a token, 16 held: 131,072 pairs, a bound of 65,536 rows), output and
    gradients, as the chip's compiler leaves it (PR 47). Two `cond`s, the
    forward's and the backward's; in the branch that runs where the held
    pairs fit the bound ONE array of 131,072 rows of 2,304 is written (what
    fans back out to every pair's slot) and none of 896, against three or
    more of each in the other branch; outside the `cond`s none at all (a
    `cond` differentiated as it stands writes the untaken branch's
    residuals as zeros there). Every Mosaic call is named `gmm` / `tgmm`,
    which is how the benchmark's readers take the grouped products from a
    profile (`benchmark/readers/routed_train.py`): traced under `jax.vjp`
    they come out as `jvp_jit_gmm__`."""
    from paddle_tpu.ops import pallas_attention
    from paddle_tpu.parallel import moe
    monkeypatch.setattr(pallas_attention, "on_tpu", lambda: True)
    N, k, D, F, E, Eh = 16384, 8, 2304, 896, 64, 16
    rows = moe.held_rows_bound(N, k, Eh, E)
    assert (N * k, rows) == (131072, 65536)
    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)

    def layer(h, wg, w1, w3, w2):
        y, _sel = moe.dropless_moe_ffn(
            h, wg, None, w1, w3, w2, top_k=k, experts_held=tuple(range(Eh)),
            impl="gmm", route="softmax")
        return jnp.sum(y.astype(jnp.float32) ** 2), y

    # tests/conftest.py asks every product for float32 passes, which
    # Mosaic's bf16 products have not
    with jax.default_matmul_precision("default"):
        c = _compile(
            jax.value_and_grad(layer, (0, 1, 2, 3, 4), has_aux=True),
            sds((N, D)), sds((D, E), jnp.float32), sds((Eh, D, F)),
            sds((Eh, D, F)), sds((Eh, F, D)))
    text = c.as_text()
    calls = re.findall(r"(%[\w.\-]+) = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", text)
    # forward 3, backward 3 again and 6 of its own, in either branch
    assert len(calls) == 2 * 12, calls
    assert all(re.match(r"%t?gmm[.\d]*$", c) for c in calls), calls
    branches = re.findall(r"branch_computations=\{(%[\w.\-]+), (%[\w.\-]+)\}",
                          text)
    assert len(branches) == 2, branches
    wide = lambda lines, width: [
        ln for ln in lines
        if re.match(rf"\s+(ROOT )?%[\w.\-]+ = \(?bf16\[{N * k},{width}\]", ln)
        and " parameter(" not in ln and " get-tuple-element(" not in ln
        and " bitcast(" not in ln]
    for whole, short in branches:           # (false, true)
        assert len(wide(_computation(text, short), D)) == 1
        assert wide(_computation(text, short), F) == []
        assert len(wide(_computation(text, whole), D)) >= 3
        assert len(wide(_computation(text, whole), F)) >= 3
    entry = re.search(r"\nENTRY (%[\w.\-]+) ", text).group(1)
    assert wide(_computation(text, entry), D) == []
    assert wide(_computation(text, entry), F) == []
