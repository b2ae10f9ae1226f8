#!/usr/bin/env python
"""Benchmark driver entry: one JSON line to stdout.

Headline metric (BASELINE config 3): BERT-base pretrain samples/sec/chip —
full MLM+NSP train step (fwd+bwd+AdamW) as ONE jitted XLA computation, bf16
autocast on the MXU, Pallas flash attention + fused layer_norm on the hot
path, hardware-RBG PRNG for dropout (threefry cost ~30% of the step; see
paddle_tpu/__init__). MFU is computed from analytic model FLOPs
(matmul-only, fwd+2×bwd) against the chip's peak bf16 FLOP/s — peak is
resolved from the device kind with a TPU_PEAK_TFLOPS_BF16 env override, and
the assumption is printed so the number is auditable.

Round-3 measured (v5e single chip): bert_base b64 s128 = 916 samples/s,
32.5% MFU; bert_base_512 b16 = 234 samples/s, 35.8% MFU (r2: 519 / 22.5%);
gpt-350M s1024 = 33.7k tokens/s, 41.5% MFU (flash attention + per-layer
remat); resnet50 = 1548 images/s. The +21% over the earlier 759 samples/s
comes from the masked-positions MLM head (only the ~15% predicted rows hit
the 30k-vocab projection, MLPerf practice; MFU accounts the REDUCED
flops). Binding-constraint analysis: step is HBM-bandwidth-bound —
XLA-counted bytes 60GB/step = ~680 GB/s sustained (~83% of v5e peak BW)
while XLA-counted FLOPs match analytic model FLOPs (no wasted compute);
marginal GEMM rate 162 TFLOP/s (82% of peak) at BERT shapes; flash
attention beats XLA sdpa 1.4x in-step (block 512 optimal at s512); amp O2
gains <3% over O1; further MFU needs fusing the LN/gelu/bias/dropout
chains (fewer materialised activations), not more matmul tuning.

The reference publishes no in-repo numbers (BASELINE.md), so vs_baseline is
1.0 until a measured reference lands.

Configs (BENCH_CONFIG=...): bert_base (default, seq 128; also records the
secondary configs in an "extras" dict unless BENCH_EXTRAS=0) | bert_base_512
| bert_tiny | lenet | gpt (350M tokens/sec) | resnet50 | widedeep |
infer (BERT predictor latency) | flash_attn (pallas-vs-jnp microbench) |
allreduce | metrics_overhead (telemetry enabled-vs-disabled decode
step-time delta, <2% bar) | flight_overhead (flight recorder only
toggled, same harness and bar) | perfwatch_overhead (perf-plane step
sampler at its default cadence vs off, same harness and bar) |
checkpoint (store save/restore MB/s,
dedup ratio on a 1%-mutated state, async-vs-sync save step overhead,
<5% bar) | slo (open-loop traffic replay against the serving tier:
SLO attainment, goodput, p99 TTFT/ITL) | prefix (shared-prefix radix
KV cache A/B, cache on vs off on a system-prompt + unique-suffix mix:
goodput tokens/s, p99 TTFT, prefill-FLOPs reduction and the measured
effective-KV-capacity multiplier) | chaos (same seeded traffic +
a serving_decode stall mid-run: watchdog detection + recovery seconds
and post-recovery SLO delta vs the fault-free baseline) | router
(replicated fleet behind the fault-tolerant router: one replica killed
mid-run under wire traffic — failover detect + respawn recovery
seconds, post-recovery attainment delta, wire TTFT via streaming) |
kernels (per-kernel fused-vs-unfused speedups for the epilogue-fused
decoder sub-blocks + autobench tuning-cache cold/warm first-call
latency) | transport (multiplexed RPC A/B: wire TTFT p50/p99 through
ONE shared client under a concurrency sweep of long streams, mux vs
legacy one-call-per-channel, plus the zero-copy pull path's
bytes-copied-per-payload-byte on both paths) | online (continuous
publish pipeline: PS push -> servable-version staleness on the wire,
streamed-generate max inter-token gap across a staggered 2-replica
rollout vs steady-state ITL, cross-version chunk dedup ratio on a
one-row-mutated embedding) | ps_ha (PS high-availability plane:
kill-primary -> promoted-standby first-push wall time vs the pre-HA
snapshot-respawn baseline, semi-sync vs async push-ack tax, and
steady-state replication lag under a wide&deep-style push stream) |
tsdb (time-series plane: collector TSDB + alert evaluator toggled
A/B/A behind a live agent, same <2% decode bar, plus the store's own
ingest rate, bytes/sample after downsampling, and range/rate/quantile
query latency).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_T0 = time.perf_counter()



def _sync(x):
    """Wait for `x` (any pytree of device arrays): jax dispatch is
    asynchronous, so every timed region ends here."""
    import jax
    jax.block_until_ready(x)


def _finish_timed(t0, loss):
    """Close a timed loop started at t0: seconds until `loss` is ready."""
    _sync(loss)
    return time.perf_counter() - t0


def chip_peak_flops():
    """(peak bf16 FLOP/s, label). The peak table lives in the perf plane
    (ONE source for the live MFU gauges and the bench reports). A device
    kind the table does not know has no peak, and a bench that reports
    an MFU fails there instead of assuming a chip."""
    from paddle_tpu.observability import perf as _perf
    peak, kind = _perf.chip_peak_flops()
    if peak is None:
        raise RuntimeError(
            f"no peak FLOP/s known for device kind {kind!r}: an MFU is "
            f"reported on a TPU the perf plane's table lists, or with "
            f"TPU_PEAK_TFLOPS_BF16 set")
    if os.environ.get("TPU_PEAK_TFLOPS_BF16"):
        return peak, "env"
    return peak, kind


def bert_train_flops_per_step(cfg, batch, seq, n_pred=None):
    """Analytic matmul FLOPs for one train step (fwd + 2x for bwd).

    Counts the dense projections, attention score/context matmuls, the MLM
    transform + vocab projection and the NSP head; elementwise/norm
    FLOPs are ignored (MFU convention). n_pred = masked positions per
    sequence actually projected into the vocab (None = all `seq`
    positions — the naive head)."""
    H, L, V = cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size
    I = cfg.intermediate_size
    tokens = batch * seq
    per_layer = (
        2 * H * (3 * H)          # qkv proj
        + 2 * H * H              # attention out proj
        + 2 * 2 * seq * H        # scores QK^T + context PV (per token)
        + 2 * H * I + 2 * I * H  # ffn up + down
    )
    pred_tokens = batch * (n_pred if n_pred is not None else seq)
    mlm_head = 2 * H * H + 2 * H * V    # transform + vocab proj
    fwd = tokens * L * per_layer + pred_tokens * mlm_head \
        + batch * (2 * H * 2)
    return 3 * fwd  # fwd + bwd(≈2x fwd)


def bench_lenet(batch=256, steps=30, warmup=5):
    import paddle_tpu as paddle
    from paddle_tpu.fluid import Executor, framework, optimizer, unique_name
    from paddle_tpu.fluid.scope import Scope, scope_guard
    from paddle_tpu.models import build_lenet_program

    paddle.enable_static()
    with unique_name.guard():
        main, startup, feeds, fetches = build_lenet_program()
        with framework.program_guard(main, startup):
            opt = optimizer.Adam(learning_rate=1e-3)
            opt.minimize(fetches["loss"])
    scope = Scope()
    with scope_guard(scope):
        exe = Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        img = rng.randn(batch, 1, 28, 28).astype("float32")
        lab = rng.randint(0, 10, (batch, 1)).astype("int64")
        for _ in range(warmup):
            exe.run(main, feed={"img": img, "label": lab},
                    fetch_list=[fetches["loss"]])
        _sync(out := exe.run(main, feed={"img": img, "label": lab},
                             fetch_list=[fetches["loss"]],
                             return_numpy=False))
        t0 = time.perf_counter()
        for _ in range(steps):
            out = exe.run(main, feed={"img": img, "label": lab},
                          fetch_list=[fetches["loss"]], return_numpy=False)
        _sync(out)
        dt = time.perf_counter() - t0
    paddle.disable_static()
    return {"metric": "mnist_lenet_static_train_examples_per_sec",
            "value": round(batch * steps / dt, 2), "unit": "examples/sec"}


def bench_bert(cfg_name="base", batch=16, seq=128, steps=32, warmup=3):
    """BERT pretrain step (BASELINE config 3).

    r04 bandwidth profile (v5e, batch 64, s128, measured 2026-07-30):
    the compiled step accesses ~48.7 GB per step (XLA cost analysis); at
    the chip's 819 GB/s that is a ~59 ms bandwidth floor against a
    ~70 ms measured step — the program runs at ~85% of its own floor,
    which caps MFU at ~38-39% for this op structure. Experiments that
    did NOT move the number (all within the run-to-run variance of
    that chip, ±5%): layer_norm/softmax off the f32 AMP
    blacklist (the Pallas LN/flash kernels already keep their f32 math
    internal), batch 128. The attention path already runs the Pallas
    flash kernel fwd+bwd; dropout+residual+LN runs the fused Pallas
    epilogue.

    r05 activation-traffic audit (xplane device trace, b64 s128): the
    largest non-matmul cost is the FFN gelu tier — 12 fwd
    `select_convert_fusion`s (erf gelu + saved branch predicate over
    bf16[64,128,3072]) + 12 bwd partners at ~0.51 ms each ≈ 12 ms of the
    ~64 ms step (19%). These passes run ~5x above their bandwidth floor,
    i.e. they are VPU-compute-bound on the erf polynomial, not HBM-bound;
    notably the f32-erf lowering measured FASTER than bf16-erf (which
    up-converts with extra selects), so the existing AMP placement is
    already the fast variant. The FFN pair IS now fused into one Pallas
    kernel (ops/pallas_ffn.py: poly-erf gelu computed in VMEM, 4H
    intermediate never reaches HBM, bwd rematerialises) wired through
    nn.TransformerEncoderLayer. In isolation the kernel beats the XLA
    chain 1.35x fwd / 1.23x fwd+bwd at BERT shapes (70 vs 52 TF/s fwd);
    at FULL-STEP granularity a same-process A/B measured ~1.00x
    (65.5-66.7 ms both ways, 3 reps) — XLA's schedule already overlaps
    the gelu tier with neighboring work, so removing it does not
    shorten the critical path. The fused path stays on (never slower,
    structurally less HBM traffic, guaranteed-fusion contract), and the
    r04 ~39% structural cap stands. (r04/r05 were measured through a
    PJRT transport that no longer exists; none of these figures has
    been re-measured on a directly attached chip.)"""
    import jax
    from paddle_tpu.jit.functional import make_train_step
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    cfg = BertConfig.base() if cfg_name.startswith("base") \
        else BertConfig.tiny()
    model = BertForPretraining(cfg)
    model.train()

    # MLPerf-BERT convention: only max_predictions_per_seq (~15%) masked
    # positions reach the vocab projection (models/bert.py
    # masked_positions path)
    n_pred = min(seq, max(8, int(round(seq * 0.15))))

    def loss_fn(m, ids, pos, mlm, nsp):
        logits, nsp_logits = m(ids, masked_positions=pos)
        return m.loss(logits, nsp_logits, mlm, nsp)

    step = make_train_step(model, loss_fn, optimizer="adamw", lr=1e-4,
                           amp_level="O1")
    rng = np.random.RandomState(0)
    import jax.numpy as jnp
    ids_np = rng.randint(4, cfg.vocab_size, (batch, seq)).astype("int64")
    pos_np = np.stack([
        np.sort(rng.choice(seq, n_pred, replace=False))
        for _ in range(batch)]).astype("int64")
    mlm_np = np.take_along_axis(ids_np, pos_np, axis=1)
    ids = jnp.asarray(ids_np)
    pos = jnp.asarray(pos_np)
    mlm = jnp.asarray(mlm_np)
    nsp = jnp.asarray(rng.randint(0, 2, (batch, 1)).astype("int64"))
    jax.block_until_ready([ids, pos, mlm, nsp])
    for _ in range(warmup):
        loss = step(ids, pos, mlm, nsp)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids, pos, mlm, nsp)
    dt = _finish_timed(t0, loss)

    samples_sec = batch * steps / dt
    flops_step = bert_train_flops_per_step(cfg, batch, seq, n_pred)
    peak, kind = chip_peak_flops()
    mfu = flops_step * steps / dt / peak
    suffix = f"_{seq}" if seq != 128 else ""
    return {"metric": f"bert_{cfg_name.split('_')[0]}{suffix}"
                      "_pretrain_samples_per_sec_per_chip",
            "value": round(samples_sec, 2), "unit": "samples/sec/chip",
            "mfu": round(mfu, 4), "model_flops_per_step": flops_step,
            "peak_flops_assumed": peak, "device_kind": str(kind),
            "batch": batch, "seq": seq}


def bench_flash_attn(steps=20, warmup=3):
    """Pallas flash attention vs jnp sdpa at BERT-base seq-512 shapes
    (fwd+bwd). The 'value' is the pallas step speedup over jnp."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.flash_attention import sdpa_reference
    from paddle_tpu.ops.pallas_attention import can_use_flash, flash_attention

    B, H, S, D = 16, 12, 512, 64
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32),
                    dtype=jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32),
                    dtype=jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32),
                    dtype=jnp.bfloat16)
    assert can_use_flash(q, k, v, None)

    def time_fn(f):
        # repeat inside ONE jit via scan so the comparison is of the
        # kernels, not of per-call dispatch
        rep = 8
        grad = jax.grad(lambda q, k, v: jnp.sum(
            f(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2))

        @jax.jit
        def loop(q, k, v):
            def body(c, _):
                dq, dk, dv = grad(c[0], c[1], c[2])
                return (dq * 1e-6 + q, dk * 1e-6 + k, dv * 1e-6 + v), None
            c, _ = jax.lax.scan(body, (q, k, v), None, length=rep)
            return c

        out = loop(q, k, v)
        _sync(out[0])
        t0 = time.perf_counter()
        for _ in range(max(steps // rep, 2)):
            out = loop(*out)
        _sync(out[0])
        return (time.perf_counter() - t0) / (max(steps // rep, 2) * rep)

    t_pallas = time_fn(lambda q, k, v: flash_attention(q, k, v))
    t_jnp = time_fn(lambda q, k, v: sdpa_reference(q, k, v))
    return {"metric": "flash_attention_seq512_speedup_vs_jnp",
            "value": round(t_jnp / t_pallas, 3), "unit": "x",
            "pallas_ms": round(t_pallas * 1e3, 3),
            "jnp_ms": round(t_jnp * 1e3, 3)}


def gpt_train_flops_per_step(cfg, batch, seq):
    """Matmul-only analytic FLOPs, fwd + 2x bwd (MFU convention)."""
    H, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    F = cfg.intermediate_size
    tokens = batch * seq
    per_layer = (3 * 2 * H * H      # q, k, v projections
                 + 2 * H * H        # out projection
                 + 2 * 2 * seq * H  # scores + context (per token)
                 + 2 * H * F + 2 * F * H)
    fwd = tokens * (L * per_layer + 2 * H * V)
    return 3 * fwd


def bench_gpt(batch=8, seq=1024, steps=10, warmup=2, dp=1, pp=1, tp=1):
    """GPT-350M causal-LM train step (BASELINE config 5 single-chip proxy;
    the full dp x pp x tp path is validated by dryrun_multichip and scales
    via the same HybridParallelTrainStep)."""
    import jax
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.parallel.hybrid import HybridParallelTrainStep

    from paddle_tpu.ops.pallas_attention import on_tpu
    cfg = GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                    max_position_embeddings=max(1024, seq),
                    amp_dtype="bfloat16",
                    attn_impl="flash" if on_tpu() else "xla")
    step = HybridParallelTrainStep(cfg, dp=dp, pp=pp, tp=tp,
                                   n_microbatches=2 * pp if pp > 1 else None,
                                   grad_clip_norm=1.0)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    for _ in range(warmup):
        loss = step(ids)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids)
    dt = _finish_timed(t0, loss)
    toks = batch * seq * steps / dt
    peak, kind = chip_peak_flops()
    mfu = gpt_train_flops_per_step(cfg, batch, seq) * steps / dt / peak
    return {"metric": "gpt_350m_train_tokens_per_sec_per_chip",
            "value": round(toks, 1), "unit": "tokens/sec/chip",
            "mfu": round(mfu, 4), "batch": batch, "seq": seq,
            "dp": dp, "pp": pp, "tp": tp, "device_kind": str(kind)}


def bench_gpt_1p3b(batch=1, seq=1024, steps=4, warmup=1):
    """GPT-3 XL (1.3B params) with per-block remat, ONE chip (the round-4
    verdict's missing entry). Memory math first: AdamW keeps f32 params +
    m1 + m2 = 3 x 5.3 GB = 16.0 GB for 1.33B params before grads or
    activations — against v5e's 16 GB HBM this cannot fit even at
    batch 1 with remat, so the expected record is the documented-
    impossible entry with the allocator's own numbers. The 2-way pp or tp
    split that WOULD fit (8 GB of optimizer state per chip) needs 2
    physical chips; this environment exposes one (dryrun_multichip
    validates those meshes on virtual devices instead)."""
    import jax
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.parallel.hybrid import HybridParallelTrainStep
    from paddle_tpu.ops.pallas_attention import on_tpu

    cfg = GPTConfig.gpt3_1p3b(amp_dtype="bfloat16",
                              attn_impl="flash" if on_tpu() else "xla",
                              remat=True)
    D, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    n_params = V * D + cfg.max_position_embeddings * D + 2 * D \
        + L * (12 * D * D + 13 * D)
    base = {"metric": "gpt_1p3b_train_tokens_per_sec_per_chip",
            "unit": "tokens/sec/chip", "batch": batch, "seq": seq,
            "n_params": n_params, "remat": True}
    # memory precheck BEFORE paying the (large, doomed) compile: f32
    # params + AdamW m1/m2 + bf16 grads; v5e HBM = 16 GiB. A config
    # that passes the precheck and then fails raises, like any other.
    hbm_gib = float(os.environ.get("TPU_HBM_GIB", 16))
    need_gib = n_params * (3 * 4 + 2) / 2**30
    if need_gib > hbm_gib * 0.95:
        base.update(
            value=None,
            impossible_on_1_chip=(
                f"f32 AdamW master+moments + bf16 grads = {need_gib:.1f} "
                f"GiB vs {hbm_gib:.0f} GiB HBM; fits under pp=2 or tp=2 "
                "(needs 2 physical chips, not available here; "
                "dp2xpp2xtp2 compiles+runs in dryrun_multichip)"))
        return base
    step = HybridParallelTrainStep(cfg, dp=1, pp=1, tp=1,
                                   grad_clip_norm=1.0)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    for _ in range(warmup):
        loss = step(ids)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids)
    dt = _finish_timed(t0, loss)
    peak, kind = chip_peak_flops()
    mfu = gpt_train_flops_per_step(cfg, batch, seq) * steps / dt / peak
    base.update(value=round(batch * seq * steps / dt, 1),
                mfu=round(mfu, 4), device_kind=str(kind))
    return base


def resnet_train_flops_per_step(batch):
    """ResNet-50 224x224 forward = 8.18 GFLOP/image (2 x 4.09 GMACs,
    derived per-layer below); train step = fwd + dX + dW = 3x forward.

    CORRECTION (r05): rounds 3-4 used 4.1e9 here, mislabelled "2x MACs" —
    4.09G is ResNet-50's MAC count (the number torchvision quotes as
    "GFLOPS"), so every prior-round resnet MFU was UNDERSTATED 2x. The
    chip peak (197 TF/s bf16) counts an FMA as 2 flops; the model count
    must too, and the BERT/GPT entries already do (2*params*tokens).
    """
    blocks = [(3, 64), (4, 128), (6, 256), (3, 512)]
    f = 2 * 7 * 7 * 3 * 64 * 112 * 112          # stem
    cin, hw = 64, 56 * 56
    for si, (n, cmid) in enumerate(blocks):
        cout = cmid * 4
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            hw2 = hw // (stride * stride)
            f += 2 * cin * cmid * hw            # 1x1 reduce
            f += 2 * 9 * cmid * cmid * hw2      # 3x3
            f += 2 * cmid * cout * hw2          # 1x1 expand
            if bi == 0:
                f += 2 * cin * cout * hw2       # downsample shortcut
            cin, hw = cout, hw2
    f += 2 * 2048 * 1000                        # fc
    return 3 * f * batch


def bench_resnet50(batch=256, steps=12, warmup=3):
    """ResNet-50 ImageNet train step (BASELINE config 2), bf16 autocast.

    NHWC trunk (channel-minor, the native TPU conv layout; one transpose
    at the stem), bf16 BN IO with f32 statistics (custom-VJP batch_norm).

    Measured profile (r05, v5e, xplane device trace of the compiled step,
    scripts/resnet_decompose.py): device-busy 100.1 ms at b256 =
    **conv-containing fusions 79%** (XLA fuses the BN statistics
    reductions INTO the convolutions — the `convert_reduce_fusion`s that
    dominate the timeline each contain a convolution), BN-normalize/relu/
    residual elementwise passes ~15%, copies ~4%, maxpool-bwd ~2%. The
    convolutions sustain ~43% MXU efficiency — the v5e conv lowering's
    rate at these shapes (K=64..576 contractions, stride-2 layers) — so
    the step is CONV-COMPUTE-bound, not HBM-bound. This retracts r04's
    46.7 GB/step bandwidth-floor profile: that estimate double-counted
    logical passes XLA had already fused away (a 46.7 GB step at the
    measured 100 ms would imply 467 GB/s, 57% of peak, not 99%). The
    remaining headroom (elementwise+copies ~19%) bounds any further BN
    fusion win; a hand-written conv would have to beat XLA's own conv to
    move the 79%.

    (r05 was measured through a PJRT transport that no longer exists;
    not re-measured on a directly attached chip.)"""
    import jax
    from paddle_tpu.jit.functional import make_train_step
    from paddle_tpu.vision.models import resnet50
    import paddle_tpu.nn.functional as F

    model = resnet50(num_classes=1000, data_format="NHWC")
    model.train()

    def loss_fn(m, img, label):
        logits = m(img)
        return F.cross_entropy(logits, label)

    step = make_train_step(model, loss_fn, optimizer="momentum", lr=0.1,
                           amp_level="O1")
    rng = np.random.RandomState(0)
    import jax.numpy as jnp
    # device-resident batch: measures the train step, not the 38 MB/step
    # host upload (a real input pipeline prefetches to device)
    img = jnp.asarray(rng.randn(batch, 3, 224, 224).astype("float32"))
    lab = jnp.asarray(rng.randint(0, 1000, (batch, 1)).astype("int64"))
    jax.block_until_ready([img, lab])
    for _ in range(warmup):
        loss = step(img, lab)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(img, lab)
    dt = _finish_timed(t0, loss)
    peak, kind = chip_peak_flops()
    mfu = resnet_train_flops_per_step(batch) * steps / dt / peak
    return {"metric": "resnet50_train_images_per_sec",
            "value": round(batch * steps / dt, 2), "unit": "images/sec",
            "mfu": round(mfu, 4), "batch": batch, "device_kind": str(kind)}


def bench_widedeep_ps_tcp(steps=10, warmup=2, batch=4096, workers=2,
                          servers=2, mode=None):
    """wide&deep through the REAL PS transport (r04 weak #8): `servers`
    PSServer processes + `workers` DownpourWorker processes over
    localhost TCP, reporting aggregate ex/s and the measured pull/push
    wire bytes (PSClient byte counters). mode="boxps" runs the same job
    through the BoxPS-style hot-row cache (boxps_cache.py) — the
    follow-on perf lever of r04 missing #2."""
    import json
    import os as _os
    import socket as _socket
    import subprocess
    import sys as _sys

    def free_port():
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    script = _os.path.join(_os.path.dirname(_os.path.abspath(__file__)),
                           "scripts", "widedeep_ps_bench.py")
    eps = [f"127.0.0.1:{free_port()}" for _ in range(servers)]
    env0 = dict(_os.environ)
    env0["PYTHONPATH"] = _os.path.dirname(_os.path.abspath(__file__))
    env0["PS_ENDPOINTS"] = ",".join(eps)
    procs = []
    for ep in eps:
        env = dict(env0)
        env.update(ROLE="server", MY_ENDPOINT=ep)
        procs.append(subprocess.Popen(
            [_sys.executable, script], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
    wps = []
    for wid in range(workers):
        env = dict(env0)
        env.update(ROLE="worker", WORKER_ID=str(wid), STEPS=str(steps),
                   WARMUP=str(warmup), BATCH=str(batch))
        if mode:
            env["MODE"] = mode
        wps.append(subprocess.Popen(
            [_sys.executable, script], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    outs = []
    try:
        for pr in wps:
            out, err = pr.communicate(timeout=420)
            if pr.returncode != 0:
                raise RuntimeError(
                    f"widedeep PS worker exited {pr.returncode}: "
                    f"{err[-400:]}")
            outs.append(json.loads(out.strip().splitlines()[-1]))
    finally:
        for pr in procs + wps:   # reap workers too on error/timeout
            pr.terminate()
            try:
                pr.wait(timeout=10)
            except Exception:
                pr.kill()
    rec = {"transport": "tcp_ps" + (f"+{mode}" if mode else ""),
           "servers": servers, "workers": workers, "batch": batch,
           "examples_per_sec": round(sum(
               o["examples_per_sec"] for o in outs), 1),
           "wire_mb_out_per_worker_step": round(np.mean(
               [o["push_pull_mb_out"] / o["steps"] for o in outs]), 2),
           "wire_mb_in_per_worker_step": round(np.mean(
               [o["push_pull_mb_in"] / o["steps"] for o in outs]), 2)}
    return rec


def bench_widedeep(batch=4096, steps=20, warmup=3):
    """wide&deep CTR train step (BASELINE config 4), two paths:

    headline `value` — the TPU-native mesh path (WideDeepTrainStep:
    embedding tables sharded over the device mesh, XLA collective
    lookup; on one chip dp=mp=1 everything is in-HBM compute, no PS).

    `ps_tcp` / `ps_tcp_boxps` — the CTR-production path over the REAL
    transport: PS shards + Downpour workers on TCP (ex/s + measured
    wire bytes), and the same through the BoxPS-style hot-row cache
    (aggregated deltas every flush interval -> ~flush_every x less wire
    traffic)."""
    from paddle_tpu.models.wide_deep import WideDeepConfig, WideDeepTrainStep

    cfg = WideDeepConfig()  # 1M hashed vocab, 26 slots, 13 dense
    step = WideDeepTrainStep(cfg, dp=1, mp=1)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, cfg.num_slots))
    dense = rng.randn(batch, cfg.dense_dim).astype(np.float32)
    label = (ids[:, 0] % 2).astype(np.float32)[:, None]
    for _ in range(warmup):
        loss = step(ids, dense, label)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids, dense, label)
    dt = _finish_timed(t0, loss)
    rec = {"metric": "widedeep_train_examples_per_sec",
           "value": round(batch * steps / dt, 1), "unit": "examples/sec",
           "transport": "mesh (in-HBM, XLA collective lookup)",
           "batch": batch, "vocab": cfg.vocab_size,
           "slots": cfg.num_slots}
    mode = os.environ.get("BENCH_WIDEDEEP_PS", "1")
    if mode == "min":
        # reduced budget: one small run through the real transport so the
        # record always carries the TCP numbers (r04 weak #8)
        rec["ps_tcp"] = bench_widedeep_ps_tcp(steps=4, warmup=1)
    elif mode != "0":
        rec["ps_tcp"] = bench_widedeep_ps_tcp(steps=8, warmup=1)
        rec["ps_tcp_boxps"] = bench_widedeep_ps_tcp(steps=8, warmup=1,
                                                    mode="boxps")
    return rec


def bench_serving(num_requests=48, num_slots=8, hidden=512, layers=8,
                  heads=8, max_new=64, seed=0):
    """Offline serving throughput through paddle_tpu.serving: a fixed
    request mix (prompt lens 16..192, outputs 16..max_new) continuously
    batched over the paged KV cache. Reports end-to-end tokens/sec
    (prefill+decode, compile EXCLUDED via a warmup mix that touches
    every bucket), p50/p99 request latency at that offered load, page
    occupancy and the compile-per-bucket counters."""
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving import Engine, GPTDecodeModel

    cfg = GPTConfig(hidden_size=hidden, num_layers=layers, num_heads=heads,
                    max_position_embeddings=512, vocab_size=8192)
    model = GPTDecodeModel(cfg, seed=seed)
    eng = Engine(model, num_slots=num_slots, num_pages=256, page_size=16,
                 max_seq_len=448)
    rng = np.random.RandomState(seed)

    def mix(n):
        out = []
        for _ in range(n):
            plen = int(rng.choice([16, 31, 64, 100, 128, 192]))
            mnt = int(rng.choice([16, 32, max_new]))
            out.append((rng.randint(0, cfg.vocab_size, (plen,)), mnt))
        return out

    # warmup: one prompt per length choice so EVERY prefill bucket (and
    # the decode program) compiles before the timed window — a random
    # warmup mix can miss a bucket and charge its XLA compile to the
    # measurement
    for plen in (16, 31, 64, 100, 128, 192):
        eng.submit(rng.randint(0, cfg.vocab_size, (plen,)), 16)
    eng.run_until_idle()
    reqs = [eng.submit(p, m) for p, m in mix(num_requests)]
    t0 = time.perf_counter()
    eng.run_until_idle()
    dt = time.perf_counter() - t0
    ntok = sum(len(r.generated) for r in reqs)
    lats = sorted(r.latency() for r in reqs)
    st = eng.stats()
    return {"metric": "serving_decode_tokens_per_sec",
            "value": round(ntok / dt, 1), "unit": "tokens/sec",
            "requests": num_requests, "slots": num_slots,
            "model": f"gpt-h{hidden}-l{layers}",
            "p50_ms": round(lats[len(lats) // 2] * 1e3, 1),
            "p99_ms": round(lats[min(len(lats) - 1,
                                     int(0.99 * len(lats)))] * 1e3, 1),
            "compiles": st["compiles"],
            "preemptions": st["preemptions"],
            "pool_pages": st["pool"]["num_pages"]}


def _slo_engine(hidden=256, layers=4, heads=4, num_slots=8, seed=0):
    """Small serving engine, every prefill bucket + the decode program
    pre-compiled (compiles must never land inside an SLO window)."""
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving import Engine, GPTDecodeModel

    cfg = GPTConfig(hidden_size=hidden, num_layers=layers,
                    num_heads=heads, max_position_embeddings=256,
                    vocab_size=4096)
    eng = Engine(GPTDecodeModel(cfg, seed=seed), num_slots=num_slots,
                 num_pages=128, page_size=8, max_seq_len=96)
    for plen in (4, 8, 16, 32):
        eng.submit(np.full((plen,), 1, np.int32), 2)
    eng.run_until_idle()
    return eng


def _slo_traffic(duration, rate, seed):
    from paddle_tpu.serving import TrafficConfig
    return TrafficConfig(
        rate=rate, duration=duration, arrival="diurnal",
        diurnal_period=duration, seed=seed,
        prompt_lens={4: 3, 8: 3, 16: 2, 32: 1},
        output_lens={4: 3, 8: 2, 16: 1},
        tenants={"web": 3, "batch": 1}, tiers={0: 1, 1: 2, 2: 1},
        deadlines={0: 10.0, 1: 20.0, 2: None}, vocab_size=512)


def bench_transport(concurrencies=(1, 4, 8), probes=30, seed=0):
    """BENCH_CONFIG=transport (docs/PS_WIRE_PROTOCOL.md mux framing):
    the multiplexed transport's reason to exist, measured. ONE shared
    RpcClient carries N long streamed generates while short streamed
    probes measure wire TTFT (time to FIRST frame — queueing included);
    the sweep repeats with mux=False (exclusive one-call-per-channel
    legacy mode), which reproduces the PR-9 head-of-line symptom.
    Also reports the zero-copy pull path: transport bytes-copied per
    payload byte, mux vs legacy."""
    import socketserver
    import threading

    from paddle_tpu.distributed.fleet.runtime import rpc

    class _Srv(socketserver.ThreadingTCPServer):
        allow_reuse_address = True
        daemon_threads = True

        def __init__(self):
            state = rpc.RpcServerState(
                read_ops=frozenset({"ping", "pull", "gen"}))

            def dispatch(req):
                op = req["op"]
                if op == "ping":
                    return "pong"
                if op == "pull":
                    n, d = int(req["n"]), int(req["d"])
                    return {"rows": np.zeros((n, d), np.float32)}

                def g():
                    for i in range(int(req["n"])):
                        time.sleep(float(req.get("gap", 0.02)))
                        yield {"i": i}
                    return {"done": True}
                return g()

            class H(socketserver.BaseRequestHandler):
                def handle(self):
                    rpc.serve_connection(self.request, dispatch, state)

            super().__init__(("127.0.0.1", 0), H)
            self.endpoint = f"127.0.0.1:{self.server_address[1]}"
            threading.Thread(target=self.serve_forever,
                             daemon=True).start()

    def _copied(path):
        for vals, child in rpc._MUX_BYTES_COPIED._series():
            if vals == (path,):
                return child.value
        return 0.0

    srv = _Srv()
    modes = {}
    for mode, mux in (("mux", True), ("legacy", False)):
        cli = rpc.RpcClient(srv.endpoint, mux=mux, pool_size=2,
                            timeout=30.0, deadline=60.0)
        sweep = {}
        for conc in concurrencies:
            stop = threading.Event()

            def pump():
                # a continuous long stream occupying the shared client
                while not stop.is_set():
                    gen = cli.call_stream(
                        {"op": "gen", "n": 10, "gap": 0.03},
                        timeout=30, stream_timeout=30)
                    try:
                        for _ in gen:
                            if stop.is_set():
                                break
                    finally:
                        gen.close()

            threads = [threading.Thread(target=pump, daemon=True)
                       for _ in range(conc)]
            for th in threads:
                th.start()
            time.sleep(0.2)      # streams in flight before probing
            lats = []
            for _ in range(probes):
                t0 = time.perf_counter()
                gen = cli.call_stream({"op": "gen", "n": 1, "gap": 0.0},
                                      timeout=30, stream_timeout=30)
                next(gen)        # FIRST frame = wire TTFT
                lats.append(time.perf_counter() - t0)
                for _ in gen:    # drain the final reply
                    pass
            stop.set()
            for th in threads:
                th.join(timeout=30)
            lats.sort()
            sweep[conc] = {
                "ttft_p50_ms": round(lats[len(lats) // 2] * 1e3, 2),
                "ttft_p99_ms": round(
                    lats[min(len(lats) - 1,
                             int(0.99 * len(lats)))] * 1e3, 2)}
        # zero-copy pull path: bytes memcpy'd per payload byte
        n, d, reps = 512, 64, 8
        path = "mux" if mux else "legacy"
        c0 = _copied(path)
        for _ in range(reps):
            cli.call({"op": "pull", "n": n, "d": d}, timeout=30)
        copied_per_byte = (_copied(path) - c0) / (reps * n * d * 4)
        cli.close()
        modes[mode] = {"ttft": sweep,
                       "pull_bytes_copied_per_payload_byte":
                       round(copied_per_byte, 4)}
    srv.shutdown()
    srv.server_close()
    top = max(concurrencies)
    mux_p99 = modes["mux"]["ttft"][top]["ttft_p99_ms"]
    legacy_p99 = modes["legacy"]["ttft"][top]["ttft_p99_ms"]
    return {"metric": "transport_wire_ttft_p99_ms",
            "value": mux_p99, "unit": "ms",
            "concurrency": top, "probes": probes,
            "p99_speedup_vs_legacy": round(legacy_p99 / mux_p99, 2)
            if mux_p99 else None,
            "modes": modes}


def bench_slo(duration=6.0, rate=30.0, seed=7):
    """Production traffic replay (docs/SERVING.md harness): a seeded
    open-loop diurnal mix of prompt/output lengths, tenants and
    priority tiers drives the serving engine; reports SLO attainment
    (met/offered), goodput (tokens from requests that met their
    deadline) and p99 TTFT / inter-token latency at that offered
    load."""
    from paddle_tpu.serving import LoadGenerator, slo_report

    eng = _slo_engine()
    gen = LoadGenerator(_slo_traffic(duration, rate, seed),
                        name="bench_slo")
    with eng:
        res = gen.run_engine(eng)
        finished = res.wait(300)
    rep = slo_report(res)
    st = eng.stats()
    return {"metric": "serving_slo_attainment",
            "value": rep["attainment"], "unit": "met/offered",
            "offered": rep["offered"],
            "offered_rate_rps": rate, "duration_s": duration,
            "goodput_tokens_per_sec": rep["goodput_tokens_per_sec"],
            "ttft_ms_p50": rep["ttft_ms_p50"],
            "ttft_ms_p99": rep["ttft_ms_p99"],
            "itl_ms_p99": rep["itl_ms_p99"],
            "by_status": rep["by_status"],
            "shed": st["shed"], "preemptions": st["preemptions"],
            "expired_in_queue": st["expired_in_queue"],
            "all_finished": bool(finished)}


def bench_prefix(num_requests=24, pool_prompts=2, prefix_len=64,
                 suffix_len=8, max_new=8, num_slots=8, seed=0):
    """BENCH_CONFIG=prefix (docs/SERVING.md shared-prefix section):
    the radix prefix cache A/B'd on the workload it exists for — every
    request is one of `pool_prompts` long system prompts plus a unique
    user suffix. The SAME request mix runs cache-off then cache-on
    (both warmed so XLA compiles never land in a timed window) and the
    record reports goodput tokens/s, p99 TTFT, the prefill-compute
    reduction (prefill cost is token-proportional at one model config,
    so saved prefill tokens ARE saved prefill FLOPs), and the measured
    effective-KV-capacity multiplier: logical KV pages the live batch
    addresses per physical page allocated (1.0 unshared; the
    acceptance bar is >= 2x on this mix)."""
    import threading

    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving import Engine, GPTDecodeModel

    cfg = GPTConfig(hidden_size=256, num_layers=4, num_heads=4,
                    max_position_embeddings=256, vocab_size=4096)
    model = GPTDecodeModel(cfg, seed=seed)
    rng = np.random.RandomState(seed)
    pool = [rng.randint(0, cfg.vocab_size,
                        (prefix_len,)).astype(np.int32)
            for _ in range(pool_prompts)]
    prompts = []
    for i in range(num_requests):
        sfx = rng.randint(0, cfg.vocab_size,
                          (suffix_len,)).astype(np.int32)
        prompts.append(np.concatenate([pool[i % pool_prompts], sfx]))
    total_prompt_tokens = sum(int(p.size) for p in prompts)

    def run(cache_pages):
        eng = Engine(model, num_slots=num_slots, num_pages=128,
                     page_size=8, max_seq_len=96,
                     prefix_cache_pages=cache_pages)
        peak = {"mult": 1.0, "used": 0}
        stop = threading.Event()

        def sampler():
            # effective KV capacity, measured live: logical pages the
            # active batch addresses vs DISTINCT physical pages backing
            # them (shared pages counted once). Read-only racy peek at
            # the slot array — a torn read mid-admission just skips one
            # sample.
            while not stop.is_set():
                try:
                    live = [r for r in eng.scheduler.slots
                            if r is not None]
                    logical = sum(len(r.table.pages) for r in live)
                    phys = len({p for r in live for p in r.table.pages})
                    if phys and len(live) >= num_slots // 2:
                        peak["mult"] = max(peak["mult"],
                                           logical / phys)
                    peak["used"] = max(peak["used"],
                                       eng.pool.stats()["used_pages"])
                except Exception:
                    pass
                time.sleep(0.002)
        with eng:
            # warmup compiles every bucket this mix touches and leaves
            # the cache hot, so the timed window measures steady-state
            # serving. The suffixes must DIFFER: a repeat of the same
            # prompt is a full-prompt match (bootstrap, no prefill at
            # all), and the prefill_tail bucket would then pay its XLA
            # compile inside the timed window
            for pfx in pool:
                for _ in range(2):
                    w = np.concatenate([pfx, rng.randint(
                        0, cfg.vocab_size,
                        (suffix_len,)).astype(np.int32)])
                    eng.generate(w, 2)
            pre = eng.stats()["prefix_cache"] or {}
            th = threading.Thread(target=sampler, daemon=True)
            th.start()
            t0 = time.perf_counter()
            reqs = [eng.submit(p, max_new) for p in prompts]
            eng.run_until_idle()
            dt = time.perf_counter() - t0
            stop.set()
            th.join(timeout=5)
            post = eng.stats()["prefix_cache"] or {}
            st = eng.stats()
        ntok = sum(len(r.generated) for r in reqs)
        ttfts = sorted(r.ttft() for r in reqs if r.ttft() is not None)
        saved = post.get("tokens_saved", 0) - pre.get("tokens_saved", 0)
        return {
            "goodput_tokens_per_sec": round(ntok / dt, 1),
            "ttft_ms_p50": round(ttfts[len(ttfts) // 2] * 1e3, 2),
            "ttft_ms_p99": round(ttfts[min(len(ttfts) - 1,
                                           int(0.99 * len(ttfts)))]
                                 * 1e3, 2),
            "prefill_tokens_saved": int(saved),
            "prefill_flops_reduction": round(
                saved / total_prompt_tokens, 4),
            "kv_capacity_multiplier": round(peak["mult"], 2),
            "peak_used_pages": peak["used"],
            "compiles": st["compiles"],
            "cache": post or None,
        }

    off = run(0)
    on = run(64)
    off_p99 = off["ttft_ms_p99"]
    return {"metric": "prefix_cache_kv_capacity_multiplier",
            "value": on["kv_capacity_multiplier"], "unit": "x logical/physical",
            "requests": num_requests, "pool_prompts": pool_prompts,
            "prefix_len": prefix_len, "suffix_len": suffix_len,
            "max_new": max_new,
            "goodput_speedup": round(
                on["goodput_tokens_per_sec"]
                / max(1e-9, off["goodput_tokens_per_sec"]), 2),
            "ttft_p99_speedup": round(
                off_p99 / max(1e-9, on["ttft_ms_p99"]), 2),
            "prefill_flops_reduction": on["prefill_flops_reduction"],
            "cache_on": on, "cache_off": off}


def bench_chaos(duration=8.0, rate=25.0, seed=7, stall_s=0.8,
                wd_deadline=0.5):
    """Chaos drill as a bench (docs/DEBUGGING.md recipe): the SAME
    seeded traffic replayed twice — fault-free baseline, then with the
    serving_decode stall knob wedging the step thread mid-run. Reports
    watchdog detection seconds, recovery seconds (fault armed ->
    progress again), and the post-recovery SLO attainment delta vs the
    baseline's identical traffic slice."""
    import threading

    from paddle_tpu.distributed.fleet.runtime import (
        fault_injection as fi)
    from paddle_tpu.observability.watchdog import WATCHDOG
    from paddle_tpu.serving import LoadGenerator, slo_report

    mk_gen = lambda name: LoadGenerator(
        _slo_traffic(duration, rate, seed), name=name)
    eng_a = _slo_engine()
    with eng_a:
        res_a = mk_gen("chaos_base").run_engine(eng_a)
        res_a.wait(300)
    base = slo_report(res_a)

    # the engine's watchdog token captures its deadline at registration
    prev = os.environ.get("PADDLE_TPU_WATCHDOG_DEADLINE")
    os.environ["PADDLE_TPU_WATCHDOG_DEADLINE"] = str(wd_deadline)
    try:
        eng_b = _slo_engine()
    finally:
        if prev is None:
            os.environ.pop("PADDLE_TPU_WATCHDOG_DEADLINE", None)
        else:
            os.environ["PADDLE_TPU_WATCHDOG_DEADLINE"] = prev
    token = f"serving.engine.{eng_b.engine_id}"
    box = []
    detect_s = recovery_s = None
    with eng_b:
        runner = threading.Thread(
            target=lambda: box.append(
                mk_gen("chaos_fault").run_engine(eng_b)), daemon=True)
        runner.start()
        time.sleep(min(1.0, duration / 4))          # traffic flowing
        t_fault = time.monotonic()
        fi.reset_injector(fi.FaultInjector(
            stall=stall_s, stall_point="serving_decode"))
        while detect_s is None \
                and time.monotonic() - t_fault < 30:
            # level-triggered stalled(), not check_once()'s fire
            # event: an auto-started watchdog poll thread
            # (PADDLE_TPU_WATCHDOG=1) would consume the edge
            WATCHDOG.check_once()
            if token in WATCHDOG.stalled():
                detect_s = time.monotonic() - t_fault
            time.sleep(0.05)
        fi.reset_injector(fi.FaultInjector())
        t_cleared = time.monotonic()
        while recovery_s is None \
                and time.monotonic() - t_cleared < 30:
            WATCHDOG.check_once()
            if token not in WATCHDOG.stalled():
                recovery_s = time.monotonic() - t_fault
            time.sleep(0.05)
        runner.join(timeout=300)
        res_b = box[0] if box else None
        if res_b is not None:
            res_b.wait(300)
    faulted = slo_report(res_b) if res_b is not None else None
    # post-recovery window: identical arrivals in both runs
    post = post_base = None
    if res_b is not None and recovery_s is not None:
        rec_off = (t_cleared + stall_s) - res_b.started_at
        if rec_off < duration - 0.5:
            post = slo_report(res_b, window=(rec_off, float("inf")),
                              gen="chaos_post")
            post_base = slo_report(res_a,
                                   window=(rec_off, float("inf")),
                                   gen="chaos_post_base")
    delta = None
    if post is not None and post_base is not None \
            and post_base["attainment"] is not None:
        delta = round(post_base["attainment"] - post["attainment"], 4)
    return {"metric": "serving_chaos_slo_delta", "value": delta,
            "unit": "attainment_drop_post_recovery",
            "fault": f"stall@serving_decode {stall_s}s",
            "detect_s": None if detect_s is None
            else round(detect_s, 3),
            "recovery_s": None if recovery_s is None
            else round(recovery_s, 3),
            "baseline_attainment": base["attainment"],
            "faulted_attainment": None if faulted is None
            else faulted["attainment"],
            "post_recovery_attainment": None if post is None
            else post["attainment"],
            "post_recovery_baseline": None if post_base is None
            else post_base["attainment"],
            "baseline_goodput_tokens_per_sec":
                base["goodput_tokens_per_sec"],
            "faulted_goodput_tokens_per_sec": None if faulted is None
            else faulted["goodput_tokens_per_sec"],
            "offered_rate_rps": rate, "duration_s": duration}


def bench_router(duration=8.0, rate=25.0, seed=7, kill_at=2.5):
    """BENCH_CONFIG=router (docs/SERVING.md replicated serving): the
    SAME seeded traffic replayed twice over the WIRE through the
    fault-tolerant router fronting two replicas — fault-free baseline,
    then with one replica killed mid-run (listener + live connections
    severed, decode loop halted). Reports failover detect seconds
    (kill -> replica out of rotation), recovery seconds (kill ->
    respawned-from-checkpoint replica healthy again), post-recovery
    attainment delta vs the baseline's identical traffic slice, and
    wire TTFT (streaming generate), mirroring BENCH_CONFIG=chaos."""
    import tempfile
    import threading

    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.observability import REGISTRY
    from paddle_tpu.serving import (GPTDecodeModel, InProcessReplica,
                                    LoadGenerator, Router,
                                    ServingClient, slo_report)

    root = os.path.join(tempfile.mkdtemp(prefix="bench_router_"), "gpt")
    cfg = GPTConfig(hidden_size=256, num_layers=4, num_heads=4,
                    max_position_embeddings=256, vocab_size=4096)
    GPTDecodeModel(cfg, seed=0).save_checkpoint(root)
    engine_kw = dict(num_slots=8, num_pages=128, page_size=8,
                     max_seq_len=96)

    def fleet():
        reps = []
        for i in range(2):
            r = InProcessReplica(root, name=f"rep{i}",
                                 engine_kw=engine_kw)
            r.start()
            for plen in (4, 8, 16, 32):   # compile outside the window
                r.engine.submit(np.full((plen,), 1, np.int32), 2)
            r.engine.run_until_idle()
            reps.append(r)
        router = Router("127.0.0.1:0",
                        replicas=[r.spec() for r in reps],
                        ping_interval=0.2, ping_timeout=1.0,
                        suspect_after=1, dead_after=2, token_stall=5.0,
                        respawn_cooldown=0.5)
        return router, reps

    mk_gen = lambda name: LoadGenerator(
        _slo_traffic(duration, rate, seed), name=name)

    router_a, reps_a = fleet()
    with router_a:
        cli = ServingClient(router_a.endpoint)
        res_a = mk_gen("router_base").run_client(cli, timeout=120)
        res_a.wait(300)
        cli.close()
    for r in reps_a:
        r.stop()
    base = slo_report(res_a)

    router_b, reps_b = fleet()
    detect_s = recovery_s = None
    t_kill = None
    with router_b:
        cli = ServingClient(router_b.endpoint)
        box = []
        runner = threading.Thread(
            target=lambda: box.append(
                mk_gen("router_fault").run_client(cli, timeout=120)),
            daemon=True)
        runner.start()
        time.sleep(kill_at)
        t_kill = time.monotonic()
        reps_b[1].kill()
        while time.monotonic() - t_kill < 60 \
                and (detect_s is None or recovery_s is None):
            state = router_b.stats()["replicas"]["rep1"]["state"]
            if detect_s is None and state != "healthy":
                detect_s = time.monotonic() - t_kill
            if detect_s is not None and state == "healthy":
                recovery_s = time.monotonic() - t_kill
            time.sleep(0.05)
        runner.join(300)
        res_b = box[0] if box else None
        if res_b is not None:
            res_b.wait(300)
        cli.close()
    for r in reps_b:
        r.stop()
    faulted = slo_report(res_b) if res_b is not None else None
    fo = REGISTRY.get("paddle_tpu_router_failovers_total")
    failovers = sum(s.value for lv, s in fo._series()
                    if lv[0] == router_b.router_id)
    post = post_base = None
    if res_b is not None and recovery_s is not None:
        rec_off = (t_kill + recovery_s) - res_b.started_at
        if rec_off < duration - 0.5:
            post = slo_report(res_b, window=(rec_off, float("inf")),
                              gen="router_post")
            post_base = slo_report(res_a,
                                   window=(rec_off, float("inf")),
                                   gen="router_post_base")
    delta = None
    if post is not None and post_base is not None \
            and post_base["attainment"] is not None:
        delta = round(post_base["attainment"] - post["attainment"], 4)
    return {"metric": "serving_router_slo_delta", "value": delta,
            "unit": "attainment_drop_post_recovery",
            "fault": f"replica kill @ {kill_at}s of {duration}s",
            "detect_s": None if detect_s is None
            else round(detect_s, 3),
            "recovery_s": None if recovery_s is None
            else round(recovery_s, 3),
            "failovers": int(failovers),
            "baseline_attainment": base["attainment"],
            "faulted_attainment": None if faulted is None
            else faulted["attainment"],
            "post_recovery_attainment": None if post is None
            else post["attainment"],
            "post_recovery_baseline": None if post_base is None
            else post_base["attainment"],
            "wire_ttft_ms_p50": base["ttft_ms_p50"],
            "wire_ttft_ms_p99": base["ttft_ms_p99"],
            "wire_itl_ms_p99": base["itl_ms_p99"],
            "offered_rate_rps": rate, "duration_s": duration}


def bench_online(staleness_rounds=5, cadence_steps=3, stream_tokens=64,
                 dedup_rows=512, dedup_dim=256, seed=0):
    """BENCH_CONFIG=online (docs/ONLINE_LEARNING.md): the continuous
    publish pipeline end to end. Three numbers: (1) publish staleness
    — PS training pushes into a publish-wired PSServer; time from the
    cadence-triggering commit to the new version answering on the
    pub_latest wire (manifest + registry both durable, i.e. servable);
    (2) swap pause — a streamed wire generate spans a staggered
    2-replica rollout; max inter-token gap inside the flip window vs
    the same stream's gap outside it (the adopt happens under the
    engine step lock, so the pause should be ~one weight load, not a
    drain); (3) cross-version chunk dedup — a one-row-mutated
    embedding republished through the content-addressed store."""
    import tempfile
    import threading

    from paddle_tpu.distributed.fleet.runtime.parameter_server_runtime \
        import PSClient, PSServer
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.publish import Publisher, RegistryClient
    from paddle_tpu.serving import (GPTDecodeModel, InProcessReplica,
                                    Router, ServingClient)

    base = tempfile.mkdtemp(prefix="bench_online_")

    # -- (1) train-push -> servable staleness over the PS wire --------
    ps_pub = os.path.join(base, "ps_pub")
    srv = PSServer("127.0.0.1:0", publish_dir=ps_pub,
                   publish_every_steps=cadence_steps)
    srv.serve_in_thread()
    cl = PSClient([srv.endpoint])
    watcher = RegistryClient(srv.endpoint)
    rng = np.random.RandomState(seed)
    staleness = []
    try:
        for round_i in range(staleness_rounds):
            for j in range(cadence_steps):
                ids = np.arange(j * 8, j * 8 + 8)
                t0 = time.perf_counter()
                cl.push("emb", 64, ids, rng.randn(8, 64))
            want = round_i + 1
            while watcher.latest()["latest"] < want:
                time.sleep(0.002)
            staleness.append(time.perf_counter() - t0)
    finally:
        watcher.close()
        cl.close()
        srv.shutdown()
        srv.server_close()
    staleness.sort()
    stale_p50 = staleness[len(staleness) // 2]

    # -- (2) swap pause on the wire -----------------------------------
    ckpt = os.path.join(base, "gpt")
    pub = os.path.join(base, "pub")
    cfg = GPTConfig(hidden_size=256, num_layers=4, num_heads=4,
                    max_position_embeddings=256, vocab_size=4096)
    GPTDecodeModel(cfg, seed=seed).save_checkpoint(ckpt)
    engine_kw = dict(num_slots=8, num_pages=128, page_size=8,
                     max_seq_len=96)
    reps = []
    for i in range(2):
        r = InProcessReplica(ckpt, name=f"rep{i}", engine_kw=engine_kw,
                             publish_root=pub)
        r.start()
        r.engine.submit(np.full((4,), 1, np.int32), 2)
        r.engine.run_until_idle()   # compile outside the window
        reps.append(r)
    router = Router("127.0.0.1:0", replicas=[r.spec() for r in reps],
                    ping_interval=0.2, ping_timeout=1.0,
                    suspect_after=1, dead_after=2, token_stall=5.0,
                    respawn_cooldown=0.5, publish_root=pub)
    frames = []          # (arrival_monotonic, index)
    flip = {}
    with router:
        cli = ServingClient(router.endpoint)
        try:
            def publish_and_roll():
                # flip once the stream is warmed up (a few frames in)
                while len(frames) < 4:
                    time.sleep(0.005)
                Publisher(pub).publish_model(
                    GPTDecodeModel(cfg, seed=seed + 1), step=100)
                flip["t0"] = time.monotonic()
                flip["res"] = router.rollout_version()
                flip["t1"] = time.monotonic()

            flipper = threading.Thread(target=publish_and_roll,
                                       daemon=True)
            flipper.start()
            cli.generate(np.array([9, 8, 7], np.int32),
                         max_new_tokens=stream_tokens, stream=True,
                         on_token=lambda toks, idx: frames.append(
                             (time.monotonic(), idx)))
            flipper.join(120)
        finally:
            cli.close()
    for r in reps:
        r.stop()
    gaps_in, gaps_out = [], []
    for (t_prev, _i0), (t_cur, _i1) in zip(frames, frames[1:]):
        gap = t_cur - t_prev
        if "t0" in flip and flip["t0"] <= t_cur <= flip["t1"] + 0.05:
            gaps_in.append(gap)
        else:
            gaps_out.append(gap)
    pause_ms = max(gaps_in) * 1e3 if gaps_in else 0.0
    steady_ms = (sorted(gaps_out)[len(gaps_out) // 2] * 1e3
                 if gaps_out else 0.0)

    # -- (3) cross-version chunk dedup --------------------------------
    # chunk grid smaller than the table so a one-row delta shares all
    # untouched chunks with the previous version (the production-scale
    # shape; at the default chunk size this toy table is ONE chunk)
    from paddle_tpu.checkpoint import CheckpointStore
    dedup_root = os.path.join(base, "dedup")
    dpub = Publisher(dedup_root,
                     store=CheckpointStore(dedup_root,
                                           chunk_bytes=16384))
    table = np.random.RandomState(seed + 2).randn(
        dedup_rows, dedup_dim).astype(np.float32)
    dpub.publish_arrays({"r:emb": table}, step=1, kind="ps-table")
    table[dedup_rows // 2, :] += 1.0   # one-row online update
    t0 = time.perf_counter()
    rec2 = dpub.publish_arrays({"r:emb": table}, step=2,
                               kind="ps-table")
    publish_s = time.perf_counter() - t0
    return {"metric": "online_publish_staleness_s",
            "value": round(stale_p50, 4), "unit": "s_push_to_servable",
            "staleness_p50_s": round(stale_p50, 4),
            "staleness_max_s": round(staleness[-1], 4),
            "cadence_steps": cadence_steps,
            "swap_pause_ms": round(pause_ms, 2),
            "steady_itl_ms": round(steady_ms, 2),
            "rollout_wall_s": round(flip["t1"] - flip["t0"], 3)
            if "t1" in flip else None,
            "rollout_adopted": (flip.get("res") or {}).get("adopted"),
            "stream_frames": len(frames),
            "dedup_ratio": round(float(
                rec2["extra"]["dedup"]), 4),
            "dedup_republish_s": round(publish_s, 4),
            "dedup_array_mb": round(table.nbytes / 2**20, 2)}


def _bench_serving_toggle_overhead(set_enabled, metric_name, steps=200,
                                   hidden=256, layers=4, heads=4,
                                   slots=4, seed=0):
    """Shared A/B/A harness: decode step time with some telemetry
    subsystem enabled vs disabled (``set_enabled(bool)``) on the SAME
    engine (same compiled programs, same slot occupancy). A/B/A
    ordering (on, off, on) so cache warmup or clock drift cannot
    masquerade as telemetry cost."""
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving import Engine, GPTDecodeModel

    cfg = GPTConfig(hidden_size=hidden, num_layers=layers,
                    num_heads=heads, max_position_embeddings=512,
                    vocab_size=8192)
    model = GPTDecodeModel(cfg, seed=seed)
    eng = Engine(model, num_slots=slots, num_pages=128, page_size=16,
                 max_seq_len=448)
    rng = np.random.RandomState(seed)

    def timed(n_steps):
        # keep every slot busy for the whole window (big token budget),
        # then time pure decode steps
        reqs = [eng.submit(rng.randint(0, cfg.vocab_size, (16,)),
                           max_new_tokens=420) for _ in range(slots)]
        for _ in range(5):
            eng.step()  # prefills + first decodes
        t0 = time.perf_counter()
        for _ in range(n_steps):
            eng.step()
        dt = (time.perf_counter() - t0) / n_steps
        for r in reqs:
            eng.cancel(r)
        return dt

    timed(20)  # compile both programs outside the measurement
    on1 = timed(steps)
    set_enabled(False)
    try:
        off = timed(steps)
    finally:
        set_enabled(True)
    on2 = timed(steps)
    on = min(on1, on2)
    overhead = (on - off) / off * 100 if off > 0 else 0.0
    return {"metric": metric_name,
            "value": round(overhead, 2), "unit": "%",
            "enabled_step_ms": round(on * 1e3, 4),
            "disabled_step_ms": round(off * 1e3, 4),
            "enabled_runs_ms": [round(on1 * 1e3, 4),
                                round(on2 * 1e3, 4)],
            "steps": steps, "slots": slots,
            "model": f"gpt-h{hidden}-l{layers}"}


def bench_metrics_overhead(steps=200, hidden=256, layers=4, heads=4,
                           slots=4, seed=0):
    """Telemetry cost guardrail: the whole observability substrate
    (registry + tracer + flight recorder) enabled vs disabled. The
    acceptance bar is <2% overhead enabled — the counters/spans/events
    on the Engine.step hot path are host-side microseconds against a
    millisecond jitted decode."""
    from paddle_tpu import observability as obs
    return _bench_serving_toggle_overhead(
        obs.set_enabled, "serving_metrics_overhead_pct", steps=steps,
        hidden=hidden, layers=layers, heads=heads, slots=slots,
        seed=seed)


def bench_flight_overhead(steps=200, hidden=256, layers=4, heads=4,
                          slots=4, seed=0):
    """Flight-recorder cost guardrail (ISSUE 5 acceptance): ONLY the
    flight rings toggled — registry and tracer stay on both ways, so
    the delta isolates the recorder's per-event cost (ring append
    under one lock + two counter incs) on the decode hot path. Same
    <2% bar as metrics_overhead."""
    from paddle_tpu.observability import flight
    return _bench_serving_toggle_overhead(
        flight.RECORDER.set_enabled, "serving_flight_overhead_pct",
        steps=steps, hidden=hidden, layers=layers, heads=heads,
        slots=slots, seed=seed)


def bench_telemetry_overhead(steps=200, hidden=256, layers=4, heads=4,
                             slots=4, seed=0):
    """Fleet-telemetry cost guardrail (ISSUE 13 acceptance): a LIVE
    TelemetryAgent streaming spans/flight events to an in-process
    collector, toggled A/B/A on the same engine. The agent's sinks are
    bounded-queue appends and all socket IO rides the agent's own
    thread, so the decode hot path should see the same <2% bar as the
    other observability toggles."""
    from paddle_tpu.observability import agent as tel_agent
    from paddle_tpu.observability.collector import CollectorServer

    srv = CollectorServer("127.0.0.1:0").start()

    def set_enabled(on):
        if on:
            tel_agent.arm(srv.endpoint)
        else:
            tel_agent.disarm()

    set_enabled(True)
    try:
        return _bench_serving_toggle_overhead(
            set_enabled, "serving_telemetry_overhead_pct", steps=steps,
            hidden=hidden, layers=layers, heads=heads, slots=slots,
            seed=seed)
    finally:
        tel_agent.disarm()
        srv.stop()


def bench_perfwatch_overhead(steps=200, hidden=256, layers=4, heads=4,
                             slots=4, seed=0):
    """Perf-plane cost guardrail (ISSUE 14 acceptance): the step
    sampler toggled A/B/A at its DEFAULT cadence vs fully off on the
    same engine. Between samples the decode hot path only pays one
    sampler tick (an int increment + modulo); a sampled step adds a
    block_until_ready fence the following np.asarray would have paid
    anyway. Same <2% bar as the other observability toggles."""
    from paddle_tpu.observability import perf

    default_every = perf.sampling_every() or 50

    def set_enabled(on):
        perf.set_every(default_every if on else 0)

    set_enabled(True)
    try:
        return _bench_serving_toggle_overhead(
            set_enabled, "serving_perfwatch_overhead_pct", steps=steps,
            hidden=hidden, layers=layers, heads=heads, slots=slots,
            seed=seed)
    finally:
        perf.set_every(default_every)


def bench_checkpoint(state_mb=64, train_steps=150, save_every=50,
                     hidden=1024, seed=0):
    """Checkpoint-store economics (ISSUE 4 acceptance): save/restore
    MB/s, the dedup ratio of a 1%-mutated re-save (content-addressed
    chunks re-referenced, not rewritten), and the train-step overhead
    of saving every `save_every` steps — async (host-copy + background
    writer) vs sync (blocking chunk IO), A/B/A wall-clock against a
    no-save baseline. Bar: async <5% at the benched cadence. Note the
    cadence is already ~100x compressed vs real jobs (one save per
    ~0.5s of stepping vs one per minutes), and on a CPU-only host the
    background writer competes with XLA for the same cores — a TPU
    host pays only the host-copy slice, so the CPU number is the
    worst case. Per-save interference is recorded so any cadence can
    be extrapolated."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from paddle_tpu.checkpoint import CheckpointStore

    rs = np.random.RandomState(seed)
    per = state_mb * (1 << 20) // 4 // 8
    state = {f"w{i}": rs.randn(per).astype(np.float32)
             for i in range(8)}
    nbytes = sum(a.nbytes for a in state.values())
    root = tempfile.mkdtemp(prefix="ckpt_bench_")
    try:
        st = CheckpointStore(root)
        t0 = time.perf_counter()
        st.save(state)
        save_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out, _ = st.restore()
        restore_s = time.perf_counter() - t0
        del out

        # 1%-mutated re-save: dedup ratio + bytes actually written.
        # The mutation is 1% of TOTAL state bytes, contiguous (the
        # "touched embedding rows" pattern) — chunk-granular dedup
        # keeps every untouched chunk
        mutated = dict(state)
        b = state["w0"].copy()
        n_mut = max(1, (8 * len(b)) // 100)
        b[:n_mut] += 1.0
        mutated["w0"] = b
        w0, h0 = st.chunks.chunks_written, st.chunks.dedup_hits
        bytes0 = st.chunks.bytes_written
        t0 = time.perf_counter()
        st.save(mutated)
        incr_s = time.perf_counter() - t0
        new_chunks = st.chunks.chunks_written - w0
        hits = st.chunks.dedup_hits - h0
        dedup_ratio = hits / max(new_chunks + hits, 1)
        incr_bytes = st.chunks.bytes_written - bytes0

        # async-vs-sync step overhead on a real jitted train step
        p = jnp.asarray(rs.randn(hidden, hidden).astype(np.float32))
        x = jnp.asarray(rs.randn(64, hidden).astype(np.float32))

        @jax.jit
        def step(p, x):
            def loss(p):
                h = jnp.tanh(x @ p)
                h = jnp.tanh(h @ p)
                return jnp.sum(h * h)
            g = jax.grad(loss)(p)
            return p - 1e-4 * g

        n_saves = (train_steps + save_every - 1) // save_every

        def run(mode, store):
            nonlocal p
            _sync(step(p, x))  # warm
            t0 = time.perf_counter()
            for i in range(train_steps):
                p = step(p, x)
                if store is not None and i % save_every == 0:
                    if mode == "async":
                        store.save_async({"p": p})
                    else:
                        store.save({"p": p})
            _sync(p)
            if store is not None:
                store.wait()
            return (time.perf_counter() - t0) / train_steps

        base1 = run("none", None)
        async_root = tempfile.mkdtemp(prefix="ckpt_bench_a_")
        sync_root = tempfile.mkdtemp(prefix="ckpt_bench_s_")
        try:
            t_async = run("async", CheckpointStore(async_root))
            t_sync = run("sync", CheckpointStore(sync_root))
        finally:
            shutil.rmtree(async_root, ignore_errors=True)
            shutil.rmtree(sync_root, ignore_errors=True)
        base2 = run("none", None)
        base = min(base1, base2)
        async_pct = (t_async - base) / base * 100 if base > 0 else 0.0
        sync_pct = (t_sync - base) / base * 100 if base > 0 else 0.0
        async_ms_per_save = (t_async - base) * train_steps * 1e3 \
            / n_saves
        sync_ms_per_save = (t_sync - base) * train_steps * 1e3 \
            / n_saves
        return {"metric": "ckpt_save_MBps",
                "value": round(nbytes / (1 << 20) / save_s, 1),
                "unit": "MB/s",
                "restore_MBps": round(nbytes / (1 << 20) / restore_s,
                                      1),
                "state_mb": state_mb,
                "incremental_save_s": round(incr_s, 4),
                "incremental_bytes_written": int(incr_bytes),
                "dedup_ratio_1pct_mutation": round(dedup_ratio, 4),
                "async_save_overhead_pct": round(async_pct, 2),
                "sync_save_overhead_pct": round(sync_pct, 2),
                "async_overhead_bar_pct": 5.0,
                "async_interference_ms_per_save":
                    round(async_ms_per_save, 2),
                "sync_blocked_ms_per_save":
                    round(sync_ms_per_save, 2),
                "baseline_step_ms": round(base * 1e3, 4),
                "async_step_ms": round(t_async * 1e3, 4),
                "sync_step_ms": round(t_sync * 1e3, 4),
                "save_every": save_every,
                "train_steps": train_steps}
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_elastic(train_steps=120, save_every=30, hidden=512, seed=0):
    """BENCH_CONFIG=elastic (docs/ELASTIC.md): the economics of the
    elastic-training substrate. Three numbers:

    - cluster-checkpoint cadence overhead, async vs sync, A/B/A
      wall-clock against a no-save baseline on a jitted train step
      (bar: async <5% at the benched cadence, same as checkpoint);
    - detect→resume wall time of a SIGKILL-mid-step gang restart
      through the real launcher (kill at step 7, backoff 0.05s),
      measured as the largest inter-record gap in the drill fixture's
      per-step jsonl;
    - loss-continuation delta of the resumed run vs a fault-free one
      (bit-for-bit at the same world ⇒ 0.0)."""
    import json as _json
    import shutil
    import subprocess
    import sys as _sys
    import tempfile

    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.cluster_ckpt import ClusterCheckpoint

    rs = np.random.RandomState(seed)
    p = jnp.asarray(rs.randn(hidden, hidden).astype(np.float32))
    x = jnp.asarray(rs.randn(64, hidden).astype(np.float32))

    @jax.jit
    def step(p, x):
        def loss(p):
            h = jnp.tanh(x @ p)
            h = jnp.tanh(h @ p)
            return jnp.sum(h * h)
        g = jax.grad(loss)(p)
        return p - 1e-4 * g

    def run(ck):
        nonlocal p
        _sync(step(p, x))  # warm
        t0 = time.perf_counter()
        for i in range(train_steps):
            p = step(p, x)
            if ck is not None:
                ck.maybe_save(i, replicated={"p": p})
        _sync(p)
        if ck is not None:
            ck.wait()
        return (time.perf_counter() - t0) / train_steps

    def cadenced(async_save):
        root = tempfile.mkdtemp(prefix="elastic_bench_")
        try:
            return run(ClusterCheckpoint(
                root, rank=0, world=1, every_steps=save_every,
                async_save=async_save))
        finally:
            shutil.rmtree(root, ignore_errors=True)

    base1 = run(None)
    t_async = cadenced(True)
    t_sync = cadenced(False)
    base2 = run(None)
    base = min(base1, base2)
    async_pct = (t_async - base) / base * 100 if base > 0 else 0.0
    sync_pct = (t_sync - base) / base * 100 if base > 0 else 0.0

    # gang-restart drill through the real launcher (fixture arms a
    # deterministic kill at step 7; resumed life recomputes from the
    # committed step)
    repo = os.path.dirname(os.path.abspath(__file__))
    fixture = os.path.join(repo, "tests", "fixtures",
                           "elastic_trainer.py")

    def free_port():
        import socket
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def drill(extra_env, launcher_args):
        work = tempfile.mkdtemp(prefix="elastic_drill_")
        out, ckpt = os.path.join(work, "out"), os.path.join(work, "c")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   ELASTIC_DRILL_OUT=out,
                   ELASTIC_DRILL_STEPS="12",
                   ELASTIC_DRILL_SAVE_EVERY="2",
                   ELASTIC_DRILL_STEP_SLEEP="0.02", **extra_env)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH",
                                                        "")
        env.pop("XLA_FLAGS", None)
        res = subprocess.run(
            [_sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node=2", f"--started_port={free_port()}",
             "--log_dir", os.path.join(work, "logs"),
             f"--cluster_ckpt_dir={ckpt}"] + launcher_args + [fixture],
            env=env, capture_output=True, text=True, timeout=300)
        recs = []
        for r in range(2):
            path = os.path.join(out, f"loss_rank{r}.jsonl")
            if os.path.exists(path):
                with open(path) as f:
                    recs += [_json.loads(ln) for ln in f]
        curve = {}
        for rec in sorted(recs, key=lambda r: r["t"]):
            if rec["rank"] == 0:
                curve[rec["step"]] = rec["loss"]
        shutil.rmtree(work, ignore_errors=True)
        return res.returncode, recs, curve

    rc0, _, want = drill({}, [])
    rc1, recs, got = drill(
        {"ELASTIC_DRILL_KILL_RANK": "1", "ELASTIC_DRILL_KILL_AT": "7"},
        ["--max_restarts=2", "--restart_backoff=0.05"])
    ts = sorted(r["t"] for r in recs)
    detect_resume_s = max(b - a for a, b in zip(ts, ts[1:])) \
        if len(ts) > 1 else float("nan")
    deltas = [abs(got[s] - want[s]) / max(abs(want[s]), 1e-12)
              for s in want if s in got]
    loss_delta = max(deltas) if deltas else float("nan")

    return {"metric": "elastic_detect_resume_s",
            "value": round(detect_resume_s, 3),
            "unit": "s",
            "drill_rc": [rc0, rc1],
            "loss_continuation_max_rel_delta": loss_delta,
            "async_save_overhead_pct": round(async_pct, 2),
            "sync_save_overhead_pct": round(sync_pct, 2),
            "async_overhead_bar_pct": 5.0,
            "baseline_step_ms": round(base * 1e3, 4),
            "async_step_ms": round(t_async * 1e3, 4),
            "sync_step_ms": round(t_sync * 1e3, 4),
            "save_every": save_every,
            "train_steps": train_steps}


def bench_ps_ha(n_rows=4096, dim=32, batch=64, lat_pushes=150,
                stream_pushes=200, seed=0):
    """BENCH_CONFIG=ps_ha (docs/PS_HA.md): the economics of the PS
    high-availability plane. Three numbers:

    - failover recovery — kill the primary under a live group client,
      promote the hot standby (epoch-fenced), and time kill -> first
      successful push; versus the pre-HA baseline of
      restart_from_snapshot on the same seeded table (bar: promotion
      wins — the standby already holds the rows);
    - semi-sync ack tax — p50 push latency with
      PADDLE_PS_HA_SEMISYNC=1 vs async replication on an identical
      pair (bar: <150% — the ack is one replication round-trip
      overlapped outside the commit scope, so at most ~one extra
      loopback RTT on top of the push RTT);
    - steady-state replication lag under a wide&deep-style stream
      (4 slot tables, 80/20 hot/uniform id batches), sampled per push
      from the hub's per-peer feeds, plus the drain-to-caught-up time
      once the stream stops."""
    import shutil
    import tempfile

    from paddle_tpu.distributed.fleet.runtime.parameter_server_runtime \
        import PSClient, PSServer
    from paddle_tpu.distributed.fleet.runtime.ps_ha import promote_best

    root = tempfile.mkdtemp(prefix="bench_ps_ha_")
    rng = np.random.RandomState(seed)
    rows = rng.randn(n_rows, dim).astype(np.float32)

    def wait_for(cond, timeout=30.0, what="condition"):
        deadline = time.perf_counter() + timeout
        while not cond():
            if time.perf_counter() > deadline:
                raise TimeoutError(f"ps_ha bench: timed out on {what}")
            time.sleep(0.002)

    def pair(tag, semisync=None):
        env = {} if semisync is None else {
            "PADDLE_PS_HA_SEMISYNC": str(semisync),
            "PADDLE_PS_HA_SEMISYNC_TIMEOUT": "10.0"}
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            prim = PSServer(
                "127.0.0.1:0", wal=True,
                snapshot_dir=os.path.join(root, tag, "p"))
            prim.serve_in_thread()
            stby = PSServer(
                "127.0.0.1:0", wal=True, primary=prim.endpoint,
                snapshot_dir=os.path.join(root, tag, "s"))
            stby.serve_in_thread()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        wait_for(lambda: stby._ha_replicator.synced.is_set(),
                 what=f"{tag} standby bootstrap")
        return prim, stby

    def stop(*servers):
        for s in servers:
            try:
                s.shutdown()
                s.server_close()
            except Exception:
                pass

    def seed_table(cl, name):
        for lo in range(0, n_rows, 256):
            ids = np.arange(lo, min(lo + 256, n_rows))
            cl.push(name, dim, ids, rows[ids])

    def push_p50(cl, name):
        lats = []
        for _ in range(lat_pushes):
            ids = np.unique(rng.randint(0, n_rows, batch))
            vals = rng.randn(len(ids), dim).astype(np.float32)
            t0 = time.perf_counter()
            cl.push(name, dim, ids, vals)
            lats.append(time.perf_counter() - t0)
        lats.sort()
        return lats[len(lats) // 2]

    try:
        # -- semi-sync ack tax: identical pairs, async vs K=1 ---------
        prim_a, stby_a = pair("async")
        cl_a = PSClient([prim_a.endpoint])
        seed_table(cl_a, "emb")
        push_p50(cl_a, "emb")  # warm
        async_p50 = push_p50(cl_a, "emb")

        prim_s, stby_s = pair("semi", semisync=1)
        cl_s = PSClient([prim_s.endpoint])
        seed_table(cl_s, "emb")
        push_p50(cl_s, "emb")  # warm
        semi_p50 = push_p50(cl_s, "emb")
        semi_degraded = int(prim_s._ha.degraded)
        cl_s.close()
        stop(stby_s, prim_s)

        # -- steady-state replication lag under wide&deep-style load --
        hot = rng.randint(0, n_rows, 1024)
        lag_samples = []
        t0 = time.perf_counter()
        for i in range(stream_pushes):
            if rng.rand() < 0.8:
                ids = np.unique(hot[rng.randint(0, len(hot), batch)])
            else:
                ids = np.unique(rng.randint(0, n_rows, batch))
            vals = rng.randn(len(ids), dim).astype(np.float32)
            cl_a.push(f"slot{i % 4}", dim, ids, vals)
            st = prim_a._ha.status()
            if st:
                lag_samples.append(st[0]["lag_rows"])
        stream_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        wait_for(lambda: all(f["lag_rows"] == 0
                             for f in prim_a._ha.status()),
                 what="replication drain")
        drain_s = time.perf_counter() - t0

        # -- failover: kill primary, promote, first push lands --------
        grp = PSClient([prim_a.endpoint + "|" + stby_a.endpoint])
        probe_ids = np.arange(8)
        probe = np.ones((8, dim), np.float32)
        grp.push("emb", dim, probe_ids, probe)
        wait_for(lambda: (stby_a._ha_replicator.applied_seq
                          >= prim_a._ha.seq),
                 what="standby caught up pre-kill")
        t0 = time.perf_counter()
        prim_a.kill()
        new_prim = promote_best([stby_a.endpoint], 2, timeout=10.0)
        grp.push("emb", dim, probe_ids, probe)
        failover_s = time.perf_counter() - t0
        grp.close()
        cl_a.close()
        stop(stby_a)

        # -- pre-HA baseline: snapshot-respawn on the same endpoint.
        # A real respawn is a fresh PROCESS (launcher child) that
        # restores snapshot+WAL before serving, so the baseline spawns
        # the killable-server fixture, not an in-process restart.
        import subprocess
        solo_dir = os.path.join(root, "solo")
        srv = PSServer("127.0.0.1:0", wal=True, snapshot_dir=solo_dir)
        srv.serve_in_thread()
        cl = PSClient([srv.endpoint])
        seed_table(cl, "emb")
        ep = srv.endpoint
        srv.kill()
        repo = os.path.dirname(os.path.abspath(__file__))
        env = dict(os.environ, PS_ENDPOINT=ep, PADDLE_PS_WAL="1",
                   PADDLE_PS_SNAPSHOT_DIR=solo_dir,
                   JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = repo + os.pathsep + env.get(
            "PYTHONPATH", "")
        t0 = time.perf_counter()
        proc = subprocess.Popen(
            [sys.executable,
             os.path.join(repo, "tests", "fixtures",
                          "ps_fault_server.py")],
            env=env, stdout=subprocess.PIPE, text=True)
        try:
            proc.stdout.readline()  # READY line: restored + serving
            cl.push("emb", dim, probe_ids, probe)
            respawn_s = time.perf_counter() - t0
        finally:
            proc.kill()
            proc.wait()
        cl.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    overhead_pct = ((semi_p50 - async_p50) / async_p50 * 100
                    if async_p50 > 0 else 0.0)
    return {"metric": "ps_ha_failover_first_push_s",
            "value": round(failover_s, 4),
            "unit": "s",
            "respawn_first_push_s": round(respawn_s, 4),
            "promotion_beats_respawn": bool(failover_s < respawn_s),
            "promoted_ok": bool(new_prim is not None),
            "async_push_p50_ms": round(async_p50 * 1e3, 4),
            "semisync_push_p50_ms": round(semi_p50 * 1e3, 4),
            "semisync_overhead_pct": round(overhead_pct, 2),
            "semisync_overhead_bar_pct": 150.0,
            "semisync_bar_ok": bool(overhead_pct <= 150.0),
            "semisync_degraded_acks": semi_degraded,
            "stream_lag_rows_mean": round(
                float(np.mean(lag_samples)), 2) if lag_samples
            else float("nan"),
            "stream_lag_rows_max": int(max(lag_samples))
            if lag_samples else -1,
            "stream_push_per_s": round(stream_pushes / stream_s, 1),
            "lag_drain_s": round(drain_s, 4),
            "rows": n_rows, "dim": dim, "batch": batch,
            "lat_pushes": lat_pushes, "stream_pushes": stream_pushes}


def bench_tiered(vocab=1 << 26, dim=8, batch=256, train_steps=400,
                 serve_steps=400, warm_budget=256 * 1024, seed=0):
    """BENCH_CONFIG=tiered (docs/PS_TIERED.md): widedeep-style
    training + serving against a 2^26-row embedding vocab on a tiered
    parameter server whose warm budget is a tiny fraction of the
    touched bytes. Ids follow a zipf(1.2) skew, so the hot head lives
    warm and the long tail demand-pages from the chunk store.

    Headline = serving-phase p99 pull latency (the SLO number a
    lookup service sees when the tail faults cold rows in). Also
    records per-tier hit rates, demotion counts, warm residency vs
    budget after a drain, and client-observed cold-fault totals."""
    import shutil
    import tempfile

    from paddle_tpu.distributed.fleet.runtime.parameter_server_runtime \
        import PSClient, PSServer

    root = tempfile.mkdtemp(prefix="bench_tiered_")
    rng = np.random.default_rng(seed)
    try:
        srv = PSServer("127.0.0.1:0", wal=True,
                       snapshot_dir=os.path.join(root, "snap"),
                       tier_warm_bytes=warm_budget,
                       tier_store_dir=os.path.join(root, "store"))
        srv.serve_in_thread()
        cl = PSClient([srv.endpoint])

        def ids_for(step):
            # zipf rank -> id directly: rank 1 is the hottest row and
            # stays hot across steps, so the head settles warm while
            # the tail keeps faulting from the chunk store.
            return (rng.zipf(1.2, batch).astype(np.int64) - 1) % vocab

        # -- train: pull + push per step ------------------------------
        t0 = time.perf_counter()
        for step in range(train_steps):
            ids = ids_for(step)
            v = cl.pull("emb", dim, ids)
            cl.push("emb", dim, ids, 0.01 * v)
        train_s = time.perf_counter() - t0
        train_faults = cl.cold_faults

        # -- serve: pulls only, timed per call ------------------------
        lats = []
        for step in range(serve_steps):
            ids = ids_for(train_steps + step)
            t1 = time.perf_counter()
            cl.pull("emb", dim, ids)
            lats.append(time.perf_counter() - t1)
        serve_faults = cl.cold_faults - train_faults

        t = srv.tables["emb"]
        t.drain()
        st = t.stats()
        warm_after_drain = t.warm_resident_bytes()
        touched = st["warm_rows"] + st["cold_rows"]
        lookups = st["warm_hits"] + st["cold_faults"]
        hit_warm = (st["warm_hits"] / lookups) if lookups else 0.0
        cl.close()
        srv.kill()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    lats.sort()
    p50 = lats[len(lats) // 2]
    p99 = lats[min(int(len(lats) * 0.99), len(lats) - 1)]
    steps_s = (train_steps + serve_steps) / (
        train_s + sum(lats)) if lats else 0.0
    return {"metric": "ps_tier_serve_pull_p99_ms",
            "value": round(p99 * 1e3, 4),
            "unit": "ms",
            "serve_pull_p50_ms": round(p50 * 1e3, 4),
            "train_examples_per_s": round(
                train_steps * batch / train_s, 1),
            "steps_per_s": round(steps_s, 1),
            "vocab_rows": vocab,
            "touched_rows": touched,
            "warm_budget_bytes": warm_budget,
            "warm_resident_bytes": warm_after_drain,
            "warm_under_budget": bool(warm_after_drain <= warm_budget),
            "warm_hit_rate": round(hit_warm, 4),
            "cold_fault_rate": round(1.0 - hit_warm, 4),
            "warm_rows": st["warm_rows"],
            "cold_rows": st["cold_rows"],
            "segments": st["segments"],
            "demoted_clean": st["demoted_clean"],
            "demoted_flush": st["demoted_flush"],
            "cold_read_errors": st["cold_read_errors"],
            "client_cold_faults_train": int(train_faults),
            "client_cold_faults_serve": int(serve_faults),
            "dim": dim, "batch": batch,
            "train_steps": train_steps, "serve_steps": serve_steps}


def bench_infer_latency(batch=1, seq=128, steps=30, warmup=5):
    """BERT-base inference latency through the Predictor (analysis
    predictor parity path): save -> load -> timed ZeroCopyRun.

    Headline = steady-state per-inference latency via the zero-copy
    handle API (outputs device-side, one host sync at the end) — the
    number a pipelined serving loop sees. ``blocked_ms`` additionally
    reports single-shot run-to-numpy latency (run, then copy the output
    to the host, every call)."""
    import shutil
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu.inference import Config, Predictor
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    from paddle_tpu.static import InputSpec

    cfg = BertConfig.base()
    model = BertForPretraining(cfg)
    model.eval()
    d = tempfile.mkdtemp()
    try:
        paddle.jit.save(model, d,
                        input_spec=[InputSpec([-1, seq], "int64", "ids")])
        c = Config(model_dir=d)
        c.enable_bf16()
        pred = Predictor(c)
        ids = np.random.RandomState(0).randint(
            4, cfg.vocab_size, (batch, seq)).astype("int64")
        in_h = pred.get_input_handle(pred.get_input_names()[0])
        out_h = pred.get_output_handle(pred.get_output_names()[0])
        in_h.copy_from_cpu(ids)
        for _ in range(warmup):
            pred.run()
        _sync(out_h._value)
        # steady-state: chain zero-copy runs, one sync at the end
        t0 = time.perf_counter()
        for _ in range(steps):
            pred.run()
        dt = _finish_timed(t0, out_h._value) / steps
        # single-shot blocked (run + fetch to numpy each call)
        t0 = time.perf_counter()
        for _ in range(3):
            pred.run()
            _ = out_h.copy_to_cpu()
        blocked = (time.perf_counter() - t0) / 3
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return {"metric": "bert_base_infer_latency_ms",
            "value": round(dt * 1e3, 3), "unit": "ms", "batch": batch,
            "seq": seq, "blocked_ms": round(blocked * 1e3, 3),
            "note": "zero-copy steady-state; blocked_ms adds the full "
                    "output transfer to the host on every call"}


def bench_allreduce(mb=64, steps=30, warmup=5):
    """Achieved allreduce bandwidth over the device mesh (BASELINE config 2
    companion metric). Algorithmic bandwidth: 2·(n-1)/n · bytes / time."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = np.array(jax.devices())
    n = len(devs)
    mesh = Mesh(devs, ("dp",))
    nbytes = mb * 1024 * 1024
    x = jnp.zeros((n, nbytes // 4), jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P("dp")))

    @jax.jit
    def allreduce(x):
        return jax.shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                             in_specs=P("dp"), out_specs=P("dp"))(x)

    for _ in range(warmup):
        out = allreduce(x)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = allreduce(out)
    _sync(out)
    dt = (time.perf_counter() - t0) / steps
    bw = 2 * (n - 1) / max(n, 1) * nbytes / dt / 1e9
    return {"metric": "allreduce_algbw_gbps", "value": round(bw, 2),
            "unit": "GB/s", "devices": n, "payload_mb": mb}


def bench_kernels(reps=5):
    """BENCH_CONFIG=kernels: per-kernel fused-vs-unfused speedups at
    model shapes (the PR-7 epilogue-fused decoder sub-blocks + the
    pre-existing fused FFN/LN kernels) plus tuning-cache COLD vs WARM
    first-call latency — the number a serving fleet saves per replica
    by shipping a pre-warmed PADDLE_TPU_AUTOBENCH_CACHE. The shapes are
    the gpt_350m / bert_base_512 hot shapes. TPU only: timing the
    Pallas interpreter says nothing about the kernels. A candidate
    that fails to run is listed under "errors" and fails the bench."""
    import tempfile

    import jax
    from paddle_tpu.ops import autobench
    from paddle_tpu.ops import pallas_block, pallas_ffn, pallas_layer_norm
    from paddle_tpu.ops.pallas_attention import on_tpu

    if not on_tpu():
        raise RuntimeError(
            f"BENCH_CONFIG=kernels times compiled Pallas kernels and needs "
            f"a TPU; jax found {jax.devices()[0].platform!r}")
    dt = "bfloat16"
    gates = {
        "out_ln_bert512":
            pallas_block._gate_out_ln(8192, 768, 768, dt),
        "ffn_block_bert512":
            pallas_block._gate_ffn_ln(8192, 768, 3072, dt, "gelu",
                                      "post"),
        "out_ln_gpt350m":
            pallas_block._gate_out_ln(8192, 1024, 1024, dt),
        "ffn_block_gpt350m":
            pallas_block._gate_ffn_ln(8192, 1024, 4096, dt,
                                      "gelu_tanh", "none"),
        "ffn_bert512": pallas_ffn._gate_ffn(8192, 768, 3072, dt),
        "layer_norm_bert512":
            pallas_layer_norm._gate_ln(8192, 768, dt),
    }
    kernels = {}
    speedups = []
    for name, (key, cands, make_args) in gates.items():
        t = {}
        for cname, fn in cands.items():
            try:
                t[cname] = autobench._measure(fn, make_args, reps)[0]
            except Exception as e:
                t[cname] = None
                kernels.setdefault("errors", {})[f"{name}/{cname}"] = \
                    f"{type(e).__name__}: {e}"
        rec = {c: (round(v * 1e3, 3) if v else None)
               for c, v in t.items()}
        if t.get("pallas") and t.get("xla"):
            rec["speedup_fused"] = round(t["xla"] / t["pallas"], 3)
            speedups.append(rec["speedup_fused"])
        kernels[name] = rec

    # tuning-cache cold vs warm first-call latency: cold pays the
    # measuring round; warm (a "restarted replica") adopts from disk.
    # A pre-existing cache env is restored afterwards — an operator's
    # real fleet cache must survive a bench run.
    saved_cache = os.environ.get("PADDLE_TPU_AUTOBENCH_CACHE")
    with tempfile.TemporaryDirectory() as d:
        os.environ["PADDLE_TPU_AUTOBENCH_CACHE"] = \
            os.path.join(d, "autobench.json")
        try:
            import jax.numpy as jnp
            cands = {"a": lambda x: jnp.tanh(x) @ x,
                     "b": lambda x: x @ x}
            mk = lambda: (jnp.ones((256, 256), jnp.float32),)
            autobench.clear()
            t0 = time.perf_counter()
            autobench.prefer(("bench_cache_probe",), cands, mk, reps=3)
            cold = time.perf_counter() - t0
            autobench.clear()  # new-process simulation; file survives
            t0 = time.perf_counter()
            autobench.prefer(("bench_cache_probe",), cands, mk, reps=3)
            warm = time.perf_counter() - t0
            warm_stats = autobench.stats()
        finally:
            if saved_cache is None:
                del os.environ["PADDLE_TPU_AUTOBENCH_CACHE"]
            else:
                os.environ["PADDLE_TPU_AUTOBENCH_CACHE"] = saved_cache
            autobench.clear()
    geo = float(np.exp(np.mean(np.log(speedups)))) if speedups else None
    return {"metric": "kernels_fused_geomean_speedup",
            "value": round(geo, 3) if geo else None,
            "unit": "x_vs_composed_xla",
            "kernels": kernels,
            "cache": {"cold_first_call_ms": round(cold * 1e3, 2),
                      "warm_first_call_ms": round(warm * 1e3, 2),
                      "warm_measures": warm_stats["measures"],
                      "warm_hits": warm_stats["cache_hits"]},
            "device_kind": str(jax.devices()[0].device_kind)}


def bench_tsdb(steps=200, hidden=256, layers=4, heads=4, slots=4,
               seed=0, ingest_batches=2500, query_reps=50):
    """Time-series-plane cost guardrail (ISSUE 18 acceptance): a LIVE
    agent streams to a collector whose TSDB + alert evaluator are
    toggled A/B/A on the same engine — the toggle isolates the
    history/alerting cost ON TOP of fleet telemetry (agent stays armed
    both ways), and all TSDB writes ride the collector's server
    threads, so the decode hot path sees the same <2% bar as the other
    observability toggles. Supplementary stats measure the plane
    itself against a disk-backed store: batch ingest rate, bytes per
    sample on disk after block sealing + downsampling, and query
    latency for range/rate/quantile over the ingested history."""
    import shutil
    import tempfile

    from paddle_tpu.observability import agent as tel_agent
    from paddle_tpu.observability.collector import (CollectorServer,
                                                    TelemetryCollector)
    from paddle_tpu.observability.timeseries import TimeSeriesDB

    col = TelemetryCollector(tsdb=TimeSeriesDB())
    srv = CollectorServer("127.0.0.1:0", collector=col).start()
    paused = []

    def set_enabled(on):
        # ingest() reads tsdb/alerts without holding the collector
        # lock, so the swap is a plain attribute flip
        if on:
            if paused:
                col.tsdb, col.alerts = paused.pop()
        else:
            paused.append((col.tsdb, col.alerts))
            col.tsdb = col.alerts = None

    tel_agent.arm(srv.endpoint)
    try:
        rec = _bench_serving_toggle_overhead(
            set_enabled, "serving_tsdb_overhead_pct", steps=steps,
            hidden=hidden, layers=layers, heads=heads, slots=slots,
            seed=seed)
    finally:
        tel_agent.disarm()
        srv.stop()

    # -- plane economics: a dedicated disk-backed store, block size
    # shrunk so sealing + downsampling actually fire inside the bench
    root = tempfile.mkdtemp(prefix="tsdb_bench_")
    try:
        db = TimeSeriesDB(dir_=os.path.join(root, "tsdb"),
                          block_bytes=256 * 1024,
                          retention_bytes=8 * 2**20)
        hist_buckets = (0.001, 0.005, 0.025, 0.1, 0.5, 2.5)
        base_t = 1_700_000_000.0
        t0 = time.perf_counter()
        appended = 0
        for i in range(ingest_batches):
            # 1s cadence over ~40min of history: crosses the raw
            # window (900s) so mid-resolution downsampling is exercised
            t = base_t + i
            entries = [("bench_counter_total",
                        {"host": "h", "pid": str(p), "role": "trainer"},
                        "counter", float(i * 10 + p), None)
                       for p in range(8)]
            entries += [("bench_gauge",
                         {"host": "h", "pid": str(p),
                          "role": "trainer"},
                         "gauge", float((i + p) % 97), None)
                        for p in range(8)]
            cum = tuple(min(i + 1, (b + 1) * (i + 1) // 7 + 1)
                        for b in range(len(hist_buckets) + 1))
            entries.append(("bench_latency_seconds",
                            {"host": "h", "pid": "0",
                             "role": "trainer"},
                            "histogram",
                            (cum, 0.01 * (i + 1), float(cum[-1])),
                            hist_buckets))
            appended += db.append(t, entries)
        ingest_s = time.perf_counter() - t0
        st = db.stats()
        end_t = base_t + ingest_batches - 1

        def timeit(fn):
            q0 = time.perf_counter()
            for _ in range(query_reps):
                fn()
            return (time.perf_counter() - q0) / query_reps * 1e3

        q_range = timeit(lambda: db.range(
            "bench_gauge", start=end_t - 300, end=end_t))
        q_rate = timeit(lambda: db.rate(
            "bench_counter_total", 300, at=end_t))
        q_quantile = timeit(lambda: db.quantile(
            "bench_latency_seconds", 0.99, 300, at=end_t))
        db.close()
        rec["tsdb"] = {
            "ingest_samples_per_s": round(appended / ingest_s),
            "samples": appended,
            "series": st["series"],
            "bytes_on_disk": st["bytes_on_disk"],
            "bytes_per_sample": round(
                st["bytes_on_disk"] / max(1, appended), 2),
            "blocks_sealed": st["counts"]["sealed"],
            "blocks_compacted": st["counts"]["compacted"],
            "blocks_deleted": st["counts"]["deleted"],
            "query_ms": {"range_5m": round(q_range, 3),
                         "rate_5m": round(q_rate, 3),
                         "quantile_p99_5m": round(q_quantile, 3)},
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return rec


def _run_extra(name, fn, failed: list):
    """One secondary config: its failure is written into the record AND
    remembered, so the other configs still run and the process still
    exits non-zero."""
    try:
        return fn()
    except Exception as e:
        failed.append(name)
        return {"error": f"{type(e).__name__}: {e}"}


def main():
    from paddle_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    which = os.environ.get("BENCH_CONFIG", "bert_base")
    failed = []
    if which == "lenet":
        rec = bench_lenet()
    elif which == "bert_tiny":
        rec = bench_bert("tiny", batch=8, seq=64)
    elif which == "bert_base_512":
        rec = bench_bert("base_512", batch=16, seq=512, steps=24)
    elif which == "flash_attn":
        rec = bench_flash_attn()
    elif which == "allreduce":
        rec = bench_allreduce()
    elif which == "gpt":
        rec = bench_gpt()
    elif which == "resnet50":
        rec = bench_resnet50()
    elif which == "widedeep":
        rec = bench_widedeep()
    elif which == "infer":
        rec = bench_infer_latency()
    elif which == "serving":
        rec = bench_serving()
    elif which == "slo":
        rec = bench_slo()
    elif which == "prefix":
        rec = bench_prefix()
    elif which == "chaos":
        rec = bench_chaos()
    elif which == "router":
        rec = bench_router()
    elif which == "metrics_overhead":
        rec = bench_metrics_overhead()
    elif which == "flight_overhead":
        rec = bench_flight_overhead()
    elif which == "telemetry_overhead":
        rec = bench_telemetry_overhead()
    elif which == "perfwatch_overhead":
        rec = bench_perfwatch_overhead()
    elif which == "checkpoint":
        rec = bench_checkpoint()
    elif which == "elastic":
        rec = bench_elastic()
    elif which == "gpt_1p3b":
        rec = bench_gpt_1p3b()
    elif which == "kernels":
        rec = bench_kernels()
        if rec["kernels"].get("errors"):
            failed.append("kernels")
    elif which == "transport":
        rec = bench_transport()
    elif which == "online":
        rec = bench_online()
    elif which == "ps_ha":
        rec = bench_ps_ha()
    elif which == "tiered":
        rec = bench_tiered()
    elif which == "tsdb":
        rec = bench_tsdb()
    else:
        # batch 64 wins on v5e since the rbg-PRNG switch removed the
        # dropout-mask cost (32.5% MFU vs 31.8% at batch 32; pre-rbg,
        # batch 64 regressed)
        rec = bench_bert("base", batch=64)
        # secondary configs ride along in the single JSON line so every
        # round's BENCH record carries the whole BASELINE matrix
        if os.environ.get("BENCH_EXTRAS", "1") != "0":
            extras = {}
            # (name, full-steps runner, reduced-steps runner), in a fixed
            # order. Every config records every run: when the budget runs
            # out configs drop to a minimal 2-step run rather than
            # skipping — 2 steps still records a real number.
            configs = [
                ("widedeep",
                 lambda: bench_widedeep(steps=10, warmup=2),
                 # reduced mode keeps ONE small run through the real
                 # TCP transport so ps_tcp always lands in the record
                 lambda: (os.environ.__setitem__(
                     "BENCH_WIDEDEEP_PS", "min"),
                     bench_widedeep(steps=2, warmup=1))[1]),
                ("infer_latency",
                 lambda: bench_infer_latency(steps=15, warmup=3),
                 lambda: bench_infer_latency(steps=5, warmup=1)),
                ("serving",
                 lambda: bench_serving(),
                 lambda: bench_serving(num_requests=12, hidden=256,
                                       layers=4, heads=4, max_new=32)),
                ("flash_attn", bench_flash_attn,
                 lambda: bench_flash_attn(steps=6, warmup=1)),
                ("resnet50",
                 lambda: bench_resnet50(steps=8, warmup=2),
                 lambda: bench_resnet50(steps=2, warmup=1)),
                ("bert_base_512",
                 lambda: bench_bert("base_512", batch=16, seq=512,
                                    steps=16, warmup=2),
                 lambda: bench_bert("base_512", batch=16, seq=512,
                                    steps=2, warmup=1)),
                ("gpt_350m",
                 lambda: bench_gpt(steps=6, warmup=2),
                 lambda: bench_gpt(steps=2, warmup=1)),
                ("gpt_1p3b",
                 lambda: bench_gpt_1p3b(steps=4, warmup=1),
                 lambda: bench_gpt_1p3b(steps=2, warmup=1)),
            ]
            budget = float(os.environ.get("BENCH_EXTRAS_BUDGET", 420))
            for i, (name, full, reduced) in enumerate(configs):
                # wall budget so the driver's bench window is never blown
                # badly (each config costs a fresh XLA compile ~20-40s);
                # share the remaining budget across the configs still
                # queued and shrink step counts rather than skipping
                left = budget - (time.perf_counter() - _T0)
                share = left / (len(configs) - i)
                # a full config costs ~25s compile + ~15s steps; run
                # full whenever the fair share covers that, reduced
                # otherwise (reduced still records a real number)
                extras[name] = _run_extra(
                    name, full if share > 45 else reduced, failed)
            import jax
            if len(jax.devices()) > 1:
                extras["allreduce"] = _run_extra(
                    "allreduce", bench_allreduce, failed)
            rec["extras"] = extras
    rec.setdefault("vs_baseline", 1.0)
    # every config leaves a schema-versioned record; the same writer
    # backs `perfwatch record`, and PADDLE_TPU_BENCH_OUT collects a
    # sweep into one JSONL artifact for `perfwatch compare`
    from paddle_tpu.observability.perfwatch import finalize_record
    finalize_record(rec, which)
    print(json.dumps(rec))
    if failed:
        sys.exit(f"bench.py: failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
