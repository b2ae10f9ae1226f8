"""Hybrid parallelism: tensor parallel + pipeline parallel + dp composed.

Reference parity: PipelineOptimizer chain
(/root/reference/python/paddle/fluid/optimizer.py:3666,
meta_optimizers/pipeline_optimizer.py:24); TP is absent in the reference
(SURVEY SS2.9) and designed fresh as GSPMD PartitionSpec rules.  All tests
run on the virtual 8-device CPU mesh per SURVEY SS4's distributed test
strategy."""
import numpy as np
import pytest

# model-scale suite: excluded from the <2-min core lane
pytestmark = pytest.mark.slow

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.models.gpt import (GPTConfig, init_gpt_params, gpt_loss,
                                   gpt_forward)
from paddle_tpu.parallel.hybrid import HybridParallelTrainStep
from paddle_tpu.parallel.pipeline import pipeline_apply


def _ids(cfg, b=8, t=32, seed=0):
    return np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (b, t)).astype(np.int32)


def test_pipeline_apply_matches_sequential():
    devs = np.array(jax.devices()).reshape(2, 2, 2)
    mesh = Mesh(devs, ("dp", "pp", "tp"))
    S, M, mb, D = 2, 4, 4, 8
    rng = np.random.RandomState(0)
    W = jnp.asarray(rng.randn(S, D, D).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.randn(M, mb, D).astype(np.float32))
    stage_fn = lambda w, h: jnp.tanh(h @ w)
    Wsh = jax.device_put(W, NamedSharding(mesh, P("pp")))
    xsh = jax.device_put(x, NamedSharding(mesh, P(None, "dp", "tp")))

    def loss_pp(W, x):
        return jnp.mean(pipeline_apply(stage_fn, W, x, mesh, "pp") ** 2)

    def loss_ref(W, x):
        h = x
        for s in range(S):
            h = stage_fn(W[s], h)
        return jnp.mean(h ** 2)

    l1, g1 = jax.jit(jax.value_and_grad(loss_pp))(Wsh, xsh)
    l2, g2 = jax.jit(jax.value_and_grad(loss_ref))(W, x)
    assert abs(float(l1) - float(l2)) < 1e-6
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)


def test_pipeline_rejects_too_few_microbatches():
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs.reshape(4), ("pp",))
    W = jnp.zeros((4, 4, 4))
    x = jnp.zeros((2, 2, 4))  # 2 microbatches < 4 stages
    with pytest.raises(ValueError, match="microbatches"):
        pipeline_apply(lambda w, h: h @ w, W, x, mesh, "pp")


@pytest.mark.parametrize("dp,pp,tp,micro", [
    (2, 2, 2, 4),   # full hybrid
    (1, 4, 1, 8),   # pipeline-heavy
    (1, 1, 8, None),  # tp-only
    (8, 1, 1, None),  # dp-only
])
def test_hybrid_matches_single_device(dp, pp, tp, micro):
    cfg = GPTConfig.tiny()
    ids = _ids(cfg)
    s1 = HybridParallelTrainStep(cfg, dp=1, pp=1, tp=1, seed=0,
                                 devices=jax.devices()[:1])
    s8 = HybridParallelTrainStep(cfg, dp=dp, pp=pp, tp=tp,
                                 n_microbatches=micro, seed=0)
    for i in range(3):
        l1, l8 = float(s1(ids)), float(s8(ids))
        assert abs(l1 - l8) < 5e-4, f"step {i}: {l1} vs {l8}"
    # loss decreased (it actually trains)
    assert float(s8(ids)) < l1


@pytest.mark.parametrize("dp,pp,tp,micro", [(2, 2, 2, 4), (1, 1, 4, None)])
def test_hybrid_flash_per_shard_matches_single_device(dp, pp, tp, micro):
    """attn_impl="flash" on a mesh: the Pallas kernel on each device's
    (batch over dp, heads over tp) shard, inside the pp-manual 1F1B
    region too, against the composed attention on one device."""
    ids = _ids(GPTConfig.tiny(), t=64)
    s1 = HybridParallelTrainStep(GPTConfig.tiny(), seed=0,
                                 devices=jax.devices()[:1])
    s8 = HybridParallelTrainStep(GPTConfig.tiny(attn_impl="flash"), dp=dp,
                                 pp=pp, tp=tp, n_microbatches=micro, seed=0)
    for i in range(3):
        l1, l8 = float(s1(ids)), float(s8(ids))
        assert abs(l1 - l8) < 5e-4, f"step {i}: {l1} vs {l8}"
    with pytest.raises(ValueError, match="num_heads"):
        HybridParallelTrainStep(GPTConfig.tiny(attn_impl="flash"), tp=8)


def test_hybrid_params_actually_sharded():
    cfg = GPTConfig.tiny(attn_impl="flash")
    s = HybridParallelTrainStep(cfg, dp=2, pp=2, tp=2, n_microbatches=4)
    # jax cannot partition a Mosaic kernel: on a multi-device mesh flash
    # attention runs per shard and the fused decoder tail is off (said
    # in a warning); one device keeps both
    assert s.cfg.attn_impl == "flash" and not s.cfg.fused_blocks
    one = HybridParallelTrainStep(cfg, devices=jax.devices()[:1])
    assert one.cfg.attn_impl == "flash" and one.cfg.fused_blocks
    blk = s.params["blocks"]["w_up"]
    # [pp, L/pp, D, F]: dim0 over pp, dim3 over tp
    assert blk.sharding.spec == P("pp", None, None, "tp")
    shard_shape = blk.sharding.shard_shape(blk.shape)
    assert shard_shape[0] == blk.shape[0] // 2
    assert shard_shape[3] == blk.shape[3] // 2
    # optimizer state sharded like the param
    assert s.opt_state["blocks"]["w_up"]["m1"].sharding.spec == \
        blk.sharding.spec


def test_fleet_strategy_consumes_pipeline_and_tp():
    import paddle_tpu.distributed.fleet as fleet
    from paddle_tpu.distributed.fleet.base.fleet_base import _fleet
    strategy = fleet.DistributedStrategy()
    strategy.pipeline = True
    strategy.pipeline_configs = {"accumulate_steps": 4}
    strategy.tensor_parallel = True
    strategy.tensor_parallel_configs = {"tensor_parallel_degree": 2}
    strategy.hybrid_configs = {"dp_degree": 2, "pp_degree": 2,
                               "mp_degree": 1}
    _fleet.init(is_collective=True, strategy=strategy)
    cfg = GPTConfig.tiny()
    step = _fleet.hybrid_train_step(cfg, seed=0)
    assert dict(step.mesh.shape) == {"pp": 2, "dp": 2, "sp": 1, "ep": 1,
                                     "tp": 2}
    assert step.n_micro == 4
    loss = step(_ids(cfg))
    assert np.isfinite(float(loss))


def test_static_tensor_parallel_rules(fresh_programs):
    """strategy.tensor_parallel on a static program: rules shard fc weights
    over the tp axis; result matches the unsharded run."""
    import paddle_tpu as paddle
    from paddle_tpu.fluid import Executor, framework, layers, optimizer
    import paddle_tpu.distributed.fleet as fleet
    from paddle_tpu.fluid import unique_name
    from paddle_tpu.fluid.scope import Scope, scope_guard

    def build(seed):
        main, startup = framework.Program(), framework.Program()
        main.random_seed = startup.random_seed = seed
        with framework.program_guard(main, startup):
            x = layers.data("x", [-1, 16], "float32")
            y = layers.data("y", [-1, 1], "float32")
            h = layers.fc(x, 32, act="relu")
            pred = layers.fc(h, 1)
            d = layers.elementwise_sub(pred, y)
            loss = layers.mean(layers.elementwise_mul(d, d))
            optimizer.SGD(learning_rate=0.05).minimize(loss)
        return main, startup, loss

    def train(tp_on, steps=10):
        with unique_name.guard():
            main, startup, loss = build(seed=11)
        if tp_on:
            main._sharding_info = {
                "mode": "dp", "tp": 2,
                "tp_rules": [(r"fc_0\.w_0", (None, "tp")),
                             (r"fc_0\.b_0", ("tp",))]}
        rng = np.random.RandomState(5)
        w_true = rng.randn(16, 1).astype("float32")
        out = []
        with scope_guard(Scope()):
            exe = Executor()
            exe.run(startup)
            for _ in range(steps):
                xb = rng.randn(32, 16).astype("float32")
                yb = xb @ w_true
                lv, = exe.run(main, feed={"x": xb, "y": yb},
                              fetch_list=[loss])
                out.append(float(np.ravel(lv)[0]))
        return out

    base = train(False)
    tp = train(True)
    assert tp[-1] < tp[0] * 0.5
    np.testing.assert_allclose(base, tp, rtol=2e-3, atol=1e-4)


def test_static_tp_with_adam_accumulators(fresh_programs):
    """Adam's shape-(1,) beta-pow accumulators share the weight's name
    prefix; the rule resolver must leave them replicated instead of
    applying the rank-2 weight spec (code-review regression)."""
    import paddle_tpu as paddle
    from paddle_tpu.fluid import Executor, framework, layers, optimizer
    from paddle_tpu.fluid import unique_name
    from paddle_tpu.fluid.scope import Scope, scope_guard

    with unique_name.guard():
        main, startup = framework.Program(), framework.Program()
        main.random_seed = startup.random_seed = 3
        with framework.program_guard(main, startup):
            x = layers.data("x", [-1, 16], "float32")
            y = layers.data("y", [-1, 1], "float32")
            h = layers.fc(x, 32, act="relu")
            pred = layers.fc(h, 1)  # fc_1.w_0 is [32,1]: tp won't divide
            d = layers.elementwise_sub(pred, y)
            loss = layers.mean(layers.elementwise_mul(d, d))
            optimizer.Adam(learning_rate=0.01).minimize(loss)
    main._sharding_info = {"mode": "dp", "tp": 2,
                           "tp_rules": [(r"fc_0\.w_0", (None, "tp")),
                                        (r"fc_1\.w_0", (None, "tp"))]}
    rng = np.random.RandomState(1)
    with scope_guard(Scope()):
        exe = Executor()
        exe.run(startup)
        for _ in range(3):
            xb = rng.randn(32, 16).astype("float32")
            lv, = exe.run(main, feed={"x": xb,
                                      "y": xb[:, :1].copy()},
                          fetch_list=[loss])
        assert np.isfinite(float(np.ravel(lv)[0]))


def test_zero1_sharding_optimizer_state():
    """strategy.sharding (ZeRO-1): optimizer moments shard over dp; loss
    parity with the unsharded run; per-chip moment memory / dp."""
    cfg = GPTConfig.tiny()
    ids = _ids(cfg)
    s_plain = HybridParallelTrainStep(cfg, dp=4, tp=2, seed=0)
    s_zero = HybridParallelTrainStep(cfg, dp=4, tp=2, seed=0,
                                     sharding=True)
    m1 = s_zero.opt_state["blocks"]["wq"]["m1"]
    assert "dp" in jax.tree_util.tree_leaves(
        [m1.sharding.spec])[0] or "dp" in tuple(m1.sharding.spec)
    shard = m1.sharding.shard_shape(m1.shape)
    full = s_plain.opt_state["blocks"]["wq"]["m1"]
    assert np.prod(shard) == np.prod(full.shape) // 4 // 2  # dp=4, tp=2
    for i in range(3):
        lp, lz = float(s_plain(ids)), float(s_zero(ids))
        assert abs(lp - lz) < 5e-4, (i, lp, lz)


def test_fleet_strategy_consumes_zero_sharding():
    import paddle_tpu.distributed.fleet as fleet
    from paddle_tpu.distributed.fleet.base.fleet_base import _fleet
    strategy = fleet.DistributedStrategy()
    strategy.sharding = True
    strategy.hybrid_configs = {"dp_degree": 4, "pp_degree": 1,
                               "mp_degree": 2}
    _fleet.init(is_collective=True, strategy=strategy)
    step = _fleet.hybrid_train_step(GPTConfig.tiny(), seed=0)
    assert step.zero_sharding


@pytest.mark.slow
def test_sp_x_pp_matches_single_device():
    """sp x pp composition (r04 weak #5): ring attention inside 1F1B
    stage functions, sequence GSPMD-sharded over sp within each stage,
    pp manual outside. Runs in a subprocess (the XLA multi-mesh
    process-state caveat, parallel/pipeline_1f1b.py docstring) and
    checks loss parity against the single-device trajectory."""
    import json
    import os
    import subprocess
    import sys
    code = (
        "import os, json, numpy as np\n"
        "os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS','') + "
        "' --xla_force_host_platform_device_count=8'\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from paddle_tpu.models.gpt import GPTConfig\n"
        "from paddle_tpu.parallel.hybrid import HybridParallelTrainStep\n"
        "cfg = GPTConfig.tiny(dropout=0.0)\n"
        "ids = np.random.RandomState(0).randint("
        "0, cfg.vocab_size, (8, 64)).astype('int32')\n"
        "s1 = HybridParallelTrainStep(cfg, seed=0, "
        "devices=jax.devices()[:1])\n"
        "s8 = HybridParallelTrainStep(cfg, dp=2, pp=2, sp=2, seed=0, "
        "n_microbatches=2, pipeline_schedule='1F1B')\n"
        "out = [[float(s1(ids)), float(s8(ids))] for _ in range(3)]\n"
        "print(json.dumps(out))\n")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    pairs = json.loads(r.stdout.strip().splitlines()[-1])
    for i, (l1, l8) in enumerate(pairs):
        assert abs(l1 - l8) < 5e-4, f"step {i}: {l1} vs {l8}"
    assert pairs[-1][1] < pairs[0][1]
