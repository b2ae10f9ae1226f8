"""Test config: force a virtual 8-device CPU mesh (no TPU needed).

Mirrors the reference's multiprocess-on-one-host distributed test strategy
(SURVEY §4): sharding/collective tests run on
xla_force_host_platform_device_count=8 virtual devices.
"""
import os

# must be set before jax import (force: the session env may name the
# TPU; unit tests always run on the virtual CPU mesh)
os.environ["JAX_PLATFORMS"] = "cpu"
# numeric-gradient checks need exact f32 matmuls; production keeps the fast
# (MXU bf16) default
os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "highest")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        flags + " --xla_force_host_platform_device_count=8"

import jax
import numpy as np
import pytest

# a plugin may have pinned the platform in jax's config while importing;
# pin cpu after import, before backend init
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
assert jax.devices()[0].platform == "cpu" and len(jax.devices()) == 8


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)
    yield


@pytest.fixture(autouse=True, scope="session")
def _program_spans_only():
    """The suite counts the program's own spans (seven a decode step,
    three a training step), and a pass of the collector takes a
    millisecond or more every few thousand allocations of a process this
    size: the tracer's pause hooks (`host.gc`, `jit.*`) are off here, and
    tests/test_tracing_pauses.py installs them for itself."""
    from paddle_tpu.observability.tracing import TRACER
    TRACER.remove_pause_hooks()
    yield


@pytest.fixture()
def fresh_programs():
    """Fresh main/startup programs + scope for static-graph tests."""
    import paddle_tpu as paddle
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.fluid.scope import Scope, scope_guard
    paddle.enable_static()
    main, startup = framework.Program(), framework.Program()
    scope = Scope()
    with framework.program_guard(main, startup), scope_guard(scope), \
            unique_name.guard():
        yield main, startup, scope
    paddle.disable_static()
