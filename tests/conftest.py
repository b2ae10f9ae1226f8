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


# -- slowest-test tracker (perf plane) ----------------------------------
# Every run leaves a per-test duration artifact so
# `python -m paddle_tpu.observability.perfwatch compare --tests old new`
# can flag tests that got >2x slower between two runs (the tier-1 wall
# time ratchet). Path override: PADDLE_TPU_TEST_TIMES.
_test_durations: dict = {}


def pytest_runtest_logreport(report):
    if report.when == "call":
        _test_durations[report.nodeid] = \
            _test_durations.get(report.nodeid, 0.0) + report.duration


def pytest_sessionfinish(session, exitstatus):
    if not _test_durations:
        return
    import json
    path = os.environ.get("PADDLE_TPU_TEST_TIMES") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".pytest_times.json")
    try:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"schema": "paddle_tpu.test_times/1",
                       "tests": {k: round(v, 4)
                                 for k, v in _test_durations.items()}},
                      f, indent=0, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass


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
