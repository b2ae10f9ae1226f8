"""Low-level helpers: dtypes, places, global flags.

TPU-native replacements for the reference's platform layer:
  - Place variants        (/root/reference/paddle/fluid/platform/place.h:106)
  - gflags runtime knobs  (/root/reference/paddle/fluid/platform/flags.cc)
  - float16/bfloat16      (native jnp dtypes on TPU; platform/bfloat16.h)

On TPU there is no buddy allocator / device-context pool to manage: XLA owns
device memory and streams. `Place` survives as a lightweight routing tag used
by the executor to pick a jax device/backend.
"""
from __future__ import annotations

import os
from typing import Any

import jax
import numpy as np

# ---------------------------------------------------------------------------
# dtypes
# ---------------------------------------------------------------------------

_DTYPE_ALIASES = {
    "float32": "float32", "fp32": "float32", "float": "float32",
    "float64": "float64", "fp64": "float64", "double": "float64",
    "float16": "float16", "fp16": "float16", "half": "float16",
    "bfloat16": "bfloat16", "bf16": "bfloat16",
    "int8": "int8", "uint8": "uint8", "int16": "int16",
    "int32": "int32", "int64": "int64", "bool": "bool",
    "complex64": "complex64", "complex128": "complex128",
}


def batched_to_numpy(arrays):
    """Device→host gather with ONE blocking synchronization.

    ``np.asarray`` per fetch blocks on each array in turn: N serial
    waits, each starting its copy only after the previous one landed.
    Starting every copy async first lets the transfers overlap each
    other and whatever the device is still computing, so the host
    blocks once for the whole batch (not measured on the chip).

    Reference bar: the predictor/executor fetch loop is zero-copy per op
    (/root/reference/paddle/fluid/inference/api/analysis_predictor.h:120);
    this is the equivalent here — amortize the sync, not the copy.

    Non-jax entries (numpy arrays, scalars) pass through unchanged.
    """
    for a in arrays:
        if hasattr(a, "copy_to_host_async"):
            try:
                a.copy_to_host_async()
            except Exception:
                pass  # committed-elsewhere / deleted buffers: asarray below
    return [np.asarray(a) for a in arrays]


def batched_to_numpy_dict(named):
    """``{name: np.ndarray}`` from ``[(name, device_array), ...]`` with one
    device synchronization (see batched_to_numpy)."""
    return dict(zip([n for n, _ in named],
                    batched_to_numpy([v for _, v in named])))


def convert_dtype(dtype: Any) -> str:
    """Normalise any dtype spec (str/np/jnp) to a canonical string."""
    if dtype is None:
        return "float32"
    if isinstance(dtype, str):
        s = dtype.lower()
        if s in _DTYPE_ALIASES:
            return _DTYPE_ALIASES[s]
        raise ValueError(f"unsupported dtype string {dtype!r}")
    # VarDesc.VarType-style enums from our own namespace pass through
    name = getattr(dtype, "name", None)
    if name and name in _DTYPE_ALIASES:
        return _DTYPE_ALIASES[name]
    return np.dtype(dtype).name


def is_float_dtype(dtype: Any) -> bool:
    return convert_dtype(dtype) in ("float16", "bfloat16", "float32", "float64")


# ---------------------------------------------------------------------------
# Places — routing tags, not allocators
# ---------------------------------------------------------------------------

class Place:
    """Base device tag (reference: platform/place.h:106 PlaceBase variant)."""

    backend: str = "cpu"
    device_id: int = 0

    def __eq__(self, other):
        return (type(self) is type(other)
                and self.device_id == getattr(other, "device_id", 0))

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def jax_device(self):
        devs = jax.devices(self.backend) if self.backend != "default" \
            else jax.devices()
        return devs[self.device_id % len(devs)]


class CPUPlace(Place):
    backend = "cpu"

    def __init__(self, device_id: int = 0):
        self.device_id = device_id


class TPUPlace(Place):
    """The native accelerator place (north-star `paddle.TPUPlace`)."""
    # jax's default backend: the TPU where JAX found one, else whatever
    # JAX_PLATFORMS / jax's own platform search yields (the CPU in the
    # sandbox and the tests). Nothing here checks that it IS a TPU —
    # chip_smoke.py does, and fails when it is not.
    backend = "default"

    def __init__(self, device_id: int = 0):
        self.device_id = device_id


# Alias for API parity with reference CUDAPlace-based user code.
CUDAPlace = TPUPlace
CUDAPinnedPlace = CPUPlace
XPUPlace = TPUPlace


def default_place() -> Place:
    return TPUPlace(0)


# ---------------------------------------------------------------------------
# Global flags (reference: platform/flags.cc + global_value_getter_setter.cc)
# ---------------------------------------------------------------------------

_FLAGS: dict[str, Any] = {
    "FLAGS_check_nan_inf": False,        # per-op NaN sweep (checkify on TPU)
    "FLAGS_benchmark": False,            # force block_until_ready per run
    "FLAGS_eager_delete_tensor_gb": 0.0, # no-op: XLA owns memory
    "FLAGS_paddle_num_threads": 1,
    "FLAGS_use_system_allocator": False,
    "FLAGS_executor_log_level": 0,
    "FLAGS_jit_cache_size": 512,         # compiled-executable cache entries
    "FLAGS_tracer_amp_level": 0,
    "FLAGS_cudnn_deterministic": True,   # parity name; XLA is deterministic
    "FLAGS_profile": False,
}


def _load_env_flags():
    for k, v in os.environ.items():
        if k.startswith("FLAGS_"):
            cur = _FLAGS.get(k)
            if isinstance(cur, bool):
                _FLAGS[k] = v.lower() in ("1", "true", "yes")
            elif isinstance(cur, int):
                _FLAGS[k] = int(v)
            elif isinstance(cur, float):
                _FLAGS[k] = float(v)
            else:
                _FLAGS[k] = v


_load_env_flags()


def get_flags(keys):
    if isinstance(keys, str):
        keys = [keys]
    return {k: _FLAGS.get(k) for k in keys}


def set_flags(flags: dict):
    for k, v in flags.items():
        _FLAGS[k] = v


def globals_flags():
    return dict(_FLAGS)
