"""Inference API — Config + Predictor
(reference paddle/fluid/inference/api/analysis_predictor.h:82,
analysis_config.cc, ZeroCopyTensor; python surface
paddle.inference.create_predictor).

TPU redesign of the analysis stack: the reference runs ~30 IR fuse passes
then a NaiveExecutor op loop; here the feed->fetch-pruned Program is traced
ONCE into a single jitted XLA computation (fusion/memory planning are the
compiler's job — SURVEY §7), cached per input signature, with params held
as device arrays in a private scope.
"""
from __future__ import annotations

import os
import threading
from typing import Sequence

import numpy as np

__all__ = ["Config", "Predictor", "create_predictor", "PredictorTensor"]


class Config:
    """Subset of the reference AnalysisConfig surface that is meaningful
    on TPU; GPU/MKLDNN/TensorRT switches are accepted and recorded as
    no-ops for API compatibility."""

    def __init__(self, model_dir=None, prog_file=None, params_file=None):
        self._model_dir = model_dir or (os.path.dirname(prog_file)
                                        if prog_file else None)
        self._model_filename = os.path.basename(prog_file) \
            if prog_file else None
        self._params_filename = os.path.basename(params_file) \
            if params_file else None
        self._use_bf16 = False
        self._memory_optim = True
        self._ir_optim = True
        self._glog_info = True
        self._warmup = True

    # -- reference switches (recorded; XLA owns the machinery) ----------
    def set_model(self, model_path, params_file=None):
        """set_model(dir) or set_model(prog_file, params_file) — the
        two-argument reference form passes FILE paths."""
        if params_file is not None:
            self._model_dir = os.path.dirname(model_path) or "."
            self._model_filename = os.path.basename(model_path)
            self._params_filename = os.path.basename(params_file)
        else:
            self._model_dir = model_path

    def model_dir(self):
        return self._model_dir

    def enable_memory_optim(self, flag=True):
        self._memory_optim = flag

    def switch_ir_optim(self, flag=True):
        self._ir_optim = flag

    def disable_glog_info(self):
        self._glog_info = False

    def enable_use_gpu(self, *a, **k):  # accepted for parity; TPU build
        pass

    def disable_gpu(self):
        pass

    def set_cpu_math_library_num_threads(self, n):
        pass

    def enable_bf16(self, flag=True):
        """TPU-native switch: run inference compute in bfloat16 (MXU)."""
        self._use_bf16 = flag

    def switch_use_feed_fetch_ops(self, flag):
        pass


def _upcast(a):
    """Host-side output convention: bf16 compute results surface as f32."""
    return a.astype(np.float32) if a.dtype.name == "bfloat16" else a


class PredictorTensor:
    """ZeroCopyTensor equivalent (reference
    inference/api/analysis_predictor.h:120 ZeroCopy path): the handle may
    hold a *device-side* jax array after ``run()``; ``copy_to_cpu`` is the
    one host synchronization, so a caller that chains predictions and
    fetches only what it needs never pays a per-step device round-trip."""

    def __init__(self, name):
        self.name = name
        self._value = None

    def copy_from_cpu(self, arr):
        self._value = np.ascontiguousarray(arr)

    def copy_to_cpu(self):
        from ..fluid import core
        return _upcast(core.batched_to_numpy([self._value])[0])

    def reshape(self, shape):
        if self._value is not None:
            self._value = self._value.reshape(shape)

    @property
    def shape(self):
        return None if self._value is None else tuple(self._value.shape)


class Predictor:
    def __init__(self, config: Config):
        from ..fluid import core
        from ..fluid.executor import Executor
        from ..fluid.io import load_inference_model
        from ..fluid.scope import Scope, scope_guard

        if not config.model_dir():
            raise ValueError("Config has no model_dir/prog_file")
        self._config = config
        self._scope = Scope()
        self._exe = Executor(core.default_place())
        with scope_guard(self._scope):
            self._program, feeds, fetch_vars = load_inference_model(
                config.model_dir(), self._exe,
                model_filename=config._model_filename,
                params_filename=config._params_filename)
        self._feed_names = list(feeds)
        self._fetch_vars = fetch_vars
        self._fetch_names = [v.name for v in fetch_vars]
        # int8-stored weights (slim post-training quantization) are
        # reconstructed into the scope on load
        from ..slim.quantization import load_quantized_weights
        load_quantized_weights(config.model_dir(), self._scope)
        self._inputs = {n: PredictorTensor(n) for n in self._feed_names}
        self._outputs = {n: PredictorTensor(n) for n in self._fetch_names}
        # one predictor, many threads: the handle tensors are shared
        # mutable state, so run() (set inputs -> execute -> set outputs)
        # must be atomic or two concurrent callers interleave buffers
        # (reference semantics: one ZeroCopy predictor per thread, but a
        # lock is cheaper than a clone and the jit cache is shared)
        self._run_lock = threading.RLock()
        if config._use_bf16:
            # real bf16 inference: params live in HBM as bf16, matmuls hit
            # the MXU at full rate; outputs are cast back to fp32 in run()
            import jax.numpy as jnp
            for name in self._scope.local_var_names():
                v = self._scope.find_var(name)
                if hasattr(v, "dtype") and v.dtype == jnp.float32:
                    self._scope.set(name, v.astype(jnp.bfloat16))

    # -- handle API (reference ZeroCopy path) ---------------------------
    def get_input_names(self):
        return list(self._feed_names)

    def get_output_names(self):
        return list(self._fetch_names)

    def get_input_handle(self, name):
        return self._inputs[name]

    def get_input_tensor(self, name):  # old-API alias
        return self._inputs[name]

    def get_output_handle(self, name):
        return self._outputs[name]

    def get_output_tensor(self, name):
        return self._outputs[name]

    def run(self, inputs: Sequence[np.ndarray] | None = None):
        """Positional-inputs convenience (returns list of np arrays) or
        handle-style (copy_from_cpu then run() with no args).

        Thread-safe: concurrent run() calls serialize on an internal
        lock (handle-style callers that copy_from_cpu OUTSIDE run()
        from several threads still race by construction — use
        positional inputs or one predictor per thread for that)."""
        with self._run_lock:
            return self._run_locked(inputs)

    def _run_locked(self, inputs):
        from ..fluid.scope import scope_guard
        if inputs is not None:
            if len(inputs) != len(self._feed_names):
                raise ValueError(
                    f"run() got {len(inputs)} inputs, model expects "
                    f"{len(self._feed_names)}: {self._feed_names}")
            for n, a in zip(self._feed_names, inputs):
                self._inputs[n].copy_from_cpu(np.asarray(a))
        feed = {n: self._inputs[n]._value for n in self._feed_names}
        missing = [n for n, v in feed.items() if v is None]
        if missing:
            raise ValueError(
                f"inputs {missing} not set — copy_from_cpu them or pass "
                f"positional inputs to run()")
        if self._config._use_bf16:
            import jax.numpy as jnp
            feed = {n: (v.astype(jnp.bfloat16)
                        if v.dtype == np.float32 else v)
                    for n, v in feed.items()}
        # the executor compiles+caches per input signature — no separate
        # warmup pass needed. Outputs stay DEVICE-SIDE here (ZeroCopyRun
        # semantics): the handle's copy_to_cpu is the one sync point. The
        # positional convenience API below converts with a single batched
        # sync (core.batched_to_numpy) rather than one blocked fetch per
        # output.
        with scope_guard(self._scope):
            outs = self._exe.run(self._program, feed=dict(feed),
                                 fetch_list=self._fetch_names,
                                 return_numpy=False)
        for n, v in zip(self._fetch_names, outs):
            self._outputs[n]._value = v
        if inputs is None:
            return True  # handle-style ZeroCopyRun: fetch via handles
        from ..fluid import core
        return [_upcast(a) for a in core.batched_to_numpy(outs)]

    def clone(self):
        return Predictor(self._config)

    def clear_intermediate_tensor(self):
        pass

    def try_shrink_memory(self):
        self._exe._cache.clear()


def create_predictor(config: Config) -> Predictor:
    """reference paddle_infer.create_predictor."""
    return Predictor(config)
