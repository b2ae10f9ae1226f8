"""Save/load of parameters and inference programs.

Parity with reference python/paddle/fluid/io.py (save_persistables,
load_persistables, save_inference_model, load_inference_model) and
paddle.static.save/load (io.py:1669,1730). Storage format: one `.pdparams`
npz-style archive for tensors + a serialised Program (paddle_tpu proto) for
inference models.

Checkpoint-store routing: with ``PADDLE_TPU_CKPT`` set, save paths write
through ``paddle_tpu.checkpoint`` (content-addressed chunks + CRC'd
manifest, atomic commit, incremental dedup across steps, no pickle on
restore — docs/CHECKPOINT.md) into a ``<name>.ckpt`` directory beside
where the legacy file would sit. Load paths AUTO-DETECT the format, so
legacy archives stay readable regardless of the env knob.
"""
from __future__ import annotations

import os
import pickle

import numpy as np

from . import core
from .executor import global_scope
from .framework import Program, Variable, default_main_program

__all__ = [
    "DataLoader",
    "save_vars", "save_params", "save_persistables", "load_vars",
    "load_params", "load_persistables", "save_inference_model",
    "load_inference_model", "save", "load", "save_train_model",
]


def _collect(program, predicate):
    return [v for v in program.list_vars() if predicate(v)]


def _ckpt_root(path: str) -> str:
    """Store-format sibling of a legacy archive path."""
    return path + ".ckpt"


def _save_blob(blob: dict, path: str):
    """One name->ndarray blob to disk: checkpoint store when
    PADDLE_TPU_CKPT is on, legacy pickle archive otherwise."""
    from .. import checkpoint as ckpt
    if ckpt.enabled():
        ckpt.CheckpointStore(_ckpt_root(path)).save(blob)
        return
    with open(path, "wb") as f:
        pickle.dump(blob, f, protocol=4)


def _prefer_store(root: str, legacy_path: str) -> bool:
    """Format auto-detection. When BOTH a committed store and a legacy
    archive exist (a job toggled PADDLE_TPU_CKPT between saves), the
    NEWER save wins — silently loading stale parameters from the older
    format is the one wrong answer."""
    from .. import checkpoint as ckpt
    manifests = ckpt.list_manifests(root)
    if not manifests:
        return False
    if not os.path.exists(legacy_path):
        return True
    store_mtime = max(os.path.getmtime(p) for _s, p in manifests)
    return store_mtime >= os.path.getmtime(legacy_path)


def _save_legacy_pickle(obj, path: str):
    """Write one legacy pickle archive (the PADDLE_TPU_CKPT=off format;
    incubate's CheckpointSaver routes here to stay import-free of
    pickle itself)."""
    with open(path, "wb") as f:
        pickle.dump(obj, f, protocol=4)


def legacy_pickle_load(path: str):
    """Read one LEGACY on-disk pickle archive (pre-store formats:
    .pdparams blobs, incubate ckpt-N/params.pkl). Deliberately the
    only pickle-deserialization entry point outside this module's own
    loaders: the wire/checkpoint trees (distributed/, checkpoint/,
    incubate/) are pickle-free by static check, and their legacy
    back-compat reads route HERE — a local disk archive the operator
    placed, never wire input."""
    with open(path, "rb") as f:
        return pickle.load(f)


def _load_blob(path: str) -> dict:
    """Auto-detecting load: the newest of {committed store dir, legacy
    archive}; else a clear FileNotFoundError (not a bare KeyError)."""
    from .. import checkpoint as ckpt
    root = _ckpt_root(path)
    if _prefer_store(root, path):
        blob, _meta = ckpt.CheckpointStore(root).restore()
        return blob
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no parameter archive at {path} (and no checkpoint store "
            f"at {root})")
    with open(path, "rb") as f:
        return pickle.load(f)


def _is_persistable(v):
    return v.persistable and not v.is_data


def save_vars(executor, dirname, main_program=None, vars=None,
              predicate=None, filename=None):
    program = main_program or default_main_program()
    if vars is None:
        vars = _collect(program, predicate or _is_persistable)
    os.makedirs(dirname, exist_ok=True)
    scope = global_scope()
    # one device sync for the whole save, not one per var (core.py
    # batched_to_numpy)
    blob = core.batched_to_numpy_dict(
        [(v.name, val) for v in vars
         if (val := scope.find_var(v.name)) is not None])
    path = os.path.join(dirname, filename or "__all__.pdparams")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    _save_blob(blob, path)
    return path


def save_params(executor, dirname, main_program=None, filename=None):
    return save_vars(executor, dirname, main_program,
                     predicate=lambda v: getattr(v, "trainable", False),
                     filename=filename)


def save_persistables(executor, dirname, main_program=None, filename=None):
    return save_vars(executor, dirname, main_program, filename=filename)


def load_vars(executor, dirname, main_program=None, vars=None,
              predicate=None, filename=None):
    import jax.numpy as jnp
    path = os.path.join(dirname, filename or "__all__.pdparams")
    blob = _load_blob(path)
    scope = global_scope()
    program = main_program or default_main_program()
    want = None
    if vars is not None:
        want = {v.name for v in vars}
    elif predicate is not None:
        want = {v.name for v in _collect(program, predicate)}
    if want is not None:
        missing = sorted(want - set(blob))
        if missing:
            raise ValueError(
                f"variables missing from {path}: {missing} "
                f"(archive holds {len(blob)} vars)")
    for name, arr in blob.items():
        if want is None or name in want:
            scope.set(name, jnp.asarray(arr))


def load_params(executor, dirname, main_program=None, filename=None):
    load_vars(executor, dirname, main_program, filename=filename)


def load_persistables(executor, dirname, main_program=None, filename=None):
    load_vars(executor, dirname, main_program, filename=filename)


def save_inference_model(dirname, feeded_var_names, target_vars, executor,
                         main_program=None, model_filename=None,
                         params_filename=None, export_for_deployment=True,
                         program_only=False):
    """Prune program to feed→fetch path + save params
    (reference io.py:1164). Program serialisation via paddle_tpu proto."""
    from .proto import serialize_program
    program = main_program or default_main_program()
    program = program.clone(for_test=True)
    # prune to the feed->fetch slice (reference framework/prune.h via
    # io.py:1164): ops outside the path — e.g. the loss/metric branch
    # reading labels — must not survive into the deployed model
    from .executor import _prune_to_fetch
    gb = program.global_block()
    keep = _prune_to_fetch(program, [v.name for v in target_vars])
    gb.ops[:] = keep
    # prune vars too: optimizer accumulators are persistable and would
    # otherwise ship (and triple) the deployed params file
    referenced = set(feeded_var_names) | \
        {n for op in keep for n in op.input_arg_names} | \
        {n for op in keep for n in op.output_arg_names}
    for name in [n for n in gb.vars if n not in referenced]:
        del gb.vars[name]
    program._bump_version()
    os.makedirs(dirname, exist_ok=True)
    meta = {
        "feed_names": list(feeded_var_names),
        "fetch_names": [v.name for v in target_vars],
    }
    model_path = os.path.join(dirname, model_filename or "__model__")
    # model_filename may itself carry subdirectories ("deploy/__model__")
    os.makedirs(os.path.dirname(model_path) or ".", exist_ok=True)
    with open(model_path, "wb") as f:
        f.write(serialize_program(program, meta))
    if not program_only:
        save_persistables(executor, dirname, program,
                          filename=params_filename)
    return meta["fetch_names"]


def load_inference_model(dirname, executor, model_filename=None,
                         params_filename=None):
    from .proto import deserialize_program
    model_path = os.path.join(dirname, model_filename or "__model__")
    with open(model_path, "rb") as f:
        program, meta = deserialize_program(f.read())
    load_persistables(executor, dirname, program, filename=params_filename)
    fetch_vars = [program.global_block()._var_recursive(n)
                  for n in meta["fetch_names"]]
    return program, meta["feed_names"], fetch_vars


def save(program: Program, model_path: str):
    """paddle.static.save (reference io.py:1669): params + opt state."""
    dirname = os.path.dirname(model_path) or "."
    os.makedirs(dirname, exist_ok=True)
    scope = global_scope()
    blob = core.batched_to_numpy_dict(
        [(v.name, val) for v in program.list_vars() if v.persistable
         and (val := scope.find_var(v.name)) is not None])
    _save_blob(blob, model_path + ".pdparams")


def load(program: Program, model_path: str, executor=None, var_list=None):
    import jax.numpy as jnp
    blob = _load_blob(model_path + ".pdparams")
    scope = global_scope()
    for name, arr in blob.items():
        scope.set(name, jnp.asarray(arr))


class DataLoader:
    """Static-graph data loader (reference fluid/reader.py GeneratorLoader
    / py_reader): `from_generator(feed_list, capacity)` builds an iterable
    that prefetches generator batches on a background thread and yields
    executor feed dicts — the py_reader double-buffer, minus the device-
    side queue ops XLA's async dispatch makes redundant."""

    def __init__(self, feed_list, capacity, iterable=True):
        self._feed_list = list(feed_list)
        self._capacity = max(2, int(capacity))
        self._iterable = iterable
        self._gen = None

    @staticmethod
    def from_generator(feed_list=None, capacity=16, use_double_buffer=True,
                       iterable=True, return_list=False,
                       use_multiprocess=False, drop_last=True):
        if not feed_list:
            raise ValueError("from_generator needs feed_list variables")
        return DataLoader(feed_list, capacity, iterable)

    # -- generator binding (reference set_* trio) -----------------------
    def set_sample_generator(self, reader, batch_size, drop_last=True,
                             places=None):
        def batched():
            batch = []
            for sample in reader():
                batch.append(sample if isinstance(sample, (list, tuple))
                             else (sample,))
                if len(batch) == batch_size:
                    yield [np.stack([b[i] for b in batch])
                           for i in range(len(batch[0]))]
                    batch = []
            if batch and not drop_last:
                yield [np.stack([b[i] for b in batch])
                       for i in range(len(batch[0]))]
        self._gen = batched
        return self

    def set_sample_list_generator(self, reader, places=None):
        def batched():
            for samples in reader():
                yield [np.stack([s[i] for s in samples])
                       for i in range(len(samples[0]))]
        self._gen = batched
        return self

    def set_batch_generator(self, reader, places=None):
        self._gen = reader
        return self

    def __call__(self):
        return iter(self)

    def __iter__(self):
        if self._gen is None:
            raise RuntimeError(
                "bind a generator first: set_batch_generator / "
                "set_sample_generator / set_sample_list_generator")
        import queue as _q
        import threading
        q: "_q.Queue" = _q.Queue(maxsize=self._capacity)
        _END = object()
        err = []

        def producer():
            try:
                for batch in self._gen():
                    q.put(batch)
            except BaseException as e:
                err.append(e)
            finally:
                q.put(_END)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        names = [v.name for v in self._feed_list]
        while True:
            item = q.get()
            if item is _END:
                break
            if not isinstance(item, dict):
                item = dict(zip(names, item))
            yield item
        if err:
            raise err[0]


def save_train_model(dirname, feeded_var_names, loss, executor,
                     main_program=None, startup_program=None):
    """Save a TRAINABLE program pair for language-free training hosts
    (reference fluid/train/demo/demo_trainer.cc loads exactly this:
    startup + main with backward/optimizer ops + persistables). Consumed
    by capi/train_host.py behind the PD_Trainer C ABI."""
    from .proto import serialize_program
    from . import framework as fw
    main_program = main_program or fw.default_main_program()
    startup_program = startup_program or fw.default_startup_program()
    os.makedirs(dirname, exist_ok=True)
    meta = {"feed_names": list(feeded_var_names),
            "fetch_names": [loss.name if hasattr(loss, "name") else
                            str(loss)]}
    with open(os.path.join(dirname, "main.program"), "wb") as f:
        f.write(serialize_program(main_program, meta))
    with open(os.path.join(dirname, "startup.program"), "wb") as f:
        f.write(serialize_program(startup_program))
    if executor is not None:
        pdir = os.path.join(dirname, "params")
        os.makedirs(pdir, exist_ok=True)
        save_persistables(executor, pdir, main_program)
