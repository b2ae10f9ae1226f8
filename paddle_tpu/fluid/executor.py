"""Executor: lowers a whole Program block to ONE jitted XLA computation.

The reference Executor is a per-op interpreter — `for op in ops: op->Run`
(/root/reference/paddle/fluid/framework/executor.cc:476), with kernel choice,
data transfer and shape inference on every step. On TPU that loop is the
enemy: instead we trace the Block once with jax (each op's registered compute
fn), `jit` the result, and let XLA fuse/schedule. Parameter updates become
functional: updated persistables are returned from the jitted step and
donated, so optimizer ops get in-place semantics without a mutable Scope on
device (replaces inplace_op_inference.h behaviors).

Public surface mirrors reference python/paddle/fluid/executor.py:474,915
(`Executor(place).run(program, feed, fetch_list, ...)`).
"""
from __future__ import annotations

import logging
import warnings
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import (flight as _flight, perf as _perf,
                             registry as _obs)
from . import core, registry
from .framework import Block, Program, Variable, default_main_program
from .scope import Scope, global_scope

logger = logging.getLogger(__name__)

# executor telemetry: the compile cache is the recompile-storm tripwire
# — a rising miss rate with a flat run rate means feed shapes/structure
# keys churn (Operator Fusion in XLA, PAPERS.md) and every miss pays a
# full XLA compile
_EXEC_RUNS = _obs.counter(
    "paddle_tpu_executor_runs_total",
    "Executor.run invocations (one fused XLA step each)")
_EXEC_CACHE_HITS = _obs.counter(
    "paddle_tpu_executor_cache_hits_total",
    "run() served by an already-compiled program signature")
_EXEC_COMPILES = _obs.counter(
    "paddle_tpu_executor_compiles_total",
    "new program signatures traced+jitted (cache misses)")
_EXEC_RUN_SECONDS = _obs.histogram(
    "paddle_tpu_executor_run_seconds",
    "wall time of Executor.run (incl. compile on a miss)")

__all__ = ["Executor", "ExecContext", "global_scope", "scope_guard"]

from .scope import scope_guard  # re-export for API parity


class ExecContext:
    """Per-trace context handed to op compute fns.

    Carries the step RNG key (rng streams are derived per-op via fold_in on
    the op's stable `_rng_id`, so fwd and auto-vjp grad ops see identical
    randomness — the mask-saving trick of the reference's dropout grad for
    free), test/train mode, and a re-entrant block runner for control flow.
    """

    def __init__(self, rng_key, is_test: bool = False, executor=None):
        self.rng_key = rng_key
        self.is_test = is_test
        self.executor = executor
        self.mesh = None  # set by distributed executors

    def rng(self, attrs: dict):
        rid = attrs.get("_rng_id", 0)
        return jax.random.fold_in(self.rng_key, rid)

    def exec_block(self, block: Block, env: dict) -> dict:
        return trace_block(block, env, self)


def _env_get(env: dict, name: str):
    try:
        return env[name]
    except KeyError:
        raise RuntimeError(
            f"variable {name!r} is not initialised — feed it, produce it with "
            f"an op, or run the startup program first") from None


def trace_block(block: Block, env: dict, ctx: ExecContext,
                ops=None) -> dict:
    """Symbolically run every op of `block` (or the `ops` subset) against
    `env` (name -> value)."""
    for i, op in enumerate(block.ops if ops is None else ops):
        opdef = registry.require(op.type)
        ins = {slot: [_env_get(env, n) for n in names]
               for slot, names in op.inputs.items()}
        scope_name = op.attrs.get("name_scope") or op.type
        try:
            with jax.named_scope(scope_name.replace("/", ".") or op.type):
                outs = opdef.compute(ctx, ins, op.attrs)
        except (RuntimeError, ValueError, TypeError, IndexError) as e:
            from .errors import wrap_op_error
            shapes = {slot: [getattr(v, "shape", None) for v in vals]
                      for slot, vals in ins.items()}
            raise wrap_op_error(e, op.type, i,
                                extra=f"input shapes {shapes}:") from e
        for slot, names in op.outputs.items():
            vals = outs.get(slot) or []
            for name, val in zip(names, vals):
                if val is not None and name != "@EMPTY@":
                    env[name] = val
    return env


def _analyze_ops(ops):
    """Find names read before written (external inputs) and all writes."""
    written: set[str] = set()
    ext_reads: set[str] = set()

    def visit(op_list):
        for op in op_list:
            for n in op.input_arg_names:
                if n not in written:
                    ext_reads.add(n)
            for v in op.attrs.values():
                if isinstance(v, Block):
                    visit(v.ops)  # conservative: sub-block reads count here
            for n in op.output_arg_names:
                written.add(n)

    visit(ops)
    return ext_reads, written


def _block_reads(block: Block) -> set[str]:
    reads: set[str] = set()

    def visit(b):
        for op in b.ops:
            reads.update(op.input_arg_names)
            for v in op.attrs.values():
                if isinstance(v, Block):
                    visit(v)

    visit(block)
    return reads


def _prune_to_fetch(program: Program, fetch_names):
    """Backward slice: keep only ops whose outputs (transitively) feed a
    fetch target (reference framework/prune.h + Executor use_prune).
    Fetching only `loss` from a program that also contains optimizer ops
    skips the parameter updates, like the reference."""
    needed = set(fetch_names)
    keep: list = []
    for op in reversed(list(program.global_block().ops)):
        if set(op.output_arg_names) & needed:
            keep.append(op)
            needed.update(op.input_arg_names)
            for v in op.attrs.values():
                if isinstance(v, Block):
                    needed.update(_block_reads(v))
    keep.reverse()
    return keep


class Executor:
    """Reference executor.py:474 — but `run` compiles, caches and launches a
    single XLA computation per (program-structure, arg-signature)."""

    def __init__(self, place: core.Place | None = None):
        self.place = place or core.default_place()
        self._cache: dict[tuple, Any] = {}
        self._run_counter = 0
        # perf plane: compile misses time the first (compiling) call and
        # register the program's XLA cost; steady-state runs are fenced
        # and decomposed only when the sampler fires
        self._compile_missed = False
        self._perf_sampler = _perf.StepSampler("executor")
        self._perf_flops: dict[str, float] = {}

    # -- public API --------------------------------------------------------
    def run(self, program: Program | None = None, feed: dict | None = None,
            fetch_list: Sequence | None = None, scope: Scope | None = None,
            return_numpy: bool = True, use_program_cache: bool = True,
            use_prune: bool = False):
        import time as _time
        _EXEC_RUNS.inc()
        t0 = _time.perf_counter()
        try:
            return self._run_impl(program, feed, fetch_list, scope,
                                  return_numpy, use_program_cache,
                                  use_prune)
        finally:
            _EXEC_RUN_SECONDS.observe(_time.perf_counter() - t0)

    def _run_impl(self, program, feed, fetch_list, scope, return_numpy,
                  use_program_cache, use_prune):
        import time as _time
        t_host0 = _time.perf_counter()
        program = program if program is not None else default_main_program()
        # CompiledProgram.with_data_parallel → batch-axis sharding over the
        # mesh (replaces reference ParallelExecutor, parallel_executor.cc:443)
        if hasattr(program, "_program"):  # CompiledProgram wrapper
            program = program._program
        feed = dict(feed or {})
        scope = scope or global_scope()
        fetch_names = [v.name if isinstance(v, Variable) else str(v)
                       for v in (fetch_list or [])]

        # pserver programs run host-side: a blocking service loop has no
        # place inside a traced computation (reference executor runs
        # listen_and_serv the same way)
        for op in program.global_block().ops:
            if op.type == "listen_and_serv":
                from .ops.ps_ops import run_listen_and_serv
                run_listen_and_serv(op)
                return []

        run_ops = None
        if use_prune:
            # cached like _analysis_cache: pruning + analysis are O(#ops)
            # python per call otherwise
            pc = getattr(program, "_prune_cache", None)
            if pc is None:
                pc = program._prune_cache = {}
            key = tuple(fetch_names)
            if key not in pc:
                run_ops = _prune_to_fetch(program, fetch_names)
                ext_reads, written = _analyze_ops(run_ops)
                persistable = {v.name for v in program.list_vars()
                               if v.persistable}
                pc[key] = (run_ops, ext_reads, written, persistable,
                           (program._structure_key(), "prune", key))
            run_ops, ext_reads, written, persistable, skey = pc[key]
        else:
            if program._analysis_cache is None:
                ext_reads, written = _analyze_ops(
                    program.global_block().ops)
                persistable = {v.name for v in program.list_vars()
                               if v.persistable}
                program._analysis_cache = (ext_reads, written, persistable,
                                           program._structure_key())
            ext_reads, written, persistable, skey = \
                program._analysis_cache

        feed_names = sorted(feed)
        # persistables the computation must read from the scope
        ro_names, upd_names = [], []
        for n in sorted(persistable):
            is_input = n in ext_reads and n not in feed
            is_output = n in written
            if not is_input and not is_output:
                continue
            if is_output:
                upd_names.append(n)
            elif is_input:
                ro_names.append(n)
        # updated vars that are also read need their current value too
        upd_in_names = [n for n in upd_names if n in ext_reads]

        missing = [n for n in ext_reads - set(feed)
                   if n in persistable and not scope.has(n)]
        if missing:
            raise RuntimeError(
                f"persistable vars {missing[:8]} not found in scope — run the "
                f"startup program first")

        feed_vals = []
        for n in feed_names:
            var = program.global_block()._var_recursive(n)
            dtype = var.dtype if var is not None and var.dtype else None
            val = _to_array(feed[n], dtype)
            if var is not None and var.shape is not None:
                declared = var.shape
                ok = len(declared) == len(val.shape) and all(
                    d < 0 or d == s for d, s in zip(declared, val.shape))
                if not ok:
                    raise ValueError(
                        f"feed {n!r} has shape {tuple(val.shape)} but the "
                        f"graph declares {tuple(declared)}")
            feed_vals.append(val)

        upd_in_vals = [scope.find_var(n) for n in upd_in_names]
        ro_vals = [scope.find_var(n) for n in ro_names]

        mesh = self._mesh_for(program)
        if mesh is not None:
            feed_vals = [self._shard_batch(v, mesh) for v in feed_vals]

        fn = self._compile(program, skey, feed_names, feed_vals, ro_names,
                           ro_vals, upd_names, upd_in_names, upd_in_vals,
                           fetch_names, mesh, run_ops)

        self._run_counter += 1
        seed = np.uint32(
            (program.random_seed * 1000003 + self._run_counter) & 0xFFFFFFFF
            if program.random_seed
            else np.random.randint(0, 2**31))
        miss = self._compile_missed
        sample = (not miss) and self._perf_sampler.tick()
        ckey = None
        if miss or sample:
            ckey = _cost_key(feed_names, feed_vals, program._is_test)
        if miss:
            # lowering is abstract and rides the path that pays the
            # compile anyway; the buffers are still valid pre-call
            fl = _perf.register_jit_cost(
                "executor", ckey, fn, tuple(upd_in_vals), tuple(ro_vals),
                tuple(feed_vals), seed)
            if fl:
                self._perf_flops[ckey] = fl
        t_disp0 = _time.perf_counter()
        fetches, updates = fn(tuple(upd_in_vals), tuple(ro_vals),
                              tuple(feed_vals), seed)
        if miss or sample:
            t_disp1 = _time.perf_counter()
            jax.block_until_ready((fetches, updates))
            t_dev = _time.perf_counter()
            if miss:
                _perf.note_compile_seconds("executor", t_dev - t_disp0)
            else:
                fl = self._perf_flops.get(ckey)
                if fl:
                    _perf.set_mfu("executor",
                                  _perf.mfu(fl, t_dev - t_disp0))
        for n, v in zip(upd_names, updates):
            scope.set(n, v)
        if core.get_flags("FLAGS_benchmark")["FLAGS_benchmark"]:
            jax.block_until_ready(fetches)
        if core.get_flags("FLAGS_check_nan_inf")["FLAGS_check_nan_inf"]:
            # post-step sweep over fetches + updated persistables (the
            # whole block is ONE fused computation, so the reference's
            # per-op sweep maps to a per-step output sweep; for op-level
            # isolation run dygraph eager where the tracer checks per op)
            floats = [(n, v) for n, v in
                      list(zip(fetch_names, fetches))
                      + list(zip(upd_names, updates))
                      if jnp.issubdtype(jnp.result_type(v), jnp.floating)]
            # one stacked device reduction + one host read, not one blocked
            # fetch per var
            if floats:
                flags = core.batched_to_numpy([jnp.stack(
                    [jnp.all(jnp.isfinite(v)) for _, v in floats])])[0]
                bad = [n for (n, _), ok in zip(floats, flags) if not ok]
            else:
                bad = []
            if bad:
                raise RuntimeError(
                    f"NaN/Inf detected in {bad[:8]} after executor step "
                    f"(FLAGS_check_nan_inf)")
        if not sample:
            if return_numpy:
                return core.batched_to_numpy(fetches)
            return list(fetches)
        # sampled run: close the breakdown with the host->numpy copy as
        # the transfer phase (zero when the caller keeps device arrays)
        t_tr0 = _time.perf_counter()
        out = core.batched_to_numpy(fetches) if return_numpy \
            else list(fetches)
        _perf.record_breakdown("executor", {
            "host": t_disp0 - t_host0,
            "dispatch": t_disp1 - t_disp0,
            "device": t_dev - t_disp1,
            "transfer": (_time.perf_counter() - t_tr0)
            if return_numpy else 0.0,
        })
        return out

    # -- data-parallel sharding --------------------------------------------
    def _mesh_for(self, program):
        """Mesh when the program is marked data-parallel. Grad allreduce is
        implicit: batch-sharded inputs make XLA insert the psum in the
        sharded backward (replaces details/all_reduce_op_handle.cc).
        When `strategy.tensor_parallel` set a tp degree, the mesh gains a
        "tp" axis and persistables matching the strategy's sharding_rules
        are partitioned over it (GSPMD tensor parallelism — fresh design,
        absent in reference per SURVEY §2.9)."""
        info = getattr(program, "_sharding_info", None)
        if not info:
            return None
        import jax
        if len(jax.devices()) <= 1:
            return None
        tp = int(info.get("tp") or 1)
        if tp > 1:
            from ..distributed.mesh import make_mesh
            return make_mesh({"dp": -1, "tp": tp})
        from ..distributed.mesh import default_mesh
        return default_mesh()

    @staticmethod
    def _param_sharding(name, mesh, info, shape=None):
        """Resolve a persistable's NamedSharding from the strategy's
        tensor-parallel rules; default replicated. A matching rule is
        applied only where it fits the value: optimizer accumulators
        inherit their param's name prefix (fc_0.w_0_beta1_pow_acc_0), so a
        spec with more dims than the value is ignored (scalar beta-pows
        stay replicated, same-shaped moments pick up the param's sharding),
        and spec axes that don't divide the dim are dropped."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        if info and info.get("tp_rules"):
            from ..parallel.sharding import ShardingRules
            rules = ShardingRules(
                [(pat, P(*spec)) for pat, spec in info["tp_rules"]])
            spec = rules.spec(name, mesh)
            if shape is not None:
                if len(spec) > len(shape):
                    spec = P()
                else:
                    def fits(i, entry):
                        axes = entry if isinstance(entry, (tuple, list)) \
                            else (entry,)
                        size = int(np.prod([mesh.shape[a] for a in axes]))
                        return shape[i] % size == 0
                    spec = P(*(e if e is None or fits(i, e) else None
                               for i, e in enumerate(spec)))
            return NamedSharding(mesh, spec)
        return NamedSharding(mesh, P())

    @staticmethod
    def _val_sharding(val, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P
        ndev = mesh.shape["dp"]
        if getattr(val, "ndim", 0) >= 1 and val.shape[0] % ndev == 0:
            return NamedSharding(mesh, P("dp"))
        return NamedSharding(mesh, P())

    @classmethod
    def _shard_batch(cls, val, mesh):
        import jax
        return jax.device_put(val, cls._val_sharding(val, mesh))

    # -- compilation -------------------------------------------------------
    def _compile(self, program, skey, feed_names, feed_vals, ro_names,
                 ro_vals, upd_names, upd_in_names, upd_in_vals, fetch_names,
                 mesh=None, run_ops=None):
        sig = (
            skey,
            None if mesh is None else tuple(mesh.shape.items()),
            repr((getattr(program, "_sharding_info", None) or {})
                 .get("tp_rules")),
            tuple(ro_names), tuple(upd_names), tuple(upd_in_names),
            tuple(fetch_names),
            tuple((n, v.shape, str(jnp.result_type(v)))
                  for n, v in zip(feed_names, feed_vals)),
            tuple((v.shape, str(jnp.result_type(v)))
                  for v in list(upd_in_vals) + list(ro_vals)),
            program._is_test,
        )
        fn = self._cache.get(sig)
        if fn is not None:
            self._cache[sig] = self._cache.pop(sig)  # refresh LRU order
            _EXEC_CACHE_HITS.inc()
            self._compile_missed = False
            return fn
        _EXEC_COMPILES.inc()
        self._compile_missed = True
        # one flight event per cache miss: a burst of these in a
        # postmortem ring IS a recompile storm (feed shapes/structure
        # churning), with the feed shapes as the evidence
        _flight.record("executor", "compile",
                       feeds=[[n, list(v.shape)] for n, v
                              in zip(feed_names, feed_vals)],
                       cache_size=len(self._cache))

        is_test = program._is_test
        gb = program.global_block()

        def step(upd_in, ro, feeds, seed):
            env: dict[str, Any] = {}
            env.update(zip(upd_in_names, upd_in))
            env.update(zip(ro_names, ro))
            env.update(zip(feed_names, feeds))
            ctx = ExecContext(jax.random.PRNGKey(seed), is_test=is_test,
                              executor=self)
            trace_block(gb, env, ctx, ops=run_ops)
            fetches = tuple(_env_get(env, n) for n in fetch_names)
            updates = tuple(env[n] for n in upd_names)
            return fetches, updates

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # cpu donation warnings
            if mesh is None:
                fn = jax.jit(step, donate_argnums=(0,))
            else:
                # params/state replicated unless a tensor-parallel rule
                # matches; fetches replicated; the batch stays sharded
                # inside, grads psum automatically
                from jax.sharding import NamedSharding, PartitionSpec as P
                info = getattr(program, "_sharding_info", None)
                repl = NamedSharding(mesh, P())
                shapes = {n: getattr(v, "shape", None)
                          for n, v in list(zip(upd_in_names, upd_in_vals))
                          + list(zip(ro_names, ro_vals))}
                psh = {n: self._param_sharding(n, mesh, info,
                                               shapes.get(n))
                       for n in set(upd_in_names) | set(ro_names)
                       | set(upd_names)}
                fn = jax.jit(
                    step, donate_argnums=(0,),
                    in_shardings=(
                        tuple(psh[n] for n in upd_in_names),
                        tuple(psh[n] for n in ro_names),
                        tuple(self._val_sharding(v, mesh)
                              for v in feed_vals),
                        None),
                    out_shardings=(tuple(repl for _ in fetch_names),
                                   tuple(psh[n] for n in upd_names)))
        cap = core.get_flags(
            "FLAGS_jit_cache_size")["FLAGS_jit_cache_size"]
        while self._cache and len(self._cache) >= cap:
            self._cache.pop(next(iter(self._cache)))  # evict oldest (LRU)
        if cap > 0:
            self._cache[sig] = fn
        return fn

    # -- dataset training ---------------------------------------------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           fetch_handler=None):
        """Run the program over every Dataset batch (reference
        executor.py:1597 → C++ MultiTrainer/HogwildWorker loop,
        trainer.h:85, device_worker.h:215). Here the dataset's reader
        threads keep the input queue full while one device loop feeds the
        single fused XLA step; `thread` is accepted for API parity and
        routed to the dataset's reader pool."""
        if dataset is None:
            raise ValueError("train_from_dataset needs a dataset")
        if thread:
            dataset.set_thread(thread)
        fetch_list = fetch_list or []
        fetch_info = fetch_info or [getattr(v, "name", str(v))
                                    for v in fetch_list]
        last = None
        for step_i, feed in enumerate(dataset.batch_iter()):
            res = self.run(program, feed=feed, fetch_list=fetch_list,
                           scope=scope)
            last = res
            if print_period and (step_i + 1) % print_period == 0:
                if fetch_list:
                    msg = ", ".join(
                        f"{n}={np.ravel(np.asarray(v))[0]:.6f}"
                        for n, v in zip(fetch_info, res))
                    print(f"[train_from_dataset] step {step_i + 1}: "
                          f"{msg}", flush=True)
                # fetch_handler fires on the period regardless of
                # fetch_list (reference FetchHandler runs independently
                # of printing)
                if fetch_handler is not None:
                    fetch_handler(res)
        return last

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           fetch_handler=None):
        """Like train_from_dataset but for test-mode programs (reference
        executor.py:1476)."""
        return self.train_from_dataset(program, dataset, scope, thread,
                                       debug, fetch_list, fetch_info,
                                       print_period, fetch_handler)

    def close(self):
        self._cache.clear()


def _cost_key(feed_names, feed_vals, is_test: bool) -> str:
    """Deterministic low-cardinality cost-registry key for a compiled
    program signature: mode + the first few feed shapes (what actually
    distinguishes compile buckets in practice)."""
    feeds = ";".join(
        f"{n}{'x'.join(map(str, v.shape)) or 'scalar'}"
        for n, v in list(zip(feed_names, feed_vals))[:4])
    return f"{'test' if is_test else 'train'}[{feeds}]"


def _to_array(x, dtype=None):
    if hasattr(x, "dtype") and not isinstance(x, np.ndarray):
        return x  # already a device array / Tensor value
    arr = np.asarray(x)
    if dtype is not None and arr.dtype != np.dtype(dtype):
        arr = arr.astype(dtype)
    return jnp.asarray(arr)
