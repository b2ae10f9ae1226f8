"""Invariant rules migrated from the three legacy AST scripts
(scripts/check_no_wire_pickle.py, check_metric_names.py,
check_env_knobs.py).

The detection logic lives HERE, once: tree-level helper functions
(``wire_hits``, ``metric_regs``, ``knobs_in_tree``) operate on an
already-parsed AST so the engine runs them on its single shared parse,
while the ``*_main`` entry points reproduce the legacy scripts'
standalone behavior — same argv conventions, same stdout, same exit
codes — so the script files themselves are thin wrappers and the
existing test wiring stays green.
"""
from __future__ import annotations

import ast
import os
import re

from ..core import FileContext, KeyCounter, Rule, register

__all__ = ["WirePickleRule", "MetricNamesRule", "EnvKnobsRule",
           "REQUIRED_METRICS", "wire_hits", "metric_regs",
           "knobs_in_tree", "wire_main", "metric_main", "env_main"]


# ---------------------------------------------------------------------------
# no-pickle-on-the-wire (from check_no_wire_pickle.py)
# ---------------------------------------------------------------------------

BANNED_PICKLE_ATTRS = {"load", "loads", "Unpickler"}
PICKLE_MODULES = {"pickle", "cPickle", "_pickle", "dill"}

# subtrees held to the data-only rule when scanning the shipped tree
# (relative to paddle_tpu/): the transport package and every
# checkpoint RESTORE path (docs/PS_WIRE_PROTOCOL.md, CHECKPOINT.md).
# incubate/ joined when its CheckpointSaver moved onto the store: its
# one legacy pickle read lives in fluid/io.legacy_pickle_load (a
# position-exempt disk-archive shim, like fluid/io's own)
WIRE_SUBTREES = ("distributed/", "checkpoint/", "incubate/")


def _pickle_aliases(tree: ast.AST) -> set[str]:
    """Names that refer to a pickle module or its load/loads in this
    module (import pickle / import pickle as p / from pickle import
    loads as x)."""
    mods, funcs = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.split(".")[0] in PICKLE_MODULES:
                    mods.add(a.asname or a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            if node.module and node.module.split(".")[0] \
                    in PICKLE_MODULES:
                for a in node.names:
                    if a.name in BANNED_PICKLE_ATTRS:
                        funcs.add(a.asname or a.name)
    return mods | funcs


def wire_hits(tree: ast.AST) -> list[tuple[int, str]]:
    """(line, what) pickle-deserialization sites in one parsed file."""
    aliases = _pickle_aliases(tree)
    hits = []
    for node in ast.walk(tree):
        # pickle.load(...)/pickle.loads(...)/pickle.Unpickler(...)
        if isinstance(node, ast.Attribute) \
                and node.attr in BANNED_PICKLE_ATTRS \
                and isinstance(node.value, ast.Name) \
                and node.value.id in aliases:
            hits.append((node.lineno,
                         f"{node.value.id}.{node.attr}"))
        # from pickle import loads; loads(...)
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Name) \
                and node.func.id in aliases:
            hits.append((node.lineno, f"{node.func.id}(...)"))
        # np.load(..., allow_pickle=True)
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "load":
            for kw in node.keywords:
                if kw.arg == "allow_pickle" \
                        and isinstance(kw.value, ast.Constant) \
                        and kw.value.value is True:
                    hits.append((node.lineno,
                                 "np.load(allow_pickle=True)"))
    return hits


def _wire_check_path(path: str) -> list[tuple[int, str]]:
    """Standalone-file form (legacy script path): parse + scan."""
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, path)
    except SyntaxError as e:
        return [(e.lineno or 0, f"unparseable: {e.msg}")]
    return wire_hits(tree)


def wire_main(argv: list[str], repo: str) -> int:
    """check_no_wire_pickle.py behavior, byte-identical output."""
    if len(argv) > 1:
        roots = argv[1:]
    else:
        roots = [os.path.join(repo, "paddle_tpu", "distributed"),
                 os.path.join(repo, "paddle_tpu", "checkpoint"),
                 os.path.join(repo, "paddle_tpu", "incubate")]
    bad = []
    for root in roots:
        for dirpath, _dirs, files in os.walk(root):
            for fn in sorted(files):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                for lineno, what in _wire_check_path(path):
                    bad.append(f"{path}:{lineno}: {what}")
    shown = ", ".join(roots)
    if bad:
        print("pickle deserialization is banned under "
              f"{shown} (wire-safety, see docs/PS_WIRE_PROTOCOL.md "
              "and docs/CHECKPOINT.md):")
        print("\n".join(bad))
        return 1
    print(f"OK: no pickle deserialization under {shown}")
    return 0


@register
class WirePickleRule(Rule):
    name = "wire-pickle"
    description = ("pickle deserialization in the transport/checkpoint "
                   "trees (RCE-on-the-wire hazard)")

    def visit(self, ctx: FileContext):
        # a file INSIDE the shipped tree is judged by its position
        # there whatever the scan root (paddle_tpu/fluid/io.py's
        # legacy disk-archive pickle is exempt even under
        # `--root paddle_tpu/fluid`); files outside the tree
        # (fixtures) are all held to the rule
        if ctx.tree_rel is not None and not ctx.tree_rel.startswith(
                WIRE_SUBTREES):
            return ()
        dedup = KeyCounter()   # content-based keys; #2.. on repeats
        keypath = ctx.tree_rel or ctx.relpath  # stable across roots
        return [self.finding(
            ctx, line,
            f"{what} — pickle deserialization is banned here "
            f"(wire-safety: docs/PS_WIRE_PROTOCOL.md, "
            f"docs/CHECKPOINT.md)",
            key=dedup(f"{keypath}::{what}"))
            for line, what in wire_hits(ctx.tree)]


# ---------------------------------------------------------------------------
# metric naming (from check_metric_names.py)
# ---------------------------------------------------------------------------

REGISTER_FUNCS = {"counter", "gauge", "histogram"}
NAME_RE = re.compile(r"^paddle_tpu_[a-z][a-z0-9_]*$")
# the registry's own implementation/docs mention registration calls in
# prose/examples; skip only files that themselves DEFINE the helpers
SKIP_FILES = {os.path.join("observability", "registry.py"),
              os.path.join("observability", "__init__.py")}

# metric families whose presence is contractual (docs/CHECKPOINT.md,
# docs/DEBUGGING.md): a registration site must exist for each, or the
# check fails
REQUIRED_METRICS = {
    "paddle_tpu_ckpt_save_seconds",
    "paddle_tpu_ckpt_restore_seconds",
    "paddle_tpu_ckpt_bytes_written_total",
    "paddle_tpu_ckpt_chunks_written_total",
    "paddle_tpu_ckpt_chunks_dedup_hits_total",
    "paddle_tpu_ckpt_wal_rows_appended_total",
    "paddle_tpu_ckpt_wal_compactions_total",
    "paddle_tpu_ckpt_manifests_committed_total",
    # checkpoint async-writer queue (docs/DEBUGGING.md): a rising depth
    # means the save cadence is outrunning the writer
    "paddle_tpu_ckpt_writer_queue_depth",
    "paddle_tpu_ckpt_writer_pending_bytes",
    "paddle_tpu_ckpt_inflight_save_seconds",
    # stall watchdog + flight recorder (docs/DEBUGGING.md): the
    # postmortem tier's own observability is part of its acceptance
    # contract — deleting it would ship silent hang detection
    "paddle_tpu_watchdog_checks_total",
    "paddle_tpu_watchdog_stalls_total",
    "paddle_tpu_watchdog_stalled",
    "paddle_tpu_watchdog_progress_age_seconds",
    "paddle_tpu_flight_events_total",
    "paddle_tpu_flight_dropped_total",
    # SLO harness (docs/SERVING.md production traffic harness): the
    # load generator's attainment/goodput surface and the scheduler's
    # admission-control decisions are acceptance-contractual — the
    # chaos drills assert against these exact names
    "paddle_tpu_slo_ttft_seconds",
    "paddle_tpu_slo_inter_token_seconds",
    "paddle_tpu_slo_deadline_met_total",
    "paddle_tpu_slo_deadline_missed_total",
    "paddle_tpu_slo_goodput_tokens_total",
    "paddle_tpu_slo_attainment_ratio",
    "paddle_tpu_serving_expired_in_queue_total",
    "paddle_tpu_serving_shed_total",
    "paddle_tpu_serving_quota_rejected_total",
    # serving router (docs/SERVING.md replicated serving): failover,
    # replica health and respawn visibility is the fleet's acceptance
    # contract — the chaos drills assert against these exact names
    "paddle_tpu_router_requests_total",
    "paddle_tpu_router_dispatch_total",
    "paddle_tpu_router_failovers_total",
    "paddle_tpu_router_replica_state",
    "paddle_tpu_router_respawns_total",
    "paddle_tpu_router_stream_stalls_total",
    "paddle_tpu_router_inflight",
    # autobench persistent tuning cache (docs/KERNELS.md): whether a
    # replica is measuring in-process (cold) or adopting pre-warmed
    # decisions (hit) is the cache's acceptance contract
    "paddle_tpu_autobench_cache_hits_total",
    "paddle_tpu_autobench_cache_misses_total",
    "paddle_tpu_autobench_cache_stale_total",
    "paddle_tpu_autobench_cache_corrupt_total",
    "paddle_tpu_autobench_measure_total",
    # multiplexed RPC transport (docs/PS_WIRE_PROTOCOL.md mux framing):
    # in-flight depth, pool size, zero-copy proof (bytes-copied by
    # path) and reply reordering are the transport's acceptance
    # contract — tests/test_rpc_mux.py asserts against these exact names
    "paddle_tpu_rpc_mux_inflight",
    "paddle_tpu_rpc_mux_channels",
    "paddle_tpu_rpc_mux_bytes_copied_total",
    "paddle_tpu_rpc_mux_out_of_order_total",
    # online-learning publish pipeline (docs/ONLINE_LEARNING.md):
    # publication/rollback counts, cross-version chunk dedup, hot-swap
    # phase timing and subscriber staleness are the loop's acceptance
    # contract — tests/test_publish.py asserts against these exact
    # names
    "paddle_tpu_publish_publications_total",
    "paddle_tpu_publish_rollbacks_total",
    "paddle_tpu_publish_dedup_ratio",
    "paddle_tpu_publish_seconds",
    "paddle_tpu_publish_swap_seconds",
    "paddle_tpu_publish_subscriber_lag_versions",
    # fleet telemetry plane (docs/OBSERVABILITY.md): span-ring loss,
    # agent-side backpressure drops and the tail-sampling verdict split
    # are the plane's honesty surface — without them telemetry loss is
    # silent and every downstream dashboard lies
    "paddle_tpu_trace_dropped_total",
    "paddle_tpu_telemetry_agent_dropped_total",
    "paddle_tpu_telemetry_traces_total",
    # perf observability plane (docs/OBSERVABILITY.md perf plane): the
    # cost registry, live MFU/breakdown attribution, compile wall-time
    # and memory headroom gauges are the plane's acceptance contract —
    # the collector's summary and the `top` perf pane read these exact
    # names
    "paddle_tpu_perf_flops",
    "paddle_tpu_perf_bytes",
    "paddle_tpu_perf_mfu",
    "paddle_tpu_perf_step_breakdown_seconds",
    "paddle_tpu_perf_compile_seconds",
    "paddle_tpu_perf_hbm_bytes",
    "paddle_tpu_perf_kv_cache_bytes",
    # elastic training (docs/ELASTIC.md): hang-vs-straggler split,
    # restart/give-up accounting and resume latency are the gang-
    # restart tier's acceptance contract — the chaos drills and the
    # launcher's watchdog read these exact names
    "paddle_tpu_elastic_heartbeats_total",
    "paddle_tpu_elastic_stale_ranks",
    "paddle_tpu_elastic_straggler_ranks",
    "paddle_tpu_elastic_step_lag",
    "paddle_tpu_elastic_restarts_total",
    "paddle_tpu_elastic_crash_loop_giveups_total",
    "paddle_tpu_elastic_resume_seconds",
    # PS high availability (docs/PS_HA.md): role/epoch/fencing state,
    # per-standby replication lag, semi-sync degradation and the
    # promotion/handoff/resync counts are the HA plane's acceptance
    # contract — the failover drills (tests/test_ps_ha.py) read these
    # exact names
    "paddle_tpu_ps_ha_role",
    "paddle_tpu_ps_ha_epoch",
    "paddle_tpu_ps_ha_standbys_connected",
    "paddle_tpu_ps_ha_replication_lag_rows",
    "paddle_tpu_ps_ha_replication_lag_bytes",
    "paddle_tpu_ps_ha_replication_lag_seconds",
    "paddle_tpu_ps_ha_records_shipped_total",
    "paddle_tpu_ps_ha_semisync_total",
    "paddle_tpu_ps_ha_fenced_writes_total",
    "paddle_tpu_ps_ha_promotions_total",
    "paddle_tpu_ps_ha_handoffs_total",
    "paddle_tpu_ps_ha_resyncs_total",
    # tiered embedding store (docs/PS_TIERED.md): per-tier hit/miss
    # and residency, demand-page faults, demotions, cold-read errors
    # and the by-tier pull latency histogram are the tier hierarchy's
    # acceptance contract — tests/test_tiered_store.py and the
    # collector/top tier pane read these exact names
    "paddle_tpu_ps_tier_hits_total",
    "paddle_tpu_ps_tier_misses_total",
    "paddle_tpu_ps_tier_resident_rows",
    "paddle_tpu_ps_tier_resident_bytes",
    "paddle_tpu_ps_tier_faults_total",
    "paddle_tpu_ps_tier_demotions_total",
    "paddle_tpu_ps_tier_cold_read_errors_total",
    "paddle_tpu_ps_tier_pull_seconds",
    # fleet time-series plane (docs/OBSERVABILITY.md): TSDB
    # durability/retention accounting, alert lifecycle counts and the
    # per-tenant usage series are the plane's acceptance contract —
    # the burn-rate chaos drill (tests/test_timeseries.py) and `top
    # history/alerts/tenants` read these exact names
    "paddle_tpu_tsdb_samples_total",
    "paddle_tpu_tsdb_series",
    "paddle_tpu_tsdb_bytes_on_disk",
    "paddle_tpu_tsdb_blocks_sealed_total",
    "paddle_tpu_tsdb_blocks_compacted_total",
    "paddle_tpu_tsdb_blocks_deleted_total",
    "paddle_tpu_tsdb_torn_tail_truncated_total",
    "paddle_tpu_alerts_evaluations_total",
    "paddle_tpu_alerts_transitions_total",
    "paddle_tpu_alerts_firing",
    "paddle_tpu_tenant_tokens_in_total",
    "paddle_tpu_tenant_tokens_out_total",
    "paddle_tpu_tenant_queue_seconds_total",
    "paddle_tpu_tenant_kv_page_seconds_total",
    "paddle_tpu_tenant_flops_total",
    "paddle_tpu_tenant_requests_total",
    "paddle_tpu_tenant_router_requests_total",
    "paddle_tpu_tenant_overflow_total",
    "paddle_tpu_telemetry_procs_retired_total",
    # shared-prefix KV reuse + replayable sampling (docs/SERVING.md):
    # cache effectiveness (hit/miss/tokens-saved), the COW and
    # eviction safety valves, residency gauges, and how much traffic
    # rides stochastic decode — the collector's summary and the `top`
    # prefix row read these exact names
    "paddle_tpu_prefix_lookup_hits_total",
    "paddle_tpu_prefix_lookup_misses_total",
    "paddle_tpu_prefix_prefill_tokens_saved_total",
    "paddle_tpu_prefix_cow_copies_total",
    "paddle_tpu_prefix_evicted_pages_total",
    "paddle_tpu_prefix_cached_pages",
    "paddle_tpu_prefix_shared_pages",
    "paddle_tpu_sampling_requests_total",
    "paddle_tpu_sampling_tokens_total",
}


def _call_name(node: ast.Call) -> str | None:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def metric_regs(tree: ast.AST) -> tuple[list[tuple[int, str]],
                                        list[tuple[str, int]]]:
    """(violations, registrations): violations are (line, message);
    registrations are (metric_name, line) for the duplicate pass."""
    bad: list[tuple[int, str]] = []
    regs: list[tuple[str, int]] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if _call_name(node) not in REGISTER_FUNCS:
            continue
        if not node.args:
            continue
        first = node.args[0]
        if not (isinstance(first, ast.Constant)
                and isinstance(first.value, str)):
            continue
        name = first.value
        if not NAME_RE.match(name):
            bad.append((node.lineno,
                        f"metric name {name!r} must match "
                        f"{NAME_RE.pattern}"))
        else:
            regs.append((name, node.lineno))
    return bad, regs


def _metric_check_path(path: str):
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, path)
    except SyntaxError as e:
        return [(e.lineno or 0, f"unparseable: {e.msg}")], []
    return metric_regs(tree)


def metric_main(argv: list[str], repo: str) -> int:
    """check_metric_names.py behavior, byte-identical output."""
    default_root = len(argv) <= 1
    if not default_root:
        root = argv[1]
    else:
        root = os.path.join(repo, "paddle_tpu")
    violations: list[str] = []
    sites: dict[str, list[str]] = {}
    for dirpath, _dirs, files in os.walk(root):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root)
            if rel in SKIP_FILES:
                continue
            bad, regs = _metric_check_path(path)
            for lineno, what in bad:
                violations.append(f"{path}:{lineno}: {what}")
            for name, lineno in regs:
                sites.setdefault(name, []).append(f"{path}:{lineno}")
    for name, where in sorted(sites.items()):
        if len(where) > 1:
            violations.append(
                f"duplicate registration of {name!r} at "
                + ", ".join(where))
    if default_root:  # an explicit root is a partial tree by design
        for name in sorted(REQUIRED_METRICS - set(sites)):
            violations.append(
                f"required metric {name!r} has no registration site "
                "(checkpoint-tier instrumentation is contractual — "
                "docs/CHECKPOINT.md)")
    if violations:
        print(f"metric naming violations under {root} "
              "(see docs/OBSERVABILITY.md naming scheme):")
        print("\n".join(violations))
        return 1
    print(f"OK: {sum(len(w) for w in sites.values())} metric "
          f"registrations under {root} are well-named and unique")
    return 0


@register
class MetricNamesRule(Rule):
    name = "metric-names"
    description = ("metric naming scheme, single registration site, "
                   "required-metric ratchet")

    def __init__(self):
        self._sites: dict[str, list[tuple[str, str, int]]] = {}

    def visit(self, ctx: FileContext):
        # SKIP_FILES are positions in the SHIPPED tree — honored for
        # any scan root that reaches them (registry.py defines the
        # helpers; its example strings are not registrations)
        if ctx.tree_rel is not None \
                and ctx.tree_rel.replace("/", os.sep) in SKIP_FILES:
            return ()
        bad, regs = metric_regs(ctx.tree)
        for name, lineno in regs:
            self._sites.setdefault(name, []).append(
                (ctx.path, ctx.relpath, lineno))
        dedup = KeyCounter()   # content-based keys; #2.. on repeats
        keypath = ctx.tree_rel or ctx.relpath  # stable across roots
        return [self.finding(ctx, line, msg,
                             key=dedup(f"{keypath}::{msg}"))
                for line, msg in bad]

    def finalize(self, run):
        out = []
        for name, where in sorted(self._sites.items()):
            if len(where) > 1:
                shown = ", ".join(f"{p}:{ln}" for p, _r, ln in where)
                out.append(self.finding(
                    where[0][0], where[0][2],
                    f"duplicate registration of {name!r} at {shown}",
                    key=f"dup::{name}"))
        if run.default_scan:  # a subtree is a partial view by design
            for name in sorted(REQUIRED_METRICS - set(self._sites)):
                out.append(self.finding(
                    run.root, 0,
                    f"required metric {name!r} has no registration "
                    f"site (its tier's instrumentation is "
                    f"contractual — docs/CHECKPOINT.md, "
                    f"docs/DEBUGGING.md)",
                    key=f"required::{name}"))
        return out


# ---------------------------------------------------------------------------
# env-knob documentation (from check_env_knobs.py)
# ---------------------------------------------------------------------------

# full uppercase-snake knob names only: the trailing-underscore prefix
# literals the typo guard scans with ("PADDLE_PS_FAULT_") are not knobs
KNOB_RE = re.compile(r"^PADDLE_(?:TPU|PS)_[A-Z0-9]+(?:_[A-Z0-9]+)*$")
FIND_RE = re.compile(r"PADDLE_(?:TPU|PS)_[A-Z0-9_]*[A-Z0-9]")


def _knob_names_in(text: str):
    for m in FIND_RE.finditer(text):
        # a match the text continues with "_" is a prefix literal
        # ("PADDLE_PS_FAULT_" in the typo guard, "PADDLE_PS_FAULT_*"
        # in prose), not a knob name
        if m.end() < len(text) and text[m.end()] == "_":
            continue
        if KNOB_RE.match(m.group(0)):
            yield m.group(0)


def knobs_in_tree(tree: ast.AST) -> dict[str, int]:
    """knob name -> first line, from string literals in one file."""
    out: dict[str, int] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value,
                                                        str):
            for name in _knob_names_in(node.value):
                out.setdefault(name, node.lineno)
    return out


def _knobs_in_path(path: str) -> dict[str, str]:
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, path)
    except SyntaxError:
        return {}
    return {name: f"{path}:{line}"
            for name, line in knobs_in_tree(tree).items()}


def knobs_in_docs(paths: list[str]) -> set[str]:
    found: set[str] = set()
    for path in paths:
        if not os.path.isfile(path):
            continue
        with open(path, encoding="utf-8") as f:
            text = f.read()
        found.update(_knob_names_in(text))
    return found


def default_docs_paths(repo: str) -> list[str]:
    docs_dir = os.path.join(repo, "docs")
    paths = [os.path.join(docs_dir, f)
             for f in sorted(os.listdir(docs_dir))
             if f.endswith(".md")]
    paths.append(os.path.join(repo, "README.md"))
    return paths


def env_main(argv: list[str], repo: str) -> int:
    """check_env_knobs.py behavior, byte-identical output."""
    code_root = argv[1] if len(argv) > 1 else os.path.join(repo,
                                                           "paddle_tpu")
    if len(argv) > 2:
        docs_paths = [os.path.join(argv[2], f)
                      for f in sorted(os.listdir(argv[2]))
                      if f.endswith(".md")]
    else:
        docs_paths = default_docs_paths(repo)
    code: dict[str, str] = {}
    for dirpath, _dirs, files in os.walk(code_root):
        if "__pycache__" in dirpath:
            continue
        for fn in sorted(files):
            if fn.endswith(".py"):
                for name, site in _knobs_in_path(
                        os.path.join(dirpath, fn)).items():
                    code.setdefault(name, site)
    documented = knobs_in_docs(docs_paths)
    missing = sorted(set(code) - documented)
    if missing:
        print(f"undocumented env knobs under {code_root} "
              "(add them to a docs/ table — docs/ENV_KNOBS.md is the "
              "master index):")
        for name in missing:
            print(f"  {name}  (first read at {code[name]})")
        return 1
    print(f"OK: {len(code)} env knobs under {code_root} are all "
          f"documented across {len(docs_paths)} docs files")
    return 0


@register
class EnvKnobsRule(Rule):
    name = "env-knobs"
    description = ("every PADDLE_TPU_*/PADDLE_PS_* knob read by the "
                   "code is documented in docs/")

    # tests may point the docs side elsewhere
    docs_paths: list[str] | None = None

    def __init__(self):
        self._code: dict[str, tuple[str, int]] = {}

    def visit(self, ctx: FileContext):
        for name, line in knobs_in_tree(ctx.tree).items():
            self._code.setdefault(name, (ctx.path, line))
        return ()

    def finalize(self, run):
        from ..core import repo_root
        paths = self.docs_paths
        if paths is None:
            # fixture/subtree roots are still held to the REPO docs
            # contract — a knob is documented or it is not, regardless
            # of which subtree the scan started from
            paths = default_docs_paths(repo_root())
        documented = knobs_in_docs(paths)
        return [self.finding(
            self._code[name][0], self._code[name][1],
            f"undocumented env knob {name!r} — add a row to "
            f"docs/ENV_KNOBS.md (master index)",
            key=f"knob::{name}")
            for name in sorted(set(self._code) - documented)]
