"""Persistent fusion-aware autobench tuning cache (PR 7 tentpole):
round-trip across processes (second process hits disk with ZERO
measuring calls), CRC/version/corruption degradation, concurrent
publishers, the FORCE typo guard, and the list/warm/invalidate CLI."""
import json
import os
import subprocess
import sys
import zlib

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import autobench

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def cache_file(tmp_path, monkeypatch):
    path = str(tmp_path / "autobench.json")
    monkeypatch.setenv("PADDLE_TPU_AUTOBENCH_CACHE", path)
    autobench.clear()
    yield path
    autobench.clear()


def _cands():
    # "a" (slower: extra work) vs "b"; the winner itself is irrelevant —
    # the tests assert cache behavior, not timing
    return {"a": lambda x: (x @ x) + 1.0, "b": lambda x: x + 1.0}


def _mk():
    return (jnp.ones((16, 16), jnp.float32),)


def test_gate_inside_jit_and_scan_times_concrete_arrays():
    """The gate is reached at trace time from inside jitted bodies: its
    candidates must get concrete device arrays (not the ambient trace's
    tracers), and one that raises is kept with its error."""
    import jax
    from jax.sharding import PartitionSpec as P, get_abstract_mesh
    autobench.clear()
    made, seen = [], []

    def mk():
        made.append(_mk())
        return made[-1]

    def ok(x):
        try:
            jax.lax.axis_size("pp")
            bound = True
        except NameError:
            bound = False
        if (get_abstract_mesh().empty, bound) not in seen:
            seen.append((get_abstract_mesh().empty, bound))
        return x @ x

    def refused(x):
        raise ValueError("mosaic says no")

    def gate():
        return autobench.prefer(("trace_gate", 16),
                                {"pallas": refused, "xla": ok}, mk,
                                default="pallas", reps=1)

    @jax.jit
    def step(x):
        winners = [gate()]

        def body(c, _):
            winners.append(gate())       # inside lax.scan inside jit
            return c + 1.0, None

        c, _ = jax.lax.scan(body, x, None, length=2)
        assert winners == ["xla", "xla"]
        return c

    # reached first inside a shard_map stage inside jit, as in the
    # trainer: there the measuring round must also be free of the
    # stage's axis environment and context mesh
    mesh = jax.make_mesh((1,), ("pp",))
    staged = jax.shard_map(step, mesh=mesh, in_specs=P(), out_specs=P(),
                           axis_names=frozenset({"pp"}), check_vma=False)
    assert float(jax.jit(staged)(jnp.zeros(()))) == 2.0
    assert float(step(jnp.zeros(()))) == 2.0
    assert made and not any(isinstance(a, jax.core.Tracer)
                            for args in made for a in args)
    assert seen == [(True, False)]        # top level: no mesh, no axes
    assert autobench.stats()["measures"] == 1
    from paddle_tpu.observability import perf
    row = perf.kernels()[str(("trace_gate", 16))]
    assert row["winner"] == "xla" and row["source"] == "measured"
    assert row["candidates_ms"]["xla"] > 0
    assert "pallas" not in row["candidates_ms"]
    assert row["errors"] == {"pallas": "ValueError: mosaic says no"}
    assert autobench.stats()["candidate_errors"] == 1
    # a make_args that does hand back tracers is refused, not "timed"
    with pytest.raises(RuntimeError, match="concrete"):
        jax.jit(lambda x: autobench._measure(ok, lambda: (x,), 1))(
            jnp.ones((4, 4)))
    autobench.clear()


def test_default_keeps_the_slot_on_a_tie(monkeypatch):
    """A lead the clock cannot resolve must not flip the decision from
    run to run: the challenger has to beat `default` by more than the
    measured spread and by more than _MIN_MARGIN."""
    cands = {"pallas": "pallas", "xla": "xla"}

    def decide(key, timed, default):
        monkeypatch.setattr(autobench, "_measure",
                            lambda fn, make_args, reps: timed[fn])
        return autobench.prefer(key, cands, tuple, default=default)

    autobench.clear()
    # 3% ahead with no spread: inside _MIN_MARGIN, default stays
    assert decide(("tie", 1), {"pallas": (1.00e-3, 0.0),
                               "xla": (0.97e-3, 0.0)}, "pallas") == "pallas"
    # 20% ahead but the samples spread over 30%: default stays
    assert decide(("tie", 2), {"pallas": (1.0e-3, 0.3e-3),
                               "xla": (0.8e-3, 0.0)}, "pallas") == "pallas"
    # 20% ahead, tight samples: the challenger takes the slot
    assert decide(("tie", 3), {"pallas": (1.0e-3, 1e-5),
                               "xla": (0.8e-3, 1e-5)}, "pallas") == "xla"
    # the default is not handicapped when it is the faster one
    assert decide(("tie", 4), {"pallas": (0.99e-3, 0.0),
                               "xla": (1.0e-3, 0.0)}, "pallas") == "pallas"
    assert decide(("tie", 5), {"pallas": (0.97e-3, 0.0),
                               "xla": (1.0e-3, 0.0)}, "xla") == "xla"
    autobench.clear()


def test_measure_times_batches_of_calls(monkeypatch):
    """One sample is many back-to-back calls under one sync."""
    import jax
    monkeypatch.setattr(autobench, "_MAX_CALLS", 5)
    runs = []

    def fn(x):
        jax.debug.callback(lambda: runs.append(1))
        return x + 1.0

    med, spread = autobench._measure(fn, _mk, 2)
    jax.effects_barrier()
    assert med > 0 and spread >= 0
    # the warm-up call, the sizing call, two samples of five calls
    assert len(runs) == 1 + 1 + 2 * 5


def _recrc(rec):
    body = {k: v for k, v in rec.items() if k != "crc"}
    return zlib.crc32(json.dumps(
        body, sort_keys=True, separators=(",", ":")).encode()) & 0xFFFFFFFF


def test_decision_published_and_readopted_without_measuring(cache_file):
    w = autobench.prefer(("cache", 1), _cands(), _mk, reps=1)
    s = autobench.stats()
    assert s["measures"] == 1 and s["publishes"] == 1
    doc = json.load(open(cache_file))
    assert doc["format"].startswith("paddle-tpu-autobench")
    (rec,) = doc["records"]
    assert rec["winner"] == w and rec["crc"] == _recrc(rec)
    assert rec["kernels"] == autobench.KERNEL_VERSION
    # simulated fresh process: in-memory state dropped, disk survives
    autobench.clear()
    assert autobench.prefer(("cache", 1), _cands(), _mk, reps=1) == w
    s = autobench.stats()
    assert s["measures"] == 0 and s["cache_hits"] == 1


def test_second_process_hits_disk_zero_measures(cache_file):
    """The fleet pre-warm contract: a real second PROCESS adopts the
    published decision with zero in-process measuring calls."""
    w = autobench.prefer(("proc", 2, "f32"), _cands(), _mk, reps=1)
    code = (
        "import json, jax.numpy as jnp\n"
        "from paddle_tpu.ops import autobench\n"
        "cands = {'a': lambda x: (x @ x) + 1.0, 'b': lambda x: x + 1.0}\n"
        "w = autobench.prefer(('proc', 2, 'f32'), cands,\n"
        "                     lambda: (jnp.ones((16, 16), jnp.float32),),\n"
        "                     reps=1)\n"
        "print(json.dumps({'winner': w, **autobench.stats()}))\n")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["winner"] == w
    assert got["measures"] == 0
    assert got["cache_hits"] == 1


def test_stale_version_record_is_remeasured(cache_file):
    autobench.prefer(("stale", 3), _cands(), _mk, reps=1)
    doc = json.load(open(cache_file))
    doc["records"][0]["kernels"] = autobench.KERNEL_VERSION + 1
    doc["records"][0]["crc"] = _recrc(doc["records"][0])
    json.dump(doc, open(cache_file, "w"))
    autobench.clear()
    autobench.prefer(("stale", 3), _cands(), _mk, reps=1)
    s = autobench.stats()
    assert s["cache_stale"] == 1 and s["measures"] == 1
    # the remeasured decision was republished with the CURRENT version
    (rec,) = json.load(open(cache_file))["records"]
    assert rec["kernels"] == autobench.KERNEL_VERSION


def test_corrupt_record_crc_skipped(cache_file):
    autobench.prefer(("crc", 4), _cands(), _mk, reps=1)
    doc = json.load(open(cache_file))
    doc["records"][0]["winner"] = "tampered"  # crc now wrong
    json.dump(doc, open(cache_file, "w"))
    autobench.clear()
    w = autobench.prefer(("crc", 4), _cands(), _mk, reps=1)
    s = autobench.stats()
    assert w in ("a", "b")
    assert s["cache_corrupt"] >= 1 and s["measures"] == 1


def test_corrupt_file_degrades_to_measuring(cache_file):
    with open(cache_file, "w") as f:
        f.write("{definitely not json")
    w = autobench.prefer(("corrupt", 5), _cands(), _mk, reps=1)
    s = autobench.stats()
    assert w in ("a", "b")
    assert s["cache_corrupt"] >= 1 and s["measures"] == 1
    # the next publish overwrote the corrupt file with a valid one
    doc = json.load(open(cache_file))
    assert len(doc["records"]) == 1


def test_concurrent_publishers_keep_disjoint_keys(cache_file):
    """read-merge-write: two decisions published from different
    in-memory states (simulating two processes) both survive."""
    autobench.prefer(("conc", "k1"), _cands(), _mk, reps=1)
    autobench.clear()  # second "process"
    autobench.prefer(("conc", "k2"), _cands(), _mk, reps=1)
    keys = {r["key"] for r in json.load(open(cache_file))["records"]}
    assert keys == {str(("conc", "k1")), str(("conc", "k2"))}


def test_no_cache_env_keeps_in_process_behavior(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_AUTOBENCH_CACHE", raising=False)
    autobench.clear()
    autobench.prefer(("nofile", 6), _cands(), _mk, reps=1)
    s = autobench.stats()
    assert s["publishes"] == 0 and s["cache_misses"] == 0
    autobench.clear()


def test_force_unknown_candidate_warns(cache_file, monkeypatch, caplog):
    """PR-7 satellite: a FORCE name no gate offers used to be silently
    ignored — it now warns through the paddle_tpu.autobench logger
    (PR-6 fault-knob typo-guard idiom) and benchmarks normally."""
    import logging
    monkeypatch.setenv("PADDLE_TPU_AUTOBENCH_FORCE", "palas")  # typo
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.autobench"):
        w = autobench.prefer(("force", 7), _cands(), _mk, reps=1)
    assert w in ("a", "b")
    assert any("PADDLE_TPU_AUTOBENCH_FORCE" in r.message
               and "palas" in r.message for r in caplog.records)
    # a KNOWN name is still honored without measuring
    autobench.clear()
    monkeypatch.setenv("PADDLE_TPU_AUTOBENCH_FORCE", "a")
    assert autobench.prefer(("force", 8), _cands(), _mk, reps=1) == "a"
    assert autobench.stats()["measures"] == 0


def test_cli_list_warm_invalidate(cache_file, tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PADDLE_TPU_PALLAS_INTERPRET": "1"}

    def cli(*args):
        return subprocess.run(
            [sys.executable, "-m", "paddle_tpu.ops.autobench", *args],
            capture_output=True, text=True, cwd=REPO, env=env)

    # warm through a spec file (tiny shapes; interpret-mode Pallas so
    # the kernel candidates run off-TPU — the point is the plumbing,
    # not the timings)
    specs = [{"kernel": "fused_layer_norm", "rows": 16, "cols": 128,
              "dtype": "float32"}]
    spec_file = tmp_path / "specs.json"
    spec_file.write_text(json.dumps(specs))
    r = cli("warm", "--path", cache_file, "--specs", str(spec_file))
    assert r.returncode == 0, r.stderr
    assert "warmed 1 specs" in r.stdout
    recs = json.load(open(cache_file))["records"]
    assert any("fused_layer_norm" in rec["key"] for rec in recs)
    # list shows it
    r = cli("list", "--path", cache_file)
    assert r.returncode == 0 and "fused_layer_norm" in r.stdout
    r = cli("list", "--path", cache_file, "--json")
    assert r.returncode == 0 and json.loads(r.stdout)
    # invalidate by match, then all
    r = cli("invalidate", "--path", cache_file, "--match", "layer_norm")
    assert r.returncode == 0 and "removed 1" in r.stdout
    r = cli("invalidate", "--path", cache_file, "--all")
    assert r.returncode == 0


def test_unwritable_cache_path_never_blocks_the_gate(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_AUTOBENCH_CACHE",
                       "/proc/definitely/not/writable/ab.json")
    autobench.clear()
    w = autobench.prefer(("rofs", 9), _cands(), _mk, reps=1)
    assert w in ("a", "b")
    autobench.clear()


def test_warm_presets_are_registered():
    autobench._import_warmer_modules()
    for name, specs in autobench.PRESETS.items():
        for spec in specs:
            assert spec["kernel"] in autobench._WARMERS, \
                (name, spec["kernel"])


# the gpt_1p3b_serve preset's decode key before PR 25, when the gate timed
# rank-4 pools [P, ps, H, d]
_PAGED_SPEC = {"kernel": "paged_attention", "s": 8, "h": 16, "d": 128,
               "p": 1025, "ps": 16, "m": 128}
_OLD_PAGED_KEY = ("paged_attention", 8, 16, 128, 1025, 16, 128, "bfloat16")


def _paged_gate_calls(monkeypatch):
    """Run the gate with `_measure` replaced; returns what it was handed:
    [(candidate name, shapes of make_args())]."""
    seen = []

    def fake_measure(fn, make_args, reps):
        name = "pallas" if "pallas" in fn.__name__ else "xla"
        seen.append((name, [tuple(a.shape) for a in make_args()]))
        return (1e-3 if name == "pallas" else 2e-3), 0.0

    monkeypatch.setattr(autobench, "_measure", fake_measure)
    monkeypatch.setattr(autobench._perf, "costs_enabled", lambda: False)
    return seen


@pytest.mark.parametrize("through", ["warmer", "gate"])
def test_paged_gate_times_the_stacked_form_on_one_layer(
        cache_file, monkeypatch, through):
    """Both candidates are timed in the form the decode body runs: pools
    [1, P, ps, H, d] and the layer as a sixth, traced, argument."""
    from paddle_tpu.ops import paged_attention as pa
    seen = _paged_gate_calls(monkeypatch)
    if through == "warmer":
        [(_spec, winner)] = autobench.warm([_PAGED_SPEC])
    else:
        key, cands, make_args = pa._gate_paged(8, 16, 128, 1025, 16, 128,
                                               jnp.bfloat16)
        winner = autobench.prefer(key, cands, make_args, default="xla")
    assert winner == "pallas"
    assert sorted(n for n, _ in seen) == ["pallas", "xla"]
    for _name, shapes in seen:
        assert shapes == [(8, 16, 128), (1, 1025, 16, 16, 128),
                          (1, 1025, 16, 16, 128), (8, 128), (8,), ()]
    [key] = autobench.decisions()
    # the marker names the kernel that was judged ("stacked" until the
    # kernel walked live pages only, PR 29); the form timed is the same
    assert key[:2] == ("paged_attention", "live_pages")
    assert key[2:] == _OLD_PAGED_KEY[1:]


def test_paged_gate_candidates_run_on_their_own_args():
    """The candidates as the gate jits them, at a small size: the stacked
    pool of one layer with a traced layer gives what the rank-4 form
    gives (Pallas in interpret mode here: the gate's own candidate pins
    interpret=False, for the chip)."""
    from paddle_tpu.ops import paged_attention as pa
    _key, cands, make_args = pa._gate_paged(4, 4, 16, 13, 8, 3, jnp.float32)
    q, k, v, pt, ln, layer = make_args()
    assert k.shape == (1, 13, 8, 4, 16) and layer.dtype == jnp.int32
    want = pa.paged_attention_xla(q, k[0], v[0], pt, ln)
    got = jax.jit(cands["xla"])(q, k, v, pt, ln, layer)
    assert jnp.array_equal(got, want)
    got = jax.jit(lambda *a: pa.paged_attention_pallas(
        *a[:-1], interpret=True, layer=a[-1]))(q, k, v, pt, ln, layer)
    assert jnp.allclose(got, want, atol=1e-5, rtol=1e-5)


# the same key from PR 25 to PR 28, when the kernel behind `pallas` ran a
# grid step for every table entry whatever the contexts held
_GRID_PAGED_KEY = ("paged_attention", "stacked") + _OLD_PAGED_KEY[1:]


@pytest.mark.parametrize("old_key", [_OLD_PAGED_KEY, _GRID_PAGED_KEY],
                         ids=["rank4", "stacked_grid"])
def test_paged_record_of_the_rank4_gate_does_not_answer(cache_file,
                                                        monkeypatch,
                                                        old_key):
    """A decision a fleet cached before PR 25 (rank-4 pools), or before
    PR 29 (the grid over every table entry), was measured on another
    kernel: it stays in the file and is never adopted."""
    autobench._publish(cache_file, {
        "key": str(old_key), "device": autobench._device_kind(),
        "winner": "xla", "jax": autobench._jax_version(),
        "kernels": autobench.KERNEL_VERSION,
        "timings_ms": {"xla": 1.0, "pallas": 2.0}, "errors": {},
        "ts": 0.0})
    autobench.clear()
    seen = _paged_gate_calls(monkeypatch)
    [(_spec, winner)] = autobench.warm([_PAGED_SPEC])
    assert winner == "pallas" and len(seen) == 2      # measured anew
    st = autobench.stats()
    assert st["cache_hits"] == 0 and st["cache_misses"] == 1
    keys = {rec["key"]: rec["winner"]
            for rec in autobench.list_entries(cache_file)}
    assert keys[str(old_key)] == "xla"
    assert sum("'live_pages'" in k for k in keys) == 1
