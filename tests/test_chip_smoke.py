"""Bring-up contract on the CPU: chip_smoke.py refuses to run without a
TPU and rehearses its code paths at tiny widths; the compile cache is
placed from outside or at one fixed path; importing the package starts
no backend; the launcher gives a chip to one process; a Pallas kernel
under a multi-device mesh runs per shard."""
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from paddle_tpu.distributed import launch  # noqa: E402
from paddle_tpu.utils import compile_cache  # noqa: E402


def test_chip_smoke_refuses_the_cpu(monkeypatch, tmp_path, capsys):
    # env set: the smoke places no cache itself (and this process's jax
    # config stays as it was)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == ""                      # no result line
    assert "needs a TPU" in err and "platform='cpu'" in err
    # --chips 4 fails, not skips, on fewer than four devices of a TPU;
    # here the platform check comes first and fails it all the same
    assert chip_smoke.main(["--chips", "4"]) != 0


def test_chip_smoke_rehearsal_passes_and_is_never_a_pass(capsys):
    before = jax.config.jax_compilation_cache_dir
    assert chip_smoke.main(["--rehearse"]) == 0
    assert jax.config.jax_compilation_cache_dir == before
    out = capsys.readouterr().out
    assert "rehearsal" in out.splitlines()[0]
    last = json.loads(out.splitlines()[-1])
    assert last["ok"] is False and last["rehearsal"] is True
    assert last["rehearsal_passed"] is True
    assert last["device"]["platform"] == "cpu"


def test_compile_cache_is_placed_from_outside_or_fixed(monkeypatch,
                                                       tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert calls == []                    # jax reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.setup_compile_cache() == fixed
    assert calls == [("jax_compilation_cache_dir", fixed)]
    # the same path from two other processes, whatever their pid and cwd
    # (the module is stdlib-only at import: loaded straight from its file)
    code = ("import importlib.util as u, sys; s = u.spec_from_file_location("
            "'cc', sys.argv[1]); m = u.module_from_spec(s); "
            "s.loader.exec_module(m); print(m.cache_dir())")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    seen = {subprocess.run(
        [sys.executable, "-c", code, compile_cache.__file__], cwd=cwd,
        env=env, capture_output=True, text=True, timeout=60,
        check=True).stdout.strip() for cwd in (REPO, str(tmp_path))}
    assert seen == {fixed}


def test_import_starts_no_backend():
    code = ("import paddle_tpu, paddle_tpu.distributed.launch, "
            "chip_smoke\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


def test_launcher_gives_a_chip_to_one_process(monkeypatch):
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setattr(launch, "_host_has_tpu", lambda: True)
    trainers = [(f"trainer.{i}", {}, []) for i in range(2)]
    assert "one process" in launch._assign_chips(trainers)
    assert launch._assign_chips(trainers[:1]) is None
    # the cpu is not a chip: host-only drills keep their shape
    cpu = [(f"trainer.{i}", {"JAX_PLATFORMS": "cpu"}, []) for i in range(2)]
    assert launch._assign_chips(cpu) is None
    replicas = [(f"replica.{i}", {}, []) for i in range(3)]
    assert launch._assign_chips(replicas) is None
    assert [env["TPU_VISIBLE_CHIPS"] for _n, env, _a in replicas] == \
        ["0", "1", "2"]
    assert all(env["TPU_PROCESS_BOUNDS"] == "1,1,1"
               for _n, env, _a in replicas)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert launch._assign_chips(trainers) is None


def test_launcher_leaves_a_host_without_chips_alone(monkeypatch):
    """JAX_PLATFORMS unset on a CPU-only or GPU host: several processes
    per node and several replicas are that host's normal shape."""
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(launch.glob, "glob", lambda pat: [])
    assert not launch._host_has_tpu()
    trainers = [(f"trainer.{i}", {}, []) for i in range(2)]
    replicas = [(f"replica.{i}", {}, []) for i in range(2)]
    assert launch._assign_chips(trainers + replicas) is None
    assert all(env == {} for _n, env, _a in trainers + replicas)
    # and with the v5e's device files there, the same call is refused
    monkeypatch.setattr(
        launch.glob, "glob",
        lambda pat: ["/dev/vfio/1"] if pat.startswith("/dev/vfio") else [])
    assert "one process" in launch._assign_chips(trainers)


def test_flash_attention_runs_per_shard_on_a_mesh(monkeypatch):
    """jax will not partition a Mosaic kernel. Traced under
    sharding.kernel_mesh, the model runs flash attention on each
    device's shard: the same numbers as the composed path, and lowered
    for the TPU the program holds the kernel — where without the wrap
    jax refuses it (the error the four-chip trainer met)."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.models.gpt import _causal_attention
    from paddle_tpu.ops import pallas_attention
    from paddle_tpu.parallel.hybrid import make_hybrid_mesh
    from paddle_tpu.parallel.sharding import kernel_mesh

    mesh = make_hybrid_mesh(dp=2, tp=2)
    rng = np.random.RandomState(0)
    q, k, v = (jax.device_put(
        jnp.asarray(rng.randn(4, 64, 64), jnp.float32),
        NamedSharding(mesh, P("dp", None, "tp"))) for _ in range(3))

    def attend():          # a fresh function: nothing cached across traces
        return jax.jit(lambda q, k, v: _causal_attention(q, k, v, 4, "flash"))

    want = _causal_attention(q, k, v, 4, "xla")
    with kernel_mesh(mesh):
        got = attend()(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    with kernel_mesh(mesh):
        text = attend().trace(q, k, v).lower(
            lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        attend().trace(q, k, v).lower(lowering_platforms=("tpu",))
