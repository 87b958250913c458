"""The training entry point, ``repro.launch.train.run``, on CPU devices."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import cache
from repro.launch.train import run

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
SMOKE_ARGS = ["--steps", "2", "--seq-len", "32", "--per-node-batch", "1"]


@pytest.fixture
def cache_config():
    """Give back JAX's compilation-cache setting as the test found it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_run_one_device_smoke(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    out = run(SMOKE_ARGS)
    assert len(out["losses"]) == 2
    assert np.isfinite(out["losses"]).all()
    assert dict(out["mesh"].shape) == {"data": 1, "model": 1}
    assert out["setup"].n_nodes == 1
    assert out["schedule"].n_nodes == 1
    assert out["compile_s"] > 0 and len(out["step_s"]) == len(out["batch_s"]) == 2


def test_run_times_are_its_spans_on_the_profiler_clock(monkeypatch, tmp_path,
                                                       cache_config):
    """Under jax.profiler.trace the launcher's batch, compile and step times
    are host events ``train.batch``, ``train.compile`` and ``train.step``,
    each as long as the time ``run`` returns for it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    with jax.profiler.trace(str(tmp_path / "trace")):
        out = run(SMOKE_ARGS)
    path = next((tmp_path / "trace").rglob("*.xplane.pb"))
    events = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(e)
    for name, times in [("train.batch", out["batch_s"]),
                        ("train.step", out["step_s"]),
                        ("train.compile", [out["compile_s"]])]:
        seen = sorted(events[name], key=lambda e: e.start_ns)
        assert len(seen) == len(times)
        for e, s in zip(seen, times):
            assert s - 5e-3 <= e.duration_ns * 1e-9 <= s + 1e-4


def test_run_rejects_a_mesh_that_is_not_the_devices(cache_config):
    with pytest.raises(SystemExit):
        run(SMOKE_ARGS + ["--data", "2"])


def test_compile_cache_dir_rule(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert cache.enable_compile_cache() == os.path.join(repo, ".jax_cache")


def test_run_four_virtual_devices(tmp_path):
    """Four host devices: the mesh comes from the devices, each is one node
    holding its own shard, the STL-FW schedule spans the four nodes, and
    the compiled step lands in JAX_COMPILATION_CACHE_DIR and nowhere else."""
    default_dir = cache.DEFAULT_CACHE_DIR
    before = sorted(os.listdir(default_dir)) if default_dir.is_dir() else []
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    code = textwrap.dedent(f"""
        import json
        import jax, numpy as np
        from repro.launch.train import run
        out = run({SMOKE_ARGS!r})
        leaf = jax.tree_util.tree_leaves(out["params"])[0]
        print(json.dumps({{
            "mesh": dict(out["mesh"].shape),
            "n_nodes": out["setup"].n_nodes,
            "schedule_nodes": out["schedule"].n_nodes,
            "leading": leaf.shape[0],
            "shard_devices": sorted(s.device.id for s in leaf.addressable_shards),
            "finite": bool(np.isfinite(out["losses"]).all()),
        }}))
    """)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=480, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["mesh"] == {"data": 4, "model": 1}
    assert got["n_nodes"] == got["schedule_nodes"] == got["leading"] == 4
    assert got["shard_devices"] == [0, 1, 2, 3]
    assert got["finite"]
    assert any(name.startswith("jit_train_step") for name in os.listdir(tmp_path))
    after = sorted(os.listdir(default_dir)) if default_dir.is_dir() else []
    assert after == before
