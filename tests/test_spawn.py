"""Who gets the chip: worker environments, the compile cache's place,
and `chip_smoke.py` off the chip."""

import os
import subprocess
import sys
import time

from ray_tpu._private import spawn
from ray_tpu.util import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_worker_is_scoped_to_its_chip_and_not_forced_to_cpu(
        monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS")     # as on a machine with chips
    env = spawn.worker_env(chips=[2])
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert "JAX_PLATFORMS" not in env
    assert set(spawn.chip_scope_env([2])) <= set(spawn.CHIP_SCOPE_VARS)


def test_worker_without_chips_is_forced_to_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    env = spawn.worker_env()
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in env
    # an explicit runtime_env override still wins
    env = spawn.worker_env(
        runtime_env={"env_vars": {"JAX_PLATFORMS": "tpu"}})
    assert env["JAX_PLATFORMS"] == "tpu"


def test_compile_cache_honours_the_variable(monkeypatch, tmp_path):
    import jax
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # set no other


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(
        monkeypatch):
    import jax
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path    # never moves
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_unmeetable_tpu_request_fails_with_a_message():
    """A driver-mode session on a host with no chips can never serve a
    `num_tpus` request: the scheduler says so instead of waiting."""
    code = (
        "import ray_tpu\n"
        "ray_tpu.init(num_cpus=1, num_tpus=0)\n"
        "@ray_tpu.remote(num_tpus=1)\n"
        "def f(): return 1\n"
        "try:\n"
        "    ray_tpu.get(f.remote(), timeout=60)\n"
        "except ray_tpu.exceptions.RayTpuError as e:\n"
        "    print('ERR', e)\n"
        "finally:\n"
        "    ray_tpu.shutdown()\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
        env={**os.environ, "RAY_TPU_NUM_TPUS": "0"})
    assert "ERR" in out.stdout and "asks for 1 TPU chip(s)" in out.stdout, \
        out.stdout + out.stderr


def test_chip_smoke_fails_fast_off_the_chip():
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""            # prints no result
    assert "needs 1 TPU chip" in out.stderr
    assert time.monotonic() - t0 < 60
