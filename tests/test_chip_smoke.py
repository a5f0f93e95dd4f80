"""chip_smoke.py at smoke sizes on the CPU.

The script itself refuses any platform but a TPU; its phase functions
do not check, so these tests drive them with the smoke configs (kernels
interpreted). The four-chip phase runs on four virtual CPU devices in a
child process, which sets XLA_FLAGS before JAX starts.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def keep_cache_dir():
    """The entry points place the compile cache; restore it after."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def _child_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def test_train_phase_smoke(chip_smoke, tmp_path, keep_cache_dir):
    out = chip_smoke.train_phase(
        ["--arch", "xlstm-350m", "--smoke", "--peers", "2",
         "--local-steps", "1", "--batch", "1", "--seq", "64",
         "--steps", "5"], tmp_path / "train.jsonl")
    assert len(out["losses"]) == 5
    assert out["losses"][-1] < out["losses"][0]
    assert all(s > 0 for s in out["step_s"])


def test_serve_phase_smoke(chip_smoke):
    out = chip_smoke.serve_phase(
        ["--arch", "starcoder2-3b", "--smoke", "--sessions", "8",
         "--prompt-len", "32", "--vary-prompts", "--gen", "6",
         "--max-batch", "4"])
    assert out["tokens"] == 8 * 6
    assert out["srv"].cfg.block_size == 16       # the engine's default
    assert out["kernel_err"] < 8e-2


def test_four_chip_phase_on_virtual_devices():
    code = ("import json, chip_smoke; r = chip_smoke.four_chip_phase("
            "'xlstm-350m', smoke=True, seq=64, batch=1); "
            "print(json.dumps(r))")
    res = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=600,
        env=_child_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert res.returncode == 0, res.stderr[-4000:]
    r = json.loads(res.stdout.strip().splitlines()[-1])
    assert 0.2 <= r["share"] <= 0.3
    assert "all-reduce" in r["collectives"]
    assert r["max_rel"] <= 2 ** -6


def test_script_refuses_a_host_without_tpu():
    res = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], cwd=ROOT,
        capture_output=True, text=True, timeout=300, env=_child_env())
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "no TPU" in res.stderr


def test_compile_cache_defaults_to_repo(keep_cache_dir, monkeypatch):
    from repro.launch.compile_cache import REPO_CACHE_DIR, use_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert use_compile_cache() == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    assert REPO_CACHE_DIR == ROOT / ".jax_cache"
    ignored = subprocess.run(["git", "check-ignore", "-q", ".jax_cache"],
                             cwd=ROOT)
    assert ignored.returncode in (0, 128)   # 128: not a git checkout


def test_compile_cache_env_dir_is_used(tmp_path):
    code = ("import jax, jax.numpy as jnp; "
            "from repro.launch.compile_cache import use_compile_cache; "
            "print(use_compile_cache()); "
            "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64)))"
            ".block_until_ready()")
    res = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=300,
        env=_child_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                       JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                       JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0"))
    assert res.returncode == 0, res.stderr[-4000:]
    assert res.stdout.strip().splitlines()[-1] == str(tmp_path)
    assert any(tmp_path.iterdir()), "nothing was cached in the env dir"
