"""Entry-point device plumbing: compile-cache placement, the device report,
and chip_smoke.py's refusal to run anywhere but on a TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import device

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defaults_to_a_fixed_directory_in_the_checkout(
        monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = device.enable_compile_cache()
    assert got == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert device.enable_compile_cache() == got      # no per-call names


def test_compile_cache_leaves_the_environment_alone(monkeypatch,
                                                   cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert device.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_describe_devices_names_the_backend():
    dev = device.describe_devices()
    assert dev == {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())}
    if dev["platform"] != "tpu":
        assert device.device_target() is None


def _run_smoke(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _ok_lines(out: str):
    lines = []
    for line in out.splitlines():
        try:
            lines.append(json.loads(line).get("ok"))
        except (ValueError, AttributeError):
            pass
    return lines


def test_chip_smoke_refuses_a_cpu_backend():
    proc = _run_smoke(ROOT)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert _ok_lines(proc.stdout) == []


def test_chip_smoke_fails_without_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run_smoke(tmp_path)
    assert proc.returncode != 0
    assert _ok_lines(proc.stdout) == []
