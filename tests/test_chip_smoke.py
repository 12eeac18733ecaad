"""``chip_smoke.py`` rehearsed on the CPU at the reduced size: the same
phase functions the chip run calls, so the script cannot rot between
chip runs.  ``main()`` itself refuses to run without a TPU."""
from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phases_reduced(smoke):
    lines = []
    out = smoke.serve_phases(get_config(smoke.MODEL, reduced=True),
                             max_slots=2, max_context=128, max_tokens=6,
                             log=lines.append)
    assert out["logit_err"] <= smoke.LOGIT_ATOL, lines
    assert out["prefix_cached_tokens"] > 0
    assert out["tokens"] >= 4 and out["exec_steps"] > 0
    assert any(ln.startswith("model phi-3.5-mini-smoke") for ln in lines)


def test_main_refuses_without_tpu():
    """No accelerator: non-zero exit, no result line, nothing built."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_router_phases_on_four_cpu_devices(smoke):
    """The --replicas path on four virtual CPU devices: replicas land on
    distinct devices and answer like the one-replica baseline."""
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('s', "
        f"{str(ROOT / 'chip_smoke.py')!r})\n"
        "m = importlib.util.module_from_spec(spec); "
        "spec.loader.exec_module(m)\n"
        "from repro.configs import get_config\n"
        "m.router_phases(get_config(m.MODEL, reduced=True), 4,\n"
        "                max_context=64, max_tokens=3, per_replica=1)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "replicas on distinct devices" in proc.stdout, proc.stdout


def test_compile_cache_rule(monkeypatch):
    """Entry points leave a set JAX_COMPILATION_CACHE_DIR to JAX and set
    no cache of their own off the TPU."""
    import jax

    from repro.launch.compile_cache import use_repo_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert use_repo_compile_cache() == "/somewhere/else"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert use_repo_compile_cache() is None          # CPU: no cache
    assert jax.config.jax_compilation_cache_dir == before
