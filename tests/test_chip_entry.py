"""The chip entry points: compile-cache placement, interpret-mode choice,
one process per chip, and ``chip_smoke.py``'s refusal off a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache
from repro.kernels import resident

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_follows_env(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_cache_defaults_to_checkout_root(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_interpret_mode_only_off_tpu(monkeypatch):
    assert resident.interpret_mode() is True  # the CPU test backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resident.interpret_mode() is False


@pytest.mark.parametrize("engine", ["stencil_pallas", "multispin_pallas",
                                    "bitplane_pallas"])
def test_engines_take_interpret_and_blocks_from_planner(monkeypatch,
                                                        engine):
    from repro.core.engine import ENGINES
    from repro.core.sim import SimConfig
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = ENGINES[engine](SimConfig(n=64, m=64, engine=engine))
    assert eng.interpret is False
    assert eng.block_rows == resident.block_plan(
        eng.resident_family, 64, 64).block_rows


def _python(code, env=None):
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)


def test_weakscale_import_sets_no_xla_flags():
    """Importing the weak-scaling module leaves JAX's flags alone: a
    parent that imports it can still hand the chip to a child."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = str(ROOT / "src")
    out = _python("import os, repro.dist.weakscale; "
                  "print(os.environ.get('XLA_FLAGS'))", env=env)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "None"


def _smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def _printed_result(stdout):
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return True
        except ValueError:
            pass
    return False


def test_chip_smoke_refuses_without_tpu():
    res = _smoke(ROOT, ROOT / "chip_smoke.py")
    assert res.returncode == 2, res.stderr
    assert "no TPU" in res.stderr
    assert not _printed_result(res.stdout)


def test_chip_smoke_fails_outside_the_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    res = _smoke(tmp_path, tmp_path / "chip_smoke.py")
    assert res.returncode != 0
    assert not _printed_result(res.stdout)
