"""``chip_smoke.py`` off the chip, and where the entry points cache.

Without a TPU the smoke rehearses every phase at a tiny size and must
exit non-zero without its result line.  The compile-cache helper
honours ``JAX_COMPILATION_CACHE_DIR`` and otherwise names one fixed
directory inside the checkout; importing ``repro`` names none.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch.cache import ENV_VAR, use_compile_cache

pytestmark = pytest.mark.tier1

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_cache_dir_from_environment(cache_config, monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert use_compile_cache(ROOT) == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_cache_dir_fixed_inside_checkout(cache_config, monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    expected = str(ROOT / ".jax_cache")
    assert use_compile_cache(ROOT) == expected
    assert jax.config.jax_compilation_cache_dir == expected
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def _child_env(**extra):
    env = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"), **extra)
    return env


def test_importing_repro_sets_no_cache():
    out = subprocess.run(
        [sys.executable, "-c",
         "import repro.api, repro.engine, repro.serving, jax; "
         "print(repr(jax.config.jax_compilation_cache_dir))"],
        capture_output=True, text=True, timeout=120, env=_child_env())
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "None"


def test_chip_smoke_rehearses_and_refuses_without_tpu(tmp_path):
    cache = tmp_path / "cache"
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=600, cwd=tmp_path,
        env=_child_env(**{ENV_VAR: str(cache)}))
    lines = out.stdout.strip().splitlines()
    assert out.returncode == 2, out.stdout + out.stderr[-3000:]
    assert f"compile cache: {cache}" in lines
    assert "device: platform=cpu kind=cpu count=1" in lines
    assert "DIFFERENT" not in out.stdout
    assert lines[-1] == "rehearsal passed; exiting non-zero without a TPU"
    for line in lines:
        if line.startswith("{"):
            assert not json.loads(line).get("ok"), line


def test_chip_smoke_alone_fails(tmp_path):
    """Copied out of the repository, the script has no program to run."""
    script = tmp_path / "chip_smoke.py"
    script.write_text((ROOT / "chip_smoke.py").read_text())
    env = _child_env()
    env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=120, cwd=tmp_path, env=env)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
