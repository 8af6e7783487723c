"""The chip smoke's guards, checked without a chip: where the compilation
cache goes, and that the script refuses to run anywhere but on a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parent.parent


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    return calls


def test_cache_dir_from_environment_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _record_updates(monkeypatch)
    assert compile_cache.configure_compile_cache() == (str(tmp_path), True)
    assert calls == []


def test_cache_dir_default_is_fixed_inside_the_repo(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _record_updates(monkeypatch)
    first = compile_cache.configure_compile_cache()
    assert first == compile_cache.configure_compile_cache()
    path, from_env = first
    assert not from_env
    assert Path(path) == REPO / ".jax_cache"
    assert calls == [("jax_compilation_cache_dir", path)] * 2


def _run_smoke(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_smoke_refuses_the_cpu():
    res = _run_smoke(REPO / "chip_smoke.py", REPO)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "needs a TPU" in res.stderr


def test_smoke_fails_without_the_repository(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    res = _run_smoke(tmp_path / "chip_smoke.py", tmp_path)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
