"""chip_smoke.py refuses to run without its TPU and its checkout, and the
launchers' compile cache goes where the placement rule says."""

import contextlib
import io
import os
import pathlib
import shutil
import subprocess
import sys

import jax

from repro.launch import cache

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load_smoke():
    import importlib.util
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_fails_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    err = io.StringIO()
    with contextlib.redirect_stderr(err):
        rc = _load_smoke().main([])
    out = capsys.readouterr().out
    assert rc != 0
    assert "no TPU" in err.getvalue()
    assert '"ok"' not in out


def test_chip_smoke_fails_outside_its_checkout(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "repro package" in proc.stderr


def test_compile_cache_keeps_env_dir(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_defaults_to_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        d = cache.enable_compile_cache()
        assert d == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == d
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
