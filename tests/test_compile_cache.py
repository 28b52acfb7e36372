"""Where the persistent compilation cache goes (``repro.compile_cache``)."""

import os
import subprocess
import sys

import jax

from repro import compile_cache

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")


def _restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    return lambda: jax.config.update("jax_compilation_cache_dir", was)


def test_default_is_fixed_repo_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    restore = _restore_cache_dir()
    try:
        got = compile_cache.enable()
        assert got == str(compile_cache.DEFAULT_DIR)
        assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
        assert (compile_cache.DEFAULT_DIR.parent / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        restore()


def test_environment_wins_and_nothing_else_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    restore = _restore_cache_dir()
    try:
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        restore()


def _run(code: str, env: dict) -> str:
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env={**env, "PYTHONPATH": SRC,
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    return r.stdout.strip()


def test_cache_lands_in_environment_dir(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env[compile_cache.ENV_VAR] = str(tmp_path / "cache")
    _run("import jax, jax.numpy as jnp\n"
         "from repro import compile_cache\n"
         "compile_cache.enable()\n"
         "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
         "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()\n",
         env)
    assert any((tmp_path / "cache").iterdir())


def test_importing_repro_sets_no_cache():
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    out = _run("import jax\n"
               "import repro.compile_cache, repro.serve.codec_engine\n"
               "import repro.serve.service, repro.bench.cli\n"
               "print(jax.config.jax_compilation_cache_dir)\n", env)
    assert out == "None"
