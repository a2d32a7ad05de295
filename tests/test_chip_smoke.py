"""chip_smoke.py and the compile-cache helper, as far as a CPU world can
check them: the smoke must FAIL here (it never runs small), and the
cache lands either where JAX_COMPILATION_CACHE_DIR says or at the one
fixed path inside the checkout."""

import os
import subprocess
import sys

import jax

from horovod_tpu.utils import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout  # no verdict line without a chip


def test_compile_cache_is_placed_or_fixed(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        # placed by the environment: JAX honours the variable itself,
        # the helper sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(_REPO, ".jax_cache")
        assert compile_cache.enable() == fixed
        assert compile_cache.enable() == fixed  # same path every call
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
