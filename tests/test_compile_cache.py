"""The persistent compilation cache lives where the environment says, or at
a fixed path inside the checkout (``launch/compile_cache.py``)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_without_the_variable_the_cache_is_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        compile_cache.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_the_variable_places_the_cache_and_the_helper_defers(tmp_path):
    code = """
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    print(jax.config.jax_compilation_cache_dir)
    jax.jit(lambda x: jnp.sin(x) * 2).lower(jnp.ones(16)).compile()
    """
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               # cache even this tiny program (JAX's own setting)
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.split()[0] == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry written"
