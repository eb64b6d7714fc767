"""Where the persistent XLA compilation cache goes: the directory named by
JAX_COMPILATION_CACHE_DIR, else one fixed path in the checkout."""

import os
import subprocess
import sys
import textwrap

import jax

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_default_is_a_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert REPO_CACHE_DIR.parent.samefile(ROOT)


def test_env_dir_is_used_and_nothing_else(tmp_path):
    """A compile lands in the named directory; the in-checkout default is
    not touched.  (A child process: the cache is process-global state.)"""
    code = textwrap.dedent("""
        import os, sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache
        before = REPO_CACHE_DIR.exists() and sorted(os.listdir(REPO_CACHE_DIR))
        assert enable_compile_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
        jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(7)).block_until_ready()
        after = REPO_CACHE_DIR.exists() and sorted(os.listdir(REPO_CACHE_DIR))
        assert before == after
        print("PASS")
    """)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=ROOT, env=env, timeout=300)
    assert "PASS" in r.stdout, r.stderr[-2000:]
    assert any(p.name.endswith("-cache") for p in (tmp_path / "xla").iterdir())
