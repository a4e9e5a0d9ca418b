"""The entry points' compile-cache helper: ``JAX_COMPILATION_CACHE_DIR``
wins when set; otherwise the cache sits at a fixed, git-ignored path inside
the checkout."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMPILE_ONE = textwrap.dedent(
    """
    from repro.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    print(where)
    print(jax.config.jax_compilation_cache_dir)
    """
)

CONFIGURE_ONLY = textwrap.dedent(
    """
    from repro.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    import jax

    print(where)
    print(jax.config.jax_compilation_cache_dir)
    """
)


def _run(code: str, env: dict) -> list[str]:
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.split()


def _env(**extra) -> dict:
    env = {
        k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"
    }
    env.update(PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu")
    env.update(extra)
    return env


def test_environment_directory_receives_the_programs(tmp_path):
    cache = tmp_path / "cache"
    where, configured = _run(COMPILE_ONE, _env(JAX_COMPILATION_CACHE_DIR=str(cache)))
    assert where == configured == str(cache)
    assert cache.is_dir() and any(cache.iterdir())


def test_default_directory_is_fixed_and_git_ignored():
    from repro.compile_cache import DEFAULT_CACHE_DIR

    assert DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    where, configured = _run(CONFIGURE_ONLY, _env())
    assert where == configured == DEFAULT_CACHE_DIR
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
