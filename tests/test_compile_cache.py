"""``utils/compile_cache.use_compile_cache``: the operator's directory wins and
the code then sets nothing; otherwise one fixed path beside the package."""

import pathlib
import subprocess
import sys

import jax

from photon_tpu.utils import compile_cache
from tests._helpers import subprocess_env

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_env_placed_cache_is_left_to_jax(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the helper must not touch
    ``jax_compilation_cache_dir`` — JAX reads the variable itself."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/placed")
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **kw: updates.append(a))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == "/somewhere/placed"
    assert updates == []
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_is_one_fixed_path_across_calls_and_processes():
    """Unset, the directory is ``<checkout>/.jax_cache``: no pid, time or
    temporary name in it (the path is part of the cache key)."""
    want = str(REPO / ".jax_cache")
    assert str(compile_cache.DEFAULT_DIR) == want
    env = subprocess_env()
    del env["JAX_COMPILATION_CACHE_DIR"]
    script = (
        "import jax\n"
        "from photon_tpu.utils.compile_cache import use_compile_cache\n"
        "a = use_compile_cache(); b = use_compile_cache()\n"
        "assert a == b == jax.config.jax_compilation_cache_dir, (a, b)\n"
        "print(a)\n"
    )
    seen = [
        subprocess.run([sys.executable, "-c", script], env=env, cwd="/",
                       capture_output=True, text=True, timeout=120, check=True
                       ).stdout.strip()
        for _ in range(2)
    ]
    assert seen == [want, want]
