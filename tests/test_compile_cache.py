"""Where the entry points keep JAX's persistent compilation cache.

Each case runs in a child process: turning the cache on is process-wide
state that the rest of the suite must not inherit.
"""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

_SCRIPT = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, "src")
    import jax
    from repro.launch.cache import enable_compilation_cache
    where = enable_compilation_cache()
    print("RETURNED", where)
    print("CONFIG", jax.config.jax_compilation_cache_dir)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(3)).block_until_ready()
""")


@pytest.mark.parametrize("env_dir", [False, True], ids=["default", "env"])
def test_compilation_cache_location(tmp_path, env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    expected = str(REPO / ".jax_cache")
    if env_dir:
        expected = str(tmp_path / "cache")
        env["JAX_COMPILATION_CACHE_DIR"] = expected
    res = subprocess.run([sys.executable, "-c", _SCRIPT], cwd=REPO,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    assert f"RETURNED {expected}\n" in res.stdout
    assert f"CONFIG {expected}\n" in res.stdout
    if env_dir:
        # The compile landed in the environment's directory.
        assert any(pathlib.Path(expected).iterdir())
