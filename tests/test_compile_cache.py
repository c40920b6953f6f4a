"""The persistent compile cache goes where the environment says, or to the
checkout's fixed ``.jax_cache``.  Each case runs in a fresh process, since
the cache directory is fixed at JAX's first compile."""

import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")

_PROBE = """
import os, sys
import jax, jax.numpy as jnp
from repro.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache
path = enable_compile_cache()
assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
if sys.argv[1] == "env":
    jax.jit(lambda x: jnp.sin(x) + 1)(jnp.ones((8, 8))).block_until_ready()
    assert path == os.environ["JAX_COMPILATION_CACHE_DIR"], path
    assert os.listdir(path), "nothing was cached"
else:
    assert path == DEFAULT_CACHE_DIR, path
    assert jax.config.jax_compilation_cache_dir == DEFAULT_CACHE_DIR
    assert os.path.basename(path) == ".jax_cache"
print("OK")
"""


@pytest.mark.parametrize("where", ["env", "default"])
def test_compile_cache_location(tmp_path, where):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if where == "env":
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run([sys.executable, "-c", _PROBE, where], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-2000:]
