"""repro.compile_cache: where the persistent compilation cache lands.

Each case runs a fresh interpreter, since the cache directory is fixed
when a process first compiles."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import use_compile_cache
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "repo"])
def test_compile_cache_location(tmp_path, from_env):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    if from_env:
        want = str(tmp_path / "x")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    else:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        want = os.path.join(REPO, ".jax_cache")
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=tmp_path)
    assert out.returncode == 0, out.stderr
    returned, configured = out.stdout.split()[:2]
    assert returned == configured == want
    assert os.listdir(want), "nothing was cached"
