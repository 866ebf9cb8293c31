"""Where JAX keeps the programs it compiles, from one run to the next.

Compiling the FEMNIST-CNN round takes most of a cold run's set-up time,
so every entry point (``chip_smoke.py``, ``launch/train.py``,
``benchmarks/run.py``, ``benchmarks/time_to_accuracy.py``) calls
:func:`use_compile_cache` before it compiles anything.

The directory is fixed: the cache is found again only at the same path,
so it never holds a temporary name, a pid or a time. Set
``JAX_COMPILATION_CACHE_DIR`` to put it elsewhere; JAX reads that
variable itself, and this module then sets nothing.
"""
from __future__ import annotations

import os

import jax

#: the checkout's own cache directory (``<repo>/.jax_cache``, gitignored)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` when set, else
    :data:`REPO_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
