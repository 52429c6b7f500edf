"""Where JAX's persistent compilation cache lives — decided in one place.

The chip is reached one sealed machine per command, so a cold process
recompiles everything it runs (ResNet50's train step alone is most of a
minute). Processes of one command, and repeat runs in one checkout,
share compiled programs through the persistent cache — but only if they
all name the SAME directory: the path is part of what a process looks
up, so a temp name, a pid or a timestamp in it never hits.

The rule: whoever launches the program places the cache with
``JAX_COMPILATION_CACHE_DIR`` and the code then sets nothing; otherwise
it is ``<checkout>/.jax_cache`` (git-ignored). Entry points
(`chip_smoke.py`, `benchmark/run.py`) call :func:`place_compile_cache`
before their first compile; no other code sets a cache directory.
"""
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def place_compile_cache() -> str:
    """Apply the rule above; returns the directory in effect."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed           # JAX reads the variable itself
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
