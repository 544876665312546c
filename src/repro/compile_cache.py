"""Where JAX keeps its persistent compilation cache.

One place decides it for every entry point that compiles for the chip
(``chip_smoke.py`` and the benchmark CLIs).  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set in code; otherwise the cache lives at one fixed directory inside the
checkout, ``<repo>/.jax_cache`` (git-ignored).  The directory is part of
what a later process must find again, so it never derives from a temp
name, a pid or the clock.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set and non-empty, else the
    fixed in-checkout directory."""
    return os.environ.get(ENV_VAR) or CHECKOUT_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on at :func:`compile_cache_dir` and return
    that directory.  Call before the first compilation."""
    path = compile_cache_dir()
    if path == CHECKOUT_DIR:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
