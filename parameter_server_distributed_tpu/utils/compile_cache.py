"""Where the persistent XLA compilation cache lives.

Every entry point that compiles (pst-worker, pst-train, pst-serve,
pst-generate, pst-eval, pst-parameter-server with a device optimizer,
perfbench/run.py, chip_smoke.py) calls :func:`enable_compile_cache` before its
first jit, so a second process on the same machine reads the first one's
executables instead of compiling them again.

The directory can be placed from outside: when ``JAX_COMPILATION_CACHE_DIR``
is set JAX reads it itself and this module sets nothing.  Otherwise the
cache is ``<checkout>/.jax_cache``, found from the package's own path — the
path is part of the cache key, so it never depends on the working
directory, a temporary name, a pid or the time.
"""

from __future__ import annotations

import os

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    from_env = os.environ.get(ENV_CACHE_DIR)
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
