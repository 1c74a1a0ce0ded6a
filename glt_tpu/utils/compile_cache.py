"""Where the persistent XLA compilation cache lives.

One helper, called first by every entry point that compiles for the
chip (``chip_smoke.py``, ``bench.py``, each example's ``main``).  The
cache directory is part of the cache key, so it must not move between
runs: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
this does nothing; where it is not set the cache goes to one fixed path
inside the checkout (git-ignored).
"""
from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` — fixed, never a tempdir, pid or timestamp.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Make sure a persistent compile cache is on; return its directory.

    Call before the first compilation of the process.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
