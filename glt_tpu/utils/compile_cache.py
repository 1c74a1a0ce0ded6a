"""Where the persistent XLA compilation cache lives, and what its key holds.

One helper, called first by every entry point that compiles for the
chip (``chip_smoke.py``, ``bench.py``, ``chipbench/run.py``, each
example's ``main``).  The cache directory is part of the cache key, so it
must not move between runs: where ``JAX_COMPILATION_CACHE_DIR`` is set
JAX reads it itself and this sets no other; where it is not set the
cache goes to one fixed path inside the checkout (git-ignored).

The key holds the programs' metadata.  JAX's default key strips it, so a
cache filled by an older checkout serves executables with that
checkout's name stacks and line numbers; the per-layer metrics read the
program's stages out of exactly that metadata (``jax.named_scope``,
:mod:`glt_tpu.obs.scopes`), and a scope added, renamed or moved without
a change to the arithmetic would never reach a profile.  With the
metadata in the key such a change compiles anew, once.  Source paths are
keyed relative to the checkout, so two checkouts of one commit share
their entries.
"""
from __future__ import annotations

import os
import re

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: ``<checkout>/.jax_cache`` — fixed, never a tempdir, pid or timestamp.
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Make sure a persistent compile cache is on; return its directory.

    Call before the first compilation of the process.
    """
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(_CHECKOUT + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
