"""Where JAX's persistent compilation cache lives.

One rule for every entry point (``hvd.init``, ``serving.serve``,
``bench.py``, ``chip_smoke.py``), so that processes of one job and runs
of one checkout find each other's compiled programs: if
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here; otherwise the cache is ``<checkout>/.jax_cache``.  The path is
part of the cache key's neighbourhood (entries are only found again under
the same directory), so it is never derived from ``tempfile``, a pid or
the clock.

A program's names are part of its entry's key.  What a program calls its
parts (``obs.trace.region``) lives in its instructions' metadata, which
JAX leaves out of the key by default; a program that differs from an
older tree's in metadata alone would then be handed that tree's
executable, and a profile of it, whose copy of the module is the
executable's, would read the older tree's names (none, for a tree from
before the regions).  So the key holds the metadata too.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def ensure_compile_cache() -> str:
    """Point JAX at the persistent compile cache; returns its directory.

    Call before the first compilation: JAX decides once per process,
    at its first compile, whether a cache is in use.
    """
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
