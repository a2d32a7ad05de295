"""Where the persistent XLA compilation cache lives.

Entry points that compile on the chip (chip_smoke.py, bench.py's
vehicles, the examples' ``main()``) call :func:`enable` before their
first compile; ``hvd.init()`` does not — the cache directory is a
process-wide JAX setting and a library must not overwrite the user's.
"""

from __future__ import annotations

import os

# fixed, inside the checkout: the directory is part of what a later run
# has to find again, so it is never built from tempfile, a pid or the time
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return it. ``JAX_COMPILATION_CACHE_DIR`` set: JAX already honours
    it and nothing is set here; unset: :data:`REPO_CACHE_DIR`. JAX
    decides once per process, at its first compile, whether the cache
    is in use — call this before anything compiles."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
