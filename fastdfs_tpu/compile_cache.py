"""Where compiled XLA programs are kept between process starts.

Every entry point that jits (the sidecar, chip_smoke.py, the tests'
conftest) calls :func:`configure` before its
first compile, and nothing else in the tree names a cache directory.

The directory is placed from outside when ``JAX_COMPILATION_CACHE_DIR``
is set: JAX reads that variable itself, so no directory is set in code.
Otherwise it is ONE fixed path inside the checkout.  Either way every
program is kept, whatever it took to compile.  The path is part of
the cache key, so a directory built from a temp name, a pid or a time
would never hit; a fixed one means a second sidecar start skips the tile
shapes (``dedup.engine.plan_shapes``) x two kernels that
``DedupEngine.warmup`` compiles.
"""

from __future__ import annotations

import os

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(_REPO, ".jax_cache")


def configure() -> str:
    """Point JAX's persistent compilation cache; returns the directory."""
    import jax

    # Cache everything, wherever the directory is: the Pallas kernels
    # compile in 0.7 to 2 s each, under JAX's default 1 s threshold as
    # often as over it, and a program under it is compiled again at
    # every start (sixteen of them: 6.5 s of warm-up on the v5e, PERF.md
    # section 6, PR 33).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
