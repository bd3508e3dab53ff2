"""Placement of JAX's persistent compilation cache, for entry points.

Called by the programs a user starts (``chip_smoke.py``, ``bench.py``,
``bench_matrix.py``, the CLI) and never at import: a library that
configured a cache on import would make the test suite write one.

The cache's directory is part of its key, so it must not move between
runs: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and
this module sets no directory at all; otherwise the cache lives at
``<checkout>/.jax_cache`` (git-ignored), derived from this file's own
location.  The key of an entry covers the program's metadata too — name
stacks and source locations: an executable carries the name stacks it
was compiled with.
"""

from __future__ import annotations

import os
from typing import Optional

ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))


def default_cache_dir() -> Optional[str]:
    """The directory this module would set: ``None`` where the
    environment already places the cache, else ``<checkout>/.jax_cache``."""
    if os.environ.get(ENV):
        return None
    return os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and
    return the directory in use.  Every program is kept, however quickly
    it compiled: a run is dozens of small programs, and the next process
    should find all of them."""
    import jax

    path = default_cache_dir()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # the key covers the operations' name stacks: they are what a device
    # trace is read by (the step's phase scopes, ``seq/*``), and JAX's
    # default key leaves them out, so a step whose scopes alone changed
    # would run the cached executable and carry the old names.  The flag
    # keeps ALL debug info in the key, source files and line numbers too:
    # an edit that shifts a line of a traced file, or a moved checkout,
    # compiles again
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return jax.config.jax_compilation_cache_dir
