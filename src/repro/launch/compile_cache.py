"""JAX persistent compilation cache location.

A compiled executable is keyed in part on the cache directory's path, so
the directory has to be the same from one run to the next to ever hit:
`JAX_COMPILATION_CACHE_DIR` where the environment sets it, otherwise a
fixed `.jax_cache` at the root of the repository.
"""

from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache for every compile in this
    process and return its directory.  Call before the first compile: JAX
    opens the cache once."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    # Pallas kernels compile in well under the 1 s default floor, and a
    # served scan compiles dozens of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
