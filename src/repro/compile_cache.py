"""JAX's persistent compilation cache, kept at one fixed place.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and nothing
here overrides it.  Otherwise the cache lives in ``.jax_cache/`` at the
root of the checkout (git-ignored): a fixed path, because the path is
part of every entry's key and a directory that moves never hits.

The query engine compiles many small programs -- one fused scan program
per plan signature, one per kernel shape -- most of which compile in a
fraction of a second.  JAX's default threshold (1 s of compile time)
would cache none of them, so the threshold drops to 0.1 s: below that,
reading and deserializing an entry costs about what compiling does.
Every entry size is kept (0 bytes minimum, JAX's default).
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"
MIN_COMPILE_SECS = 0.1
MIN_ENTRY_BYTES = 0


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      MIN_ENTRY_BYTES)
    return path
