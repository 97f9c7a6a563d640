"""JAX's persistent compilation cache, placed from outside the program.

Entry points call `enable_compile_cache()` first thing in `main()`; nothing
calls it on import. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
itself and this module sets no directory. Otherwise the cache lives at the
fixed path `<checkout>/.jax_cache` (git-ignored): the directory is part of
what a later run must find again, so it is never built from a temporary
name, a process id or the time. On the CPU backend the helper leaves the
cache alone: CPU compiles are cheap, and XLA:CPU warns on every reload.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the persistent compilation cache on for an accelerator;
    returns its directory (None on the CPU backend)."""
    if jax.default_backend() == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: the serve step's kernels compile in well under
    # the default one-second floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
