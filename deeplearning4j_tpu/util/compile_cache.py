"""Where compiled programs are kept between processes.

A cold ``serve --generate`` start compiles a whole program family
(decode x table buckets, prefill x chunk buckets) before its banner; JAX's
persistent compilation cache lets the next process skip that. The entry
points that compile call :func:`enable_compile_cache` first thing; nothing
calls it at package import.

The directory is placed from OUTSIDE: when ``JAX_COMPILATION_CACHE_DIR`` is
set JAX already reads it, and no code here sets another. Unset, the cache
goes to one fixed path inside the checkout (``<repo>/.jax_cache``, ignored
by git), resolved from this file's own location — never from ``tempfile``,
a pid or a timestamp, so every process of a run and every later run share
the same directory.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_IN_CHECKOUT = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(_IN_CHECKOUT)
        jax.config.update("jax_compilation_cache_dir", path)
    # the serving families are many programs of a second or less each:
    # the default one-second floor would cache almost none of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
