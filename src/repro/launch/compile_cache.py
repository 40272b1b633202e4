"""Persistent XLA compile cache: the one rule every entry point applies.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache and JAX reads it
itself — nothing here sets another directory.  Otherwise the cache lives at
a fixed path inside the checkout (``<repo>/.jax_cache``, git-ignored): the
path is part of what a later run must find again, so it is never built
from a temporary name, a process id or the time.

Every compile is written, however short: the eager serving path compiles
hundreds of small programs, each under JAX's default one-second threshold,
and together they are most of a cold start.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping

__all__ = ["CACHE_ENV", "compile_cache_dir", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = Path(__file__).resolve().parents[3]   # src/repro/launch -> repo


def compile_cache_dir(environ: Mapping[str, str] | None = None) -> Path:
    """The cache directory the rule picks for ``environ`` (default: this
    process's environment)."""
    env = os.environ if environ is None else environ
    named = env.get(CACHE_ENV)
    return Path(named) if named else _CHECKOUT / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn the persistent cache on for this process; return its directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
