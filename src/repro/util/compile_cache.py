"""Persistent XLA compilation cache placement.

A process that compiles the same programs as an earlier one (a second
run of a script, a fleet agent beside its siblings) reads them back
instead of compiling again.  The cache key includes the directory, so
the directory is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when it is set
(JAX reads that variable itself, and nothing else is configured), else
``<checkout>/.jax_cache`` — never a temporary, per-process or per-run
name.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                        "..", ".."))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
