"""Persistent XLA compilation cache.

The engine's per-bucket programs take seconds to minutes each to compile,
and the reference has nothing comparable to pay — its "backend" is an HTTP
call. JAX's persistent compilation cache makes every program a one-time cost
per cache directory instead of per process.

Two rules, and no third:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX's own handling of it stands and
  this module sets no directory. Whoever runs the program (a driver, a CI
  job) places the cache.
- unset: the directory is ``<checkout>/.jax_cache``, resolved from this
  package's own location — never ``$HOME``, a temp name, a pid or the time.
  The path is part of the cache key, so a directory that moves never hits.

Every device-touching entry point (TpuBackend, LongContextBackend,
EmbeddingModel, Trainer) calls :func:`enable_compilation_cache`
before building programs.
"""
from __future__ import annotations

import os
from pathlib import Path

_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compilation_cache() -> str:
    """Make sure compiled programs persist on disk; returns the directory
    they land in. Idempotent."""
    placed = os.environ.get(_ENV)
    if placed:
        return placed
    import jax

    if jax.config.jax_compilation_cache_dir != _DEFAULT_DIR:
        os.makedirs(_DEFAULT_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR
