"""JAX's persistent compilation cache at one fixed place per checkout.

Serving warms every stage bucket before it takes traffic, and at published
widths each bucket is a multi-second XLA/Mosaic compile. The persistent
cache turns the second process's warmup into cache reads. The directory is
part of the cache's key, so it must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself; nothing else is
  set here.
* otherwise: ``<repo>/.jax_cache`` (listed in ``.gitignore``).

Call :func:`enable_compile_cache` before the first compile. A
:class:`CacheEvents` counts the cache's hits and misses from jax's own
monitoring events, so a run can report whether its warmup was served from
the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


class CacheEvents:
    """Counts persistent-cache hits and misses from the moment it is made
    (jax's monitoring listeners are process-wide and stay registered)."""

    def __init__(self):
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kwargs) -> None:
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1
