"""JAX's persistent compilation cache, placed for an entry script.

Entry scripts (``chip_smoke.py``, ``examples/register_volumes.py``,
``launch/serve_registration.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` before their first compile; importing the
library never turns a cache on.

* With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already caches there and this
  sets nothing else.
* Otherwise the cache goes to ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``).  The path is fixed on purpose: it is part of what makes a
  later process find the entries, so it is never built from a temporary
  name, a pid or the time.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "CacheCounter", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}


class CacheCounter:
    """Counts persistent-cache hits and misses as JAX reports them."""

    def __init__(self):
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        name = _EVENTS.get(event)
        if name is not None:
            setattr(self, name, getattr(self, name) + 1)

    def close(self):
        jax.monitoring.unregister_event_listener(self._on_event)


def enable_compile_cache() -> tuple[str, CacheCounter]:
    """Turn the persistent cache on; returns ``(directory, counter)``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path, CacheCounter()
