"""Where JAX's persistent compilation cache lives for the entry points
that touch the chip (``chip_smoke.py``, ``benchmarks/run.py``).

The directory is part of every cache key's world: a cache that moves
never hits. So there are exactly two places. Where the outside set
``JAX_COMPILATION_CACHE_DIR``, JAX has already read it and this module
sets nothing. Otherwise it is ``<checkout>/.jax_cache`` — one fixed
path, never a temporary name, a pid, a uid or a time. ``import
mxnet_tpu`` alone places no cache, so tests stay hermetic.
"""
from __future__ import annotations

import os

__all__ = ["ENV", "DEFAULT_DIR", "place", "CacheWatch"]

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def place():
    """Make sure JAX's persistent cache has a directory, and return it.
    Call before the first compile of the process."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # a compile that ran before this call latched "no cache" for the
    # process; forget that verdict
    compilation_cache.reset_cache()
    return DEFAULT_DIR


class CacheWatch:
    """Counts JAX's own persistent-cache events from construction on:
    ``requests`` (compiles that consulted the cache), ``hits`` (served
    from disk) and ``misses`` (compiled and written)."""

    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }

    def __init__(self):
        import jax
        self._counts = dict.fromkeys(self._EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        name = self._EVENTS.get(event)
        if name is not None:
            self._counts[name] += 1

    def counts(self):
        return dict(self._counts)
