"""Where JAX's persistent compilation cache lives for the entry points
that touch the chip (``chip_smoke.py``, ``benchmarks/run.py``), and the
process's one listener to what JAX builds.

The directory is part of every cache key's world: a cache that moves
never hits. So there are exactly two places. Where the outside set
``JAX_COMPILATION_CACHE_DIR``, JAX has already read it and this module
sets nothing. Otherwise it is ``<checkout>/.jax_cache`` — one fixed
path, never a temporary name, a pid, a uid or a time. ``import
mxnet_tpu`` alone places no cache, so tests stay hermetic.

The listener (``listen``, registered once a process; ``executor``
registers it as it is imported) hears JAX's monitoring events. Each
backend build, an XLA compile or a load from the persistent cache,
becomes one ``xla_build`` event in telemetry's event ring (function
name, seconds, whether the persistent cache served it, whether it ran
inside an instrumented build: ``executor._InstrumentedProgram._build``)
and bumps the ``xla.programs`` / ``xla.build_s`` / ``xla.cache_misses``
counters. JAX's per-trace events, which fire for every nested ``jit``,
are not listened to. JAX emits these only when it builds, never a step.
"""
from __future__ import annotations

import contextlib
import os
import threading

from . import telemetry

__all__ = ["ENV", "DEFAULT_DIR", "place", "listen", "instrumented_build",
           "CacheWatch"]

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

# JAX's persistent-cache events -> CacheWatch's names
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
# one per backend build, compiled or loaded (``jax._src.dispatch``)
_BUILD_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_listening = False      # guarded by: _lock
# persistent-cache events since the listener was registered, whether
# telemetry records or not (CacheWatch subtracts its own start)
_counts = dict.fromkeys(_CACHE_EVENTS.values(), 0)   # guarded by: _lock
# per thread: inside an instrumented build; the running build was a
# persistent-cache hit (set by the hit event, read by the build's end)
_tls = threading.local()


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


def listen():
    """Register the process's one JAX monitoring listener; later calls
    do nothing."""
    global _listening
    with _lock:
        if _listening:
            return
        import jax
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


@contextlib.contextmanager
def instrumented_build():
    """Marks this thread's backend builds as an instrumented build's
    (the ``instrumented`` field of their ``xla_build`` events)."""
    outer = getattr(_tls, "building", False)
    _tls.building = True
    try:
        yield
    finally:
        _tls.building = outer


def _on_event(event, **_):
    name = _CACHE_EVENTS.get(event)
    if name is None:
        return
    with _lock:
        _counts[name] += 1
    if name == "hits":
        _tls.hit = True


def _on_duration(event, duration, **kwargs):
    if event != _BUILD_EVENT:
        return
    hit = getattr(_tls, "hit", False)
    _tls.hit = False
    telemetry.record_event("xla_build", fn=kwargs.get("fun_name"),
                           s=duration, cache_hit=hit,
                           instrumented=getattr(_tls, "building", False))
    telemetry.counter_inc("xla.programs")
    telemetry.counter_inc("xla.build_s", duration)
    if not hit:
        telemetry.counter_inc("xla.cache_misses")


def _cache_counts():
    with _lock:
        return dict(_counts)


class CacheWatch:
    """A view over the process's one listener: JAX's persistent-cache
    events from construction on, ``requests`` (compiles that consulted
    the cache), ``hits`` (served from disk) and ``misses`` (compiled and
    written). Counted with telemetry on or off."""

    def __init__(self):
        listen()
        self._start = _cache_counts()

    def counts(self):
        now = _cache_counts()
        return {k: now[k] - self._start[k] for k in now}
