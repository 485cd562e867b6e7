"""Hook JAX compile events into the obs tracer/metrics.

Batch-first dispatch makes compile count the number that matters: a
grouped pnr or sim stage should trigger ONE ``jax.jit`` compile per
bucket signature, after which dispatches are cache hits.  ``jax.monitoring``
fires named duration events around every tracing/lowering/backend-compile
step; this module forwards them — when enabled — to

* the global :class:`~repro.obs.metrics.MetricsRegistry` (or one given
  to :func:`enable`): counters ``jax.compile.events`` /
  ``jax.compile.<leaf>`` and histogram ``jax.compile.secs``;
* the active tracer, as completed spans on a ``jax-compile`` side track,
  so a Perfetto timeline visually separates compile time from dispatch
  time (the span *ends* when the listener fires; its start is backdated
  by the reported duration).

The persistent compilation cache's own events (``/jax/compilation_cache/
cache_hits``, ``cache_misses``, ...) land as counters
``jax.compilation_cache.<leaf>``, so a run can tell a warm cache from a
cold one.

``jax.monitoring`` has no per-listener unregister (only a global
``clear_event_listeners``), so the listener is installed once and
consults a module flag — :func:`disable` flips the flag, it does not
touch other listeners.
"""

from __future__ import annotations

from typing import Optional

from .metrics import MetricsRegistry, global_registry
from .trace import current as current_tracer

__all__ = ["enable", "disable", "is_enabled"]

_INSTALLED = False
_ENABLED = False
_REGISTRY: Optional[MetricsRegistry] = None

# substrings of jax.monitoring event names worth accounting for
_COMPILE_MARK = "compile"
_CACHE_PREFIX = "/jax/compilation_cache/"


def _on_duration(event: str, duration: float, **kwargs) -> None:
    # the cache's "compile_time_saved_sec" is time NOT spent compiling
    if (not _ENABLED or _COMPILE_MARK not in event
            or event.startswith(_CACHE_PREFIX)):
        return
    reg = _REGISTRY or global_registry()
    leaf = event.rstrip("/").rsplit("/", 1)[-1]
    reg.inc("jax.compile.events")
    reg.inc(f"jax.compile.{leaf}")
    reg.observe("jax.compile.secs", duration)
    tracer = current_tracer()
    if tracer is not None:
        t1 = tracer.now()
        tracer.add_complete(leaf, max(t1 - duration, 0.0), duration,
                            track="jax-compile", event=event)


def _on_event(event: str, **kwargs) -> None:
    if not _ENABLED or not event.startswith(_CACHE_PREFIX):
        return
    reg = _REGISTRY or global_registry()
    reg.inc(f"jax.compilation_cache.{event[len(_CACHE_PREFIX):]}")


def enable(registry: Optional[MetricsRegistry] = None) -> bool:
    """Start forwarding jax compile events; returns False if jax is
    missing (the subsystem stays a no-op)."""
    global _INSTALLED, _ENABLED, _REGISTRY
    _REGISTRY = registry
    if not _INSTALLED:
        try:
            from jax import monitoring
        except Exception:       # pragma: no cover - jax is baked in
            return False
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _INSTALLED = True
    _ENABLED = True
    return True


def disable() -> None:
    global _ENABLED, _REGISTRY
    _ENABLED = False
    _REGISTRY = None


def is_enabled() -> bool:
    return _ENABLED
