"""A compile counter: JAX's programs compiled and loaded, per function.

:func:`count_compiles` installs (once per process) listeners on JAX's
monitoring events that count into :data:`~repro.obs.metrics.METRICS`:

* ``jax.compiles.<fun>`` — programs of the jitted function ``<fun>``
  (``jit(decode_step)``, say) that XLA compiled;
* ``jax.cache_loads.<fun>`` — those loaded from the persistent
  compilation cache instead;
* ``jax.compiles`` / ``jax.cache_loads`` — the totals, and
  ``jax.programs`` their sum;
* histogram ``jax.compile_s`` — the seconds each of them took.

JAX times every program it compiles or loads with one event that
names the function; a load also fires a cache-hit event, inside that
timing and before it ends, so the listener marks the hit and the
timing event that follows on the same thread counts it as a load.
"""
from __future__ import annotations

import threading

from .metrics import METRICS

__all__ = ["cache_loads", "compiles", "count_compiles", "programs"]

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_installed = False
_hit = threading.local()


def _on_event(event: str, **kw) -> None:
    if event == _HIT_EVENT:
        _hit.pending = True


def _on_duration(event: str, secs: float, **kw) -> None:
    if event != _COMPILE_EVENT:
        return
    kind = "jax.cache_loads" if getattr(_hit, "pending", False) else \
        "jax.compiles"
    _hit.pending = False
    METRICS.counter("jax.programs")
    METRICS.counter(kind)
    METRICS.counter(f"{kind}.{kw.get('fun_name', '?')}")
    METRICS.observe("jax.compile_s", secs)


def count_compiles() -> None:
    """Start counting compiles into ``METRICS`` (idempotent)."""
    global _installed
    if _installed:
        return
    import jax
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _installed = True


def compiles(fun: str | None = None) -> int:
    """Programs compiled so far, of the function named ``fun`` (as JAX
    names it, e.g. ``jit(decode_step)``) or of all."""
    key = "jax.compiles" if fun is None else f"jax.compiles.{fun}"
    return METRICS.counters.get(key, 0)


def cache_loads(fun: str | None = None) -> int:
    """Programs loaded from the persistent compilation cache so far."""
    key = "jax.cache_loads" if fun is None else f"jax.cache_loads.{fun}"
    return METRICS.counters.get(key, 0)


def programs() -> int:
    """Programs compiled or loaded from the cache so far, of all
    functions."""
    return METRICS.counters.get("jax.programs", 0)
