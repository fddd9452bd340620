"""Cross-cutting collectors: XLA compile tracking, HBM high-watermarks, and
labeled xprof spans.

- :class:`CompileTracker` listens to ``jax.monitoring`` duration events
  (``/jax/core/compile/backend_compile_duration`` fires once per backend
  compile) and feeds registry counters.  Recompiles — compiles beyond the
  expected warm-up set — are the silent TPU perf killer: a shape-polymorphic
  input (ragged batch, drifting pad length) silently triggers a multi-second
  XLA compile per new shape, and nothing in stock JAX tells you.
- :func:`hbm_stats` / :func:`update_hbm_gauges` read
  ``device.memory_stats()`` (None-tolerant: the CPU simulator reports
  nothing) into high-watermark gauges.
- :func:`xprof_span` wraps ``jax.profiler.TraceAnnotation`` so engine phases
  (place/dispatch/accum/step/io) show up *named*, with their attributes, in
  the profiler's trace — on the same clock as the device events — instead
  of as anonymous python frames.  Always emitted: an annotation outside an
  active trace costs about a microsecond.

``jax.monitoring`` listeners are process-global and cannot be individually
removed, so ONE module-level dispatcher is installed lazily and fans out to
live trackers (kept in a ``WeakSet`` — a dropped ``Telemetry`` object must
not leak its tracker forever).
"""

from __future__ import annotations

import threading
import weakref
from typing import Dict, Optional

#: monitoring event that fires once per XLA backend compile
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_trackers: "weakref.WeakSet[CompileTracker]" = weakref.WeakSet()
_listener_installed = False
_listener_lock = threading.Lock()


def _dispatch(event: str, duration: float, **kwargs) -> None:
    if event != _COMPILE_EVENT:
        return
    for tracker in list(_trackers):
        tracker._on_compile(duration)


def _ensure_listener() -> None:
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_dispatch)
        _listener_installed = True


class CompileTracker:
    """Per-``Telemetry`` compile accounting.

    - ``compiles`` / ``compile_time_s``: every XLA backend compile observed
      since construction (fed by the ``jax.monitoring`` dispatcher; includes
      one-off tiny eager-op programs, so treat as a warm-up-heavy total).
    - ``recompiles``: *structurally detected* re-compilations of an
      already-warm step program under a new input-shape signature, reported
      by the owning facade's engine via :meth:`note_recompile`
      (instance-scoped — the monitoring stream carries no program identity,
      and another facade's shape churn must not be charged here).  The
      actionable "your batches are shape-polymorphic" signal.
    """

    def __init__(self, registry=None):
        self._lock = threading.Lock()
        self.compiles = 0
        self.compile_time_s = 0.0
        self.recompiles = 0
        self._registry = registry
        if registry is not None:
            # pre-register so snapshots carry zeros before the first compile
            registry.counter(
                "jax/compiles_total", help="XLA backend compiles observed"
            )
            registry.counter(
                "jax/compile_time_s", help="cumulative XLA compile seconds"
            )
            registry.counter(
                "jax/recompiles_total",
                help="warm step programs re-compiled for a new input-shape "
                "signature",
            )
        _ensure_listener()
        _trackers.add(self)

    def _on_compile(self, duration: float) -> None:
        with self._lock:
            self.compiles += 1
            self.compile_time_s += float(duration)
        if self._registry is not None:
            self._registry.counter("jax/compiles_total").inc()
            self._registry.counter("jax/compile_time_s").inc(float(duration))

    def note_recompile(self, n: int = 1) -> None:
        """Record ``n`` structural recompiles (engine shape-signature
        detection)."""
        with self._lock:
            self.recompiles += int(n)
        if self._registry is not None:
            self._registry.counter("jax/recompiles_total").inc(int(n))

    _on_recompile = note_recompile  # internal alias


# --------------------------------------------------------------------------- #
# HBM high-watermark gauges
# --------------------------------------------------------------------------- #

#: memory_stats keys -> registry gauge names
_HBM_KEYS = {
    "bytes_in_use": "hbm/bytes_in_use",
    "peak_bytes_in_use": "hbm/peak_bytes",
    "bytes_limit": "hbm/bytes_limit",
    "largest_free_block_bytes": "hbm/largest_free_block_bytes",
}


def hbm_stats(device=None) -> Optional[Dict[str, int]]:
    """``memory_stats()`` of ``device`` (default: first local device), or
    None where the backend reports nothing (CPU simulator)."""
    try:
        import jax

        dev = device if device is not None else jax.local_devices()[0]
        stats = dev.memory_stats()
    except Exception:
        return None
    return stats or None


def update_hbm_gauges(registry, device=None) -> Optional[Dict[str, int]]:
    """Refresh the ``hbm/*`` gauges from ``memory_stats()``; returns the raw
    stats (None on reporting-free backends, gauges left unset)."""
    stats = hbm_stats(device)
    if not stats:
        return None
    for key, gauge_name in _HBM_KEYS.items():
        if key in stats:
            registry.gauge(gauge_name).set(stats[key])
    return stats


# --------------------------------------------------------------------------- #
# labeled xprof spans
# --------------------------------------------------------------------------- #

def xprof_span(name: str, **attrs):
    """Context manager labeling the enclosed host section in the profiler's
    trace (``jax.profiler.TraceAnnotation``).  ``attrs`` (ints, floats,
    bools, short strings) arrive there as the event's stats."""
    import jax.profiler

    return jax.profiler.TraceAnnotation(name, **attrs)
