"""Counts XLA backend compiles, so a run can show that none fell inside its
measured window.  The event fires once per program handed to the backend
compiler, whether the persistent cache then serves it or not: either way a
new program appeared where only warmed ones should run."""

from __future__ import annotations

_EVENT = "/jax/core/compile/backend_compile_duration"
_seen = {"installed": False, "count": 0}


def _on_event(event: str, duration: float, **_kwargs) -> None:
    if event == _EVENT:
        _seen["count"] += 1


def install() -> None:
    import jax.monitoring

    if not _seen["installed"]:
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _seen["installed"] = True


def count() -> int:
    """Backend compiles seen by this process since ``install``."""
    return _seen["count"]
