"""The spans the program records about itself, for the metric readers.

``repro.core.monitoring.SPANS`` records while a profiler session is active.
A traced run's session covers the measured window and nothing else, so
after the window the recorder's buffer holds the window's spans.  A
program without the recorder, or a window in which a span never opened,
gives nothing to read (``None``)."""

from __future__ import annotations

from typing import Optional


def recorder():
    """The program's span recorder, or None where the program has none."""
    try:
        from repro.core.monitoring import SPANS
    except ImportError:
        return None
    return SPANS


def spans(name: str) -> Optional[list]:
    """The window's spans of one name, or None where there are none."""
    rec = recorder()
    found = rec.spans(name) if rec is not None else []
    return found or None


def mean_ms(name: str, *, own: bool = False) -> Optional[float]:
    """Mean duration of one span name in ms; with ``own``, of its self time
    (the span less the spans recorded inside it)."""
    rec = recorder()
    if rec is None:
        return None
    s = rec.summary().get(name)
    if not s or not s["count"]:
        return None
    return (s["self_ms"] if own else s["total_ms"]) / s["count"]
