"""Named host spans of the serving path, on the profiler's own clock.

``span(name, **args)`` is ``jax.profiler.TraceAnnotation("odmoe." + name,
**args)``: while a ``jax.profiler.trace`` runs, each span is a host event
in the same ``.xplane.pb`` as the device's ops, so host phases and device
work share one clock; otherwise it costs about a microsecond.  Integer
and string arguments become the event's stats.  Spans nest on the thread
that opens them.  A string argument must not hold a comma (the profiler
splits arguments at commas), so id lists are joined with ``;``.

docs/ARCHITECTURE.md lists every ``odmoe.*`` span and what it covers.
"""
from __future__ import annotations

import gc
from typing import Iterable

from jax.profiler import TraceAnnotation

PREFIX = "odmoe."


def span(name: str, **args) -> TraceAnnotation:
    return TraceAnnotation(PREFIX + name, **args)


def joined(ids: Iterable[int]) -> str:
    """Request ids as one span argument: ``"3;7;12"``."""
    return ";".join(str(int(i)) for i in ids)


class _GCSpan:
    """``gc.callbacks`` entry: an ``odmoe.gc`` span around each
    collection of generation 1 or 2, with the generation and the number
    of objects it collected.  CPython runs one collection at a time, so
    one open span suffices."""

    def __init__(self):
        self._open = None

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] == 0:
            return
        if phase == "start":
            self._open = span("gc", generation=info["generation"])
            self._open.__enter__()
        elif self._open is not None:
            self._open.set_metadata(collected=info["collected"])
            self._open.__exit__(None, None, None)
            self._open = None


_GC_SPAN = _GCSpan()


def install_gc_span() -> None:
    """Time the interpreter's full collections as ``odmoe.gc`` spans
    (idempotent)."""
    if _GC_SPAN not in gc.callbacks:
        gc.callbacks.append(_GC_SPAN)
