"""The program's own host spans in a run's profiler trace.

The serving path opens a ``jax.profiler.TraceAnnotation`` named
``odmoe.<phase>`` around each host phase of a step (``repro.core.spans``).
They are written into the same ``.xplane.pb`` as the device's ops, on one
clock.  This module reads them with their arguments and the thread (line)
that opened them, together with JAX's own ``backend_compile_and_load``
host events, and reduces them against the first device's busy time:

* ``total_ns``: the summed time of the spans of one name;
* ``self_ns``: a span's time minus the union of the ``odmoe.*`` spans
  nested in it on its thread;
* ``busy_share``: the share of the union of some spans in which the
  device was busy;
* ``idle_by_span``: device-idle time by the innermost span open in it.

Everything is clipped to the run's traced window (``run.trace.window``).
Every reader returns ``None`` when the window holds no ``odmoe.`` span: a
program without the spans reports nothing, never 0.
"""
from __future__ import annotations

import bisect
import functools
import glob
import heapq
import itertools
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import spec, trace

PREFIX = "odmoe."
COMPILE_EVENT = "backend_compile_and_load"
COMPILE = "compile"         # idle_by_span's label for compile events
NONE = "none"               # ... and for time inside no span


@dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    dur_ns: float
    thread: Tuple[int, int]         # (plane, line) of the trace
    args: dict = field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


# ------------------------------------------------------------- reading
def from_profile(pd) -> Tuple[List[Span], List[float]]:
    """The ``odmoe.*`` and compile events of a ``ProfileData``'s host
    planes, and the starts of its ``bench.window`` spans."""
    out, windows = [], []
    for pi, plane in enumerate(pd.planes):
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                name = e.name
                if name == trace.WINDOW_SPAN:
                    windows.append(float(e.start_ns))
                elif name.startswith(PREFIX) or name == COMPILE_EVENT:
                    out.append(Span(name, float(e.start_ns),
                                    float(e.duration_ns), (pi, li),
                                    dict(e.stats)))
    return out, windows


@functools.lru_cache(maxsize=4)
def _parse(path: str, mtime_ns: int):
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def xplane_file(cell: str, base=None) -> Optional[str]:
    """The newest ``.xplane.pb`` of the cell's traced run, which
    ``chipbench.driver.run`` writes under ``<base>/.cache/trace/<cell>``."""
    base = spec.BENCH_DIR if base is None else base
    files = sorted(glob.glob(os.path.join(
        str(base), ".cache", "trace", cell, "plugins", "profile", "*",
        "*.xplane.pb")))
    return files[-1] if files else None


def window_spans(run, base=None) -> Optional[List[Span]]:
    """The run's spans that overlap its traced window (unclipped), read
    once per file; ``None`` without a trace, with a trace of another
    window, or with no ``odmoe.`` span in the window."""
    if run.trace is None:
        return None
    f = xplane_file(run.cell.name, base)
    if f is None:
        return None
    spans, windows = _parse(f, os.stat(f).st_mtime_ns)
    lo, hi = run.trace.window
    if lo not in windows:
        return None
    return in_window(spans, lo, hi)


def in_window(spans: Iterable[Span], lo: float, hi: float
              ) -> Optional[List[Span]]:
    out = [s for s in spans if s.end_ns > lo and s.start_ns < hi]
    if not any(s.name.startswith(PREFIX) for s in out):
        return None
    return out


# ----------------------------------------------------------- reductions
def _clip(s: Span, lo: float, hi: float) -> Tuple[float, float]:
    return max(s.start_ns, lo), min(s.end_ns, hi)


def _length(intervals) -> float:
    return sum(b - a for a, b in trace.union(intervals))


def total_ns(spans: Sequence[Span], name: str, lo: float, hi: float
             ) -> float:
    """Summed time of the spans named ``name``, clipped to [lo, hi]."""
    return sum(b - a for a, b in (_clip(s, lo, hi) for s in spans
                                  if s.name == name) if b > a)


def union_ns(spans: Sequence[Span], names: Iterable[str], lo: float,
             hi: float) -> float:
    """Length of the union of the spans named in ``names``, clipped."""
    names = set(names)
    return _length(trace.clip([(s.start_ns, s.end_ns) for s in spans
                               if s.name in names], lo, hi))


def self_ns(spans: Sequence[Span], name: str, lo: float, hi: float
            ) -> float:
    """Summed self time of the spans named ``name``: each one's clipped
    time less the union of the other ``odmoe.*`` spans that lie inside it
    on its thread."""
    by_thread: Dict[tuple, List[Span]] = {}
    for s in spans:
        if s.name.startswith(PREFIX):
            by_thread.setdefault(s.thread, []).append(s)
    total = 0.0
    for th in by_thread.values():
        th.sort(key=lambda s: (s.start_ns, -s.dur_ns))
        starts = [s.start_ns for s in th]
        for i, p in enumerate(th):
            if p.name != name:
                continue
            a, b = _clip(p, lo, hi)
            if b <= a:
                continue
            j = bisect.bisect_right(starts, p.end_ns)
            kids = [(c.start_ns, c.end_ns) for c in th[i + 1:j]
                    if c.end_ns <= p.end_ns]
            total += (b - a) - _length(trace.clip(kids, a, b))
    return total


class Busy:
    """Busy time of a device's ops inside any interval of [lo, hi], from
    prefix sums over the union of the op intervals."""

    def __init__(self, ops: Sequence[trace.Ev], lo: float, hi: float):
        iv = trace.union(trace.clip([(e.start_ns, e.end_ns) for e in ops],
                                    lo, hi))
        self._a = [a for a, _ in iv]
        self._b = [b for _, b in iv]
        self._cum = list(itertools.accumulate((b - a for a, b in iv),
                                              initial=0.0))

    def _upto(self, t: float) -> float:
        k = bisect.bisect_right(self._a, t)
        if k == 0:
            return 0.0
        return self._cum[k - 1] + min(t, self._b[k - 1]) - self._a[k - 1]

    def within(self, a: float, b: float) -> float:
        return self._upto(b) - self._upto(a)


def busy_share(spans: Sequence[Span], name: str, busy: Busy, lo: float,
               hi: float) -> Optional[float]:
    """Percent of the union of the spans named ``name`` in which the
    device was busy; ``None`` when there is no such span."""
    iv = trace.union(trace.clip([(s.start_ns, s.end_ns) for s in spans
                                 if s.name == name], lo, hi))
    length = sum(b - a for a, b in iv)
    if length <= 0:
        return None
    return 100.0 * sum(busy.within(a, b) for a, b in iv) / length


def bytes_per_ns(spans: Sequence[Span], name: str, key: str, lo: float,
                 hi: float) -> Optional[float]:
    """Σ of the ``key`` argument over Σ time of the spans named ``name``
    that lie wholly inside [lo, hi]; bytes per ns is GB/s."""
    whole = [s for s in spans if s.name == name and s.start_ns >= lo
             and s.end_ns <= hi]
    t = sum(s.dur_ns for s in whole)
    if t <= 0:
        return None
    return sum(s.args.get(key, 0) for s in whole) / t


def idle_by_label(spans: Sequence[Span], busy: Busy, lo: float, hi: float
                  ) -> Dict[str, float]:
    """Device-idle ns in [lo, hi] by the innermost (shortest) span open at
    each moment: an ``odmoe.*`` name, ``"compile"`` for JAX's compile
    events, or ``"none"``."""
    evs = sorted((s.start_ns, s.end_ns, s.dur_ns,
                  COMPILE if s.name == COMPILE_EVENT else s.name)
                 for s in spans)
    points = sorted({lo, hi, *(min(max(t, lo), hi)
                               for a, b, _, _ in evs for t in (a, b))})
    out: Dict[str, float] = {}
    heap: list = []
    i = 0
    for p, q in zip(points, points[1:]):
        while i < len(evs) and evs[i][0] <= p:
            a, b, dur, label = evs[i]
            heapq.heappush(heap, (dur, b, i, label))
            i += 1
        while heap and heap[0][1] <= p:
            heapq.heappop(heap)
        label = heap[0][3] if heap else NONE
        idle = (q - p) - busy.within(p, q)
        if idle > 0:
            out[label] = out.get(label, 0.0) + idle
    return out


# ------------------------------------------------------- metric helpers
def device_busy(run) -> Optional[Busy]:
    if not run.trace.devices:
        return None
    lo, hi = run.trace.window
    return Busy(run.trace.devices[0].ops, lo, hi)


def idle_by_span(run) -> Optional[Dict[str, float]]:
    """Device-idle ns of the run's traced window by innermost span
    (``idle_by_label``); ``None`` without program spans or a device."""
    spans = window_spans(run)
    busy = None if spans is None else device_busy(run)
    if busy is None:
        return None
    return idle_by_label(spans, busy, *run.trace.window)


def per_token_ms(run, ns: float) -> Optional[float]:
    """``ns`` over the output tokens decoded in the window, in ms."""
    n = run.counters.get("decoded_tokens", 0)
    return ns * 1e-6 / n if n else None


def ms_per_token(run, reduce) -> Optional[float]:
    """``reduce(spans, lo, hi)`` ns over the window's decoded tokens, in
    ms; ``None`` where the window holds no program span."""
    spans = window_spans(run)
    if spans is None:
        return None
    return per_token_ms(run, reduce(spans, *run.trace.window))
