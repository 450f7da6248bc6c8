"""Profiler trace -> device busy time, idle gaps, kernel and program times.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into a few flat
lists of events (start and duration in nanoseconds, on the profiler's
one clock); everything after that is plain arithmetic on those lists, so
it is tested on small recorded traces without a chip.

* device ops: the events of each TPU plane's "XLA Ops" line;
* device programs: the events of its "XLA Modules" line (one per
  executable run, named after the jitted function);
* host spans: the benchmark's own ``TraceAnnotation``s, named ``bench.*``,
  from the host planes; ``bench.window`` marks the traced window.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclass
class Ev:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class DeviceTrace:
    ops: List[Ev]                    # one device's XLA ops
    programs: List[Ev]               # that device's XLA modules


@dataclass
class Trace:
    devices: List[DeviceTrace]
    spans: List[Ev]                  # host bench.* spans
    window: Tuple[float, float]      # ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[len("/device:TPU:"):] \
        .isdigit()


def _events(line) -> List[Ev]:
    return [Ev(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def from_profile(pd) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a ``Trace``."""
    devices, spans = [], []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops = _events(lines["XLA Ops"]) if "XLA Ops" in lines else []
            progs = (_events(lines["XLA Modules"])
                     if "XLA Modules" in lines else [])
            devices.append(DeviceTrace(ops=ops, programs=progs))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(e for e in _events(ln)
                             if e.name.startswith(SPAN_PREFIX))
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    return Trace(devices=devices, spans=spans,
                 window=(win[0].start_ns, win[0].end_ns))


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(files[-1]))


# ---------------------------------------------------------------- busy
def clip(intervals: Sequence[Tuple[float, float]], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def union(intervals: Sequence[Tuple[float, float]]):
    """Merge overlapping intervals; returns sorted disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(ops: Sequence[Ev], lo: float, hi: float) -> float:
    """Length of the union of the op intervals inside [lo, hi]."""
    return sum(b - a for a, b in union(clip(
        [(e.start_ns, e.end_ns) for e in ops], lo, hi)))


def idle_gaps(ops: Sequence[Ev], lo: float, hi: float):
    """The intervals of [lo, hi] in which no op ran, longest first."""
    gaps, t = [], lo
    for a, b in union(clip([(e.start_ns, e.end_ns) for e in ops], lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def span_at(spans: Sequence[Ev], t: float) -> str:
    """The innermost (shortest) host span open at ``t``, else "none"."""
    open_ = [s for s in spans if s.start_ns <= t < s.end_ns
             and s.name != WINDOW_SPAN]
    if not open_:
        return "none"
    return min(open_, key=lambda s: s.dur_ns).name


def busy_s(tr: Trace) -> float:
    """Busy seconds in the window, averaged over the devices traced."""
    lo, hi = tr.window
    if not tr.devices:
        return 0.0
    return sum(busy_ns(d.ops, lo, hi) for d in tr.devices) * 1e-9 \
        / len(tr.devices)


def leaves(ops: Sequence[Ev]) -> List[Ev]:
    """The ops that enclose no other op: a loop or call op (``while``)
    shows as an op around the ops it runs."""
    srt = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    return [e for e, nxt in zip(srt, srt[1:] + [None])
            if nxt is None or nxt.start_ns >= e.end_ns]


def op_name(e: Ev) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``%fusion.3``."""
    return e.name.split(" = ")[0]


def breakdown(tr: Trace, n: int = 10) -> Dict[str, list]:
    """The device ops that took most time (leaf ops, summed by name)
    and the longest idle gaps of the first device, each named by the
    host span open at its middle."""
    lo, hi = tr.window
    dev = tr.devices[0] if tr.devices else DeviceTrace([], [])
    by_name: Dict[str, float] = {}
    for e in leaves(dev.ops):
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            by_name[op_name(e)] = by_name.get(op_name(e), 0.0) + (b - a)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    gaps = idle_gaps(dev.ops, lo, hi)[:n]
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[span_at(tr.spans, (a + b) / 2), (b - a) * 1e-9]
                          for a, b in gaps]}


# ------------------------------------------------------------- programs
def ops_in_programs(dev: DeviceTrace, program: Callable[[str], bool],
                    op: Callable[[Ev], bool], lo: float, hi: float
                    ) -> List[Ev]:
    """Ops selected by ``op`` that ran inside a program (XLA module run)
    selected by ``program``, within [lo, hi]."""
    progs = sorted((p.start_ns, p.end_ns) for p in dev.programs
                   if program(p.name) and p.end_ns > lo and p.start_ns < hi)
    out, i = [], 0
    for e in sorted(dev.ops, key=lambda e: e.start_ns):
        if not op(e) or e.end_ns <= lo or e.start_ns >= hi:
            continue
        while i < len(progs) and progs[i][1] < e.start_ns:
            i += 1
        if i < len(progs) and progs[i][0] <= e.start_ns \
                and e.end_ns <= progs[i][1]:
            out.append(e)
    return out


def module_name(event_name: str) -> str:
    """``jit_fn(123)`` -> ``jit_fn``: a program event without its id."""
    return event_name.split("(")[0]


def total_s(events: Sequence[Ev]) -> float:
    return sum(e.dur_ns for e in events) * 1e-9


def programs_within(dev: DeviceTrace, spans: Sequence[Ev], label: str,
                    lo: float, hi: float) -> List[List[Ev]]:
    """For each host span named ``label`` inside [lo, hi], the device
    programs that ran entirely within it (spans whose calls wait for
    their results, so their device work lies inside them)."""
    out = []
    progs = sorted(dev.programs, key=lambda p: p.start_ns)
    for s in spans:
        if s.name != label or s.start_ns < lo or s.end_ns > hi:
            continue
        out.append([p for p in progs
                    if p.start_ns >= s.start_ns and p.end_ns <= s.end_ns])
    return out


def ops_within(dev: DeviceTrace, programs: Sequence[Ev],
               op: Callable[[Ev], bool]) -> List[Ev]:
    """Ops selected by ``op`` inside any of ``programs``."""
    iv = sorted((p.start_ns, p.end_ns) for p in programs)
    out = []
    for e in dev.ops:
        if op(e) and any(a <= e.start_ns and e.end_ns <= b for a, b in iv):
            out.append(e)
    return out
