"""Reduce a profiler trace to device busy time, time per host span, and the
breakdown of device operations and idle gaps.

The benchmark annotates its own host spans (``jax.profiler
.TraceAnnotation("bench.<name>")``) around the program's calls into each
layer, and one ``bench.window`` span around the measured window.  The
profiler puts those spans and the device's operations on one clock, so each
device operation is labelled with the innermost benchmark span open at its
midpoint, and each idle gap with the span open at its midpoint.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from pathlib import Path

PREFIX = "bench."
WINDOW = "window"
UNLABELLED = "outside any span"


@dataclass
class Event:
    name: str
    start: float          # seconds on the trace's clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Reduced:
    """What one traced window reads as."""
    window_s: float
    busy_s: float                          # mean over the devices traced
    devices: int
    by_span: dict = field(default_factory=dict)     # span -> device s
    by_module: dict = field(default_factory=dict)   # module -> device s
    device_ops: list = field(default_factory=list)  # [[name, s]] top 10
    idle_gaps: list = field(default_factory=list)   # [[span, s]] top 10

    def module_s(self, fragment: str) -> float:
        return sum(s for m, s in self.by_module.items() if fragment in m)


def _merge(intervals: list[tuple[float, float]]) -> list[list[float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class _SpanIndex:
    """Innermost host span containing a time point."""

    def __init__(self, spans: list[Event]):
        # the span boundaries cut time into pieces; each piece takes the
        # shortest span that covers it
        self.cuts = sorted({t for e in spans for t in (e.start, e.end)})
        self.labels = []
        for a, b in zip(self.cuts, self.cuts[1:]):
            mid = 0.5 * (a + b)
            cover = [e for e in spans if e.start <= mid <= e.end]
            self.labels.append(min(cover, key=lambda e: e.dur).name
                               if cover else UNLABELLED)

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.cuts, t) - 1
        return self.labels[i] if 0 <= i < len(self.labels) else UNLABELLED


def read_events(data):
    """A ``jax.profiler.ProfileData`` -> (host spans, {device plane: [ops]},
    {device plane: [modules]})."""
    spans, ops, modules = [], {}, {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        spans.append(Event(ev.name[len(PREFIX):],
                                           ev.start_ns * 1e-9,
                                           ev.end_ns * 1e-9))
        elif plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).extend(
                        Event(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                        for ev in line.events)
                elif line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).extend(
                        Event(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                        for ev in line.events)
    return spans, ops, modules


def _op_name(hlo: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _module_name(name: str) -> str:
    """``jit_eval_loss(1117...)`` -> ``jit_eval_loss``."""
    return name.split("(", 1)[0]


def reduce(spans: list[Event], ops: dict, modules: dict, top: int = 10
           ) -> Reduced | None:
    """None when the trace holds no window span or no device operation."""
    windows = [e for e in spans if e.name == WINDOW]
    if not windows or not any(ops.values()):
        return None
    w0 = min(e.start for e in windows)
    w1 = max(e.end for e in windows)
    inner = [e for e in spans if e.name != WINDOW]
    index = _SpanIndex(inner)
    by_span: dict = {}
    by_name: dict = {}
    by_module: dict = {}
    busy = []
    gaps = []
    for plane, evs in ops.items():
        mods = sorted(modules.get(plane, []), key=lambda e: e.start)
        mod_starts = [m.start for m in mods]
        clipped = [(max(e.start, w0), min(e.end, w1), e) for e in evs
                   if e.end > w0 and e.start < w1]
        for s, e, ev in clipped:
            label = index.at(0.5 * (s + e))
            by_span[label] = by_span.get(label, 0.0) + (e - s)
            i = bisect.bisect_right(mod_starts, ev.start) - 1
            name = _op_name(ev.name)
            if i >= 0 and mods[i].end >= ev.start:
                name = f"{_module_name(mods[i].name)}/{name}"
            by_name[name] = by_name.get(name, 0.0) + (e - s)
        merged = _merge([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, index.at(0.5 * (s + e))))
    for plane, evs in modules.items():
        for ev in evs:
            s, e = max(ev.start, w0), min(ev.end, w1)
            if e > s:
                name = _module_name(ev.name)
                by_module[name] = by_module.get(name, 0.0) + (e - s)
    n = len(busy)
    return Reduced(
        window_s=w1 - w0, busy_s=sum(busy) / n, devices=n,
        by_span=by_span, by_module=by_module,
        device_ops=[[k, v] for k, v in sorted(by_name.items(),
                                               key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[label, g] for g, label in sorted(gaps, reverse=True)[:top]])


def reduce_dir(log_dir: Path) -> Reduced | None:
    """Reduce the newest ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    from jax.profiler import ProfileData
    return reduce(*read_events(ProfileData.from_file(str(files[-1]))))
