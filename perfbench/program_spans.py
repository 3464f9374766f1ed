"""The program's own spans for the window's rounds, from the span ring of
its telemetry hub (``repro.core.telemetry``).

Spans are picked by the round they carry (``step``), never by their clock:
every span of a round, and the writer's spans of the snapshot that round
took, carry the round's step.  Nothing is read when the window has no
rounds, when the program keeps no span ring, or when the ring no longer
holds the window's first round.
"""
from __future__ import annotations


def window_spans(w) -> list | None:
    """The closed spans of the window's rounds, in the order they started."""
    if not w.rounds:
        return None
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    ring = getattr(telemetry.get_default(), "spans", None)
    if ring is None:
        return None
    records = list(ring)
    steps = {step for step, _, _ in w.rounds}
    first = [s.id for s in records
             if s.name == "round" and s.step == min(steps)]
    if not first:
        return None
    # a process that ran the program more than once holds older rounds of
    # the same steps; the newest run started last
    since = max(first)
    return [s for s in records if s.id >= since and s.step in steps
            and s.end_ns is not None]


def total_ms(spans: list, name: str) -> float:
    return 1e-6 * sum(s.end_ns - s.start_ns for s in spans if s.name == name)


def count(spans: list, name: str) -> int:
    return sum(s.name == name for s in spans)


def under(spans: list, ancestor: str, names: tuple) -> list:
    """The spans named in ``names`` with an ``ancestor`` span above them on
    their own thread."""
    index = {s.id: s for s in spans}
    out = []
    for s in spans:
        if s.name not in names:
            continue
        up = index.get(s.parent)
        while up is not None and up.name != ancestor:
            up = index.get(up.parent)
        if up is not None:
            out.append(s)
    return out


def per(w, part: str, whole: str) -> float | None:
    """Milliseconds of ``part`` spans per ``whole`` span in the window."""
    spans = window_spans(w)
    if spans is None or not count(spans, whole):
        return None
    return total_ms(spans, part) / count(spans, whole)


def self_ms(span, spans: list) -> float:
    """Milliseconds of ``span`` outside the union of its direct children."""
    kids = sorted((s.start_ns, s.end_ns) for s in spans
                  if s.parent == span.id)
    covered, end = 0, span.start_ns
    for a, b in kids:
        a = max(a, end)
        if b > a:
            covered += b - a
            end = b
    return 1e-6 * (span.end_ns - span.start_ns - covered)
