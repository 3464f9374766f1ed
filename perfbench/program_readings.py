"""The program's per-unit readings for the window's rounds, from the
``readings`` ring of its telemetry hub (``repro.core.telemetry``).

Readings are picked by the round they carry (``step``); where a process
ran the program more than once, a unit's newest reading counts.  Nothing
is read when the window has no rounds or the program keeps no readings.
"""
from __future__ import annotations


def window_readings(w, names: tuple) -> dict | None:
    """{unit: {name: value}} for the window's units that have every name."""
    if not w.rounds:
        return None
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    ring = getattr(telemetry.get_default(), "readings", None)
    if ring is None:
        return None
    steps = {step for step, _, _ in w.rounds}
    units: dict = {}
    for r in list(ring):
        if r.step in steps and r.name in names:
            units.setdefault(r.unit, {})[r.name] = r.value
    units = {u: v for u, v in units.items() if len(v) == len(names)}
    return units or None
