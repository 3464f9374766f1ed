"""The readers of the program's own spans, on a hub and window made by hand:
each reads its quantity per the span it is counted against, picks spans by
the window's rounds, and reads nothing when there is nothing to read."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from perfbench import registry
from perfbench.harness import Window
from repro.core import telemetry as tlm

CELL = "granite-3-2b.snap_every_8"
PEAKS = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
MS = 1_000_000          # ns


class Hand:
    """Writes closed spans into a hub at the times given (ms)."""

    def __init__(self, hub):
        self.hub = hub
        self.next_id = 1

    def span(self, name, step, t0, t1, parent=None, **kw):
        sp = tlm.Span(self.hub, name, step, kw.get("unit"), kw.get("cause"))
        sp.id, self.next_id = self.next_id, self.next_id + 1
        sp.parent = parent.id if parent is not None else 0
        sp.start_ns, sp.end_ns = int(t0 * MS), int(t1 * MS)
        self.hub.spans.append(sp)
        return sp


def _round(h: Hand, step: int, t: float, snapshot: bool):
    """One round of 120 ms: two units, each a 10 ms grad step and a 20 ms
    validation (two leaves: copy 3 + 4 ms, digest 5 + 6 ms); fold 5 ms,
    apply 15 ms with an 8 ms optimizer inside; a 25 ms snapshot with two
    copies of 6 ms (a base image) and 9 ms (a probe's), and a writer write of 200 ms (records 50 + 30,
    put 70 + 40 ms)."""
    r = h.span("round", step, t, t + 120)
    c = t
    for unit in (2 * step, 2 * step + 1):
        h.span("grad_step", step, c, c + 10, r, unit=unit)
        v = h.span("validate", step, c + 10, c + 30, r, unit=unit)
        h.span("validate.copy", step, c + 10, c + 13, v)
        h.span("validate.digest", step, c + 13, c + 18, v)
        h.span("validate.copy", step, c + 18, c + 22, v)
        h.span("validate.digest", step, c + 22, c + 28, v)
        c += 30
    h.span("fold", step, c, c + 5, r)
    a = h.span("apply", step, c + 5, c + 20, r)
    h.span("optimizer", step, c + 5, c + 13, a)
    if snapshot:
        s = h.span("snapshot", step, c + 20, c + 45, r)
        p = h.span("snapshot.plan", step, c + 20, c + 40, s)
        h.span("snapshot.d2h", step, c + 21, c + 27, p)
        h.span("delta_encode.d2h", step, c + 28, c + 37, p)
        h.span("writer.submit", step, c + 40, c + 41, s)
        w = h.span("writer.write", step, c + 50, c + 250, cause=s.id)
        h.span("writer.records", step, c + 50, c + 100, w)
        h.span("writer.put", step, c + 100, c + 170, w)
        h.span("writer.records", step, c + 170, c + 200, w)
        h.span("writer.put", step, c + 200, c + 240, w)
    return r


# (metric, its value over rounds 5 and 6 with one snapshot, at 6)
WANT = {
    "validate.copy_ms": 7.0,
    "validate.digest_ms": 11.0,
    "fold_apply.dispatch_ms": 13.0,
    "snapshot.d2h_ms": 15.0,
    "writer.records_ms": 80.0,
    "writer.put_ms": 110.0,
    # round 5: 120 - (60 + 5 + 15) = 40; round 6: less its snapshot, 15
    "round.self_ms": (40.0 + 15.0) / 2,
}


@pytest.fixture
def hub():
    mine = tlm.Telemetry()
    prev = tlm.set_default(mine)
    try:
        yield mine
    finally:
        tlm.set_default(prev)


def _window(steps):
    cell = registry.load_cell(CELL)
    w = Window(cell, PEAKS, 1)
    w.rounds = [(s, 0.0, 1.0) for s in steps]
    return w


def _reader(name):
    return registry.load_module(registry.BENCH_DIR / "metrics" / f"{name}.py",
                                "metric_" + name)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_its_spans_per_window(name, hub):
    h = Hand(hub)
    _round(h, 4, 0, snapshot=True)            # set-up: not in the window
    _round(h, 5, 1000, snapshot=False)
    _round(h, 6, 2000, snapshot=True)
    _round(h, 7, 3000, snapshot=True)         # after the window
    assert _reader(name).read(_window([5, 6])) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_takes_the_newest_run_of_the_steps(name, hub):
    """A process that ran the program twice holds two rounds 5 and 6; only
    the later run counts."""
    h = Hand(hub)
    _round(h, 5, 0, snapshot=True)
    _round(h, 6, 1000, snapshot=True)
    first = {n: _reader(n).read(_window([5, 6])) for n in WANT}
    for r in list(hub.spans):                 # the first run was slower
        if r.name in ("validate.copy", "writer.put"):
            r.end_ns += 2 * MS
    _round(h, 5, 5000, snapshot=True)
    _round(h, 6, 6000, snapshot=True)
    assert _reader(name).read(_window([5, 6])) == pytest.approx(first[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_nothing_without_spans_to_read(name, hub):
    reader = _reader(name)
    h = Hand(hub)
    _round(h, 5, 0, snapshot=True)
    assert reader.read(_window([])) is None               # empty window
    assert reader.read(_window([9])) is None              # ring lacks it
    hub.spans.popleft()                                   # round 5 evicted
    assert reader.read(_window([5])) is None
    tlm.set_default(SimpleNamespace())                    # no span ring
    assert reader.read(_window([5])) is None


def test_snapshot_copies_leave_out_the_differs_other_callers(hub):
    """The differ's copies also serve the uplink; only those made under a
    ``snapshot`` span count as the snapshot's."""
    h = Hand(hub)
    r = _round(h, 5, 0, snapshot=True)
    h.span("delta_encode.d2h", 5, 100, 104, r)        # an uplink's copy
    assert _reader("snapshot.d2h_ms").read(_window([5])) == \
        pytest.approx(WANT["snapshot.d2h_ms"])
