"""Spans in the telemetry hub: nesting, threads, causes, the bounded ring,
the tree a training round records, and the profiler's host plane.

Every layer of the volunteer round runs under a span of the hub
(``Telemetry.span``), timed on ``time.perf_counter_ns`` and annotated for
the profiler, so a trace places each span beside the device operations it
launched.  Spans never enter the event ring: a seeded run still dumps a
byte-identical ``events.jsonl``.
"""
import json
import re
import threading
import time
from collections import Counter as Tally

import jax
import numpy as np
import pytest

from repro.core import telemetry as tlm
from repro.core.chunkstore import ChunkStore
from repro.core.snapshots import SnapshotManager

# a snapshot's copies to the host: a base image, or the differ's
COPIES = ("snapshot.d2h", "delta_encode.d2h")
SMOKE = ["--arch", "granite-3-2b", "--preset", "smoke", "--seq", "16",
         "--batch", "2", "--micro", "2", "--workers", "2", "--seed", "5",
         "--log-every", "100"]


@pytest.fixture
def fresh_hub():
    """A fresh process default hub for the test; the old one is put back."""
    hub = tlm.Telemetry()
    prev = tlm.set_default(hub)
    try:
        yield hub
    finally:
        tlm.set_default(prev)


def _by_id(spans):
    return {s.id: s for s in spans}


def _ancestors(span, index):
    out = []
    while span.parent:
        span = index[span.parent]
        out.append(span.name)
    return out


# ---------------------------------------------------------------------------
# the span itself
# ---------------------------------------------------------------------------
def test_spans_nest_inherit_step_and_time_their_work():
    hub = tlm.Telemetry()
    with hub.span("round", step=3) as r:
        with hub.span("validate", unit=7) as v:
            with hub.span("validate.copy") as c:
                time.sleep(0.002)
        with hub.span("fold") as f:
            time.sleep(0.001)
    assert [s.name for s in hub.spans] == ["round", "validate",
                                           "validate.copy", "fold"]
    assert r.parent == 0 and v.parent == r.id and c.parent == v.id
    assert f.parent == r.id
    assert {s.step for s in hub.spans} == {3}        # inherited
    assert v.unit == 7 and c.unit is None
    assert c.ms >= 2.0 and f.ms >= 1.0
    assert r.start_ns <= v.start_ns <= c.start_ns <= c.end_ns <= v.end_ns
    assert v.end_ns <= f.start_ns <= f.end_ns <= r.end_ns
    # the round's own time: itself less its direct children
    self_ns = (r.end_ns - r.start_ns) - sum(
        s.end_ns - s.start_ns for s in hub.spans if s.parent == r.id)
    assert 0 <= self_ns < r.end_ns - r.start_ns
    assert r.ms >= v.ms + f.ms


def test_explicit_step_wins_and_ids_are_unique():
    hub = tlm.Telemetry()
    with hub.span("outer", step=1):
        with hub.span("inner", step=9) as inner:
            pass
    assert inner.step == 9
    other = tlm.Telemetry()
    with other.span("x") as x:
        pass
    assert x.id not in {s.id for s in hub.spans}


def test_each_thread_nests_on_its_own_stack():
    hub = tlm.Telemetry()
    seen = {}
    ready, done = threading.Event(), threading.Event()

    def worker():
        ready.wait()
        with hub.span("writer.write", step=4, cause=seen["main"]) as w:
            with hub.span("writer.put") as p:
                pass
        seen["write"], seen["put"] = w, p
        done.set()

    t = threading.Thread(target=worker)
    t.start()
    with hub.span("snapshot", step=4) as snap:
        seen["main"] = snap.id
        ready.set()
        done.wait()
        with hub.span("writer.submit") as sub:
            pass
    t.join()
    w, p = seen["write"], seen["put"]
    assert w.parent == 0 and w.cause == snap.id          # not nested
    assert p.parent == w.id and p.step == 4
    assert sub.parent == snap.id
    assert w.thread != snap.thread and p.thread == w.thread


def test_span_ring_is_bounded_and_keeps_the_newest():
    hub = tlm.Telemetry(capacity=4)
    for i in range(10):
        with hub.span("s", step=i):
            pass
    assert len(hub.spans) == 4
    assert [s.step for s in hub.spans] == [6, 7, 8, 9]


def test_spans_stay_out_of_the_event_ring():
    hub = tlm.Telemetry(tracing=True)
    hub.event("submit", unit=1)
    with hub.span("round", step=0):
        hub.event("fold", unit=1)
    assert [e["kind"] for e in hub.events] == ["submit", "fold"]
    assert len(hub.spans) == 1


def test_module_span_uses_the_default_hub(fresh_hub):
    with tlm.span("optimizer", step=2) as sp:
        pass
    assert list(fresh_hub.spans) == [sp]


# ---------------------------------------------------------------------------
# the snapshot writer: one timer per quantity, and the cause
# ---------------------------------------------------------------------------
def test_writer_write_names_the_snapshot_span_and_feeds_its_counters(
        fresh_hub):
    rng = np.random.default_rng(0)
    state = {"a": rng.standard_normal(5000).astype(np.float32),
             "b": rng.standard_normal(300).astype(np.float32)}
    mgr = SnapshotManager(ChunkStore(chunk_bytes=1 << 12), async_mode=True,
                          delta_mode="ref")
    snaps = []
    for step in (1, 3):
        state = {k: v + 1 for k, v in state.items()}
        with tlm.span("snapshot", step=step) as sp:
            mgr.snapshot(state, step=step, block=False)
        snaps.append(sp)
    mgr.close()
    spans = list(fresh_hub.spans)
    index = _by_id(spans)
    writes = [s for s in spans if s.name == "writer.write"]
    assert [(w.step, w.cause) for w in writes] == [(s.step, s.id)
                                                   for s in snaps]
    for sp in snaps:
        names = Tally(s.name for s in spans if s.parent == sp.id)
        assert names == {"snapshot.plan": 1, "writer.submit": 1}
    for w in writes:
        kids = Tally(s.name for s in spans if s.parent == w.id)
        assert kids["writer.put"] == len(state)
        assert all(s.step == w.step for s in spans if s.parent == w.id)
    # the base image's copies and the probe's sit under the snapshot
    copies = [s for s in spans if s.name in COPIES]
    assert {s.name for s in copies} == set(COPIES)
    for s in copies:
        assert "snapshot" in _ancestors(s, index)
    stats = mgr.writer_stats
    assert stats["write_ms"] == pytest.approx(
        sum(w.ms for w in writes), rel=1e-9)
    assert stats["backpressure_ms"] == pytest.approx(
        sum(s.ms for s in spans if s.name == "writer.submit"), rel=1e-9)


def test_kernel_stats_are_a_read_only_hub_scope():
    from repro.kernels.delta_encode import ops
    assert isinstance(ops.KERNEL_STATS, tlm.StatsView)
    assert list(ops.KERNEL_STATS) == ["launches", "probe_bytes",
                                      "d2h_bytes", "ref_passes"]
    with pytest.raises(TypeError):
        ops.KERNEL_STATS["launches"] = 1
    ops.reset_kernel_stats()
    mirror = ops.DeviceMirror()
    x = np.arange(9000, dtype=np.float32)
    ops.probe_leaves({"x": x}, mode="ref", mirror=mirror)
    ops.probe_leaves({"x": x + 1}, mode="ref", mirror=mirror)
    assert ops.KERNEL_STATS["launches"] == 1
    assert ops.KERNEL_STATS["ref_passes"] == 2
    prom = ops._SCOPE.hub.prometheus()
    assert re.search(r'repro_delta_encode_launches\{scope="delta_encode",'
                     r'instance="\d+"\} 1', prom)
    before = ops.reset_kernel_stats()
    assert before["launches"] == 1
    assert dict(ops.KERNEL_STATS) == dict.fromkeys(before, 0)


# ---------------------------------------------------------------------------
# the tree one smoke training run records
# ---------------------------------------------------------------------------
def _param_leaves() -> int:
    from repro.distributed.sharding import init_tree
    from repro.launch.train import build_arch
    from repro.models import api
    cfg, _ = build_arch("granite-3-2b", "smoke")
    params = init_tree(api.state_specs(cfg).params, jax.random.key(0))
    return len(jax.tree.leaves(params))


def test_smoke_round_records_the_span_tree(fresh_hub, tmp_path):
    from repro.launch import train
    steps, micro = 4, 2
    summary = train.main(SMOKE + ["--steps", str(steps), "--snapshot-every",
                                  "2", "--async-writer"])
    spans = list(fresh_hub.spans)
    assert all(s.end_ns is not None for s in spans)
    index = _by_id(spans)
    rounds = [s for s in spans if s.name == "round"]
    assert [r.step for r in rounds] == list(range(steps))
    leaves = _param_leaves()
    snap_steps = {1, 3}
    for r in rounds:
        mine = [s for s in spans if s.step == r.step]
        kids = Tally(s.name for s in mine if s.parent == r.id)
        want = {"grad_step": micro, "validate": micro, "fold": 1,
                "apply": 1}
        if r.step in snap_steps:
            want["snapshot"] = 1
        assert kids == want, (r.step, kids)
        units = sorted(s.unit for s in mine if s.name == "grad_step")
        assert units == [r.step * micro + k for k in range(micro)]
        for v in (s for s in mine if s.name == "validate"):
            parts = Tally(s.name for s in mine if s.parent == v.id)
            assert parts == {"validate.copy": leaves,
                             "validate.digest": leaves}
        apply_ = next(s for s in mine if s.name == "apply")
        assert [s.name for s in mine if s.parent == apply_.id] == \
            ["optimizer"]
        names = {s.name for s in mine}
        snapshot_names = {"snapshot", "snapshot.plan", "writer.submit",
                          "writer.write", "writer.records", "writer.put",
                          *COPIES}
        if r.step in snap_steps:
            snap = next(s for s in mine if s.name == "snapshot")
            write = next(s for s in mine if s.name == "writer.write")
            assert write.cause == snap.id and write.parent == 0
            assert {"snapshot.plan", "writer.submit", "writer.put"} <= names
            assert names & set(COPIES)
            for s in mine:
                if s.name.startswith("writer.") and s.name not in (
                        "writer.write", "writer.submit"):
                    assert s.parent == write.id
                if s.name in COPIES:
                    assert "snapshot" in _ancestors(s, index)
        else:
            assert not names & snapshot_names
    # one timer per quantity: the round's stall is its snapshot span
    stall = sum(s.ms for s in spans if s.name == "snapshot")
    assert summary["snapshot_stall_ms"] == pytest.approx(stall, abs=0.006)
    prom = fresh_hub.prometheus()
    assert re.search(r"repro_trainer_validated_results\S* "
                     rf"{steps * micro}\b", prom)


def test_fold_span_holds_the_release_of_the_folded_gradients(
        fresh_hub, monkeypatch):
    """``fold`` closes after ``_fold_round`` has returned, so the gradients
    its frame drops are released inside the span, not in the round's own
    time."""
    from repro.core.elastic import VolunteerTrainer
    from repro.launch import train
    fold_round, returned = VolunteerTrainer._fold_round, []

    def timed(self, step):
        out = fold_round(self, step)
        returned.append(time.perf_counter_ns())
        return out

    monkeypatch.setattr(VolunteerTrainer, "_fold_round", timed)
    train.main(SMOKE + ["--steps", "2"])
    folds = [s for s in fresh_hub.spans if s.name == "fold"]
    assert len(folds) == len(returned) == 2
    for span, t in zip(folds, returned):
        assert span.start_ns < t < span.end_ns


def test_program_spans_sit_on_the_profilers_host_plane(fresh_hub,
                                                       tmp_path):
    from jax.profiler import ProfileData

    from repro.launch import train
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.outer"):
            train.main(SMOKE + ["--steps", "2", "--snapshot-every", "2"])
    files = sorted(tmp_path.rglob("*.xplane.pb"))
    assert files
    data = ProfileData.from_file(str(files[-1]))
    events = [(ev.name, ev.start_ns, ev.end_ns)
              for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    outer = [e for e in events if e[0] == "test.outer"]
    assert len(outer) == 1
    _, o0, o1 = outer[0]
    program = [e for e in events if e[0] in {
        "round", "grad_step", "validate", "validate.copy",
        "validate.digest", "fold", "apply", "optimizer", "snapshot",
        "snapshot.plan", *COPIES}]
    assert {e[0] for e in program} >= {"round", "validate.copy",
                                      "validate.digest", "optimizer",
                                      "snapshot"}
    assert all(o0 <= s <= e <= o1 for _, s, e in program)
    assert sum(e[0] == "round" for e in program) == 2


def test_seeded_runs_dump_byte_identical_events(tmp_path):
    from repro.launch import train
    prev = tlm.get_default()
    dumps = []
    try:
        for run in ("a", "b"):
            out = tmp_path / run
            train.main(SMOKE + ["--steps", "3", "--snapshot-every", "2",
                                "--async-writer", "--telemetry", str(out)])
            assert len(tlm.get_default().spans) > 0
            dumps.append((out / "events.jsonl").read_bytes())
    finally:
        tlm.set_default(prev)
    assert dumps[0] and dumps[0] == dumps[1]
    kinds = {json.loads(line)["kind"] for line in dumps[0].splitlines()}
    assert "fold" in kinds
    assert not kinds & {"round", "validate", "snapshot", "writer.write"}
