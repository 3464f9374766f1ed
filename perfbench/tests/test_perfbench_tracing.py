"""The reduction from a profiler trace to busy time, time per span and the
breakdown, on a trace written out by hand and on a small recorded one."""
from __future__ import annotations

from pathlib import Path

import pytest

from perfbench import tracing

DATA = Path(__file__).parent / "data"

# one device; the window is 0..10 us; host spans grad_step (1..4 us),
# validate.hash (4..6 us) and fold_apply (6..9 us); device ops at
# 1.5-3.5 (grad), 3-3.5 (overlapping grad), 7-8 (fold) and 9.5-12 (runs
# past the window's end)
HAND = """
planes {
  id: 1
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 3000000 }
    events { metadata_id: 5 offset_ps: 0 duration_ps: 1000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.grad_step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.validate.hash" } }
  event_metadata { key: 4 value { id: 4 name: "bench.fold_apply" } }
  event_metadata { key: 5 value { id: 5 name: "PjitFunction(f)" } }
}
planes {
  id: 2
  name: "/device:TPU:0"
  lines {
    id: 2
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1500000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 9500000 duration_ps: 2500000 }
  }
  lines {
    id: 3
    name: "XLA Modules"
    timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 1500000 duration_ps: 2000000 }
    events { metadata_id: 5 offset_ps: 7000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
  event_metadata { key: 3 value { id: 3 name: "add.3" } }
  event_metadata { key: 4 value { id: 4 name: "jit_loss(12)" } }
  event_metadata { key: 5 value { id: 5 name: "jit_fused_delta_tiles(3)" } }
}
"""


def _profile(text: str):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text)


def test_reduce_hand_written_trace():
    r = tracing.reduce(*tracing.read_events(_profile(HAND)))
    us = 1e-6
    assert r.window_s == pytest.approx(10 * us)
    # busy: 1.5-3.5, 7-8, 9.5-10 (clipped) = 3.5 us
    assert r.busy_s == pytest.approx(3.5 * us)
    assert r.by_span["grad_step"] == pytest.approx(2.5 * us)
    assert r.by_span["fold_apply"] == pytest.approx(1.0 * us)
    assert r.by_span[tracing.UNLABELLED] == pytest.approx(0.5 * us)
    assert r.module_s("fused_delta_tiles") == pytest.approx(1.0 * us)
    assert r.module_s("jit_loss") == pytest.approx(2.0 * us)
    # an op is named within the module it ran in, where there is one
    names = [n for n, _ in r.device_ops]
    assert names[0] == "jit_loss/fusion.1"
    assert set(names) == {"jit_loss/fusion.1", "jit_loss/copy.2",
                          "jit_fused_delta_tiles/add.3", "add.3"}
    gaps = {(label, round(s / us, 6)) for label, s in r.idle_gaps}
    # 0-1.5 (mid 0.75: outside), 3.5-7 (mid 5.25: hash), 8-9.5 (mid 8.75:
    # fold)
    assert gaps == {(tracing.UNLABELLED, 1.5), ("validate.hash", 3.5),
                    ("fold_apply", 1.5)}
    assert r.idle_gaps[0] == ["validate.hash", pytest.approx(3.5 * us)]


def test_reduce_needs_a_window_and_device_ops():
    no_window = HAND.replace('name: "bench.window"', 'name: "other"')
    assert tracing.reduce(*tracing.read_events(_profile(no_window))) is None
    spans, _, modules = tracing.read_events(_profile(HAND))
    assert tracing.reduce(spans, {}, modules) is None


def test_nested_spans_label_by_the_innermost():
    ev = tracing.Event
    index = tracing._SpanIndex([ev("outer", 0.0, 10.0),
                                ev("inner", 2.0, 3.0)])
    assert index.at(2.5) == "inner"
    assert index.at(5.0) == "outer"
    assert index.at(11.0) == tracing.UNLABELLED


def test_reduce_recorded_chip_trace():
    """4.5 s of a traced granite-3-2b window (a snapshot every round) on one TPU
    v5e: two grad steps, two hashes, the fold and optimizer, and the start
    of the snapshot probe."""
    text = (DATA / "granite_round.pbtxt").read_text()
    r = tracing.reduce(*tracing.read_events(_profile(text)))
    assert r.devices == 1 and r.window_s == pytest.approx(4.5)
    assert 0.1 < r.busy_s < 0.2                   # the device idles >95%
    # the grad step's device time is its module's, two units of ~18.4 ms
    assert r.by_span["grad_step"] == pytest.approx(
        r.module_s("jit_eval_loss"), rel=1e-3)
    assert r.by_span["grad_step"] / 2 == pytest.approx(0.0184, rel=0.01)
    assert r.module_s("fused_delta_tiles") > 0
    assert sum(r.by_span.values()) >= r.busy_s
    # the two longest idle gaps are the host hashing each unit's gradient
    assert [g[0] for g in r.idle_gaps[:2]] == ["validate.hash"] * 2
    assert all(g[1] > 1.9 for g in r.idle_gaps[:2])
    assert all("/" in name and " " not in name for name, _ in r.device_ops)
