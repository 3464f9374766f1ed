"""``correct`` on the CPU at the program's ``smoke`` sizes.

A run is driven end to end (set-up through ``train.main``, the window, the
reference, the comparison) without the harness's look for a chip.  An
unbroken run is correct; a run with the timed path broken underneath, once
for each fault a training cell on one chip can have, is not; and the
control, the reference one precision step down put in the program's place,
fails the cell's limits.
"""
from __future__ import annotations

import pytest

from perfbench import check, harness
from perfbench.tests.smoke import smoke_cell

CELLS = ["granite-3-2b.snap_every_8", "hymba-1.5b.train"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2 ** 31 + 11


def _unchanged(monkeypatch):
    """The optimizer step hands back the state it was given."""
    from repro.optim import adamw
    monkeypatch.setattr(adamw, "update",
                        lambda cfg, grads, state, params: (params, state, {}))


def _half_batch(monkeypatch):
    """Each unit's loss, and so its gradient, covers half of its rows."""
    from repro.models import api
    make = api.make_eval_loss

    def make_half(cfg, run):
        loss = make(cfg, run)
        return lambda p, b: loss(p, {k: v[: v.shape[0] // 2]
                                     for k, v in b.items()})
    monkeypatch.setattr(api, "make_eval_loss", make_half)


def _altered(monkeypatch):
    """Each unit's answer is off by 1% where the grad step produces it."""
    from repro.models import api
    make = api.make_eval_loss

    def make_off(cfg, run):
        loss = make(cfg, run)
        return lambda p, b: loss(p, b) * 1.01
    monkeypatch.setattr(api, "make_eval_loss", make_off)


def _torn_snapshot(monkeypatch):
    """The store keeps a changed block with one bit flipped."""
    from repro.core.chunkstore import ChunkStore
    put_delta = ChunkStore.put_delta

    def flip(b: bytes) -> bytes:
        return bytes([b[0] ^ 1]) + b[1:] if b else b

    def torn(self, parent, xor, *, full_bytes=None):
        return put_delta(self, parent, flip(xor),
                         full_bytes=None if full_bytes is None
                         else flip(full_bytes))
    monkeypatch.setattr(ChunkStore, "put_delta", torn)


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "altered": _altered, "torn_snapshot": _torn_snapshot}
CASES = [(c, None) for c in CELLS] + [
    (c, f) for c in CELLS for f in FAULTS
    if f != "torn_snapshot" or smoke_cell(c).traffic["snapshot_every"]]


@pytest.mark.parametrize("name,fault", CASES)
def test_run_is_correct_unless_broken(name, fault, monkeypatch):
    import jax

    from perfbench import run
    if fault is not None:
        FAULTS[fault](monkeypatch)
    cell = smoke_cell(name)
    res = run.measure(cell, SEED, 0.2, False, jax.devices(), PEAKS,
                      t_start=0.0)
    assert res["correct"] is (fault is None), res["checks"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limits(name):
    """The float8 reference in the program's place is not correct, on
    three seeds."""
    cell = smoke_cell(name)
    for seed in (1, 2, 3):
        ref = check.follow(cell, seed, harness.CHECK_STEPS)
        ctl = check.follow(cell, seed, harness.CHECK_STEPS, "fp8")
        same = check.gaps(ref, ref)
        assert check.judge(same, cell.limits)[0]
        ok, checks = check.judge(check.gaps(ctl, ref), cell.limits)
        assert not ok, (seed, checks)
