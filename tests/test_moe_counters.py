"""The grad step's routing counts reach the telemetry hub: per unit, one
reading of each ``moe`` counter with its round and unit, the counters'
totals in ``metrics.prom``; a model with no experts records none."""
import pytest

from repro.core import telemetry as tlm

ARGS = ["--preset", "smoke", "--seq", "16", "--batch", "2", "--micro", "2",
        "--workers", "2", "--seed", "3", "--log-every", "100", "--steps", "2",
        "--snapshot-every", "0"]


@pytest.mark.parametrize("arch", ["mellum2-12b-a2.5b-ep8", "granite-3-2b"])
def test_routing_counts_reach_the_hub(arch, tmp_path):
    from repro.launch import train
    prev = tlm.get_default()
    try:
        train.main(["--arch", arch, "--telemetry", str(tmp_path)] + ARGS)
        hub = tlm.get_default()
    finally:
        tlm.set_default(prev)
    prom = (tmp_path / "metrics.prom").read_text()
    readings = list(hub.readings)
    if arch.startswith("granite"):
        assert not readings and "repro_moe_" not in prom
        return
    # smoke share: 2 layers, top-2 of 4 experts, 2 held; 2 x 16 tokens
    pairs = 2 * 2 * 16 * 2
    by = {}
    for r in readings:
        by.setdefault((r.step, r.unit), {})[r.name] = r.value
    assert sorted(by) == [(0, 0), (0, 1), (1, 2), (1, 3)]
    for got in by.values():
        assert got["moe.pairs_here"] + got["moe.pairs_elsewhere"] == pairs
        assert 0 < got["moe.max_expert_pairs"] <= 32
    here = sum(v["moe.pairs_here"] for v in by.values())
    assert f'repro_moe_pairs_here{{scope="moe",instance="0"}} {here}' in prom
    assert "# TYPE repro_moe_max_expert_pairs gauge" in prom
    assert "repro_moe_pairs_elsewhere" in prom


def test_routing_is_read_once_the_unit_is_validated(tmp_path, monkeypatch):
    """The host reads a unit's counts after its ``validate`` span has
    closed, so the wait for the grad step stays inside that span."""
    from repro.core import elastic
    from repro.launch import train
    count, closed = elastic.VolunteerTrainer._count_routing, []

    def spy(self, unit, routing):
        closed.append([s.end_ns is not None for s in self.tel.spans
                       if s.name == "validate" and s.unit == unit.unit_id])
        return count(self, unit, routing)
    monkeypatch.setattr(elastic.VolunteerTrainer, "_count_routing", spy)
    prev = tlm.get_default()
    try:
        train.main(["--arch", "mellum2-12b-a2.5b-ep8",
                    "--telemetry", str(tmp_path)] + ARGS)
    finally:
        tlm.set_default(prev)
    assert closed == [[True]] * 4
