"""A cell of the real benchmark shrunk to the program's ``smoke`` preset, so
the harness can run end to end on a CPU in seconds.

At these widths bfloat16 products stray further from the float32 reference
than at the published ones, so the smoke cells carry limits of their own,
set the same way as the cells' (CPU, the test seeds): between the largest
gap of sound runs and the smallest of the float8 control.

| cell | sound runs (largest) | control (smallest) | limit |
| granite loss / grad / change | 1.9e-4 / 3.5e-3 / 6.0e-4 | 1.4e-4 / 7.6e-3 / 1.7e-3 | 1e-3 / 5e-3 / 1.2e-3 |
| hymba loss / grad / change | 2.0e-4 / 8.5e-3 / 2.0e-3 | 2.1e-4 / 1.5e-2 / 3.6e-3 | 1e-3 / 1.2e-2 / 2.8e-3 |
"""
from __future__ import annotations

from perfbench import registry

SMOKE = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
         "vocab_size": 256, "padded_vocab_size": 256,
         "attention_multiplier": 0.25}
SMOKE_SSM = {"mamba_d_state": 4, "mamba_d_conv": 4, "mamba_expand": 2,
             "mamba_dt_rank": 8}
SMOKE_LIMITS = {
    "dense": {"loss_gap": 1e-3, "grad_gap": 5e-3, "change_gap": 1.2e-3},
    "hybrid": {"loss_gap": 1e-3, "grad_gap": 1.2e-2, "change_gap": 2.8e-3},
}


def smoke_cell(name: str, **traffic) -> registry.Cell:
    cell = registry.load_cell(name)
    cell.config = dict(cell.config, program_preset="smoke", **SMOKE)
    if cell.config["family"] == "hybrid":
        cell.config.update(SMOKE_SSM)
    cell.limits = dict(cell.limits, **{
        k: {"limit": v} for k, v in SMOKE_LIMITS[cell.config["family"]].items()})
    # three set-up rounds, enough for the check, and a cell that snapshots
    # does so every round, so that a short window holds snapshots to check
    cell.traffic = dict(cell.traffic, seq_len=32, batch=4, warmup_rounds=3)
    if cell.traffic["snapshot_every"]:
        cell.traffic["snapshot_every"] = 1
    cell.traffic.update(traffic)
    return cell
