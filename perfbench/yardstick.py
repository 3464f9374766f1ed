"""The yardstick: operations and bytes computed from shapes.

Copied here, not imported, so that no change to the program can move the
numbers it is judged by.  The operation count follows the program's cost
model as it stood when the benchmark was defined (``launch/costmodel.py``:
6 x matmul parameters x tokens for a training step, the input embedding
excluded, plus the causal attention term); recomputed operations are not
counted.  Sizes come from the benchmark's own configuration files.
"""
from __future__ import annotations

# the snapshot probe kernel streams (8, 1024) int32 tiles and writes one
# int32 flag per tile into (8, 128) blocks
TILE_BYTES = 8 * 1024 * 4
FLAGS_PER_BLOCK = 8 * 128
FLAG_BLOCK_BYTES = 8 * 128 * 4


def matmul_params(c: dict) -> int:
    """Parameters that take part in a matrix product for each token."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    per_layer = 0
    if c["family"] in ("dense", "hybrid"):
        per_layer += d * h * hd + 2 * d * kv * hd + h * hd * d
    if c["family"] == "hybrid":
        di, n = c["mamba_expand"] * d, c["mamba_d_state"]
        r, dc = c["mamba_dt_rank"], c["mamba_d_conv"]
        per_layer += (d * 2 * di + di * dc + di * (r + 2 * n) + r * di
                      + di * n + di + di * d)
    per_layer += 3 * d * c["intermediate_size"]
    vocab = c["vocab_size"] * d
    # a tied table is counted once, as the output head; an untied input
    # table is a gather and is left out
    return c["num_hidden_layers"] * per_layer + vocab


def attention_flops_per_token(c: dict, seq: int) -> float:
    """Forward score and output products per token, causal-halved."""
    if c["family"] not in ("dense", "hybrid"):
        return 0.0
    h, hd = c["num_attention_heads"], c["head_dim"]
    window = c.get("attention_window", 0)
    span = min(window, seq) if window else seq / 2
    return 2 * 2 * h * span * hd * c["num_hidden_layers"]


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward operations one trained token requires."""
    return 6.0 * matmul_params(c) + 3.0 * attention_flops_per_token(c, seq)


def probe_bytes(nblk: int, changed: int) -> int:
    """HBM bytes one fused probe launch must move: read the old and new
    images, write the changed tiles and the flag blocks."""
    flag_blocks = -(-nblk // FLAGS_PER_BLOCK)
    return (2 * nblk + changed) * TILE_BYTES + flag_blocks * FLAG_BLOCK_BYTES
