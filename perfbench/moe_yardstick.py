"""The yardstick of a cell on one chip's share of a mixture-of-experts
model: operations from the configuration's shapes and the routing.

Kept with the benchmark, not imported from the program.  A trained token
requires 6 x its dense matrix-product parameters (attention projections,
the router at its full width, the output head over the vocabulary held;
the input embedding is a gather and is left out) plus 3 x its forward
score and output products, each layer over the mean number of keys a
query of that layer's kind sees.  The experts are counted by the (token,
expert) pairs the routing sent to the held experts, each 6 x one expert's
parameters: at random weights the routing is far from even, so a count
from ``top_k * held / experts`` would not be the work done.  Recomputed
operations are not counted.
"""
from __future__ import annotations


def expert_params(c: dict) -> int:
    """One expert's SwiGLU weights (gate, up, down)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    router = d * c["router_experts"]
    return c["num_hidden_layers"] * (attn + router) + c["vocab_size"] * d


def mean_visible_keys(seq: int, window: int) -> float:
    """Mean over causal queries 0 .. seq-1 of the keys each sees."""
    if not window or window >= seq:
        return (seq + 1) / 2
    # queries below the window see i + 1 keys, the rest see ``window``
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def attention_flops_per_token(c: dict, seq: int) -> float:
    h, hd = c["num_attention_heads"], c["head_dim"]
    total = 0.0
    for kind in c["layer_types"][:c["num_hidden_layers"]]:
        window = c["sliding_window"] if kind == "sliding_attention" else 0
        total += 2 * 2 * h * hd * mean_visible_keys(seq, window)
    return total


def dense_flops_per_token(c: dict, seq: int) -> float:
    """Everything but the experts, forward and backward."""
    return 6.0 * dense_params(c) + 3.0 * attention_flops_per_token(c, seq)


def flops_per_pair(c: dict) -> float:
    """One (token, held expert) pair's grouped products, forward (2 x 3
    products) and backward (twice that)."""
    return 6.0 * expert_params(c)
