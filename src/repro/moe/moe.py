"""Mixture-of-Experts block (DeepSeekMoE / Qwen3-MoE / Mellum2 style).

Dropless routing over a device's share of the experts:

* **Routing over all experts.**  The router keeps its published width:
  softmax over every expert, top-k, the k gates renormalised over the k
  chosen.  Gates are set before anything is left out.

* **The experts held.**  Under expert parallelism a device holds
  ``MoEConfig.held`` of the experts (all of them by default) and computes
  the part of the result that its own experts give; the pairs routed to
  experts held elsewhere are counted and left to those devices.  On one
  device there is no exchange: what the absent experts would add is left
  out, and that partial result goes on to the next layer.

* **Dropless, grouped.**  The (token, slot) pairs are sorted by expert, the
  held experts' pairs first, and each held expert multiplies its own rows
  (grouped matrix products, sizes set by the routing).  The row buffer is
  sized for the worst case (every token sends ``min(k, held)`` pairs
  here), so no pair is dropped at any imbalance; the rows past the pairs
  routed here are masked.  The grouped products and the gathers around
  them are rematerialized in the backward pass instead of saving the
  worst-case buffers.

Counts per call: pairs computed here, pairs routed elsewhere, and the
largest held expert's pairs.  Aux losses (switch-style load balance and
router z-loss) are returned for the callers that add them.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import TensorSpec, constrain
from repro.models import layers

# the per-call routing counts, as ``moe_apply``'s metrics name them
ROUTING_COUNTS = ("moe_pairs_here", "moe_pairs_elsewhere",
                  "moe_max_expert_pairs")


def moe_specs(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    e, fe = cfg.moe.n_experts, cfg.moe.d_ff_expert
    held = cfg.moe.held
    out = {
        "router": TensorSpec((d, e), ("embed", None)),
        "w_gate": TensorSpec((held, d, fe), ("experts", "embed", "expert_ff")),
        "w_up": TensorSpec((held, d, fe), ("experts", "embed", "expert_ff")),
        "w_down": TensorSpec((held, fe, d), ("experts", "expert_ff", "embed")),
    }
    if cfg.moe.n_shared_experts:
        out["shared"] = layers.mlp_specs(
            d, cfg.moe.n_shared_experts * fe)
    return out


def grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """Rows ``[sum(g[:i]), sum(g[:i+1]))`` of ``lhs`` times ``rhs[i]``;
    rows past ``sum(g)`` are not computed (their values are undefined)."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32)


def _experts(x, rows, gates, group_sizes, w_gate, w_up, w_down):
    """Each held expert's SwiGLU on its rows, gated, summed per token.

    x: (N, D); rows: (M,) token of each buffer row (held experts' pairs
    first, by expert); gates: (M,) the pair's gate, 0 past the pairs here."""
    m = rows.shape[0]
    valid = (jnp.arange(m) < group_sizes.sum())[:, None]
    dt = x.dtype
    xin = jnp.where(valid, x[rows], 0).astype(dt)
    g = grouped_matmul(xin, w_gate.astype(dt), group_sizes)
    u = grouped_matmul(xin, w_up.astype(dt), group_sizes)
    h = (jax.nn.silu(g) * u).astype(dt)
    y = grouped_matmul(h, w_down.astype(dt), group_sizes)
    y = jnp.where(valid, y, 0) * gates[:, None]
    return jnp.zeros(x.shape, jnp.float32).at[rows].add(y).astype(dt)


def moe_apply(p: dict, x: jax.Array, cfg: ArchConfig,
              first_held: int = 0) -> Tuple[jax.Array, dict]:
    """x: (B, T, D) -> (y, metrics).  Differentiable through gates.

    This device holds experts ``first_held .. first_held + held - 1`` (the
    leading dimension of ``p["w_gate"]``); the router scores all
    ``cfg.moe.n_experts``."""
    b, t, d = x.shape
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    held = p["w_gate"].shape[0]
    n = b * t
    xf = x.reshape(n, d)

    # routing decisions in float32 at full precision: a rounded score
    # moves a pair to another expert
    logits = jnp.dot(xf.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)         # (N,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)               # (N,k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)                   # renormalize

    # ---- aux losses (global means; cheap scalars) ----
    me = probs.mean(0)                                            # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(
        1.0 / (n * k))
    aux = e * jnp.sum(me * ce)
    zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    # ---- the held experts' pairs, sorted by expert, first ----
    local = expert_ids.reshape(-1) - first_held                   # (N*k,)
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held)                            # held = away
    order = jnp.argsort(key, stable=True)
    counts = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)
    group_sizes = counts[:held]
    m = n * min(k, held)              # most pairs one token set can send here
    pairs = order[:m]
    rows = (pairs // k).astype(jnp.int32)
    gates = jnp.where(jnp.arange(m) < group_sizes.sum(),
                      gate_vals.reshape(-1)[pairs], 0.0)

    y = jax.checkpoint(_experts)(xf, rows, gates, group_sizes, p["w_gate"],
                                 p["w_up"], p["w_down"]).reshape(b, t, d)
    y = constrain(y, ("act_batch", "act_seq", "act_embed"))

    if "shared" in p:
        sh = p["shared"]
        y = y + layers.swiglu(x, sh["w_gate"], sh["w_up"], sh["w_down"])

    metrics = {
        "moe_aux": aux,
        "moe_zloss": zloss,
        "moe_pairs_here": group_sizes.sum(),
        "moe_pairs_elsewhere": counts[held],
        "moe_max_expert_pairs": group_sizes.max(),
    }
    return y, metrics
