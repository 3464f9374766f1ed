"""Plain reference of Mellum2-12B-A2.5B's block on one chip's share: 8 of
the 64 experts of each layer.

Pre-norm grouped-query attention, then the experts.  Layers take their
kind from ``layer_types`` in the order ``reference.lm_loss`` calls
``block`` (layers 0 .. n-1, once each per trace): sliding layers see the
last ``sliding_window`` positions with default rotary positions, full
layers see every earlier position with YaRN's rotary positions (cos and
sin scaled by its attention factor).  The router scores all
``router_experts``: softmax, top ``num_experts_per_tok``, the chosen gates
renormalised; only the held experts' gated SwiGLU terms are added.
Attention runs over query chunks and each part of the block is
recomputed for its gradient, so that 4,096 positions fit on one chip.
"""
import collections
import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import (F32, NEG_INF, Leaf, attention_specs,
                                 lm_specs, rms_norm, swiglu)

CHUNK = 512                       # query positions per attention chunk
# blocks called so far, by depth: each forward pass of n layers calls
# ``block`` n times, so the count modulo n is the layer's index
_CALLS = collections.defaultdict(itertools.count)


def param_specs(c: dict) -> dict:
    d, e = c["hidden_size"], c["router_experts"]
    held, f = c["num_experts"], c["moe_intermediate_size"]
    one = Leaf((d,), "ones")
    return lm_specs(c, {
        "attn": attention_specs(c), "ln1": one, "ln2": one,
        "moe": {"router": Leaf((d, e)), "w_gate": Leaf((held, d, f)),
                "w_up": Leaf((held, d, f)), "w_down": Leaf((held, f, d))}})


def block(p: dict, x, c: dict, pr):
    n = c["num_hidden_layers"]
    kind = c["layer_types"][next(_CALLS[n]) % n]
    eps = c["rms_norm_eps"]
    x = x + jax.checkpoint(functools.partial(attention, c=c, kind=kind,
                                             pr=pr))(p["attn"],
                                                     rms_norm(x, p["ln1"], eps))
    return x + jax.checkpoint(functools.partial(experts, c=c, pr=pr))(
        p["moe"], rms_norm(x, p["ln2"], eps))


def rotary_table(c: dict, kind: str, t: int):
    """cos and sin (T, hd/2) of a layer kind, from its ``rope_parameters``."""
    rp = c["rope_parameters"][kind]
    hd, base = c["head_dim"], float(rp["rope_theta"])
    inv = 1.0 / base ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    scale = 1.0
    if rp["rope_type"] == "yarn":
        factor, orig = float(rp["factor"]), rp["original_max_position_embeddings"]

        def dim_of(turns):
            # the dimension whose wavelength fits ``turns`` times in orig
            return hd * math.log(orig / (turns * 2 * math.pi)) \
                / (2 * math.log(base))
        low = max(math.floor(dim_of(rp["beta_fast"])), 0)
        high = min(math.ceil(dim_of(rp["beta_slow"])), hd - 1)
        ramp = np.clip((np.arange(hd // 2) - low) / max(high - low, 1e-3),
                       0.0, 1.0)
        inv = inv / factor * ramp + inv * (1.0 - ramp)
        scale = float(rp["attention_factor"])
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * scale, F32),
            jnp.asarray(np.sin(ang) * scale, F32))


def rotate(x, cos, sin):
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p: dict, x, c: dict, kind: str, pr):
    b, t, _ = x.shape
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    window = c["sliding_window"] if kind == "sliding_attention" else 0
    cos, sin = rotary_table(c, kind, t)
    q = rotate(pr.mm("btd,dhk->bthk", x, p["wq"]), cos, sin)
    k = rotate(pr.mm("btd,dhk->bthk", x, p["wk"]), cos, sin)
    v = pr.mm("btd,dhk->bthk", x, p["wv"])
    q = q.reshape(b, t, kv, h // kv, hd)
    parts = [jax.checkpoint(functools.partial(_chunk, lo=lo, window=window,
                                              pr=pr))(q[:, lo:lo + CHUNK], k, v)
             for lo in range(0, t, CHUNK)]
    o = jnp.concatenate(parts, axis=1).reshape(b, t, h, hd)
    return pr.mm("bthk,hkd->btd", o, p["wo"])


def _chunk(qc, k, v, *, lo: int, window: int, pr):
    """Queries ``lo .. lo + len - 1`` against every key, masked causal (and
    to the window)."""
    s = pr.mm("btgrk,bsgk->bgrts", qc, k) / math.sqrt(qc.shape[-1])
    pos_q = lo + jnp.arange(qc.shape[1])
    pos_k = jnp.arange(k.shape[1])
    mask = pos_k[None, :] <= pos_q[:, None]
    if window:
        mask &= pos_k[None, :] > pos_q[:, None] - window
    s = jnp.where(mask, s, NEG_INF)
    return pr.mm("bgrts,bsgk->btgrk", jax.nn.softmax(s, axis=-1), v)


def experts(p: dict, x, c: dict, pr):
    """Softmax router over every expert, top-k, renormalised gates; the
    held experts (0 .. held-1) add their gated SwiGLU terms."""
    probs = jax.nn.softmax(pr.mm("btd,de->bte", x, p["router"]), axis=-1)
    top, idx = jax.lax.top_k(probs, c["num_experts_per_tok"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    for j in range(c["num_experts"]):
        gate = jnp.sum(jnp.where(idx == j, top, 0.0), axis=-1)
        y = y + gate[..., None] * swiglu(
            {w: p[w][j] for w in ("w_gate", "w_up", "w_down")}, x, pr)
    return y
