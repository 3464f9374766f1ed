"""Plain float32 reference of the volunteer training round.

Independent of the program: it imports nothing from ``src/`` and takes
nothing the program made.  The token rows and the initial weights are
regenerated from the seed by the recipes the configuration and traffic
files state; the model, the loss and AdamW are written out in
straightforward ``jax.numpy`` at float32 with every product at
``Precision.HIGHEST``.  Each configuration's file beside this one
(``configs/<name>.py``) composes these pieces into its own blocks.

``Precision`` is the one knob: ``"float32"`` is the reference, and
``"fp8"`` is the control, the same computation with every matrix
product's operands rounded to float8 (e4m3 forward, e5m2 for the
cotangents, one scale per tensor), the step below the bfloat16 products
the configurations state.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Inputs from the seed
# ---------------------------------------------------------------------------
def token_rows(seed: int, index: int, data: dict, vocab: int, batch: int,
               seq: int) -> tuple[np.ndarray, np.ndarray]:
    """Batch ``index`` of the job's token stream: a noisy order-k Markov
    chain per row, drawn from ``(seed, index)`` -> (tokens, labels)."""
    order, noise = int(data["markov_order"]), float(data["noise"])
    head = np.random.default_rng(seed)
    mix = head.integers(1, vocab, size=(order,), dtype=np.int64)
    bias = int(head.integers(0, vocab))
    rng = np.random.default_rng((seed, index))
    rows = np.empty((batch, seq + 1), np.int64)
    rows[:, :order] = rng.integers(0, vocab, size=(batch, order))
    flip = rng.random((batch, seq + 1)) < noise
    flip_tok = rng.integers(0, vocab, size=(batch, seq + 1))
    for j in range(order, seq + 1):
        nxt = (rows[:, j - order:j] @ mix + bias) % vocab
        rows[:, j] = np.where(flip[:, j], flip_tok[:, j], nxt)
    return rows[:, :-1].astype(np.int32), rows[:, 1:].astype(np.int32)


MAX_SEED_TRIES = 4096


def stream_seed(seed: int, data: dict, vocab: int) -> int:
    """The seed the job runs on: ``seed`` itself, or, where the traffic's
    ``data`` sets ``long_cycles``, the first of ``seed, seed + 1, ...``
    whose order-1 chain spreads over the vocabulary.

    The chain's next token is ``(mix * x + bias) % vocab``.  Where ``mix``
    shares a factor with ``vocab`` the map folds the vocabulary onto a
    third or a fifteenth of it, and where it is a permutation whose cycles
    are shorter than the runs between noise draws (``1 / noise`` tokens
    long on average) a row walks the same few tokens over and over.
    Either way fewer embedding rows are ever touched, which changes the
    work downstream (the snapshot writer's zero-run encoding of the
    optimizer's moments).  Kept: a permutation on which most tokens lie
    on cycles at least ``2 / noise`` long, so every seed draws work of
    one difficulty."""
    if not data.get("long_cycles"):
        return seed
    if int(data["markov_order"]) != 1:
        raise ValueError("long_cycles needs markov_order 1")
    need = min(math.ceil(2 / float(data["noise"])), vocab)
    x = np.arange(vocab, dtype=np.int64)
    for cand in range(seed, seed + MAX_SEED_TRIES):
        head = np.random.default_rng(cand)
        mix = int(head.integers(1, vocab, size=(1,), dtype=np.int64)[0])
        bias = int(head.integers(0, vocab))
        if math.gcd(mix, vocab) == 1 and \
                np.mean(_cycle_lengths((mix * x + bias) % vocab) < need) < 0.5:
            return cand
    raise ValueError(f"no seed from {seed} draws a long-cycled chain")


def _cycle_lengths(perm: np.ndarray) -> np.ndarray:
    """Length of the cycle each element of a permutation lies on."""
    out = np.zeros(perm.size, np.int64)
    for start in range(perm.size):
        if out[start]:
            continue
        cycle, x = [start], int(perm[start])
        while x != start:
            cycle.append(x)
            x = int(perm[x])
        out[cycle] = len(cycle)
    return out


class Leaf(NamedTuple):
    shape: tuple
    init: str = "normal"        # normal | zeros | ones | log_arange


def init_params(specs: dict, seed: int, std_cap: float) -> dict:
    """Initial weights from the seed, on the device, in one jitted call.

    One key per leaf, split from ``key(seed)`` in the order the nested dict
    flattens (keys sorted); a ``normal`` leaf is
    ``min(std_cap, fan_in ** -0.5) * N(0, 1)`` with ``fan_in`` its leading
    dimension (its last for a vector)."""
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, Leaf))

    def make():
        keys = jax.random.split(jax.random.key(seed), len(leaves))
        out = []
        for key, s in zip(keys, leaves):
            if s.init == "zeros":
                out.append(jnp.zeros(s.shape, F32))
            elif s.init == "ones":
                out.append(jnp.ones(s.shape, F32))
            elif s.init == "log_arange":
                ar = jnp.arange(1, s.shape[-1] + 1, dtype=F32)
                out.append(jnp.log(jnp.broadcast_to(ar, s.shape)))
            else:
                fan_in = s.shape[0] if len(s.shape) > 1 else s.shape[-1]
                std = min(std_cap, (1.0 / max(fan_in, 1)) ** 0.5)
                out.append(std * jax.random.normal(key, s.shape, F32))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)()


# ---------------------------------------------------------------------------
# Precision of the matrix products
# ---------------------------------------------------------------------------
def _fp8_round(x, dtype, top: float):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(F32) * scale


@jax.custom_vjp
def fp8(x):
    return _fp8_round(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, g):
    return (_fp8_round(g, jnp.float8_e5m2, 57344.0),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


class Precision:
    """How the reference computes its matrix products."""

    def __init__(self, name: str = "float32"):
        if name not in ("float32", "fp8"):
            raise ValueError(f"unknown precision {name!r}")
        self.name = name
        self._q: Callable = fp8 if name == "fp8" else (lambda x: x)

    def mm(self, spec: str, a, b):
        return jnp.einsum(spec, self._q(a), self._q(b), precision=HIGHEST,
                          preferred_element_type=F32)


# ---------------------------------------------------------------------------
# Model pieces
# ---------------------------------------------------------------------------
def rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, theta: float):
    """Rotate halves: x (B, T, H, hd), positions 0..T-1."""
    hd, t = x.shape[-1], x.shape[1]
    freqs = theta ** (-jnp.arange(hd // 2, dtype=F32) / (hd // 2))
    ang = jnp.arange(t, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p: dict, x, c: dict, pr: Precision):
    """Causal grouped-query attention with rotary positions; a window of
    ``attention_window`` positions when it is set."""
    b, t, _ = x.shape
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    q = rotary(pr.mm("btd,dhk->bthk", x, p["wq"]), c["rope_theta"])
    k = rotary(pr.mm("btd,dhk->bthk", x, p["wk"]), c["rope_theta"])
    v = pr.mm("btd,dhk->bthk", x, p["wv"])
    q = q.reshape(b, t, kv, h // kv, hd)
    s = pr.mm("btgrk,bsgk->bgrts", q, k) * c["attention_multiplier"]
    pos = jnp.arange(t)
    mask = pos[None, :] <= pos[:, None]
    if c.get("attention_window", 0):
        mask &= pos[None, :] > pos[:, None] - c["attention_window"]
    s = jnp.where(mask, s, NEG_INF)
    o = pr.mm("bgrts,bsgk->btgrk", jax.nn.softmax(s, axis=-1), v)
    return pr.mm("bthk,hkd->btd", o.reshape(b, t, h, hd), p["wo"])


def swiglu(p: dict, x, pr: Precision):
    g = pr.mm("btd,df->btf", x, p["w_gate"])
    u = pr.mm("btd,df->btf", x, p["w_up"])
    return pr.mm("btf,fd->btd", jax.nn.silu(g) * u, p["w_down"])


def mamba(p: dict, x, c: dict, pr: Precision):
    """Mamba-1 selective scan, one time step after another."""
    n, r = c["mamba_d_state"], c["mamba_dt_rank"]
    xz = pr.mm("btd,de->bte", x, p["in_proj"])
    xr, z = jnp.split(xz, 2, axis=-1)
    w = p["conv_w"]                                   # (d_conv, d_inner)
    dc, t = w.shape[0], x.shape[1]
    padded = jnp.pad(xr, ((0, 0), (dc - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + t] * w[i] for i in range(dc)) + p["conv_b"]
    xc = jax.nn.silu(conv)
    dbc = pr.mm("bte,ef->btf", xc, p["x_proj"])
    dt, bm, cm = jnp.split(dbc, [r, r + n], axis=-1)
    dt = jax.nn.softplus(pr.mm("btr,re->bte", dt, p["dt_proj"])
                         + p["dt_bias"])
    a = -jnp.exp(p["A_log"])                          # (d_inner, N)

    def step(hstate, inp):
        dt_t, x_t, b_t, c_t = inp
        hstate = (jnp.exp(dt_t[..., None] * a) * hstate
                  + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return hstate, jnp.einsum("bdn,bn->bd", hstate, c_t,
                                  precision=HIGHEST)

    h0 = jnp.zeros((x.shape[0], a.shape[0], n), F32)
    seq = tuple(jnp.swapaxes(v, 0, 1) for v in (dt, xc, bm, cm))
    _, y = jax.lax.scan(step, h0, seq)
    y = jnp.swapaxes(y, 0, 1) + xc * p["D"]
    return pr.mm("bte,ed->btd", y * jax.nn.silu(z), p["out_proj"])


def cross_entropy(logits, labels, vocab: int):
    """Mean next-token loss; columns past the vocabulary are padding."""
    col = jnp.arange(logits.shape[-1])
    logits = jnp.where(col < vocab, logits, NEG_INF)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def layer_params(params: dict, i: int) -> dict:
    return jax.tree.map(lambda a: a[i], params["layers"])


def lm_loss(params: dict, tokens, labels, c: dict, pr: Precision,
            block: Callable):
    """Embed, ``block`` per layer, final norm, output head, loss."""
    x = params["embed"][tokens] * c["embedding_multiplier"]
    for i in range(c["num_hidden_layers"]):
        x = block(layer_params(params, i), x, c, pr)
    x = rms_norm(x, params["final_norm"], c["rms_norm_eps"])
    head = params["embed"].T if c["tie_word_embeddings"] \
        else params["lm_head"]
    logits = pr.mm("btd,dv->btv", x, head) / c["logits_scaling"]
    return cross_entropy(logits, labels, c["vocab_size"])


def lm_specs(c: dict, layer: dict) -> dict:
    """Embedding, stacked layers, final norm and (untied) output head."""
    d, vp, n = c["hidden_size"], c["padded_vocab_size"], c["num_hidden_layers"]
    out = {"embed": Leaf((vp, d)),
           "layers": {k: _stack(v, n) for k, v in layer.items()},
           "final_norm": Leaf((d,), "ones")}
    if not c["tie_word_embeddings"]:
        out["lm_head"] = Leaf((d, vp))
    return out


def _stack(tree, n: int):
    if isinstance(tree, Leaf):
        return Leaf((n,) + tree.shape, tree.init)
    return {k: _stack(v, n) for k, v in tree.items()}


def attention_specs(c: dict) -> dict:
    d, h, kv, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    return {"wq": Leaf((d, h, hd)), "wk": Leaf((d, kv, hd)),
            "wv": Leaf((d, kv, hd)), "wo": Leaf((h, hd, d))}


def mlp_specs(c: dict) -> dict:
    d, f = c["hidden_size"], c["intermediate_size"]
    return {"w_gate": Leaf((d, f)), "w_up": Leaf((d, f)),
            "w_down": Leaf((f, d))}


def mamba_specs(c: dict) -> dict:
    d = c["hidden_size"]
    di, n = c["mamba_expand"] * d, c["mamba_d_state"]
    r, dc = c["mamba_dt_rank"], c["mamba_d_conv"]
    return {"in_proj": Leaf((d, 2 * di)), "conv_w": Leaf((dc, di)),
            "conv_b": Leaf((di,), "zeros"), "x_proj": Leaf((di, r + 2 * n)),
            "dt_proj": Leaf((r, di)), "dt_bias": Leaf((di,), "ones"),
            "A_log": Leaf((di, n), "log_arange"), "D": Leaf((di,), "ones"),
            "out_proj": Leaf((di, d))}


# ---------------------------------------------------------------------------
# AdamW with warm-up, cosine decay and global-norm clipping
# ---------------------------------------------------------------------------
def learning_rate(o: dict, step: int) -> float:
    warm = min(step / max(o["warmup_steps"], 1), 1.0)
    frac = min(max((step - o["warmup_steps"])
                   / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    cos = o["min_lr_frac"] + (1 - o["min_lr_frac"]) * 0.5 * (
        1 + math.cos(math.pi * frac))
    return o["lr"] * warm * cos


def adamw_factors(o: dict, step: int) -> tuple[float, float, float]:
    """(learning rate, bias corrections) of update ``step``, from 1."""
    return (learning_rate(o, step), 1 - o["beta1"] ** step,
            1 - o["beta2"] ** step)


def adamw(o: dict, params, grads, m, v, lr, b1c, b2c):
    """One update -> (params, m, v, the clipped gradient it applied)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gnorm, 1e-9))

    def one(p, g, mi, vi):
        g = g * scale
        mi = o["beta1"] * mi + (1 - o["beta1"]) * g
        vi = o["beta2"] * vi + (1 - o["beta2"]) * g * g
        upd = (mi / b1c) / (jnp.sqrt(vi / b2c) + o["eps"])
        if p.ndim >= 2:
            upd = upd + o["weight_decay"] * p
        return p - lr * upd, mi, vi, g

    out = jax.tree.map(one, params, grads, m, v)
    is_out = lambda t: isinstance(t, tuple)  # noqa: E731
    return tuple(jax.tree.map(lambda t: t[i], out, is_leaf=is_out)
                 for i in range(4))
