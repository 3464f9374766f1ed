"""Model primitives: norms, rotary, blocked attention, SwiGLU MLP.

All functions are pure and operate on *global* (unsharded) shapes; GSPMD
partitions them according to the sharding resolver's annotations.  Attention
uses an online-softmax blocked formulation (the jnp twin of the Pallas
flash-attention kernel in ``repro.kernels.flash_attention``) so that 32k+
contexts never materialize a full (T, S) score matrix.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import Rope
from repro.distributed.sharding import TensorSpec, constrain

NEG_INF = -1e30


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    # variance in f32, but x itself is NOT upcast: a whole-tensor convert
    # here gets fused below the TP partial-sum all-reduces by XLA, doubling
    # every collective's bytes (EXPERIMENTS.md §Perf cell B iter6).
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * w.astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ w_gate.astype(x.dtype)) * (x @ w_up.astype(x.dtype))
    return h @ w_down.astype(x.dtype)


def mlp_specs(d_model: int, d_ff: int) -> dict:
    """SwiGLU params; embed dim FSDP-sharded, ff dim tensor-parallel."""
    return {
        "w_gate": TensorSpec((d_model, d_ff), ("embed", "ff")),
        "w_up": TensorSpec((d_model, d_ff), ("embed", "ff")),
        "w_down": TensorSpec((d_ff, d_model), ("ff", "embed")),
    }


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------
def rope_frequencies(hd: int, rope: Rope) -> tuple[jax.Array, float]:
    """-> (inverse frequencies (hd/2,), scale of cos and sin).

    Default rotary positions, or YaRN where ``rope.factor`` > 1, as
    transformers' ``_compute_yarn_parameters`` computes them: the
    dimensions that turn fewer than ``beta_slow`` times over the original
    context are interpolated by ``factor``, those that turn more than
    ``beta_fast`` times are kept, and a linear ramp joins the two."""
    base = rope.theta
    inv = base ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    if rope.factor <= 1.0:
        return inv, 1.0

    def correction_dim(rotations: float) -> float:
        return (hd * math.log(rope.original_max_positions
                              / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rope.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp               # 1: extrapolate (fast dims), 0: interpolate
    inv = inv / rope.factor * (1.0 - keep) + inv * keep
    return inv, rope.attention_factor


def rotary(x: jax.Array, positions: jax.Array, rope: Rope) -> jax.Array:
    """x: (B, T, H, hd); positions: (B, T) int32."""
    hd = x.shape[-1]
    freqs, mscale = rope_frequencies(hd, rope)
    ang = positions[..., None].astype(jnp.float32) * freqs        # (B, T, hd/2)
    cos = jnp.cos(ang)[:, :, None, :] * mscale
    sin = jnp.sin(ang)[:, :, None, :] * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, K, hd) -> (B, S, K*n_rep, hd).  GSPMD slices the repeated head
    dim locally when it is sharded, so no device materializes all heads."""
    if n_rep == 1:
        return k
    b, s, kh, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kh, n_rep, hd)) \
              .reshape(b, s, kh * n_rep, hd)


def visible_kv_blocks(q_lo: int, q_hi: int, s: int, block_kv: int, *,
                      causal: bool, window: int) -> range:
    """KV blocks that some query in positions ``q_lo..q_hi`` can see."""
    k_hi = min(q_hi, s - 1) if causal else s - 1
    k_lo = max(q_lo - window + 1, 0) if window else 0
    return range(k_lo // block_kv, k_hi // block_kv + 1)


def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, window: int = 0, block_q: int = 1024,
                      block_kv: int = 1024,
                      scale: Optional[float] = None) -> jax.Array:
    """Online-softmax (flash) attention over (query block, KV block) pairs.

    q: (B, T, H, hd);  k, v: (B, S, K, hd) with K dividing H (grouped
    query heads share a KV head; no repeat is materialized).
    ``window``: sliding-window size (0 = full).

    Each query block visits only the KV blocks one of its queries can see
    (causal, inside the window), so a window layer's work grows with T,
    not T^2.  The backward pass keeps only the output and each query's
    log-sum-exp, and recomputes every visited pair's scores.
    """
    b, t, h, hd = q.shape
    s, kh = k.shape[1], k.shape[2]
    spec = _Blocks(t=t, s=s, causal=causal, window=window,
                   block_q=min(block_q, t), block_kv=min(block_kv, s),
                   scale=scale if scale is not None else 1.0 / math.sqrt(hd))
    qg = _pad_axis(q, 1, spec.block_q).reshape(b, -1, kh, h // kh, hd)
    out = _flash(qg, _pad_axis(k, 1, spec.block_kv),
                 _pad_axis(v, 1, spec.block_kv), spec)
    return out.reshape(b, -1, h, hd)[:, :t]


class _Blocks(NamedTuple):
    t: int
    s: int
    causal: bool
    window: int
    block_q: int
    block_kv: int
    scale: float

    def rows(self, nq: int):
        """(query block start, KV block starts it visits) per query block."""
        for i in range(nq):
            lo = i * self.block_q
            blocks = visible_kv_blocks(lo, min(lo + self.block_q, self.t) - 1,
                                       self.s, self.block_kv,
                                       causal=self.causal, window=self.window)
            yield lo, jnp.arange(blocks.start, blocks.stop,
                                 dtype=jnp.int32) * self.block_kv

    def scores(self, qb, kb, lo, start):
        """Masked scaled scores (B, K, R, Tb, Sb) in float32."""
        # inputs stay in compute dtype; the MXU accumulates in f32
        sc = jnp.einsum("btkrd,bskd->bkrts", qb, kb,
                        preferred_element_type=jnp.float32) * self.scale
        pos_q = lo + jnp.arange(self.block_q, dtype=jnp.int32)
        pos_k = start + jnp.arange(self.block_kv, dtype=jnp.int32)
        mask = jnp.broadcast_to(pos_k[None, :] < self.s,
                                (self.block_q, self.block_kv))
        if self.causal:
            mask &= pos_k[None, :] <= pos_q[:, None]
        if self.window:
            mask &= pos_k[None, :] > pos_q[:, None] - self.window
        return jnp.where(mask, sc, NEG_INF)


def _flash_forward(q, k, v, spec: _Blocks):
    """-> (out like q, log-sum-exp (B, K, R, Tp) in float32)."""
    b, tp, kh, r, hd = q.shape
    outs, lses = [], []
    for lo, starts in spec.rows(tp // spec.block_q):
        qb = q[:, lo:lo + spec.block_q]

        def body(carry, start, qb=qb, lo=lo):
            m, l, acc = carry
            kb = lax.dynamic_slice_in_dim(k, start, spec.block_kv, axis=1)
            vb = lax.dynamic_slice_in_dim(v, start, spec.block_kv, axis=1)
            sc = spec.scores(qb, kb, lo, start)
            m_new = jnp.maximum(m, sc.max(-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkrts,bskd->bkrtd", p.astype(v.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l * corr + p.sum(-1), acc), None

        shape = (b, kh, r, spec.block_q)
        (m, l, acc), _ = lax.scan(
            body, (jnp.full(shape, NEG_INF, jnp.float32),
                   jnp.zeros(shape, jnp.float32),
                   jnp.zeros(shape + (hd,), jnp.float32)), starts)
        l = jnp.maximum(l, 1e-30)
        outs.append((acc / l[..., None]).transpose(0, 3, 1, 2, 4))
        lses.append(m + jnp.log(l))
    return (jnp.concatenate(outs, axis=1).astype(q.dtype),
            jnp.concatenate(lses, axis=-1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, spec: _Blocks):
    return _flash_forward(q, k, v, spec)[0]


def _flash_fwd(q, k, v, spec):
    out, lse = _flash_forward(q, k, v, spec)
    return out, (q, k, v, out, lse)


def _flash_bwd(spec, res, dout):
    q, k, v, out, lse = res
    dt = q.dtype
    # D_i = sum_d dO_i * O_i: the softmax's own term of each row's gradient
    dsum = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1).transpose(0, 2, 3, 1)                 # (B,K,R,Tp)
    dq_blocks = []
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    for lo, starts in spec.rows(q.shape[1] // spec.block_q):
        qb = q[:, lo:lo + spec.block_q]
        dob = dout[:, lo:lo + spec.block_q].astype(dt)
        lse_b = lse[..., lo:lo + spec.block_q]
        d_b = dsum[..., lo:lo + spec.block_q]

        def body(carry, start, qb=qb, dob=dob, lse_b=lse_b, d_b=d_b, lo=lo):
            dq_b, dk, dv = carry
            kb = lax.dynamic_slice_in_dim(k, start, spec.block_kv, axis=1)
            vb = lax.dynamic_slice_in_dim(v, start, spec.block_kv, axis=1)
            p = jnp.exp(spec.scores(qb, kb, lo, start) - lse_b[..., None])
            dp = jnp.einsum("btkrd,bskd->bkrts", dob, vb,
                            preferred_element_type=jnp.float32)
            ds = (p * (dp - d_b[..., None])).astype(dt)
            dq_b = dq_b + jnp.einsum("bkrts,bskd->btkrd", ds, kb,
                                     preferred_element_type=jnp.float32)
            dk_b = jnp.einsum("bkrts,btkrd->bskd", ds, qb,
                              preferred_element_type=jnp.float32)
            dv_b = jnp.einsum("bkrts,btkrd->bskd", p.astype(dt), dob,
                              preferred_element_type=jnp.float32)

            def add(acc, blk):
                cur = lax.dynamic_slice_in_dim(acc, start, spec.block_kv, 1)
                return lax.dynamic_update_slice_in_dim(acc, cur + blk, start,
                                                       axis=1)
            return (dq_b, add(dk, dk_b), add(dv, dv_b)), None

        (dq_b, dk, dv), _ = lax.scan(
            body, (jnp.zeros(qb.shape, jnp.float32), dk, dv), starts)
        dq_blocks.append(dq_b)
    dq = jnp.concatenate(dq_blocks, axis=1) * spec.scale
    return (dq.astype(q.dtype), (dk * spec.scale).astype(k.dtype),
            dv.astype(v.dtype))


_flash.defvjp(_flash_fwd, _flash_bwd)


def _pad_axis(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, *,
                     kv_len: jax.Array, window: int = 0,
                     scale: Optional[float] = None) -> jax.Array:
    """Single-step attention over a full cache (no blocking; scores are
    (B, H, 1, S) which stays small even at 500k once S is mesh-sharded).

    q: (B, 1, H, hd); caches: (B, S, H, hd) (GQA-repeated); kv_len: (B,).
    """
    b, t, h, hd = q.shape
    s = k_cache.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bthd,bshd->bhts", q, k_cache,
                        preferred_element_type=jnp.float32) * scale
    # scores inherit the cache's len-sharding; softmax reduces over the
    # sharded dim with tiny (B,H,T) collectives
    scores = constrain(scores, ("act_batch", None, None, "cache_len"))
    pos_k = jnp.arange(s, dtype=jnp.int32)
    mask = pos_k[None, :] < kv_len[:, None]                       # (B,S)
    if window:  # sliding-window: only the last `window` positions attend
        mask &= pos_k[None, :] >= kv_len[:, None] - window
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array,
                          vocab_size: int) -> jax.Array:
    """Mean CE per token; logits may be vocab-padded (padded cols masked)."""
    padded = logits.shape[-1]
    if padded != vocab_size:
        col = jnp.arange(padded)
        logits = jnp.where(col[None, None, :] < vocab_size, logits, NEG_INF)
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)
