"""GQA attention block: param specs + train/prefill/decode application.

KV caches use layout (B, S, K, hd) with the cache length dim sharded on the
model axis (``cache_len`` rule) — always divisible (32k / 512k) even when the
KV head count (2..8) is not, which keeps decode_32k / long_500k cache memory
per device bounded.  Attention math runs on GQA-repeated heads; GSPMD slices
the repeat locally (see layers.repeat_kv).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerKind
from repro.distributed.sharding import TensorSpec, constrain
from repro.models import layers
from repro.models.layers import blocked_attention, decode_attention, rotary


class KVCache(NamedTuple):
    k: jax.Array        # (B, S, K, hd)
    v: jax.Array        # (B, S, K, hd)


def attn_specs(cfg: ArchConfig) -> dict:
    d, h, k, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    out = {
        "wq": TensorSpec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": TensorSpec((d, k, hd), ("embed", "kv_heads", "head_dim")),
        "wv": TensorSpec((d, k, hd), ("embed", "kv_heads", "head_dim")),
        "wo": TensorSpec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        out["bq"] = TensorSpec((h, hd), ("heads", "head_dim"), init="zeros")
        out["bk"] = TensorSpec((k, hd), ("kv_heads", "head_dim"), init="zeros")
        out["bv"] = TensorSpec((k, hd), ("kv_heads", "head_dim"), init="zeros")
    return out


def cache_specs(cfg: ArchConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> KVCache:
    k, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (cfg.n_layers, batch, max_len, k, hd)
    axes = (None, "batch", "cache_len", "cache_heads", "head_dim")
    return KVCache(TensorSpec(shape, axes, dtype),
                   TensorSpec(shape, axes, dtype))


def _qkv(p: dict, x: jax.Array, cfg: ArchConfig, positions: jax.Array,
         kind: LayerKind):
    dt = x.dtype
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = rotary(q, positions, kind.rope)
    k = rotary(k, positions, kind.rope)
    return q, k, v


def attn_train(p: dict, x: jax.Array, cfg: ArchConfig,
               positions: jax.Array, kind: LayerKind, *, causal: bool = True,
               block: int = 1024) -> jax.Array:
    """Full-sequence attention (training / encoder) of a ``kind`` layer."""
    q, k, v = _qkv(p, x, cfg, positions, kind)
    out = blocked_attention(q, k, v, causal=causal, window=kind.window,
                            block_q=block, block_kv=block)
    return jnp.einsum("bthk,hkd->btd", out, p["wo"].astype(x.dtype))


def attn_prefill(p: dict, x: jax.Array, cfg: ArchConfig,
                 positions: jax.Array, kind: LayerKind, *,
                 block: int = 1024) -> tuple[jax.Array, KVCache]:
    """Causal attention that also returns the layer's KV cache."""
    q, k, v = _qkv(p, x, cfg, positions, kind)
    out = blocked_attention(q, k, v, causal=True, window=kind.window,
                            block_q=block, block_kv=block)
    out = jnp.einsum("bthk,hkd->btd", out, p["wo"].astype(x.dtype))
    return out, KVCache(k, v)


def _cache_write(cache: jax.Array, new: jax.Array,
                 index: jax.Array) -> jax.Array:
    """Masked in-place token write.

    A ``dynamic_update_slice`` at a traced index on the len-sharded cache
    dim forces GSPMD into an 'involuntary full rematerialization' (all-
    gather the whole cache, update, re-shard — GBs per layer per token).
    The masked ``where`` keeps every shard's update local: broadcast the
    (B, 1, K, hd) token against the len-sharded cache and select by
    position.  Costs one cache read+write of HBM traffic (which decode
    attention pays anyway), moves ZERO collective bytes.

    ``index``: () shared position, or (B,) per-sequence positions
    (continuous batching — each slot is at its own length).
    """
    s = cache.shape[1]
    pos = jnp.arange(s, dtype=jnp.int32)[None, :, None, None]
    idx = index if index.ndim == 0 else index[:, None, None, None]
    return jnp.where(pos == idx, new.astype(cache.dtype), cache)


def attn_decode(p: dict, x: jax.Array, cfg: ArchConfig, cache: KVCache,
                index: jax.Array, kind: LayerKind
                ) -> tuple[jax.Array, KVCache]:
    """One-token decode step.  x: (B, 1, D); index: () or (B,) lengths."""
    b = x.shape[0]
    index = jnp.asarray(index, jnp.int32)
    positions = jnp.full((b, 1), index, jnp.int32) if index.ndim == 0 \
        else index[:, None]
    q, k, v = _qkv(p, x, cfg, positions, kind)
    # q is tiny: replicate it across the model axis so the scores einsum
    # keeps the CACHE's len-sharding instead of resharding the cache onto
    # q's head sharding (50 KB gather vs GBs).
    q = constrain(q, ("act_batch", None, None, None))
    k = constrain(k, ("act_batch", None, None, None))
    v = constrain(v, ("act_batch", None, None, None))
    k_cache = _cache_write(cache.k, k, index)
    v_cache = _cache_write(cache.v, v, index)
    rep = cfg.n_heads // cfg.n_kv_heads
    kv_len = jnp.full((b,), index + 1, jnp.int32) if index.ndim == 0 \
        else index + 1
    out = decode_attention(q, layers.repeat_kv(k_cache, rep),
                           layers.repeat_kv(v_cache, rep), kv_len=kv_len,
                           window=kind.window)
    out = jnp.einsum("bthk,hkd->btd", out, p["wo"].astype(x.dtype))
    return out, KVCache(k_cache, v_cache)
