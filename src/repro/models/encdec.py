"""Encoder–decoder backbone (seamless-m4t-medium).

The audio frontend is a STUB per the assignment: ``input_specs()`` provides
precomputed frame embeddings (B, S_enc, D) directly to the encoder.  The
decoder is a causal transformer with cross-attention to the encoder output;
decode shapes use a self-attention KV cache of ``seq_len`` plus a fixed
cross-attention KV computed once from the encoder (ENC_LEN_DECODE frames).
RMSNorm is used throughout for uniformity with the other archs (deviation
from the source LayerNorm, noted in DESIGN.md).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.distributed.sharding import TensorSpec, constrain, stack_specs
from repro.models import attention, layers
from repro.models.attention import KVCache
from repro.models.lm import ACT, RunConfig, cast_tree, unembed

# encoder frames backing a decode-time cross-attention cache
ENC_LEN_DECODE = 4096


def enc_block_specs(cfg: ArchConfig) -> dict:
    return {
        "ln1": TensorSpec((cfg.d_model,), ("embed",), init="ones"),
        "attn": attention.attn_specs(cfg),
        "ln2": TensorSpec((cfg.d_model,), ("embed",), init="ones"),
        "mlp": layers.mlp_specs(cfg.d_model, cfg.d_ff),
    }


def dec_block_specs(cfg: ArchConfig) -> dict:
    return {
        "ln1": TensorSpec((cfg.d_model,), ("embed",), init="ones"),
        "self_attn": attention.attn_specs(cfg),
        "ln_x": TensorSpec((cfg.d_model,), ("embed",), init="ones"),
        "cross_attn": attention.attn_specs(cfg),
        "ln2": TensorSpec((cfg.d_model,), ("embed",), init="ones"),
        "mlp": layers.mlp_specs(cfg.d_model, cfg.d_ff),
    }


def encdec_specs(cfg: ArchConfig) -> dict:
    vp = cfg.padded_vocab()
    return {
        "embed": TensorSpec((vp, cfg.d_model), ("vocab", "embed")),
        "enc_layers": stack_specs(enc_block_specs(cfg), cfg.n_layers),
        "dec_layers": stack_specs(dec_block_specs(cfg), cfg.n_layers),
        "enc_norm": TensorSpec((cfg.d_model,), ("embed",), init="ones"),
        "final_norm": TensorSpec((cfg.d_model,), ("embed",), init="ones"),
        "lm_head": TensorSpec((cfg.d_model, vp), ("embed", "vocab")),
    }


def encdec_cache_specs(cfg: ArchConfig, batch: int, max_len: int,
                       enc_len: int = ENC_LEN_DECODE) -> dict:
    k, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    self_shape = (cfg.n_layers, batch, max_len, k, hd)
    cross_shape = (cfg.n_layers, batch, enc_len, k, hd)
    axes = (None, "batch", "cache_len", "cache_heads", "head_dim")
    return {
        "self_kv": KVCache(TensorSpec(self_shape, axes, jnp.bfloat16),
                           TensorSpec(self_shape, axes, jnp.bfloat16)),
        "cross_kv": KVCache(TensorSpec(cross_shape, axes, jnp.bfloat16),
                            TensorSpec(cross_shape, axes, jnp.bfloat16)),
    }


def encode(params: dict, cfg: ArchConfig, frames: jax.Array,
           run: RunConfig = RunConfig()) -> jax.Array:
    """frames: (B, S_enc, D) precomputed embeddings (stub frontend)."""
    x = constrain(frames.astype(run.compute_dtype), ACT)
    b, t = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    enc_params = cast_tree(params["enc_layers"], run.compute_dtype)

    def body(x, lp):
        xn = layers.rms_norm(x, lp["ln1"], cfg.rms_eps)
        x = x + attention.attn_train(lp["attn"], xn, cfg, positions,
                                     cfg.layer_kinds()[0], causal=False)
        xn2 = layers.rms_norm(x, lp["ln2"], cfg.rms_eps)
        m = lp["mlp"]
        x = x + layers.swiglu(xn2, m["w_gate"], m["w_up"], m["w_down"])
        return constrain(x, ACT), None

    policy = run.remat_policy()
    if policy is not None:
        body = jax.checkpoint(body, policy=policy)
    x, _ = lax.scan(body, x, enc_params)
    return layers.rms_norm(x, params["enc_norm"], cfg.rms_eps)


def _cross_attend(lp: dict, xn: jax.Array, cfg: ArchConfig,
                  enc_kv: Optional[KVCache], enc_out: Optional[jax.Array],
                  enc_len: Optional[jax.Array] = None) -> jax.Array:
    """Cross-attention: q from decoder, k/v from encoder output or cache."""
    dt = xn.dtype
    q = jnp.einsum("btd,dhk->bthk", xn, lp["wq"].astype(dt))
    if enc_kv is None:
        k = jnp.einsum("btd,dhk->bthk", enc_out, lp["wk"].astype(dt))
        v = jnp.einsum("btd,dhk->bthk", enc_out, lp["wv"].astype(dt))
    else:
        k, v = enc_kv.k.astype(dt), enc_kv.v.astype(dt)
    rep = cfg.n_heads // cfg.n_kv_heads
    k, v = layers.repeat_kv(k, rep), layers.repeat_kv(v, rep)
    if xn.shape[1] == 1:
        kv_len = enc_len if enc_len is not None else \
            jnp.full((xn.shape[0],), k.shape[1], jnp.int32)
        out = layers.decode_attention(q, k, v, kv_len=kv_len)
    else:
        out = layers.blocked_attention(q, k, v, causal=False)
    return jnp.einsum("bthk,hkd->btd", out, lp["wo"].astype(dt))


def forward_train(params: dict, cfg: ArchConfig, frames: jax.Array,
                  tokens: jax.Array, run: RunConfig = RunConfig()):
    """Teacher-forced training forward.  frames: (B,S,D); tokens: (B,T)."""
    enc_out = encode(params, cfg, frames, run)
    x = constrain(params["embed"].astype(run.compute_dtype)[tokens], ACT)
    b, t = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    dec_params = cast_tree(params["dec_layers"], run.compute_dtype)

    def body(x, lp):
        xn = layers.rms_norm(x, lp["ln1"], cfg.rms_eps)
        x = x + attention.attn_train(lp["self_attn"], xn, cfg, positions,
                                     cfg.layer_kinds()[0])
        xc = layers.rms_norm(x, lp["ln_x"], cfg.rms_eps)
        x = x + _cross_attend(lp["cross_attn"], xc, cfg, None, enc_out)
        xn2 = layers.rms_norm(x, lp["ln2"], cfg.rms_eps)
        m = lp["mlp"]
        x = x + layers.swiglu(xn2, m["w_gate"], m["w_up"], m["w_down"])
        return constrain(x, ACT), None

    policy = run.remat_policy()
    if policy is not None:
        body = jax.checkpoint(body, policy=policy)
    x, _ = lax.scan(body, x, dec_params)
    x = layers.rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = constrain(unembed(params, cfg, x),
                       ("act_batch", "act_seq", "act_vocab"))
    return logits, {}


def prefill(params: dict, cfg: ArchConfig, frames: jax.Array,
            tokens: jax.Array, max_len: int, run: RunConfig = RunConfig()):
    """Encode + teacher-forced decoder pass building both caches."""
    enc_out = encode(params, cfg, frames, run)
    x = constrain(params["embed"].astype(run.compute_dtype)[tokens], ACT)
    b, t = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    dec_params = cast_tree(params["dec_layers"], run.compute_dtype)

    def body(x, lp):
        dt = x.dtype
        xn = layers.rms_norm(x, lp["ln1"], cfg.rms_eps)
        a, self_kv = attention.attn_prefill(lp["self_attn"], xn, cfg,
                                            positions, cfg.layer_kinds()[0])
        x = x + a
        xc = layers.rms_norm(x, lp["ln_x"], cfg.rms_eps)
        ck = jnp.einsum("btd,dhk->bthk", enc_out,
                        lp["cross_attn"]["wk"].astype(dt))
        cv = jnp.einsum("btd,dhk->bthk", enc_out,
                        lp["cross_attn"]["wv"].astype(dt))
        cross_kv = KVCache(ck.astype(jnp.bfloat16), cv.astype(jnp.bfloat16))
        x = x + _cross_attend(lp["cross_attn"], xc, cfg, cross_kv, None)
        xn2 = layers.rms_norm(x, lp["ln2"], cfg.rms_eps)
        m = lp["mlp"]
        x = x + layers.swiglu(xn2, m["w_gate"], m["w_up"], m["w_down"])
        pad = ((0, 0), (0, max_len - t), (0, 0), (0, 0))
        self_kv = KVCache(jnp.pad(self_kv.k.astype(jnp.bfloat16), pad),
                          jnp.pad(self_kv.v.astype(jnp.bfloat16), pad))
        return constrain(x, ACT), {"self_kv": self_kv, "cross_kv": cross_kv}

    x, caches = lax.scan(body, x, dec_params)
    x = layers.rms_norm(x[:, -1:], params["final_norm"], cfg.rms_eps)
    return unembed(params, cfg, x)[:, 0], caches


def decode_step(params: dict, cfg: ArchConfig, caches: dict,
                tokens: jax.Array, index: jax.Array,
                run: RunConfig = RunConfig()):
    """One-token decoder step against frozen cross-attention caches."""
    x = constrain(params["embed"].astype(run.compute_dtype)[tokens], ACT)
    dec_params = cast_tree(params["dec_layers"], run.compute_dtype)

    def body(x, lp_cache):
        lp, cache = lp_cache
        xn = layers.rms_norm(x, lp["ln1"], cfg.rms_eps)
        a, self_kv = attention.attn_decode(lp["self_attn"], xn, cfg,
                                           cache["self_kv"], index,
                                           cfg.layer_kinds()[0])
        x = x + a
        xc = layers.rms_norm(x, lp["ln_x"], cfg.rms_eps)
        x = x + _cross_attend(lp["cross_attn"], xc, cfg, cache["cross_kv"],
                              None)
        xn2 = layers.rms_norm(x, lp["ln2"], cfg.rms_eps)
        m = lp["mlp"]
        x = x + layers.swiglu(xn2, m["w_gate"], m["w_up"], m["w_down"])
        return constrain(x, ACT), {"self_kv": self_kv,
                                   "cross_kv": cache["cross_kv"]}

    x, new_caches = lax.scan(body, x, (dec_params, caches))
    x = layers.rms_norm(x, params["final_norm"], cfg.rms_eps)
    return unembed(params, cfg, x), new_caches
