"""Unified decoder-only language model covering all assigned families.

One parameterized module: dense GQA (internlm2/granite/qwen2/minitron),
MoE (deepseek/qwen3), SSM (falcon-mamba), hybrid attn+SSM (hymba) and the
early-fusion VLM backbone (chameleon — VQ image ids share the token vocab;
the VQ tokenizer itself is the stubbed frontend).  Layers are stacked and
scanned (``lax.scan``) so the HLO stays compact for 48–64 layer configs; the
per-layer body is optionally rematerialized.  Where the layers differ in
kind (window, rotary positions: ``ArchConfig.layer_kinds``) the scan runs
over whole periods of the pattern and the period is unrolled in its body;
parameter shapes and leaf paths are the same either way.

Everything is a pure function over an explicit ``TensorSpec`` param tree —
this *is* the "compile once, run on any volunteer mesh" property the capsule
layer (repro.core.capsule) relies on.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig, LayerKind
from repro.distributed.sharding import TensorSpec, constrain, stack_specs
from repro.models import attention, layers, ssm
from repro.models.attention import KVCache
from repro.moe.moe import ROUTING_COUNTS, moe_apply, moe_specs

ACT = ("act_batch", "act_seq", "act_embed")


def cast_tree(tree, dtype):
    """Cast float params to the compute dtype BEFORE the layer scan so FSDP
    all-gathers move bf16, not f32 (halves gather traffic and temp memory)."""
    def c(a):
        return a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a
    return jax.tree.map(c, tree)


def gather_weights(lp: dict, run: RunConfig) -> dict:
    """FSDP gather-then-compute (RunConfig.fsdp_gather_weights)."""
    if not run.fsdp_gather_weights:
        return lp
    return jax.tree.map(lambda a: constrain(a, (None,) * a.ndim), lp)


@dataclass(frozen=True)
class RunConfig:
    """Execution knobs (perf-iteration surface; see EXPERIMENTS.md §Perf)."""
    remat: str = "full"              # none | full | dots
    block_kv: int = 1024
    ssm_chunk: int = 256
    compute_dtype: Any = jnp.bfloat16
    logical_rules: Optional[dict] = None   # sharding-rule overrides
    # FSDP semantics: gather each layer's (sharded) weights to replicated
    # right before use — forbids GSPMD's split-K fallback (partial-sum
    # all-reduces of full activations; see EXPERIMENTS.md §Perf cell B)
    fsdp_gather_weights: bool = False

    def remat_policy(self):
        if self.remat == "none":
            return None
        if self.remat == "dots":
            return jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint_policies.nothing_saveable


# ---------------------------------------------------------------------------
# Param / cache specs
# ---------------------------------------------------------------------------
def block_specs(cfg: ArchConfig) -> dict:
    out: dict = {"ln1": TensorSpec((cfg.d_model,), ("embed",), init="ones")}
    if cfg.family == "ssm":
        out["ssm"] = ssm.ssm_specs(cfg)
        return out
    out["attn"] = attention.attn_specs(cfg)
    if cfg.family == "hybrid":
        out["ssm"] = ssm.ssm_specs(cfg)
        out["norm_attn"] = TensorSpec((cfg.d_model,), ("embed",), init="ones")
        out["norm_ssm"] = TensorSpec((cfg.d_model,), ("embed",), init="ones")
    out["ln2"] = TensorSpec((cfg.d_model,), ("embed",), init="ones")
    if cfg.is_moe:
        out["moe"] = moe_specs(cfg)
    else:
        out["mlp"] = layers.mlp_specs(cfg.d_model, cfg.d_ff)
    return out


def lm_specs(cfg: ArchConfig) -> dict:
    vp = cfg.padded_vocab()
    out = {
        "embed": TensorSpec((vp, cfg.d_model), ("vocab", "embed")),
        "layers": stack_specs(block_specs(cfg), cfg.n_layers),
        "final_norm": TensorSpec((cfg.d_model,), ("embed",), init="ones"),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = TensorSpec((cfg.d_model, vp), ("embed", "vocab"))
    return out


def cache_specs(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    out: dict = {}
    if cfg.family != "ssm":
        out["kv"] = attention.cache_specs(cfg, batch, max_len)
    if cfg.family in ("ssm", "hybrid"):
        out["ssm"] = ssm.ssm_cache_specs(cfg, batch)
    return out


# ---------------------------------------------------------------------------
# The layer scan
# ---------------------------------------------------------------------------
def layer_period(kinds: tuple) -> int:
    """Length of the shortest pattern that repeats to give ``kinds``."""
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p == 0 and all(kinds[i] == kinds[i % p] for i in range(n)):
            return p
    return n


def scan_layers(cfg: ArchConfig, body, x, xs, policy=None):
    """``lax.scan`` of ``body(x, layer_xs, kind) -> (x, layer_ys)`` over the
    stacked ``xs`` (leading dim ``n_layers``), giving each layer its kind.

    The scan runs over whole periods of the kinds' pattern, the period
    unrolled inside: stacked arrays are viewed as ``(periods, period, ...)``
    and the outputs put back as ``(n_layers, ...)``."""
    kinds = cfg.layer_kinds()
    n, per = len(kinds), layer_period(kinds)

    def period_body(x, pxs):
        ys = []
        for j in range(per):
            x, y = body(x, jax.tree.map(lambda a: a[j], pxs), kinds[j])
            ys.append(y)
        return x, jax.tree.map(lambda *a: jnp.stack(a), *ys)

    if policy is not None:
        period_body = jax.checkpoint(period_body, policy=policy)
    xs = jax.tree.map(lambda a: a.reshape((n // per, per) + a.shape[1:]), xs)
    x, ys = lax.scan(period_body, x, xs)
    return x, jax.tree.map(lambda a: a.reshape((n,) + a.shape[2:]), ys)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------
def _block_train(cfg: ArchConfig, run: RunConfig, p: dict, x: jax.Array,
                 positions: jax.Array, kind: LayerKind, causal: bool = True):
    metrics = {}
    xn = layers.rms_norm(x, p["ln1"], cfg.rms_eps)
    if cfg.family == "ssm":
        return x + ssm.ssm_train(p["ssm"], xn, cfg, run.ssm_chunk), metrics
    if cfg.family == "hybrid":
        a = attention.attn_train(p["attn"], xn, cfg, positions, kind,
                                 causal=causal, block=run.block_kv)
        s = ssm.ssm_train(p["ssm"], xn, cfg, run.ssm_chunk)
        x = x + 0.5 * (layers.rms_norm(a, p["norm_attn"], cfg.rms_eps)
                       + layers.rms_norm(s, p["norm_ssm"], cfg.rms_eps))
    else:
        x = x + attention.attn_train(p["attn"], xn, cfg, positions, kind,
                                     causal=causal, block=run.block_kv)
    xn2 = layers.rms_norm(x, p["ln2"], cfg.rms_eps)
    if cfg.is_moe:
        y, metrics = moe_apply(p["moe"], xn2, cfg)
        x = x + y
    else:
        m = p["mlp"]
        x = x + layers.swiglu(xn2, m["w_gate"], m["w_up"], m["w_down"])
    return x, metrics


def _block_decode(cfg: ArchConfig, run: RunConfig, p: dict, x: jax.Array,
                  cache: dict, index: jax.Array, kind: LayerKind):
    new_cache = {}
    xn = layers.rms_norm(x, p["ln1"], cfg.rms_eps)
    if cfg.family == "ssm":
        y, new_cache["ssm"] = ssm.ssm_decode(p["ssm"], xn, cfg, cache["ssm"])
        return x + y, new_cache
    if cfg.family == "hybrid":
        a, new_cache["kv"] = attention.attn_decode(p["attn"], xn, cfg,
                                                   cache["kv"], index, kind)
        s, new_cache["ssm"] = ssm.ssm_decode(p["ssm"], xn, cfg, cache["ssm"])
        x = x + 0.5 * (layers.rms_norm(a, p["norm_attn"], cfg.rms_eps)
                       + layers.rms_norm(s, p["norm_ssm"], cfg.rms_eps))
    else:
        a, new_cache["kv"] = attention.attn_decode(p["attn"], xn, cfg,
                                                   cache["kv"], index, kind)
        x = x + a
    xn2 = layers.rms_norm(x, p["ln2"], cfg.rms_eps)
    if cfg.is_moe:
        y, _ = moe_apply(p["moe"], xn2, cfg)
        x = x + y
    else:
        m = p["mlp"]
        x = x + layers.swiglu(xn2, m["w_gate"], m["w_up"], m["w_down"])
    return x, new_cache


# ---------------------------------------------------------------------------
# Model entry points
# ---------------------------------------------------------------------------
def embed_tokens(params: dict, cfg: ArchConfig, tokens: jax.Array,
                 dtype) -> jax.Array:
    return params["embed"].astype(dtype)[tokens]


def unembed(params: dict, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return jnp.einsum("btd,vd->btv", x, params["embed"].astype(x.dtype))
    return x @ params["lm_head"].astype(x.dtype)


def forward_train(params: dict, cfg: ArchConfig, tokens: jax.Array,
                  run: RunConfig = RunConfig(), *, causal: bool = True,
                  inputs_embeds: Optional[jax.Array] = None):
    """tokens: (B, T) -> (logits (B,T,Vp), metrics)."""
    x = inputs_embeds if inputs_embeds is not None else \
        embed_tokens(params, cfg, tokens, run.compute_dtype)
    x = constrain(x, ACT)
    b, t = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    layer_params = cast_tree(params["layers"], run.compute_dtype)

    def body(x, lp, kind):
        lp = gather_weights(lp, run)
        x, metrics = _block_train(cfg, run, lp, x, positions, kind, causal)
        return constrain(x, ACT), metrics

    x, ms = scan_layers(cfg, body, x, layer_params, run.remat_policy())
    x = layers.rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = constrain(unembed(params, cfg, x),
                       ("act_batch", "act_seq", "act_vocab"))
    # routing counts stay per layer; the rest is averaged over layers
    metrics = {k: v if k in ROUTING_COUNTS else jnp.mean(v)
               for k, v in ms.items()}
    return logits, metrics


def prefill(params: dict, cfg: ArchConfig, tokens: jax.Array, max_len: int,
            run: RunConfig = RunConfig()):
    """Build caches for ``tokens`` and return last-position logits.

    Returns (logits (B, Vp), caches).  Cache buffers are allocated at
    ``max_len`` so decode can continue in place.
    """
    x = constrain(embed_tokens(params, cfg, tokens, run.compute_dtype), ACT)
    b, t = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    layer_params = cast_tree(params["layers"], run.compute_dtype)

    def body(x, lp, kind):
        lp = gather_weights(lp, run)
        new_cache = {}
        xn = layers.rms_norm(x, lp["ln1"], cfg.rms_eps)
        if cfg.family == "ssm":
            y, new_cache["ssm"] = ssm.ssm_train(lp["ssm"], xn, cfg,
                                                run.ssm_chunk, True)
            x = x + y
        elif cfg.family == "hybrid":
            a, kv = attention.attn_prefill(lp["attn"], xn, cfg, positions,
                                           kind, block=run.block_kv)
            s, new_cache["ssm"] = ssm.ssm_train(lp["ssm"], xn, cfg,
                                                run.ssm_chunk, True)
            x = x + 0.5 * (layers.rms_norm(a, lp["norm_attn"], cfg.rms_eps)
                           + layers.rms_norm(s, lp["norm_ssm"], cfg.rms_eps))
            new_cache["kv"] = _pad_cache(kv, max_len)
        else:
            a, kv = attention.attn_prefill(lp["attn"], xn, cfg, positions,
                                           kind, block=run.block_kv)
            x = x + a
            new_cache["kv"] = _pad_cache(kv, max_len)
        if "ln2" in lp:
            xn2 = layers.rms_norm(x, lp["ln2"], cfg.rms_eps)
            if cfg.is_moe:
                y, _ = moe_apply(lp["moe"], xn2, cfg)
                x = x + y
            else:
                m = lp["mlp"]
                x = x + layers.swiglu(xn2, m["w_gate"], m["w_up"], m["w_down"])
        return constrain(x, ACT), new_cache

    x, caches = scan_layers(cfg, body, x, layer_params, run.remat_policy())
    x = layers.rms_norm(x[:, -1:], params["final_norm"], cfg.rms_eps)
    logits = unembed(params, cfg, x)[:, 0]
    return logits, caches


def _pad_cache(kv: KVCache, max_len: int) -> KVCache:
    t = kv.k.shape[1]
    pad = ((0, 0), (0, max_len - t), (0, 0), (0, 0))
    return KVCache(jnp.pad(kv.k.astype(jnp.bfloat16), pad),
                   jnp.pad(kv.v.astype(jnp.bfloat16), pad))


def decode_step(params: dict, cfg: ArchConfig, caches: dict,
                tokens: jax.Array, index: jax.Array,
                run: RunConfig = RunConfig()):
    """One-token decode.  tokens: (B, 1); index: scalar current length."""
    x = constrain(embed_tokens(params, cfg, tokens, run.compute_dtype), ACT)
    layer_params = cast_tree(params["layers"], run.compute_dtype)

    def body(x, lp_cache, kind):
        lp, cache = lp_cache
        x, new_cache = _block_decode(cfg, run, lp, x, cache, index, kind)
        return constrain(x, ACT), new_cache

    x, new_caches = scan_layers(cfg, body, x, (layer_params, caches))
    x = layers.rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = unembed(params, cfg, x)
    return logits, new_caches
