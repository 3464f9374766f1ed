"""Public model API: specs + step functions per (arch, shape).

This is the layer the capsule ("VM image") serializes: everything needed to
instantiate an arch on an arbitrary mesh is derivable from ``ArchConfig`` +
``ShapeConfig`` through these functions — no topology leaks into model code.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig
from repro.distributed.sharding import TensorSpec
from repro.models import encdec, lm
from repro.models.layers import softmax_cross_entropy
from repro.models.lm import RunConfig
from repro.moe.moe import ROUTING_COUNTS
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig, AdamWState


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------
def param_specs(cfg: ArchConfig):
    return encdec.encdec_specs(cfg) if cfg.enc_dec else lm.lm_specs(cfg)


def state_specs(cfg: ArchConfig) -> TrainState:
    ps = param_specs(cfg)
    return TrainState(params=ps, opt=adamw.state_specs(ps))


def cache_specs(cfg: ArchConfig, batch: int, max_len: int):
    if cfg.enc_dec:
        return encdec.encdec_cache_specs(cfg, batch, max_len)
    return lm.cache_specs(cfg, batch, max_len)


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct-compatible TensorSpec stand-ins for every input.

    Modality frontends are stubs per the assignment: audio provides
    precomputed frame embeddings; chameleon's VQ ids live in the shared
    vocab so its inputs are ordinary token ids.
    """
    b, t = shape.global_batch, shape.seq_len
    tok = lambda *s: TensorSpec(tuple(s), ("batch",) + (None,) * (len(s) - 1),  # noqa: E731
                                np.int32)
    if shape.kind == "train":
        out = {"tokens": tok(b, t), "labels": tok(b, t)}
        if cfg.enc_dec:
            out["frames"] = TensorSpec((b, t, cfg.d_model),
                                       ("batch", None, "embed"), np.float32)
        return out
    if shape.kind == "prefill":
        out = {"tokens": tok(b, t)}
        if cfg.enc_dec:
            out["frames"] = TensorSpec((b, t, cfg.d_model),
                                       ("batch", None, "embed"), np.float32)
        return out
    # decode: one new token against a cache of length t
    out = {"tokens": tok(b, 1),
           "index": TensorSpec((), (), np.int32)}
    return out


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------
def make_train_step(cfg: ArchConfig, run: RunConfig = RunConfig(),
                    opt_cfg: AdamWConfig = AdamWConfig()):
    vocab = cfg.vocab_size

    def loss_fn(params, batch):
        if cfg.enc_dec:
            logits, metrics = encdec.forward_train(
                params, cfg, batch["frames"], batch["tokens"], run)
        else:
            logits, metrics = lm.forward_train(
                params, cfg, batch["tokens"], run)
        loss = softmax_cross_entropy(logits, batch["labels"], vocab)
        if "moe_aux" in metrics:
            loss = loss + cfg.moe.router_aux_coef * metrics["moe_aux"] \
                + 1e-3 * metrics["moe_zloss"]
        return loss, metrics

    def train_step(state: TrainState, batch: dict):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, batch)
        new_params, new_opt, opt_metrics = adamw.update(
            opt_cfg, grads, state.opt, state.params)
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return TrainState(new_params, new_opt), metrics

    return train_step


def make_prefill_step(cfg: ArchConfig, max_len: int,
                      run: RunConfig = RunConfig()):
    def prefill_step(params, batch: dict):
        if cfg.enc_dec:
            return encdec.prefill(params, cfg, batch["frames"],
                                  batch["tokens"], max_len, run)
        return lm.prefill(params, cfg, batch["tokens"], max_len, run)
    return prefill_step


def make_decode_step(cfg: ArchConfig, run: RunConfig = RunConfig()):
    def decode_step(params, caches, batch: dict):
        fn = encdec.decode_step if cfg.enc_dec else lm.decode_step
        logits, new_caches = fn(params, cfg, caches, batch["tokens"],
                                batch["index"], run)
        return logits, new_caches
    return decode_step


def make_eval_loss(cfg: ArchConfig, run: RunConfig = RunConfig()):
    """The volunteer round's loss: mean next-token cross entropy.

    The returned function's ``with_routing`` gives ``(loss, routing)``:
    the per-layer counts ``moe.moe_apply`` reports, or nothing for a model
    with no experts."""
    def with_routing(params, batch: dict):
        if cfg.enc_dec:
            logits, metrics = encdec.forward_train(
                params, cfg, batch["frames"], batch["tokens"], run)
        else:
            logits, metrics = lm.forward_train(params, cfg, batch["tokens"],
                                               run)
        routing = {k: v for k, v in metrics.items() if k in ROUTING_COUNTS}
        return softmax_cross_entropy(logits, batch["labels"],
                                     cfg.vocab_size), routing

    def eval_loss(params, batch: dict):
        return with_routing(params, batch)[0]
    eval_loss.with_routing = with_routing
    return eval_loss


def make_grad_step(cfg: ArchConfig, run: RunConfig = RunConfig()):
    """Jitted ``(params, batch) -> ((loss, routing), grads)`` of
    ``make_eval_loss``'s loss, with its routing counts as aux.  A loss
    wrapped around that one (no ``with_routing``) reports no routing."""
    loss_fn = make_eval_loss(cfg, run)
    with_routing = getattr(loss_fn, "with_routing",
                           lambda params, batch: (loss_fn(params, batch), {}))
    return jax.jit(jax.value_and_grad(with_routing, has_aux=True))
