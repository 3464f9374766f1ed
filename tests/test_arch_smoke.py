"""Per-architecture smoke tests: every assigned arch (reduced, same family)
runs one forward + one train step on CPU; output shapes + finite values.
The FULL configs are exercised only by the dry-run (no allocation).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch, list_archs, reduced
from repro.distributed.sharding import init_tree
from repro.models import api, encdec, lm
from repro.models.lm import RunConfig
from repro.optim import adamw

RUN = RunConfig(remat="none", block_kv=16, ssm_chunk=8)
ALL_ARCHS = list_archs()


def _batch(cfg, b=2, t=16, seed=0):
    r = np.random.default_rng(seed)
    out = {"tokens": r.integers(0, cfg.vocab_size, (b, t)).astype(np.int32),
           "labels": r.integers(0, cfg.vocab_size, (b, t)).astype(np.int32)}
    if cfg.enc_dec:
        out["frames"] = r.standard_normal((b, t, cfg.d_model)).astype(
            np.float32)
    return out


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = reduced(get_arch(arch))
    params = init_tree(api.param_specs(cfg), jax.random.key(0))
    batch = _batch(cfg)
    if cfg.enc_dec:
        logits, _ = encdec.forward_train(params, cfg, batch["frames"],
                                         batch["tokens"], RUN)
    else:
        logits, _ = lm.forward_train(params, cfg, batch["tokens"], RUN)
    assert logits.shape == (2, 16, cfg.padded_vocab())
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step_no_nans(arch):
    cfg = reduced(get_arch(arch))
    specs = api.state_specs(cfg)
    state = api.TrainState(init_tree(specs.params, jax.random.key(0)),
                           init_tree(specs.opt, jax.random.key(1)))
    step = jax.jit(api.make_train_step(
        cfg, RUN, adamw.AdamWConfig(warmup_steps=1)))
    state, metrics = step(state, _batch(cfg))
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    for leaf in jax.tree.leaves(state.params):
        assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_loss_near_uniform_at_init(arch):
    cfg = reduced(get_arch(arch))
    params = init_tree(api.param_specs(cfg), jax.random.key(2))
    loss = api.make_eval_loss(cfg, RUN)(params, _batch(cfg, seed=3))
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 0.5


@pytest.mark.parametrize("arch", ["granite-3-2b", "qwen2-1.5b",
                                  "falcon-mamba-7b", "hymba-1.5b",
                                  "deepseek-moe-16b", "seamless-m4t-medium"])
def test_prefill_decode_matches_teacher_forced(arch):
    cfg = reduced(get_arch(arch))
    run = RunConfig(remat="none", block_kv=8, ssm_chunk=8,
                    compute_dtype=jnp.float32)
    params = init_tree(api.param_specs(cfg), jax.random.key(1))
    B, T, MAX = 2, 12, 20
    r = np.random.default_rng(1)
    toks = r.integers(0, cfg.vocab_size, (B, T + 1)).astype(np.int32)
    batch = {"tokens": toks[:, :T]}
    if cfg.enc_dec:
        frames = r.standard_normal((B, T, cfg.d_model)).astype(np.float32)
        batch["frames"] = frames
        full, _ = encdec.forward_train(params, cfg, frames, toks, run)
    else:
        full, _ = lm.forward_train(params, cfg, toks, run)
    last, caches = api.make_prefill_step(cfg, MAX, run)(params, batch)
    np.testing.assert_allclose(np.asarray(last), np.asarray(full[:, T - 1]),
                               rtol=5e-3, atol=5e-3)
    dl, _ = api.make_decode_step(cfg, run)(
        params, caches, {"tokens": toks[:, T:T + 1], "index": jnp.int32(T)})
    np.testing.assert_allclose(np.asarray(dl[:, 0]), np.asarray(full[:, T]),
                               rtol=5e-3, atol=5e-3)


def test_moe_routing_stats():
    cfg = reduced(get_arch("deepseek-moe-16b"))
    params = init_tree(api.param_specs(cfg), jax.random.key(0))
    tokens = _batch(cfg)["tokens"]
    logits, metrics = lm.forward_train(params, cfg, tokens, RUN)
    # dropless: every (token, slot) pair of every layer is computed here
    pairs = tokens.size * cfg.moe.top_k
    np.testing.assert_array_equal(metrics["moe_pairs_here"],
                                  [pairs] * cfg.n_layers)
    np.testing.assert_array_equal(metrics["moe_pairs_elsewhere"],
                                  [0] * cfg.n_layers)
    assert float(metrics["moe_aux"]) > 0.5     # ~1.0 when balanced
