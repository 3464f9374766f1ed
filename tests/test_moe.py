"""MoE dispatch correctness: the dropless grouped dispatch equals the dense
per-token mixture at any routing imbalance, and a device's share of the
experts computes exactly its experts' part of it."""
import dataclasses

import jax
import jax.nn as jnn
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig, get_arch, reduced
from repro.distributed.sharding import init_tree
from repro.moe.moe import moe_apply, moe_specs

# float32 on the CPU: the grouped products and the dense mixture sum the
# same terms in a different order, so they agree to round-off only
TOL = dict(rtol=1e-4, atol=1e-4)


def _dense_mixture_ref(p, x, cfg, experts=None):
    """Every expert on every token, weighted by its renormalised top-k
    gate; ``experts`` (default: all) names the experts ``p`` holds."""
    experts = range(cfg.moe.n_experts) if experts is None else experts
    logits = x @ p["router"]
    probs = jnn.softmax(logits, -1)
    gv, ei = jax.lax.top_k(probs, cfg.moe.top_k)
    gv = gv / gv.sum(-1, keepdims=True)
    ref = jnp.zeros_like(x)
    for j, e in enumerate(experts):
        h = jnn.silu(x @ p["w_gate"][j]) * (x @ p["w_up"][j])
        ye = h @ p["w_down"][j]
        w = ((ei == e) * gv).sum(-1)
        ref = ref + w[..., None] * ye
    if "shared" in p:
        sh = p["shared"]
        ref = ref + jnn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"]) \
            @ sh["w_down"]
    return ref


def _x(cfg, b, t, seed):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (b, t, cfg.d_model)), jnp.float32)


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "qwen3-moe-30b-a3b"])
@pytest.mark.parametrize("bt", [(1, 4), (2, 8), (3, 17)])
def test_dispatch_matches_dense_mixture(arch, bt):
    cfg = reduced(get_arch(arch))
    p = init_tree(moe_specs(cfg), jax.random.key(0))
    x = _x(cfg, *bt, seed=1)
    y, m = moe_apply(p, x, cfg)
    ref = _dense_mixture_ref(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), **TOL)
    assert int(m["moe_pairs_here"]) == bt[0] * bt[1] * cfg.moe.top_k
    assert int(m["moe_pairs_elsewhere"]) == 0


def test_capacity_drops_are_bounded_and_reported():
    """No capacity: at a skew that overflowed the old 0.5 capacity factor,
    every pair is computed and the output is the full mixture."""
    cfg = reduced(get_arch("deepseek-moe-16b"))
    p = init_tree(moe_specs(cfg), jax.random.key(0))
    # a router that favours expert 0 for every token (positive inputs)
    p["router"] = p["router"].at[:, 0].add(5.0)
    x = jnp.abs(_x(cfg, 2, 64, seed=2))
    y, m = moe_apply(p, x, cfg)
    pairs = 2 * 64 * cfg.moe.top_k
    assert int(m["moe_pairs_here"]) == pairs
    assert int(m["moe_max_expert_pairs"]) == 2 * 64      # all on expert 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        _dense_mixture_ref(p, x, cfg)), **TOL)


def test_every_token_on_one_expert_is_dropless():
    """top-1 routing with every token sent to one expert: that expert's
    group holds all N rows, and none is lost."""
    base = reduced(get_arch("qwen3-moe-30b-a3b"))
    cfg = dataclasses.replace(base, moe=dataclasses.replace(base.moe,
                                                            top_k=1))
    p = init_tree(moe_specs(cfg), jax.random.key(4))
    p["router"] = jnp.zeros_like(p["router"]).at[:, 2].set(1.0)
    x = jnp.abs(_x(cfg, 2, 16, seed=4))          # router logit of 2 > 0
    y, m = moe_apply(p, x, cfg)
    assert int(m["moe_pairs_here"]) == 32
    assert int(m["moe_max_expert_pairs"]) == 32
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        _dense_mixture_ref(p, x, cfg)), **TOL)


@pytest.mark.parametrize("top_k", [2, 3])
def test_disjoint_shares_add_up_to_the_whole_layer(top_k):
    """8 experts over 4 devices, 2 each: what each share computes for its
    own experts adds up to the uncut layer, and its counts split the
    pairs."""
    whole = dataclasses.replace(
        reduced(get_arch("qwen3-moe-30b-a3b")),
        moe=MoEConfig(n_experts=8, top_k=top_k, d_ff_expert=32))
    p = init_tree(moe_specs(whole), jax.random.key(5))
    x = _x(whole, 2, 12, seed=5)
    y_whole, _ = moe_apply(p, x, whole)
    share = dataclasses.replace(whole, moe=dataclasses.replace(
        whole.moe, experts_held=2))
    total, here = jnp.zeros_like(x), 0
    for dev in range(4):
        held = slice(2 * dev, 2 * dev + 2)
        ps = dict(p, **{k: p[k][held] for k in ("w_gate", "w_up", "w_down")})
        assert ps["w_gate"].shape == moe_specs(share)["w_gate"].shape
        y, m = moe_apply(ps, x, share, first_held=2 * dev)
        np.testing.assert_allclose(np.asarray(y), np.asarray(
            _dense_mixture_ref(ps, x, share, range(2 * dev, 2 * dev + 2))),
            **TOL)
        assert int(m["moe_pairs_here"]) + int(m["moe_pairs_elsewhere"]) \
            == 2 * 12 * top_k
        total, here = total + y, here + int(m["moe_pairs_here"])
    assert here == 2 * 12 * top_k
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_whole), **TOL)


def test_gates_are_differentiable():
    cfg = reduced(get_arch("qwen3-moe-30b-a3b"))
    p = init_tree(moe_specs(cfg), jax.random.key(3))
    x = _x(cfg, 1, 8, seed=3)

    def loss(p):
        y, m = moe_apply(p, x, cfg)
        return jnp.sum(y ** 2) + m["moe_aux"]

    g = jax.grad(loss)(p)
    gnorm = sum(float(jnp.abs(v).sum()) for v in jax.tree.leaves(g))
    assert np.isfinite(gnorm) and gnorm > 0
    # router receives gradient (through gates AND the aux loss)
    assert float(jnp.abs(g["router"]).sum()) > 0


def test_grads_match_dense_mixture():
    """Gradients through the grouped products, gathers and gates equal the
    dense mixture's, for a share holding half of the experts."""
    base = reduced(get_arch("deepseek-moe-16b"))
    cfg = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, n_shared_experts=0, experts_held=2))
    p = init_tree(moe_specs(cfg), jax.random.key(6))
    x = _x(cfg, 2, 9, seed=6)
    got = jax.grad(lambda p, x: jnp.sum(moe_apply(p, x, cfg, 1)[0] ** 2),
                   (0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(
        _dense_mixture_ref(p, x, cfg, (1, 2)) ** 2), (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)
