"""Blocked attention visits only the KV blocks a query block can see and
recomputes scores for the backward pass; its value and gradient equal
masked full attention's, for full and windowed layers at several blocks."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.layers import (blocked_attention, repeat_kv,
                                 visible_kv_blocks)


def _masked_full(q, k, v, causal, window):
    rep = q.shape[2] // k.shape[2]
    k, v = repeat_kv(k, rep), repeat_kv(v, rep)
    t, s = q.shape[1], k.shape[1]
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(q.shape[-1])
    pos_q, pos_k = jnp.arange(t)[:, None], jnp.arange(s)[None, :]
    mask = jnp.ones((t, s), bool)
    if causal:
        mask &= pos_k <= pos_q
    if window:
        mask &= pos_k > pos_q - window
    p = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v)


@pytest.mark.parametrize("kv_heads", [4, 1])
@pytest.mark.parametrize("t,block,causal,window", [
    (32, 8, True, 0), (32, 8, True, 8), (32, 8, True, 5), (30, 8, True, 11),
    (32, 32, True, 0), (24, 16, True, 4), (20, 8, False, 0)])
def test_value_and_grad_equal_masked_full_attention(t, block, causal, window,
                                                    kv_heads):
    """4 query heads over 4 KV heads, or grouped over one."""
    r = np.random.default_rng(t + block + window)
    q = jnp.asarray(r.standard_normal((2, t, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(r.standard_normal((2, t, kv_heads, 8)), jnp.float32)
            for _ in range(2))
    dy = jnp.asarray(r.standard_normal((2, t, 4, 8)), jnp.float32)

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(dy)

    got = run(lambda q, k, v: blocked_attention(
        q, k, v, causal=causal, window=window, block_q=block, block_kv=block))
    want = run(lambda q, k, v: _masked_full(q, k, v, causal, window))
    # float32: online softmax over blocks against one softmax; the sums
    # run in another order, so they agree to round-off
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t,block,window,pairs", [
    (1024, 512, 0, 3),          # granite and hymba: one pair in four skipped
    (4096, 512, 0, 36),         # a full layer at 4k: the causal half
    (4096, 512, 1024, 21),      # a sliding layer: at most 3 blocks a row
])
def test_pairs_visited(t, block, window, pairs):
    rows = [visible_kv_blocks(i, min(i + block, t) - 1, t, block,
                              causal=True, window=window)
            for i in range(0, t, block)]
    assert sum(len(r) for r in rows) == pairs
    assert all(len(r) <= (3 if window else t // block) for r in rows)
