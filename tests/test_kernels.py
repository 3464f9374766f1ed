"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle across a
shape/dtype sweep (the assignment's kernel deliverable)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.delta_encode.ops import diff_blocks, patch_blocks
from repro.kernels.flash_attention.ops import attend
from repro.kernels.pcor.ops import correlate, pcor_strip
from repro.kernels.pcor.ref import pcor_ref
from repro.kernels.ssm_scan.ops import selective_scan

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
ATTN_CASES = [
    # (B, T, S, H, K, hd, causal)
    (2, 256, 256, 4, 2, 64, True),
    (1, 128, 384, 8, 8, 32, False),
    (2, 200, 200, 6, 3, 64, True),      # non-block-multiple T/S
    (1, 96, 96, 4, 1, 128, False),      # MQA
    (1, 64, 64, 2, 2, 256, True),
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_vs_ref(case):
    b, t, s, h, k, hd, causal = case
    q = RNG.standard_normal((b, t, h, hd)).astype(np.float32)
    kk = RNG.standard_normal((b, s, k, hd)).astype(np.float32)
    v = RNG.standard_normal((b, s, k, hd)).astype(np.float32)
    out = attend(q, kk, v, causal=causal, mode="interpret")
    ref = attend(q, kk, v, causal=causal, mode="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = jnp.asarray(RNG.standard_normal((1, 128, 4, 64)), dtype=dtype)
    k = jnp.asarray(RNG.standard_normal((1, 128, 2, 64)), dtype=dtype)
    v = jnp.asarray(RNG.standard_normal((1, 128, 2, 64)), dtype=dtype)
    out = attend(q, k, v, causal=True, mode="interpret")
    ref = attend(q, k, v, causal=True, mode="ref")
    assert out.dtype == ref.dtype == jnp.dtype(dtype)
    tol = 2e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_blocks_sweep():
    q = RNG.standard_normal((1, 256, 2, 64)).astype(np.float32)
    k = RNG.standard_normal((1, 256, 2, 64)).astype(np.float32)
    v = RNG.standard_normal((1, 256, 2, 64)).astype(np.float32)
    ref = attend(q, k, v, causal=True, mode="ref")
    for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]:
        out = attend(q, k, v, causal=True, block_q=bq, block_k=bk,
                     mode="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ssm scan
# ---------------------------------------------------------------------------
SSM_CASES = [(2, 64, 256, 16), (1, 50, 130, 8), (3, 32, 128, 16),
             (2, 128, 384, 4), (1, 33, 257, 16)]


@pytest.mark.parametrize("case", SSM_CASES)
def test_ssm_scan_vs_ref(case):
    b, t, di, n = case
    x = RNG.standard_normal((b, t, di)).astype(np.float32)
    dt = np.abs(RNG.standard_normal((b, t, di))).astype(np.float32) * 0.1
    bm = RNG.standard_normal((b, t, n)).astype(np.float32)
    cm = RNG.standard_normal((b, t, n)).astype(np.float32)
    a = -np.abs(RNG.standard_normal((di, n))).astype(np.float32)
    out = selective_scan(x, dt, bm, cm, a, mode="interpret")
    ref = selective_scan(x, dt, bm, cm, a, mode="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ssm_scan_block_sweep():
    b, t, di, n = 1, 64, 256, 16
    x = RNG.standard_normal((b, t, di)).astype(np.float32)
    dt = np.abs(RNG.standard_normal((b, t, di))).astype(np.float32) * 0.1
    bm = RNG.standard_normal((b, t, n)).astype(np.float32)
    cm = RNG.standard_normal((b, t, n)).astype(np.float32)
    a = -np.abs(RNG.standard_normal((di, n))).astype(np.float32)
    ref = selective_scan(x, dt, bm, cm, a, mode="ref")
    for bt, bd in [(16, 128), (32, 256), (64, 128)]:
        out = selective_scan(x, dt, bm, cm, a, block_t=bt, block_di=bd,
                             mode="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# delta encode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,shape", [
    (np.float32, (1000, 517)), (np.float32, (8192,)),
    (np.int32, (3, 8193)), (np.float32, (7,)),
])
def test_delta_roundtrip_bit_exact(dtype, shape):
    if dtype == np.float32:
        old = RNG.standard_normal(shape).astype(dtype)
    else:
        old = RNG.integers(-2 ** 30, 2 ** 30, shape).astype(dtype)
    new = old.copy()
    flat = new.reshape(-1)
    idx = RNG.choice(flat.size, size=max(1, flat.size // 50), replace=False)
    flat[idx] = flat[idx] * 2 + 1
    tiles, bitmap, _ = diff_blocks(old, new, mode="interpret")
    rec = patch_blocks(old, tiles, bitmap, mode="interpret")
    assert np.array_equal(rec.view(np.uint8), new.view(np.uint8))
    t2, b2, _ = diff_blocks(old, new, mode="ref")
    assert np.array_equal(bitmap, b2) and np.array_equal(tiles, t2)


def _changed_tiles(nblk: int, pattern: str) -> np.ndarray:
    if pattern == "none":
        return np.zeros(0, np.int64)
    if pattern == "all":
        return np.arange(nblk)
    return np.unique(np.r_[0, nblk - 1, np.arange(3, nblk, 7)])


# tile counts: one tile, 129 (not a multiple of 8 or 128), exactly 256,
# and 1025 (one past a full (8, 128) flag block; interpret mode slows
# down with size, so that one runs a single pattern)
BITMAP_CASES = [(n, p) for n in (1, 129, 256)
                for p in ("none", "some", "all")] + [(1025, "some")]


@pytest.mark.parametrize("nblk,pattern", BITMAP_CASES)
def test_delta_bitmap_layout_matches_ref(nblk, pattern):
    from repro.kernels.delta_encode.kernel import (TILE, changed_bitmap,
                                                   delta_encode,
                                                   fused_delta_records)
    from repro.kernels.delta_encode.ref import fused_records_ref
    rng = np.random.default_rng(nblk)
    old = rng.standard_normal(nblk * TILE - 5).astype(np.float32)
    new = old.copy()
    for t in _changed_tiles(nblk, pattern):
        j = min(t * TILE + int(rng.integers(TILE)), old.size - 1)
        new[j] = -new[j]
    want_bm, want_tiles = fused_records_ref(old, new)
    assert want_bm.size == nblk
    assert int(want_bm.sum()) == len(_changed_tiles(nblk, pattern))

    bm, tiles, n = fused_delta_records(old, new, interpret=True)
    k = int(want_bm.sum())
    np.testing.assert_array_equal(np.asarray(bm), want_bm)
    np.testing.assert_array_equal(np.asarray(tiles)[:k], want_tiles)
    assert int(n) == old.size

    probe, _ = changed_bitmap(old, new, interpret=True)
    np.testing.assert_array_equal(np.asarray(probe), want_bm)

    delta, flags, _ = delta_encode(old, new, interpret=True)
    np.testing.assert_array_equal(np.asarray(flags), want_bm)
    np.testing.assert_array_equal(np.asarray(delta)[want_bm.astype(bool)],
                                  want_tiles)


def test_delta_unchanged_is_empty():
    x = np.ones(30_000, np.float32)
    tiles, bitmap, _ = diff_blocks(x, x.copy(), mode="interpret")
    assert tiles.shape[0] == 0 and bitmap.sum() == 0


def test_delta_nan_inf_exact():
    old = np.array([np.nan, np.inf, -np.inf, 0.0, -0.0] * 2000, np.float32)
    new = old.copy()
    new[::7] = 1.5
    tiles, bitmap, _ = diff_blocks(old, new, mode="interpret")
    rec = patch_blocks(old, tiles, bitmap, mode="interpret")
    assert np.array_equal(rec.view(np.uint8), new.view(np.uint8))


# ---------------------------------------------------------------------------
# pcor
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("g,s", [(150, 321), (256, 128), (100, 50), (64, 7)])
def test_pcor_vs_numpy(g, s):
    x = RNG.standard_normal((g, s)).astype(np.float32)
    out = np.asarray(correlate(x, mode="interpret"))
    np.testing.assert_allclose(out, np.asarray(pcor_ref(x)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out, np.corrcoef(x), rtol=1e-4, atol=1e-4)
    assert np.allclose(np.diag(out), 1.0, atol=1e-5)


def test_pcor_strips_tile_the_matrix():
    x = RNG.standard_normal((200, 64)).astype(np.float32)
    full = np.asarray(correlate(x, mode="ref"))
    a = np.asarray(pcor_strip(x, 0, 100))
    b = np.asarray(pcor_strip(x, 100, 100))
    np.testing.assert_allclose(np.concatenate([a, b]), full,
                               rtol=1e-5, atol=1e-5)
