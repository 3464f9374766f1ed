"""Zero-stall snapshot pipeline: fused-kernel parity, probe semantics,
async-writer crash consistency, and writer-vs-GC-vs-pump interleaving.

The fused probe+gather kernel runs here in ``interpret`` mode (CPU) and is
checked bit-for-bit against the numpy oracle (``ref``); the async writer
paths use ``ref`` mode so every assertion is deterministic.
"""
from __future__ import annotations

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.chunkstore import ChunkStore
from repro.core.replica import ReplicaSet
from repro.core.snapshots import SnapshotManager
from repro.kernels.delta_encode.kernel import fused_delta_records
from repro.kernels.delta_encode.ops import (KERNEL_DTYPES, KERNEL_STATS,
                                            DeviceMirror, changed_blocks,
                                            probe_leaves, reset_kernel_stats)
from repro.kernels.delta_encode.ref import fused_records_ref


def _mutate(arr: np.ndarray, idx, rng) -> np.ndarray:
    out = arr.copy()
    if np.issubdtype(out.dtype, np.integer):
        out[idx] = out[idx] + 1
    else:
        out[idx] = (rng.standard_normal(len(idx)) + 2.0).astype(out.dtype)
    return out


def _assert_fused_parity(old_np: np.ndarray, new_np: np.ndarray) -> None:
    """interpret-mode fused kernel == numpy oracle, bitmap and tiles."""
    bm_ref, tiles_ref = fused_records_ref(old_np, new_np)
    bm_dev, tiles_dev, n = fused_delta_records(
        jnp.asarray(old_np), jnp.asarray(new_np), interpret=True)
    bm_dev = np.asarray(bm_dev)
    np.testing.assert_array_equal(bm_dev, bm_ref)
    k = int(bm_dev.sum())
    np.testing.assert_array_equal(np.asarray(tiles_dev)[:k], tiles_ref)
    assert int(n) == -(-old_np.nbytes // 4)    # i32 image length


# sizes chosen to land on tile boundaries and well off them: sub-tile,
# tail after 3 whole 8192-element tiles, and a large ragged tail
TAIL_SIZES = (1000, 8192 * 3 + 5, 70000)


@pytest.mark.parametrize("size", TAIL_SIZES)
def test_fused_parity_tail_tiles(size):
    rng = np.random.default_rng(size)
    old = rng.standard_normal(size).astype(np.float32)
    new = _mutate(old, rng.integers(0, size, 17), rng)
    _assert_fused_parity(old, new)


@pytest.mark.parametrize("dtype", KERNEL_DTYPES)
def test_fused_parity_every_kernel_dtype(dtype):
    rng = np.random.default_rng(3)
    size = 8192 + 777                     # one whole tile + ragged tail
    base = rng.integers(-1000, 1000, size)
    old = np.asarray(jnp.asarray(base).astype(dtype))
    new = old.copy()
    idx = rng.integers(0, size, 9)
    new[idx] = np.asarray(jnp.asarray(base[idx] + 7).astype(dtype))
    _assert_fused_parity(old, new)


def test_fused_parity_empty_bitmap():
    old = np.arange(20000, dtype=np.int32)
    bm, tiles, _ = fused_delta_records(jnp.asarray(old), jnp.asarray(old),
                                       interpret=True)
    assert int(np.asarray(bm).sum()) == 0
    _assert_fused_parity(old, old.copy())


def test_fused_parity_all_changed():
    old = np.arange(8192 * 2 + 123, dtype=np.int32)
    new = old + 1                           # every tile flips
    bm_ref, _ = fused_records_ref(old, new)
    assert bm_ref.all()
    _assert_fused_parity(old, new)


# ---------------------------------------------------------------- probe


def _tree(rng) -> dict:
    # several size classes so leaves land in different pow2 buckets
    return {
        "tiny": rng.standard_normal(500).astype(np.float32),
        "small": rng.standard_normal(9000).astype(np.float32),
        "mid_a": rng.standard_normal(33000).astype(np.float32),
        "mid_b": rng.standard_normal(33000).astype(np.float32),
        "big": rng.standard_normal(131072).astype(np.float32),
    }


def test_probe_seeds_then_diffs_like_changed_blocks():
    rng = np.random.default_rng(11)
    t0 = _tree(rng)
    mirror = DeviceMirror()
    first = probe_leaves(t0, mode="ref", mirror=mirror)
    assert all(v is None for v in first.values())   # everything re-bases

    t1 = {k: (_mutate(v, rng.integers(0, v.size, 5), rng)
              if k in ("small", "big") else v.copy())
          for k, v in t0.items()}
    second = probe_leaves(t1, mode="ref", mirror=mirror)
    for key, v in t1.items():
        tiles, bitmap, nbytes = second[key]
        assert nbytes == v.nbytes
        ref_tiles, ref_bm, _ = changed_blocks(t0[key], v, mode="ref",
                                              fused=False)
        np.testing.assert_array_equal(bitmap.astype(bool),
                                      ref_bm.astype(bool))
        np.testing.assert_array_equal(tiles, ref_tiles)
        if key not in ("small", "big"):
            assert not bitmap.any()


def test_probe_bucketed_equals_per_leaf():
    rng = np.random.default_rng(12)
    t0 = _tree(rng)
    t1 = {k: _mutate(v, rng.integers(0, v.size, 3), rng)
          for k, v in t0.items()}
    mb, ml = DeviceMirror(), DeviceMirror()
    probe_leaves(t0, mode="ref", mirror=mb, bucketed=True)
    probe_leaves(t0, mode="ref", mirror=ml, bucketed=False)
    rb = probe_leaves(t1, mode="ref", mirror=mb, bucketed=True)
    rl = probe_leaves(t1, mode="ref", mirror=ml, bucketed=False)
    for key in t1:
        np.testing.assert_array_equal(rb[key][0], rl[key][0])
        np.testing.assert_array_equal(rb[key][1], rl[key][1])
        assert rb[key][2] == rl[key][2]


def test_probe_launches_o_buckets_not_o_leaves():
    rng = np.random.default_rng(13)
    tree = {f"l{i:02d}": rng.standard_normal(9000).astype(np.float32)
            for i in range(24)}              # 24 leaves, ONE size bucket
    mirror = DeviceMirror()
    probe_leaves(tree, mode="ref", mirror=mirror)
    nxt = {k: _mutate(v, [0], rng) for k, v in tree.items()}
    reset_kernel_stats()
    probe_leaves(nxt, mode="ref", mirror=mirror)
    assert KERNEL_STATS["launches"] == 1
    reset_kernel_stats()


@pytest.mark.parametrize("mode,extra_dtype,want_ref", [
    ("interpret", None, 0),           # every leaf on the kernel
    ("interpret", np.float64, 2),     # f64 leaf: seed + diff on numpy
    ("ref", None, 2),                 # the oracle itself: seed + diff
])
def test_ref_passes_count_numpy_fallback(mode, extra_dtype, want_ref):
    rng = np.random.default_rng(21)
    tree = {"a": rng.standard_normal(9000).astype(np.float32)}
    if extra_dtype is not None:
        tree["b"] = rng.standard_normal(100).astype(extra_dtype)
    mirror = DeviceMirror()
    reset_kernel_stats()
    probe_leaves(tree, mode=mode, mirror=mirror)
    probe_leaves({k: v + 1 for k, v in tree.items()}, mode=mode,
                 mirror=mirror)
    assert KERNEL_STATS["ref_passes"] == want_ref
    assert KERNEL_STATS["launches"] == len(tree)   # one diff per bucket
    reset_kernel_stats()


def test_probe_identity_fast_path_skips_launch_for_immutable():
    rng = np.random.default_rng(14)
    frozen = {k: jnp.asarray(v) for k, v in _tree(rng).items()}
    mirror = DeviceMirror()
    probe_leaves(frozen, mode="ref", mirror=mirror)
    probe_leaves(frozen, mode="ref", mirror=mirror)   # build both buffers
    reset_kernel_stats()
    res = probe_leaves(frozen, mode="ref", mirror=mirror)  # same objects
    assert KERNEL_STATS["launches"] == 0
    assert all(not r[1].any() for r in res.values())
    reset_kernel_stats()


def test_probe_no_fast_path_for_writeable_numpy():
    """An in-place mutation of a writeable numpy leaf MUST be detected —
    object identity alone never short-circuits mutable arrays."""
    arr = np.zeros(9000, np.float32)
    mirror = DeviceMirror()
    probe_leaves({"a": arr}, mode="ref", mirror=mirror)
    arr[123] = 5.0                        # same object, new bytes
    tiles, bitmap, _ = probe_leaves({"a": arr}, mode="ref",
                                    mirror=mirror)["a"]
    assert bitmap.any() and tiles.size


def test_probe_layout_change_rebases_bucket():
    rng = np.random.default_rng(15)
    t0 = {"a": rng.standard_normal(9000).astype(np.float32),
          "b": rng.standard_normal(9000).astype(np.float32)}
    mirror = DeviceMirror()
    probe_leaves(t0, mode="ref", mirror=mirror)
    t1 = {"a": t0["a"].reshape(-1)[:4500].copy(), "b": t0["b"].copy()}
    res = probe_leaves(t1, mode="ref", mirror=mirror)
    assert res["a"] is None               # shape changed -> re-base
    # b shared a's bucket before the change; re-seeding is allowed, but
    # the round after must diff again
    t2 = {"a": t1["a"], "b": _mutate(t1["b"], [7], rng)}
    res2 = probe_leaves(t2, mode="ref", mirror=mirror)
    assert res2["b"] is not None and res2["b"][1].any()


# ------------------------------------------------------- async writer


def _state(rng, bump: int = 0) -> dict:
    w = rng.standard_normal(30000).astype(np.float32)
    return {"w": w + bump, "m": rng.standard_normal(9000).astype(np.float32)}


def test_async_manifests_byte_identical_to_inline():
    seq = []
    rng = np.random.default_rng(21)
    state = _state(rng)
    for i in range(5):
        idx = rng.integers(0, state["w"].size, 40)
        w = state["w"].copy()
        w[idx] += 1.0
        state = {"w": w, "m": state["m"]}
        seq.append(state)

    def run(async_mode):
        mgr = SnapshotManager(ChunkStore(), keep_last=10,
                              async_mode=async_mode, delta_mode="ref")
        for i, st in enumerate(seq):
            mgr.snapshot(st, step=i, block=True)
        refs = [mgr.manifests[sid].all_refs() for sid in mgr.order]
        restored, _ = mgr.restore()
        mgr.close()
        return refs, restored

    refs_sync, rest_sync = run(False)
    refs_async, rest_async = run(True)
    assert refs_sync == refs_async        # content-addressed => identical
    np.testing.assert_array_equal(rest_sync["['w']"], rest_async["['w']"])
    np.testing.assert_array_equal(rest_sync["['w']"], seq[-1]["w"])


def test_async_write_failure_is_invisible_and_rebases():
    _async_write_failure(dense=True)


def test_async_write_failure_is_invisible_and_rebases_sparse():
    _async_write_failure(dense=False)


def _bumped(state: dict, dense: bool) -> dict:
    """Every element of both leaves moved (dense), or one of each."""
    if dense:
        return {k: v + 1.0 for k, v in state.items()}
    out = {k: v.copy() for k, v in state.items()}
    for v in out.values():
        v[5] += 1.0
    return out


def _async_write_failure(dense: bool):
    rng = np.random.default_rng(22)
    store = ChunkStore()
    mgr = SnapshotManager(store, keep_last=5, async_mode=True,
                          delta_mode="ref")
    s0 = _state(rng)
    mgr.snapshot(s0, step=0, block=True)
    ok_sid = mgr.latest()

    real = store.put_delta
    calls = {"n": 0}

    def bomb(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise OSError("disk full")
        return real(*a, **kw)

    store.put_delta = bomb
    s1 = _bumped(s0, dense)                          # >= 2 delta chunks
    mgr.snapshot(s1, step=1, block=False)
    with pytest.raises(OSError):
        mgr.wait()
    store.put_delta = real
    # the half-written snapshot never registered
    assert mgr.latest() == ok_sid
    assert len(mgr.manifests) == 1
    # next snapshot re-bases (poisoned mirrors) and restores bit-exactly
    s2 = {"w": s1["w"] + 1.0, "m": s1["m"]}
    info = mgr.snapshot(s2, step=2, block=True)
    assert info.snapshot_id != ok_sid
    restored, _ = mgr.restore()
    np.testing.assert_array_equal(restored["['w']"], s2["w"])
    np.testing.assert_array_equal(restored["['m']"], s2["m"])
    s3 = _bumped(s2, dense)              # the chain diffs on from the base
    mgr.snapshot(s3, step=3, block=True)
    restored, _ = mgr.restore()
    np.testing.assert_array_equal(restored["['w']"], s3["w"])
    np.testing.assert_array_equal(restored["['m']"], s3["m"])
    assert (store.stats["dense_chunks"] > 0) == dense
    mgr.close()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_writer_gc_pump_interleaving_never_tears_snapshot(seed):
    """Async writer commits, auto-GC sweeps, and a replica pump drains the
    outbox concurrently; a scrubber resolves the LATEST committed manifest
    the whole time.  Every committed snapshot must stay fully resolvable
    (never torn), and the final restore must be bit-exact."""
    _interleave(seed, dense=False)


@pytest.mark.parametrize("seed", [0, 1])
def test_writer_gc_pump_interleaving_never_tears_snapshot_dense(seed):
    """As above, with rounds that move every element, so the writer
    stores each changed chunk raw."""
    _interleave(seed, dense=True)


def _interleave(seed: int, dense: bool):
    rng = np.random.default_rng(seed)
    rs = ReplicaSet(ChunkStore(), [ChunkStore()])
    mgr = SnapshotManager(rs, keep_last=3, async_mode=True,
                          writer_depth=2, delta_mode="ref")
    state = _state(rng)
    stop = threading.Event()
    errors: list[BaseException] = []

    def pump_loop():
        while not stop.is_set():
            try:
                rs.pump()
                time.sleep(0.0005)
            except BaseException as e:     # noqa: BLE001 - recorded
                errors.append(e)
                return

    def scrub_loop():
        while not stop.is_set():
            time.sleep(0.0002)
            sid = mgr.latest()
            if sid is None:
                continue
            man = mgr.manifests.get(sid)
            if man is None:
                continue
            try:
                for ent in man.tensors.values():
                    rs.resolve_buffer(ent.refs)
            except BaseException as e:     # noqa: BLE001 - torn snapshot
                errors.append(e)
                return

    threads = [threading.Thread(target=pump_loop),
               threading.Thread(target=scrub_loop)]
    for t in threads:
        t.start()
    try:
        for step in range(12):
            idx = rng.integers(0, state["w"].size, 60)
            w = state["w"] * np.float32(1.0001) if dense \
                else state["w"].copy()
            w[idx] += 1.0
            state = {"w": w, "m": state["m"]}
            mgr.snapshot(state, step=step, block=False)
        mgr.wait()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    assert not errors, errors
    restored, _ = mgr.restore()
    np.testing.assert_array_equal(restored["['w']"], state["w"])
    assert (rs.primary.stats["dense_chunks"] > 0) == dense
    rs.flush()
    mgr.close()
