"""Delta-snapshot pipeline tests: device-resident differencing through
ChunkStore delta objects → v2 manifests → trainer restore → server sync.

Covers the acceptance criteria: bit-exact restore across ≥3-deep delta
chains (fp32 + bf16 with NaN payloads), v1-manifest backward compat,
chain-cap rebasing, ~0 new bytes for an unchanged state, and the <5%
changed blocks → <10% stored bytes bound.
"""
import json

import numpy as np
import pytest

from repro.core.chunkstore import ChunkStore, is_delta_ref
from repro.core.elastic import VolunteerTrainer
from repro.core.scheduler import SimClock, VolunteerScheduler
from repro.core.server import Project, VBoincServer
from repro.core.snapshots import Manifest, SnapshotManager, TensorEntry
from repro.data.pipeline import Cursor


def _bitcast(u32):
    return np.asarray(u32, np.uint32).view(np.float32)


def _nanful(rng, n, dtype):
    """Random payload with exotic bit patterns (NaN payloads, ±Inf, -0)."""
    x = rng.standard_normal(n).astype(np.float32)
    x[::97] = _bitcast(0x7FC00001)       # quiet NaN with payload
    x[1::131] = _bitcast(0xFF800000)     # -Inf
    x[2::151] = _bitcast(0x80000000)     # -0.0
    if dtype == "bfloat16":
        import jax.numpy as jnp
        return np.asarray(jnp.asarray(x, jnp.bfloat16))
    return x


def _bits(a):
    return np.asarray(a).reshape(-1).view(np.uint8)


# ---------------------------------------------------------------------------
# deep delta chains, fp32 + bf16, NaN payloads
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_deep_delta_chain_bit_exact(dtype):
    store = ChunkStore(chunk_bytes=1 << 12, max_chain=16)
    mgr = SnapshotManager(store, keep_last=10)
    rng = np.random.default_rng(0)
    x = _nanful(rng, 20_000, dtype)
    states = []
    for i in range(5):                    # base + 4 diffs -> chain depth >= 3
        x = x.copy()
        x[i * 11:i * 11 + 7] = _nanful(rng, 7, dtype)
        mgr.snapshot({"x": x, "step": np.int32(i)}, step=i)
        states.append(x.copy())
    # the chain really is delta objects, >= 3 deep
    last_refs = mgr.manifests[mgr.order[-1]].tensors["['x']"].refs
    depths = [store.ref_depth(r) for r in last_refs if is_delta_ref(r)]
    assert depths and max(depths) >= 3
    # every snapshot in the chain restores bit-exactly
    for sid, want in zip(mgr.order, states):
        got, _ = mgr.restore(sid, target_tree={"x": np.zeros_like(want),
                                               "step": np.int32(0)})
        assert np.array_equal(_bits(got["x"]), _bits(want))


def test_delta_snapshot_via_pallas_interpret():
    """The Pallas kernel path (interpret mode) is wired end-to-end."""
    store = ChunkStore(chunk_bytes=1 << 12)
    mgr = SnapshotManager(store, keep_last=5, delta_mode="interpret")
    x = np.arange(40_000, dtype=np.float32)
    mgr.snapshot({"x": x}, step=0)
    y = x.copy()
    y[123] = np.float32(np.nan)
    info = mgr.snapshot({"x": y}, step=1)
    assert 0 < info.new_bytes < x.nbytes // 10
    got, _ = mgr.restore(target_tree={"x": np.zeros_like(x)})
    assert np.array_equal(_bits(got["x"]), _bits(y))


# ---------------------------------------------------------------------------
# unchanged state stores ~0 new bytes; <5% blocks -> <10% of base bytes
# ---------------------------------------------------------------------------
def test_unchanged_state_stores_zero_bytes():
    mgr = SnapshotManager(ChunkStore(chunk_bytes=1 << 12))
    state = {"a": np.random.default_rng(1).standard_normal(30_000)
             .astype(np.float32), "b": np.int32(7)}
    mgr.snapshot(state, step=0)
    info = mgr.snapshot(state, step=1)
    assert info.kind == "diff"
    assert info.new_bytes == 0
    assert info.changed_chunks == 0 and info.reused_chunks > 0


def test_sparse_change_stores_under_10pct_of_base():
    store = ChunkStore(chunk_bytes=1 << 12)          # 256 blocks of 4 KiB
    mgr = SnapshotManager(store, keep_last=5)
    rng = np.random.default_rng(2)
    x = rng.standard_normal(262_144).astype(np.float32)   # 1 MiB
    base = mgr.snapshot({"x": x}, step=0)
    y = x.copy()
    y[0] += 1.0                      # touches 2 of 256 blocks (<5%)
    y[200_000] += 1.0
    diff = mgr.snapshot({"x": y}, step=1)
    assert diff.new_bytes < base.new_bytes * 0.10
    assert diff.changed_chunks <= 4
    got, _ = mgr.restore(target_tree={"x": np.zeros_like(x)})
    assert np.array_equal(_bits(got["x"]), _bits(y))


# ---------------------------------------------------------------------------
# chain cap -> rebase to a fresh base
# ---------------------------------------------------------------------------
def test_chain_cap_rebases_and_restores():
    store = ChunkStore(chunk_bytes=1 << 12, max_chain=2)
    mgr = SnapshotManager(store, keep_last=20)
    x = np.random.default_rng(3).standard_normal(40_000).astype(np.float32)
    for i in range(8):
        x = x.copy()
        x[5] = float(i)
        mgr.snapshot({"x": x}, step=i)
    assert store.stats["rebased"] > 0
    for ent in (mgr.manifests[s].tensors["['x']"] for s in mgr.order):
        assert all(store.ref_depth(r) <= 2 for r in ent.refs)
    got, _ = mgr.restore(target_tree={"x": np.zeros_like(x)})
    assert np.array_equal(_bits(got["x"]), _bits(x))


# ---------------------------------------------------------------------------
# v1 manifest backward compat
# ---------------------------------------------------------------------------
def test_v1_manifest_restore():
    store = ChunkStore(chunk_bytes=1 << 12)
    arr = np.arange(9_999, dtype=np.float32)
    hashes = store.put_buffer(memoryview(arr).cast("B"))
    v1 = json.dumps({                     # exactly what the v1 code wrote
        "snapshot_id": "snap-000001-deadbeef", "parent": None,
        "step": 3, "created": 0.0, "kind": "base",
        "aux": {"cursor": {"next_index": 4}},
        "tensors": {"['x']": {"shape": [9999], "dtype": "float32",
                              "hashes": hashes}},
    })
    man = Manifest.from_json(v1)
    assert man.version == 1
    assert man.tensors["['x']"].refs == hashes     # alias mapping
    mgr = SnapshotManager(store)
    mgr.manifests[man.snapshot_id] = man
    mgr.order.append(man.snapshot_id)
    got, aux = mgr.restore(target_tree={"x": np.zeros_like(arr)})
    assert np.array_equal(got["x"], arr)
    assert aux["cursor"]["next_index"] == 4


def test_v1_entry_hashes_alias_roundtrip():
    ent = TensorEntry((4,), "float32", ["abc"])
    assert ent.hashes == ent.refs == ["abc"]
    assert TensorEntry.from_json(ent.to_json()).refs == ["abc"]


# ---------------------------------------------------------------------------
# trainer-level restore through a delta chain + download accounting
# ---------------------------------------------------------------------------
def test_trainer_restore_latest_through_delta_chain():
    store = ChunkStore(chunk_bytes=1 << 12)
    mgr = SnapshotManager(store, keep_last=10)
    rng = np.random.default_rng(4)
    x = rng.standard_normal(30_000).astype(np.float32)
    early_refs: set = set()
    for i in range(4):
        x = x.copy()
        x[i] = np.float32(np.nan)
        mgr.snapshot({"params": x}, step=i,
                     aux={"cursor": Cursor(next_index=i + 1).to_state(),
                          "round": i})
        if i == 0:
            early_refs = set(mgr.manifests[mgr.order[-1]].all_refs())
    tr = VolunteerTrainer(grad_fn=None, apply_fn=None, state=None,
                          stream=None, micro_batches=1, snapshots=mgr)
    next_step = tr.restore_latest({"params": np.zeros_like(x)},
                                  client_hashes=early_refs)
    assert next_step == 4
    assert np.array_equal(_bits(tr.state["params"]), _bits(x))
    assert tr.cursor.next_index == 4
    # re-attach accounting: the volunteer holding the base downloads only
    # the delta objects written since it detached
    plan = tr.last_restore_plan
    assert plan is not None and plan["missing"] > 0
    assert 0 < plan["bytes_moved"] < x.nbytes // 10
    assert plan["bytes_dedup"] > 0


# ---------------------------------------------------------------------------
# resume scan: highest (step, created) wins even when v1 + v2 manifests
# coexist in one directory and filename order lies (PR 1 fix, now shared
# by launch/train.py --resume through SnapshotManager.load_existing)
# ---------------------------------------------------------------------------
def test_load_existing_picks_highest_step_across_v1_v2(tmp_path):
    store = ChunkStore(tmp_path / "store", chunk_bytes=1 << 12)
    root = tmp_path / "snaps"
    (root / "manifests").mkdir(parents=True)
    old = np.arange(2000, dtype=np.float32)
    new = old + 1.0
    v2_refs = store.put_buffer(memoryview(old).cast("B"))
    v1_refs = store.put_buffer(memoryview(new).cast("B"))
    # v2 manifest at step 2 whose snapshot id sorts LAST by filename
    v2 = json.dumps({
        "version": 2, "snapshot_id": "snap-000009-ffffffff", "parent": None,
        "step": 2, "created": 50.0, "kind": "base",
        "aux": {"cursor": {"next_index": 3}, "round": 2},
        "tensors": {"['x']": {"shape": [2000], "dtype": "float32",
                              "refs": v2_refs}}})
    # v1 manifest (pre-delta process) at step 5: older id, NEWER step
    v1 = json.dumps({
        "snapshot_id": "snap-000001-aaaaaaaa", "parent": None,
        "step": 5, "created": 99.0,
        "aux": {"cursor": {"next_index": 6}, "round": 5},
        "tensors": {"['x']": {"shape": [2000], "dtype": "float32",
                              "hashes": v1_refs}}})
    (root / "manifests" / "snap-000009-ffffffff.json").write_text(v2)
    (root / "manifests" / "snap-000001-aaaaaaaa.json").write_text(v1)

    mgr = SnapshotManager(store, root=root, keep_last=10)
    assert mgr.load_existing() == 2
    assert mgr.latest() == "snap-000001-aaaaaaaa"   # step order, not name
    assert mgr.load_existing() == 0                 # idempotent re-scan

    tr = VolunteerTrainer(grad_fn=None, apply_fn=None, state=None,
                          stream=None, micro_batches=1, snapshots=mgr)
    next_step = tr.restore_latest({"x": np.zeros_like(new)})
    assert next_step == 6
    assert np.array_equal(_bits(tr.state["x"]), _bits(new))
    assert tr.cursor.next_index == 6
    # a snapshot taken after adoption must not collide with adopted ids
    info = mgr.snapshot({"x": new + 1.0}, step=6)
    assert info.snapshot_id not in ("snap-000001-aaaaaaaa",
                                    "snap-000009-ffffffff")
    assert mgr.latest() == info.snapshot_id


# ---------------------------------------------------------------------------
# server-side block sync for a re-attaching volunteer
# ---------------------------------------------------------------------------
def test_server_reattach_moves_only_deltas():
    from repro.core.capsule import CapsuleSpec
    from repro.models.lm import RunConfig

    store = ChunkStore(chunk_bytes=1 << 12)
    # the store is SHARED with the server's capsule chunks, so the manager
    # must not sweep it on its own (the DiskSet rule)
    mgr = SnapshotManager(store, keep_last=10, auto_gc=False)
    x = np.random.default_rng(5).standard_normal(30_000).astype(np.float32)
    mgr.snapshot({"params": x}, step=0)

    server = VBoincServer(store)
    spec = CapsuleSpec("qwen2-1.5b", "train_4k", RunConfig())
    proj = Project("lm", spec, scheduler=VolunteerScheduler(clock=SimClock()))
    proj.snapshots = mgr
    server.publish(proj)
    key = server.register_user("vol")
    # account keys are restart-stable (sha256, not salted hash())
    assert key == server.register_user("vol")

    _, missing1, moved1 = server.fetch_capsule("lm", set(), key)
    assert moved1 > x.nbytes // 2          # first attach: ~everything moves
    client = set(missing1)
    y = x.copy()
    y[7] = 42.0
    mgr.snapshot({"params": y}, step=1)
    _, missing2, moved2 = server.fetch_capsule("lm", client, key)
    assert missing2 and all(r not in client for r in missing2)
    assert 0 < moved2 < moved1 // 10       # only the new delta objects move
    # the moved refs resolve to the new state
    client |= set(missing2)
    _, missing3, moved3 = server.fetch_capsule("lm", client, key)
    assert moved3 == 0 and not missing3


# ---------------------------------------------------------------------------
# failure hygiene: a failed store write must not poison later snapshots
# ---------------------------------------------------------------------------
def _changed(x, at: int, value: float, dense: bool):
    """``x`` with one element set (sparse), or with every element moved
    as well (dense: most XOR bytes of every chunk nonzero)."""
    y = x * np.float32(1.0001) + np.float32(1e-4) if dense else x.copy()
    y[at] = value
    return y


def test_failed_write_does_not_corrupt_next_snapshot():
    _failed_write_then_recover(dense=False)


def test_failed_write_does_not_corrupt_next_snapshot_dense():
    _failed_write_then_recover(dense=True)


def _failed_write_then_recover(dense: bool):
    store = ChunkStore(chunk_bytes=1 << 12)
    mgr = SnapshotManager(store)
    x = np.random.default_rng(6).standard_normal(20_000).astype(np.float32)
    mgr.snapshot({"x": x}, step=0)
    y = _changed(x, 3, 9.0, dense)
    real_put_delta = store.put_delta
    store.put_delta = lambda *a, **k: (_ for _ in ()).throw(IOError("disk"))
    with pytest.raises(IOError):
        mgr.snapshot({"x": y}, step=1)   # planning advanced the mirror...
    store.put_delta = real_put_delta
    z = _changed(y, 4, 10.0, dense)
    mgr.snapshot({"x": z}, step=2)       # ...but recovery re-bases cleanly
    got, _ = mgr.restore(target_tree={"x": np.zeros_like(x)})
    assert np.array_equal(_bits(got["x"]), _bits(z))
    w = _changed(z, 5, 11.0, dense)      # and the chain diffs on from it
    mgr.snapshot({"x": w}, step=3)
    got, _ = mgr.restore(target_tree={"x": np.zeros_like(x)})
    assert np.array_equal(_bits(got["x"]), _bits(w))
    assert (store.stats["dense_chunks"] > 0) == dense


def test_failed_planning_does_not_corrupt_next_snapshot(monkeypatch):
    """A plan-phase failure (e.g. device OOM mid-diff) advances some
    tensors' mirrors but not their refs; the next snapshot must re-base
    rather than record stale parent refs."""
    _failed_planning_then_recover(monkeypatch, dense=False)


def test_failed_planning_does_not_corrupt_next_snapshot_dense(monkeypatch):
    _failed_planning_then_recover(monkeypatch, dense=True)


def _failed_planning_then_recover(monkeypatch, dense: bool):
    import repro.core.snapshots as snapmod

    store = ChunkStore(chunk_bytes=1 << 12)
    mgr = SnapshotManager(store)
    rng = np.random.default_rng(8)
    a = rng.standard_normal(20_000).astype(np.float32)
    b = rng.standard_normal(20_000).astype(np.float32)
    mgr.snapshot({"a": a, "b": b}, step=0)

    real = snapmod.chunk_records
    calls = {"n": 0}

    def boom(*a_, **kw):
        calls["n"] += 1
        if calls["n"] == 2:              # tensor "a" planned, "b" explodes
            raise RuntimeError("device fell over")
        return real(*a_, **kw)

    monkeypatch.setattr(snapmod, "chunk_records", boom)
    a2, b2 = _changed(a, 0, 1.5, dense), _changed(b, 0, 2.5, dense)
    with pytest.raises(RuntimeError):
        mgr.snapshot({"a": a2, "b": b2}, step=1)
    monkeypatch.setattr(snapmod, "chunk_records", real)
    a3, b3 = _changed(a2, 1, 3.5, dense), _changed(b2, 1, 4.5, dense)
    mgr.snapshot({"a": a3, "b": b3}, step=2)
    got, _ = mgr.restore(target_tree={"a": np.zeros_like(a),
                                      "b": np.zeros_like(b)})
    assert np.array_equal(_bits(got["a"]), _bits(a3))
    assert np.array_equal(_bits(got["b"]), _bits(b3))


# ---------------------------------------------------------------------------
# RLE: dense payloads take the O(1) literal bail-out, and it round-trips
# ---------------------------------------------------------------------------
def test_rle_dense_payload_bails_to_literal():
    from repro.core.chunkstore import rle_zero_encode, rle_zero_decode

    rng = np.random.default_rng(7)
    # every 4th byte nonzero: the classic fp32 low-byte-churn XOR shape
    dense = np.zeros(1 << 16, np.uint8)
    dense[::4] = rng.integers(1, 256, dense[::4].size, dtype=np.uint8)
    enc = rle_zero_encode(dense.tobytes())
    assert len(enc) == dense.size + 5          # single literal token
    assert rle_zero_decode(enc, dense.size) == dense.tobytes()
    sparse = np.zeros(1 << 16, np.uint8)
    sparse[100:140] = 7
    enc = rle_zero_encode(sparse.tobytes())
    assert len(enc) < 100                      # RLE engaged
    assert rle_zero_decode(enc, sparse.size) == sparse.tobytes()


# ---------------------------------------------------------------------------
# scheduler pending-index semantics survive the O(1) refactor
# ---------------------------------------------------------------------------
def test_scheduler_resubmit_completed_unit_not_duplicated():
    clock = SimClock()
    s = VolunteerScheduler(clock=clock)
    s.join("w")
    s.submit(0, {})
    s.request_work("w")
    s.report("w", 0, "H")
    s.submit(0, {})                  # re-issue the same unit id
    assert len(s.pending()) == 1
    assert s.request_work("w").unit_id == 0
    s.report("w", 0, "H")
    assert s.done()



def test_scheduler_dispatch_skips_completed_backlog():
    clock = SimClock()
    s = VolunteerScheduler(clock=clock)
    s.join("w")
    for uid in range(500):
        s.submit(uid, {})
        unit = s.request_work("w")
        assert unit is not None and unit.unit_id == uid
        s.report("w", uid, "H")
        assert s.done()
    # the pending index is empty — a new unit dispatches immediately
    s.submit(500, {})
    assert len(s.pending()) == 1
    assert s.request_work("w").unit_id == 500
