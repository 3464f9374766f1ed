"""The snapshot writer's one-pass path stores what the plain path stores.

``SnapshotManager`` takes each changed chunk's XOR from the probe's tiles
(``chunk_records``) and ``ChunkStore.put_delta`` stores a dense XOR's chunk
raw without encoding it.  Both are checked here against a plain reference:
per chunk, ``old ^ new``, skip it when zero, else ``put_delta(parent,
xor.tobytes(), full_bytes=new.tobytes())`` on a second store.  Refs,
stored objects and store counters must come out identical.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core.chunkstore import (ChunkStore, DeltaRecord, Digested,
                                   is_delta_ref, sha256)
from repro.core.snapshots import SnapshotManager
from repro.kernels.delta_encode.ops import TILE_BYTES, chunk_records
from repro.kernels.delta_encode.ref import fused_records_ref

COUNTERS = ("put_bytes", "put_chunks", "delta_chunks", "rebased",
            "dense_chunks", "dedup_bytes", "dedup_chunks")


def _key(name: str) -> str:
    return f"['{name}']"


def _states(seed: int) -> list[dict]:
    """Base, then rounds that mix unchanged, sparse, dense and tail chunks;
    the chunk under ``w[:64]`` changes sparsely round after round (past any
    small ``max_chain``), then turns dense."""
    rng = np.random.default_rng(seed)
    w = rng.standard_normal(50_001).astype(np.float32)     # ragged tail
    m = rng.standard_normal(40_000).astype(np.float32)
    ids = np.arange(3, dtype=np.int32)                      # sub-tile leaf
    out = [{"w": w, "m": m, "ids": ids}]
    for i in range(1, 8):
        w, m, ids = w.copy(), m.copy(), ids + 1
        w[i] = np.float32(i)                                  # sparse
        w[20_000 + 97 * i] += 1.0                             # sparse
        if i in (2, 5):
            w[30_000:45_000] += np.float32(0.5)               # dense
        if i in (3, 6):
            w[-300:] = rng.standard_normal(300)               # tail
        if i == 7:
            w[:16_384] += rng.standard_normal(16_384).astype(np.float32)
        if i % 2:
            m[9_000:9_020] = rng.standard_normal(20)          # sparse
        else:
            m[:] = m * np.float32(1.0001) + np.float32(1e-4)  # dense
        out.append({"w": w, "m": m, "ids": ids})
    return out


def _reference(states, chunk_bytes: int, max_chain: int):
    """Per chunk: ``old ^ new``; unchanged chunks keep the parent's ref and
    count as dedup, the way the manager accounts chain reuse."""
    store = ChunkStore(chunk_bytes=chunk_bytes, max_chain=max_chain)
    cb = chunk_bytes
    img, prev, mans = {}, {}, []
    for st in states:
        man, reused, reused_bytes = {}, 0, 0
        for name in sorted(st):
            new = np.ascontiguousarray(st[name]).reshape(-1).view(np.uint8)
            key = _key(name)
            if key not in img:
                refs = store.put_buffer(memoryview(new))
            else:
                refs = []
                for ci, pref in enumerate(prev[key]):
                    cs, ce = ci * cb, min((ci + 1) * cb, new.size)
                    xor = img[key][cs:ce] ^ new[cs:ce]
                    if not xor.any():
                        refs.append(pref)
                        reused += 1
                        reused_bytes += ce - cs
                    else:
                        refs.append(store.put_delta(
                            pref, xor.tobytes(),
                            full_bytes=new[cs:ce].tobytes()))
            img[key], prev[key], man[key] = new.copy(), refs, refs
        store.metrics.dedup_bytes.inc(reused_bytes)
        store.metrics.dedup_chunks.inc(reused)
        mans.append(man)
    return mans, store


@pytest.mark.parametrize("async_mode", [False, True], ids=["sync", "async"])
@pytest.mark.parametrize("chunk_bytes", [4096, TILE_BYTES, 2 * TILE_BYTES],
                         ids=["below_tile", "tile", "two_tiles"])
def test_writer_matches_per_chunk_reference(chunk_bytes, async_mode):
    states = _states(chunk_bytes)
    store = ChunkStore(chunk_bytes=chunk_bytes, max_chain=2)
    mgr = SnapshotManager(store, keep_last=100, async_mode=async_mode,
                          delta_mode="ref")
    for i, st in enumerate(states):
        mgr.snapshot(st, step=i, block=False)
    mgr.wait()
    want, ref_store = _reference(states, chunk_bytes, max_chain=2)
    got = [{k: e.refs for k, e in mgr.manifests[sid].tensors.items()}
           for sid in mgr.order]
    assert got == want
    for c in COUNTERS:
        assert store.stats[c] == ref_store.stats[c], c
    # every path ran: raw dense chunks, RLE deltas, chain-cap rebases
    for c in ("dense_chunks", "delta_chunks", "rebased"):
        assert store.stats[c] > 0, c
    assert set(store.all_refs()) == set(ref_store.all_refs())
    for ref in store.all_refs():
        assert store.object_size(ref) == ref_store.object_size(ref)
    for sid, st in zip(mgr.order, states):
        restored, _ = mgr.restore(sid)
        for name, arr in st.items():
            assert restored[_key(name)].tobytes() == arr.tobytes()
    mgr.close()


# ------------------------------------------------------------- chunk_records


def test_chunk_records_views_tiles_and_advances_prev_in_place():
    rng = np.random.default_rng(3)
    old = rng.standard_normal(5 * TILE_BYTES // 4 + 9).astype(np.float32)
    new = old.copy()
    new[:2 * TILE_BYTES // 4] += 1.0            # tiles 0-1: whole chunk 0
    new[3 * TILE_BYTES // 4 + 5] = 7.0          # tile 3 of chunk 1's 2-3
    new[-1] = -1.0                              # tail tile 5
    bitmap, tiles = fused_records_ref(old, new)
    prev = old.copy()
    records, flat = chunk_records(prev, tiles, bitmap, old.nbytes,
                                  2 * TILE_BYTES)
    assert np.shares_memory(flat, prev)
    assert flat.tobytes() == new.tobytes()
    assert sorted(records) == [0, 1, 2]
    assert np.shares_memory(records[0], tiles)  # every tile changed: a view
    assert not np.shares_memory(records[1], tiles)   # tile 2 did not
    old_b, new_b = old.view(np.uint8), new.view(np.uint8)
    for ci, xor in records.items():
        cs = ci * 2 * TILE_BYTES
        ce = min(cs + 2 * TILE_BYTES, old.nbytes)
        assert xor.tobytes() == (old_b[cs:ce] ^ new_b[cs:ce]).tobytes()


def test_chunk_records_skips_chunks_a_changed_tile_leaves_alone():
    old = np.zeros(TILE_BYTES // 4, np.float32)     # one tile, 8 chunks
    new = old.copy()
    new[5] = 1.0                                   # chunk 0 only
    bitmap, tiles = fused_records_ref(old, new)
    records, _ = chunk_records(old.copy(), tiles, bitmap, old.nbytes, 4096)
    assert sorted(records) == [0]


def test_chunk_records_refuses_an_image_it_cannot_advance():
    old = np.zeros(TILE_BYTES // 4, np.float32)
    new = old + 1.0
    bitmap, tiles = fused_records_ref(old, new)
    old.flags.writeable = False
    with pytest.raises(ValueError):
        chunk_records(old, tiles, bitmap, old.nbytes, 4096)


# ----------------------------------------------------------------- put_delta


def _chunk(rng, n=4096):
    return rng.integers(0, 256, n, dtype=np.uint8)


def _uncompressed_record_ref(parent: str, depth: int, xor: bytes) -> str:
    """The delta ref the encoder reaches for a payload RLE cannot shrink."""
    rec = DeltaRecord(parent, depth, len(xor), xor, False).pack()
    return "d:" + sha256(rec)


def test_put_delta_dense_with_full_bytes_is_stored_raw():
    rng = np.random.default_rng(0)
    store = ChunkStore(chunk_bytes=4096)
    old, new = _chunk(rng), _chunk(rng)
    parent = store.put(old.tobytes())
    ref = store.put_delta(parent, (old ^ new).tobytes(),
                          full_bytes=new.tobytes())
    assert ref == sha256(new.tobytes()) and not is_delta_ref(ref)
    assert store.stats["dense_chunks"] == 1
    assert store.stats["delta_chunks"] == 0 and store.stats["rebased"] == 0
    assert store.resolve(ref) == new.tobytes()


def test_put_delta_dense_without_full_bytes_keeps_the_delta_record():
    rng = np.random.default_rng(1)
    store = ChunkStore(chunk_bytes=4096)
    old, new = _chunk(rng), _chunk(rng)
    parent = store.put(old.tobytes())
    xor = (old ^ new).tobytes()
    ref = store.put_delta(parent, xor)
    assert ref == _uncompressed_record_ref(parent, 1, xor)
    assert store.stats["dense_chunks"] == 0
    assert store.stats["delta_chunks"] == 1
    assert store.resolve(ref) == new.tobytes()


def test_put_delta_exactly_half_nonzero_takes_the_encoder():
    rng = np.random.default_rng(2)
    store = ChunkStore(chunk_bytes=4096)
    old = _chunk(rng)
    xor = np.zeros(4096, np.uint8)
    xor[:2048] = rng.integers(1, 256, 2048, dtype=np.uint8)
    new = old ^ xor
    parent = store.put(old.tobytes())
    ref = store.put_delta(parent, xor.tobytes(), full_bytes=new.tobytes())
    assert is_delta_ref(ref)                       # zero-run RLE won
    assert store.stats["dense_chunks"] == 0
    assert store.stats["delta_chunks"] == 1
    assert store.resolve(ref) == new.tobytes()


def test_put_delta_dense_past_max_chain_counts_rebased():
    rng = np.random.default_rng(3)
    store = ChunkStore(chunk_bytes=4096, max_chain=1)
    a = _chunk(rng)
    b = a.copy()
    b[:8] ^= 1
    parent = store.put(a.tobytes())
    d1 = store.put_delta(parent, (a ^ b).tobytes(), full_bytes=b.tobytes())
    assert store.ref_depth(d1) == 1
    c = _chunk(rng)
    ref = store.put_delta(d1, (b ^ c).tobytes(), full_bytes=c.tobytes())
    assert ref == sha256(c.tobytes())
    assert store.stats["rebased"] == 1
    assert store.stats["dense_chunks"] == 0


@pytest.mark.parametrize("kind", ["dense", "sparse", "no_full"])
@pytest.mark.parametrize("wrap", [np.asarray, memoryview],
                         ids=["ndarray", "memoryview"])
def test_put_delta_views_and_bytes_give_the_same_ref(kind, wrap):
    rng = np.random.default_rng(4)
    old = _chunk(rng, 8192)
    new = _chunk(rng, 8192) if kind != "sparse" else old.copy()
    if kind == "sparse":
        new[100:140] ^= 0x5A
    xor = old ^ new
    image = np.zeros(3 * 8192, np.uint8)        # a view into a larger image
    image[8192:16384] = new
    as_bytes = (xor.tobytes(), new.tobytes())
    as_views = (wrap(xor), wrap(image[8192:16384]))
    got = []
    for x, full in (as_bytes, as_views):
        store = ChunkStore(chunk_bytes=8192)
        parent = store.put(old.tobytes())
        ref = store.put_delta(parent, x,
                              full_bytes=None if kind == "no_full" else full)
        assert store.resolve(ref) == new.tobytes()
        got.append((ref, dict(store.stats)))
    assert got[0] == got[1]


def test_put_keeps_a_digested_chunk_under_its_own_ref():
    rng = np.random.default_rng(5)
    image = _chunk(rng, 3 * 4096)
    chunk = Digested(memoryview(image[4096:8192]))
    want = image[4096:8192].tobytes()
    image[4096:8192] = 0                  # the digested copy is its own
    assert chunk.ref == sha256(want) and bytes(chunk) == want
    assert chunk[0] == want[0] and bytes(chunk[1:]) == want[1:]
    store, plain = ChunkStore(chunk_bytes=4096), ChunkStore(chunk_bytes=4096)
    assert store.put(chunk) == plain.put(want) == chunk.ref
    assert dict(store.stats) == dict(plain.stats)
    assert store.get(chunk.ref) == want
    with pytest.raises(ValueError):       # the stored copy stays read-only
        np.frombuffer(store.get(chunk.ref), np.uint8)[0] ^= 1
    peer = ChunkStore(chunk_bytes=4096)   # a peer re-hashes what it receives
    assert peer.recv(store.send([chunk.ref])) == 4096
    assert peer.get(chunk.ref) == want
