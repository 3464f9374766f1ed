"""Jit'd wrapper + host-side compaction for differencing snapshots.

Three entry points:

* ``diff_blocks``/``patch_blocks`` — the original one-shot API: materialize
  the full delta, then compact on host (used by tests and small tensors).
* ``changed_blocks`` — the single-tensor snapshot/uplink hot path.  The
  default is the *fused* kernel: one ``pallas_call`` probes old vs new and
  DMA-compacts the changed tiles into the first k output slots, so a diff
  costs one launch and the only D2H traffic is the tiny bitmap plus the k
  changed tiles (paper §III-E: a differencing snapshot costs only the
  written-to blocks).  ``fused=False`` keeps the legacy two-launch
  probe-then-gather pipeline for comparison.
* ``tree_changed_blocks`` — the whole-pytree diff.  Leaves are grouped
  into size buckets (by power-of-two tile count) and each bucket's tile
  views are concatenated into ONE fused launch, so an optimizer tree with
  hundreds of small tensors diffs in O(size buckets) launches instead of
  O(leaves).
* ``probe_leaves`` — the SnapshotManager hot path: the same bucketed
  fused diff, but against the mirror slots ALONE — no host ``old`` images
  exist on the probing thread.  A missing or layout-mismatched slot seeds
  itself from the new tiles and reports its leaves for re-base, so the
  trainer-visible cost of a snapshot is exactly one probe plus the
  changed-tile transfer; chunking/hashing live on the writer thread.

A ``DeviceMirror`` keeps the previous state resident on device
(double-buffered: after each diff the *new* tiles become the mirror by
reference swap, not copy), eliminating the per-probe H→D re-upload of the
host mirror.  The numpy ``ref`` mode mirrors every kernel bit-for-bit
(used on hosts without a TPU runtime; the default when jax is on CPU).

``KERNEL_STATS`` is the read-only view of the ``delta_encode`` telemetry
scope: launches and streamed bytes (ref-mode passes count as one launch
each), so launches-per-snapshot can be checked to be O(buckets).
``ref_passes`` separately counts every leaf set the numpy oracle handled
(ref mode, or a kernel-mode leaf whose dtype the kernel cannot bitcast),
so a run that was meant to use the compiled kernel can assert it did.
Each copy of a probe's bitmap, changed tiles or ref-mode leaf image to
the host runs under a ``delta_encode.d2h`` span; the caller's own spans
say whose copy it is (a snapshot's plan, or the uplink's differ).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from repro.core import telemetry as tlm
from repro.kernels.delta_encode.kernel import (LANE, SUB, TILE, as_i32_tiles,
                                               changed_bitmap, delta_apply,
                                               delta_encode,
                                               fused_delta_tiles, gather_delta)
from repro.kernels.delta_encode.ref import (delta_apply_ref, delta_encode_ref,
                                            fused_records_ref, fused_tiles_ref)

TILE_BYTES = TILE * 4          # one (8, 1024) i32 tile = 32 KiB of state
_EMPTY_TILES = np.zeros((0, SUB, LANE), np.int32)

# dtypes the Pallas kernel can bitcast; everything else falls back to ref
KERNEL_DTYPES = ("int32", "float32", "bfloat16", "float16", "int16")

# leaves larger than this many tiles get their own launch; smaller ones are
# concatenated per power-of-two size bucket (256 tiles = 8 MiB of state)
MAX_BUCKET_TILES = 256

# launch/bandwidth accounting, on the hub that is the default at import; a
# ref-mode pass over a (concatenated) tile view counts as one launch, and
# also as one of ``ref_passes`` (numpy-oracle passes, seeding ones included)
_SCOPE = tlm.get_default().scope("delta_encode")
_METRICS = _SCOPE.counters("launches", "probe_bytes", "d2h_bytes",
                           "ref_passes")
KERNEL_STATS = _SCOPE.view()


def reset_kernel_stats() -> dict:
    """Zero the counters; -> their values before."""
    prev = dict(KERNEL_STATS)
    for c in vars(_METRICS).values():
        c.value = 0
    return prev


def _count_launch(tile_bytes: int, d2h: int) -> None:
    _METRICS.launches.inc()
    _METRICS.probe_bytes.inc(2 * tile_bytes)   # streams old + new
    _METRICS.d2h_bytes.inc(d2h)


def _to_host(x) -> np.ndarray:
    """A probe's output (or a leaf) copied to the host; a device array's
    copy first waits for the launch that writes it."""
    with tlm.span("delta_encode.d2h"):
        return np.asarray(x)


def _resolve_mode(mode: str) -> str:
    if mode != "auto":
        return mode
    import jax
    return "tpu" if jax.default_backend() == "tpu" else "ref"


class DeviceMirror:
    """Device-resident previous-state tiles, double-buffered per slot.

    A slot holds the (nblk, 8, 1024) i32 tile view of the last state seen
    for one leaf (or one size bucket's concatenation) plus a layout tag.
    ``swap`` stores the *new* tiles by reference — the diff's own input —
    so advancing the mirror after a snapshot costs zero copies and zero
    H→D transfers; the device-memory cost is one extra state image (the
    double buffer).  A slot may also pin the source leaf objects
    (``refs``): when the next round presents the *same immutable* arrays,
    the probe skips the launch outright — a frozen disk diffs for free."""

    def __init__(self):
        self._slots: Dict[Any, tuple] = {}  # key -> (layout, tiles, refs)

    def get(self, key, layout):
        ent = self._slots.get(key)
        if ent is None or ent[0] != layout:
            return None
        return ent[1]

    def refs(self, key, layout):
        ent = self._slots.get(key)
        if ent is None or ent[0] != layout:
            return None
        return ent[2]

    def swap(self, key, layout, tiles, refs=None) -> None:
        self._slots[key] = (layout, tiles, refs)

    def drop(self, key=None) -> None:
        if key is None:
            self._slots.clear()
        else:
            self._slots.pop(key, None)

    clear = drop

    def __len__(self) -> int:
        return len(self._slots)

    def nbytes(self) -> int:
        return sum(int(t.nbytes) for _, t, _ in self._slots.values())


def diff_blocks(old, new, *, mode: str = "interpret"):
    """-> (changed_tiles (k, 8, 1024) i32, bitmap (nblk,), orig_count)."""
    if mode == "ref":
        delta, changed = delta_encode_ref(old, new)
        n = np.asarray(old).size
    else:
        delta, changed, n = delta_encode(old, new,
                                         interpret=(mode == "interpret"))
        delta, changed = np.asarray(delta), np.asarray(changed)
    mask = changed.astype(bool)
    return delta[mask], changed, int(np.asarray(n))


def patch_blocks(old, changed_tiles, bitmap, *, mode: str = "interpret"):
    """Rebuild ``new`` from ``old`` + compacted changed tiles."""
    full = np.zeros((bitmap.size, 8, 1024), np.int32)
    full[bitmap.astype(bool)] = np.asarray(changed_tiles)
    if mode == "ref":
        return delta_apply_ref(old, full)
    out = delta_apply(old, full, interpret=(mode == "interpret"))
    return np.asarray(out)


def _check_dtypes(old, new, mode: str) -> str:
    """Validate the diff pair; returns the (possibly downgraded) mode.

    old/new dtype mismatch is always an error — silently bitcasting two
    different layouts would diff garbage.  A dtype the kernel cannot
    bitcast downgrades kernel modes to ``ref``."""
    old_dt = str(old.dtype if hasattr(old, "dtype")
                 else np.asarray(old).dtype)
    new_dt = str(new.dtype if hasattr(new, "dtype")
                 else np.asarray(new).dtype)
    if old_dt != new_dt:
        raise TypeError(f"changed_blocks: old dtype {old_dt} != new dtype "
                        f"{new_dt}; diff pairs must share a bit layout")
    if mode != "ref" and (old_dt not in KERNEL_DTYPES
                          or new_dt not in KERNEL_DTYPES):
        return "ref"
    return mode


def _fetch_compacted(bitmap: np.ndarray, tiles_dev, tile_bytes: int):
    """Host side of a fused launch: read the (tiny) bitmap, then transfer
    only the k compacted tiles (padded to the next power of two so the
    device slice sees O(log n) distinct shapes)."""
    k = int(bitmap.sum())
    if k == 0:
        _count_launch(tile_bytes, bitmap.nbytes)
        return np.zeros((0, SUB, LANE), np.int32)
    padded = min(1 << (k - 1).bit_length(), bitmap.size)
    tiles = _to_host(tiles_dev[:padded])[:k]
    _count_launch(tile_bytes, bitmap.nbytes + padded * TILE_BYTES)
    return tiles


def changed_blocks(old, new, *, mode: str = "auto", emit: str = "tiles",
                   chunk_bytes: int = 0, fused: bool = True,
                   mirror: Optional[DeviceMirror] = None,
                   mirror_key=None):
    """Fused single-launch diff of one tensor.

    -> (changed_tiles (k, 8, 1024) i32 numpy, bitmap (nblk,) i32 numpy,
        nbytes).  ``mode``: "auto" (tpu kernel on TPU, numpy ref
    otherwise), "tpu", "interpret" (Pallas interpreter), or "ref".
    On the kernel paths only the bitmap and the k changed tiles are
    transferred to host.  ``fused=False`` uses the legacy two-launch
    probe-then-gather pipeline.

    ``mirror``/``mirror_key``: a ``DeviceMirror`` keeping the previous
    state's tiles resident on device.  When the slot matches, the probe is
    pure D2D (no H→D upload of ``old``) and the slot is swapped to the new
    tiles afterwards.  ``old`` must still be the previous *host* image —
    it feeds record compaction and the ref fallback.

    ``emit="records"`` is the *upload* mode: instead of raw tiles it
    returns ``(records, new_flat, nbytes)`` where ``records`` maps
    store-chunk index -> XOR (a uint8 array) for exactly the chunks whose
    bytes changed — the per-chunk payloads ``ChunkStore.put_delta``
    expects — and ``new_flat`` is the updated uint8 host image, a copy of
    ``old`` advanced in place (the caller's next mirror).  Requires
    ``chunk_bytes``.  Both the snapshot differencing path and the
    volunteer uplink encoder ride this mode.
    """
    host_old = old
    mode = _check_dtypes(old, new, _resolve_mode(mode))
    nbytes = int(old.nbytes) if hasattr(old, "nbytes") \
        else int(np.asarray(old).nbytes)
    if mode == "ref":
        bitmap, tiles = fused_records_ref(old, new)
        _METRICS.ref_passes.inc()
        _count_launch(bitmap.size * TILE_BYTES, 0)
    elif fused:
        interpret = (mode == "interpret")
        import jax.numpy as jnp
        n32, _ = as_i32_tiles(jnp.asarray(new))
        layout = (n32.shape[0], nbytes)
        o32 = mirror.get(mirror_key, layout) if mirror is not None else None
        if o32 is None:
            import jax
            o32, _ = as_i32_tiles(jax.device_put(old))
        bm, tiles_dev = fused_delta_tiles(o32, n32, interpret=interpret)
        bitmap = _to_host(bm)
        tiles = _fetch_compacted(bitmap, tiles_dev, n32.nbytes)
        if mirror is not None:
            mirror.swap(mirror_key, layout, n32)   # swap, not copy
    else:
        import jax
        import jax.numpy as jnp
        interpret = (mode == "interpret")
        old = jax.device_put(old)         # upload the mirror ONCE; both
        bm, _ = changed_bitmap(old, new, interpret=interpret)  # passes reuse
        bitmap = _to_host(bm)           # tiny: one i32 per 32 KiB
        idx = np.flatnonzero(bitmap)
        k = idx.size
        tile_bytes = bitmap.size * TILE_BYTES
        _count_launch(tile_bytes, bitmap.nbytes)
        if k == 0:
            tiles = np.zeros((0, SUB, LANE), np.int32)
        else:
            # pad the gather index to the next power of two so gather_delta
            # sees O(log n) distinct shapes instead of recompiling per
            # changed-tile count
            padded = 1 << (k - 1).bit_length()
            idx = np.concatenate([idx,
                                  np.full(padded - k, idx[-1], idx.dtype)])
            tiles = _to_host(gather_delta(old, new,
                                          jnp.asarray(idx, jnp.int32)))[:k]
            _count_launch(tile_bytes, padded * TILE_BYTES)
    if emit == "tiles":
        return tiles, bitmap, nbytes
    if emit != "records":
        raise ValueError(f"unknown emit mode {emit!r}")
    if chunk_bytes <= 0:
        raise ValueError("emit='records' requires chunk_bytes")
    records, new_flat = chunk_records(np.array(host_old, order="C"), tiles,
                                      bitmap, nbytes, chunk_bytes)
    return records, new_flat, nbytes


def chunk_records(prev: np.ndarray, tiles: np.ndarray, bitmap: np.ndarray,
                  nbytes: int, chunk_bytes: int):
    """Advance a host image by the probe's XOR tiles, in place, and split
    the change into store-ready per-chunk XOR records.

    -> (records: {chunk index -> XOR uint8 array}, new_flat uint8 image).
    ``prev`` must be a writable, C-contiguous array the caller owns:
    ``new_flat`` is its own buffer, advanced by one contiguous XOR per run
    of consecutive changed tiles.  A chunk whose overlapping tiles all
    changed takes its XOR as a view of ``tiles`` (their compacted slots
    are consecutive); any other chunk's XOR is assembled, zero-filled.
    Tiles (32 KiB probe granules) need not align with store chunks; a
    chunk is recorded only when its bytes differ.  A chunk that holds a
    whole changed tile does (a changed tile's XOR is nonzero within the
    image); only the others are scanned, so a tile flip that straddles two
    chunks but dirties one emits one record.
    """
    if not (prev.flags.writeable and prev.flags.c_contiguous):
        raise ValueError("chunk_records advances prev in place: it must "
                         "be writable and C-contiguous")
    flat = prev.reshape(-1).view(np.uint8)
    if not bitmap.any():
        return {}, flat        # unchanged leaf: no records
    ti = np.flatnonzero(bitmap)
    tb = np.ascontiguousarray(tiles[:ti.size]).reshape(-1).view(np.uint8)
    # slot of each changed tile in ``tb``: rank[t] changed tiles precede t
    rank = np.concatenate(([0], np.cumsum(bitmap != 0)))
    brk = np.flatnonzero(np.diff(ti) != 1) + 1
    for r0, r1 in zip(np.concatenate(([0], brk)),
                      np.concatenate((brk, [ti.size]))):
        s = int(ti[r0]) * TILE_BYTES
        e = min(int(ti[r1 - 1]) * TILE_BYTES + TILE_BYTES, nbytes)
        if e > s:
            flat[s:e] ^= tb[int(r0) * TILE_BYTES:int(r0) * TILE_BYTES + e - s]
    # touched chunk set, vectorized: each changed tile covers byte range
    # [s, e) which spans chunks [s // cb, (e-1) // cb]
    s = ti * TILE_BYTES
    e = np.minimum(s + TILE_BYTES, nbytes)
    valid = e > s
    s, e = s[valid], e[valid]
    records: dict[int, np.ndarray] = {}
    if s.size == 0:
        return records, flat
    c0, c1 = s // chunk_bytes, (e - 1) // chunk_bytes
    width = int((c1 - c0).max()) + 1         # chunks per tile, usually <= 2
    cand = c0[:, None] + np.arange(width)[None, :]
    chunks = np.unique(cand[cand <= c1[:, None]])
    for ci in chunks.tolist():
        cs, ce = ci * chunk_bytes, min((ci + 1) * chunk_bytes, nbytes)
        t0, t1 = cs // TILE_BYTES, (ce - 1) // TILE_BYTES + 1
        if rank[t1] - rank[t0] == t1 - t0:
            off = int(rank[t0]) * TILE_BYTES + cs - t0 * TILE_BYTES
            xor = tb[off:off + ce - cs]
        else:
            xor = np.zeros(ce - cs, np.uint8)
            for t in ti[rank[t0]:rank[t1]].tolist():
                a, b = max(t * TILE_BYTES, cs), min((t + 1) * TILE_BYTES, ce)
                off = int(rank[t]) * TILE_BYTES - t * TILE_BYTES
                xor[a - cs:b - cs] = tb[off + a:off + b]
        # tiles [f0, f1) lie wholly inside the chunk's bytes
        f0 = -(-cs // TILE_BYTES)
        f1 = t1 if ce == nbytes else ce // TILE_BYTES
        if (f1 > f0 and rank[f1] > rank[f0]) or xor.any():
            records[ci] = xor
    return records, flat


def _leaf_ntiles(nbytes: int) -> int:
    n_i32 = -(-nbytes // 4)
    return max(1, -(-n_i32 // TILE))


def _leaf_meta(leaf) -> tuple:
    """(nbytes, exact tile count, dtype str) of one leaf."""
    arr = leaf if hasattr(leaf, "nbytes") else np.asarray(leaf)
    nbytes = int(arr.nbytes)
    n_i32 = -(-nbytes // 4)
    return nbytes, -(-n_i32 // TILE), str(arr.dtype)


def _frozen(x) -> bool:
    """True when ``x`` cannot have been mutated in place: jax arrays are
    immutable; numpy only counts with the writeable flag off."""
    flags = getattr(x, "flags", None)
    return flags is None or not flags.writeable


def probe_leaves(news: Dict[str, Any], *, mode: str = "auto",
                 mirror: DeviceMirror,
                 bucketed: bool = True,
                 max_bucket_tiles: int = MAX_BUCKET_TILES):
    """The snapshot hot path's whole device-side cost: diff a dict of
    leaves against the resident mirror tiles, no ``old`` images needed.

    -> {key: (changed_tiles, bitmap, nbytes) | None}.  ``None`` means the
    mirror had no matching slot — first snapshot, a shape/dtype change, or
    a size bucket whose membership changed — and the caller must store
    those leaves as full base images; their new tiles are installed as the
    slot in the same pass, so the next round probes them.  Matched slots
    are diffed in one fused launch per size bucket and swapped to the new
    tiles (zero copies, zero H→D), so a whole-tree probe costs O(size
    buckets) launches and the only host traffic is the bitmaps plus the
    changed tiles.

    In ``ref`` mode the mirror slots hold numpy tile images and the probe
    is the vectorized oracle — bit-for-bit the kernel's results, same slot
    lifecycle (CI runs the identical code path minus the launch)."""
    mode = _resolve_mode(mode)
    buckets: Dict[int, list] = {}
    for key, leaf in news.items():
        nbytes, ntiles, dt = _leaf_meta(leaf)
        if mode != "ref" and dt not in KERNEL_DTYPES:
            bid = -2          # kernel tree, ref-only dtype: leaf-wise ref
        elif not bucketed or ntiles > max_bucket_tiles:
            bid = -3                             # standalone launches
        else:
            bid = (ntiles - 1).bit_length()      # pow2 size class
        buckets.setdefault(bid, []).append((key, nbytes, ntiles, dt))
    out: Dict[str, Any] = {}
    for bid, leaves in sorted(buckets.items()):
        if bid == -2:
            for key, nbytes, ntiles, dt in leaves:
                out[key] = _probe_slot(key, news[key],
                                       (nbytes, ntiles, dt), "ref", mirror)
        elif bid == -3:
            for key, nbytes, ntiles, dt in leaves:
                out[key] = _probe_slot(key, news[key],
                                       (nbytes, ntiles, dt), mode, mirror)
        else:
            out.update(_probe_bucket(bid, leaves, news, mode, mirror))
    return out


def _probe_slot(key, leaf, meta: tuple, mode: str, mirror: DeviceMirror):
    """Probe one standalone leaf against its own mirror slot (or seed it)."""
    nbytes, ntiles, dt = meta
    layout = ("leaf", nbytes, ntiles, dt)
    prev = mirror.refs(key, layout)
    if prev is not None and prev[0] is leaf and _frozen(leaf):
        # same immutable array as last round: unchanged by construction
        return _EMPTY_TILES, np.zeros(ntiles, np.int32), nbytes
    if mode == "ref":
        _METRICS.ref_passes.inc()
        n32 = _ref_tiles(leaf)
        o32 = mirror.get(key, layout)
        mirror.swap(key, layout, n32, (leaf,))
        if o32 is None:
            return None
        if ntiles == 0:
            return _EMPTY_TILES, np.zeros(0, np.int32), nbytes
        bitmap, tiles = fused_tiles_ref(o32, n32)
        _count_launch(n32.nbytes, 0)
        return tiles, bitmap, nbytes
    import jax.numpy as jnp
    n32, _ = as_i32_tiles(jnp.asarray(leaf))
    o32 = mirror.get(key, layout)
    mirror.swap(key, layout, n32, (leaf,))
    if o32 is None:
        return None
    if ntiles == 0:
        return _EMPTY_TILES, np.zeros(0, np.int32), nbytes
    bm, tiles_dev = fused_delta_tiles(o32, n32,
                                      interpret=(mode == "interpret"))
    bitmap = _to_host(bm)
    tiles = _fetch_compacted(bitmap, tiles_dev, int(n32.nbytes))
    return tiles, bitmap, nbytes


def _probe_bucket(bid: int, leaves: list, news: dict, mode: str,
                  mirror: DeviceMirror):
    """One fused launch over a size bucket's concatenated leaves, against
    the bucket's mirror slot.  A layout mismatch (bucket membership or any
    leaf's shape/dtype changed) re-seeds the slot and reports every leaf
    as un-probed (None) — the re-base amplification is confined to one
    bucket and only on layout changes."""
    layout = tuple((key, nb, nt, dt) for key, nb, nt, dt in leaves)
    skey = ("bucket", bid)
    if all(nt == 0 for _, _, nt, _ in leaves):   # all-empty bucket
        seeded = mirror.get(skey, layout) is not None
        mirror.swap(skey, layout, _EMPTY_TILES)
        return {key: ((_EMPTY_TILES, np.zeros(0, np.int32), nb)
                      if seeded else None)
                for key, nb, _, _ in leaves}
    leaf_objs = [news[key] for key, _, _, _ in leaves]
    prev = mirror.refs(skey, layout)
    if prev is not None and len(prev) == len(leaf_objs) and all(
            n is p and _frozen(n) for n, p in zip(leaf_objs, prev)):
        # every leaf is the same immutable array the slot was built from
        # (a frozen disk): unchanged by construction, no launch at all
        return {key: (_EMPTY_TILES, np.zeros(nt, np.int32), nb)
                for key, nb, nt, _ in leaves}
    if mode == "ref":
        _METRICS.ref_passes.inc()
        parts = [_ref_tiles(x) for x in leaf_objs]
        n32 = parts[0] if len(parts) == 1 else np.concatenate(parts)
        o32 = mirror.get(skey, layout)
        mirror.swap(skey, layout, n32, tuple(leaf_objs))
        if o32 is None:
            return {key: None for key, _, _, _ in leaves}
        bitmap, tiles = fused_tiles_ref(o32, n32)
        _count_launch(n32.nbytes, 0)
    else:
        import jax.numpy as jnp
        parts = [as_i32_tiles(jnp.asarray(x))[0] for x in leaf_objs]
        n32 = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        o32 = mirror.get(skey, layout)
        mirror.swap(skey, layout, n32, tuple(leaf_objs))
        if o32 is None:
            return {key: None for key, _, _, _ in leaves}
        bm, tiles_dev = fused_delta_tiles(o32, n32,
                                          interpret=(mode == "interpret"))
        bitmap = _to_host(bm)
        tiles = _fetch_compacted(bitmap, tiles_dev, int(n32.nbytes))
    out = {}
    off = pos = 0
    for key, nbytes, ntiles, _dt in leaves:
        bm_leaf = bitmap[off:off + ntiles]
        k = int(bm_leaf.sum())
        out[key] = (tiles[pos:pos + k], bm_leaf, nbytes)
        off += ntiles
        pos += k
    return out


def tree_changed_blocks(old_tree, new_tree, *, mode: str = "auto",
                        mirror: Optional[DeviceMirror] = None,
                        bucketed: bool = True,
                        max_bucket_tiles: int = MAX_BUCKET_TILES):
    """Bucketed diff over two pytrees.

    -> {keypath: (changed_tiles, bitmap, nbytes)}, keyed by
    ``jax.tree_util.keystr`` paths (the same keys snapshot manifests use).

    Leaves are grouped into size buckets (power-of-two tile count, capped
    at ``max_bucket_tiles``); each bucket's per-leaf i32 tile views are
    concatenated into ONE fused launch, so the whole tree diffs in
    O(size buckets) launches instead of one probe + gather per leaf.
    Leaves above the cap launch standalone (no concat copy of big params).
    With a ``DeviceMirror``, each bucket's concatenation (and each
    standalone leaf) is diffed against its device-resident previous image
    and the slot is swapped to the new tiles — zero H→D re-upload.
    ``bucketed=False`` keeps the legacy one-launch-per-leaf pipeline."""
    import jax
    olds = {jax.tree_util.keystr(p): leaf for p, leaf in
            jax.tree_util.tree_flatten_with_path(old_tree)[0]}
    news = {jax.tree_util.keystr(p): leaf for p, leaf in
            jax.tree_util.tree_flatten_with_path(new_tree)[0]}
    if olds.keys() != news.keys():
        raise ValueError("old/new trees have different structures")
    return diff_leaves(olds, news, mode=mode, mirror=mirror,
                       bucketed=bucketed, max_bucket_tiles=max_bucket_tiles)


def diff_leaves(olds: Dict[str, Any], news: Dict[str, Any], *,
                mode: str = "auto",
                mirror: Optional[DeviceMirror] = None,
                bucketed: bool = True,
                max_bucket_tiles: int = MAX_BUCKET_TILES):
    """Dict-level core of ``tree_changed_blocks``: diff ``news[k]`` against
    ``olds[k]`` per key, with size-bucketed fused launches.  The snapshot
    manager calls this directly with its host mirror as ``olds`` so leaf
    keys stay exactly the manifest keys."""
    if olds.keys() != news.keys():
        raise ValueError("old/new leaf sets differ")
    mode = _resolve_mode(mode)
    if not bucketed:
        return {k: changed_blocks(olds[k], news[k], mode=mode,
                                  mirror=mirror, mirror_key=k)
                for k in olds}

    # partition leaves: ref-only dtypes go leaf-wise through ref; the rest
    # bucket by power-of-two tile count
    out: Dict[str, tuple] = {}
    buckets: Dict[int, list] = {}
    for key in olds:
        leaf_mode = _check_dtypes(olds[key], news[key], mode)
        nbytes = int(news[key].nbytes) if hasattr(news[key], "nbytes") \
            else int(np.asarray(news[key]).nbytes)
        ntiles = _leaf_ntiles(nbytes)
        if leaf_mode == "ref" and mode != "ref":
            bid = -2          # kernel tree, ref-only dtype: leaf-wise ref
        elif ntiles > max_bucket_tiles:
            bid = -3                             # standalone launches
        else:
            bid = (ntiles - 1).bit_length()      # pow2 size class
        buckets.setdefault(bid, []).append((key, nbytes, ntiles))
    for bid, leaves in sorted(buckets.items()):
        if bid == -2:
            for key, nbytes, _ in leaves:       # kernel tree, ref-only leaf
                out[key] = changed_blocks(olds[key], news[key], mode="ref")
            continue
        if bid == -3:
            for key, nbytes, _ in leaves:       # big leaf: own launch
                out[key] = changed_blocks(olds[key], news[key], mode=mode,
                                          mirror=mirror, mirror_key=key)
            continue
        out.update(_diff_bucket(bid, leaves, olds, news, mode, mirror))
    return out


def _diff_bucket(bid: int, leaves: list, olds: dict, news: dict,
                 mode: str, mirror: Optional[DeviceMirror]):
    """One fused launch (or one ref pass) over a size bucket's leaves."""
    layout = tuple((key, nb, nt) for key, nb, nt in leaves)
    if mode == "ref":
        o32 = np.concatenate([_ref_tiles(olds[k]) for k, _, _ in leaves])
        n32 = np.concatenate([_ref_tiles(news[k]) for k, _, _ in leaves])
        bitmap, tiles = fused_tiles_ref(o32, n32)
        _METRICS.ref_passes.inc()
        _count_launch(n32.nbytes, 0)
    else:
        import jax
        import jax.numpy as jnp
        interpret = (mode == "interpret")
        n32 = jnp.concatenate(
            [as_i32_tiles(jnp.asarray(news[k]))[0] for k, _, _ in leaves])
        o32 = mirror.get(("bucket", bid), layout) if mirror is not None \
            else None
        if o32 is None:
            o32 = jnp.concatenate(
                [as_i32_tiles(jax.device_put(olds[k]))[0]
                 for k, _, _ in leaves])
        bm, tiles_dev = fused_delta_tiles(o32, n32, interpret=interpret)
        bitmap = _to_host(bm)
        tiles = _fetch_compacted(bitmap, tiles_dev, int(n32.nbytes))
        if mirror is not None:
            mirror.swap(("bucket", bid), layout, n32)
    # split the concatenated bitmap + ascending-order compacted tiles back
    # into per-leaf results
    out = {}
    off = pos = 0
    for key, nbytes, ntiles in leaves:
        bm_leaf = bitmap[off:off + ntiles]
        k = int(bm_leaf.sum())
        out[key] = (tiles[pos:pos + k], bm_leaf, nbytes)
        off += ntiles
        pos += k
    return out


def _ref_tiles(x) -> np.ndarray:
    """Numpy mirror of ``as_i32_tiles``: flat i32 view padded to whole
    (8, 1024) tiles."""
    b = np.ascontiguousarray(_to_host(x)).reshape(-1).view(np.uint8)
    pad = (-b.size) % (TILE * 4)
    if pad:
        b = np.concatenate([b, np.zeros(pad, np.uint8)])
    return b.view(np.int32).reshape(-1, SUB, LANE)
