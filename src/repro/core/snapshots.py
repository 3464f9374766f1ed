"""System-level checkpointing with device-resident differencing snapshots.

The SnapshotManager checkpoints the ENTIRE program state transparently —
params, optimizer moments, data cursor, RNG, step — so "project developers
omit application-level checkpointing from their code" (paper §III-E).
Mechanics mirror VirtualBox snapshots, but the diff is computed *before*
anything crosses the device→host boundary:

* ``snapshot()`` — the first snapshot is a full base image.  Every later
  one is a *differencing image*: the fused Pallas probe+gather kernel
  (kernels/delta_encode) XORs the new state against a **device-resident
  mirror** of the previous snapshot (double-buffered: after each diff the
  new tiles become the mirror by reference swap, so no H→D re-upload),
  size-bucketed so the whole pytree diffs in a few concatenated launches.
  Only the changed tiles cross to host.  Unchanged store chunks re-use the
  parent manifest's refs with **no hashing at all**, and changed chunks
  are written as delta objects (``parent_ref + RLE XOR``) — snapshot cost
  is O(changed blocks), not O(state bytes).
* **Async writer** (``async_mode=True``) — the calling thread runs ONLY
  the device probe + changed-tile transfer (``probe_leaves``); chunk
  compaction, hashing, RLE, ``put_delta`` and deferred ``max_chain``
  rebase run on a background ``SnapshotWriter`` behind a bounded queue
  (dense chunks are copied and hashed on a small pool it feeds),
  so the trainer's stall is the probe and nothing else (the caller's
  ``snapshot`` span vs the writer's ``writer.write``).  Plans are
  self-contained (they carry the changed tiles + bitmap, or the full
  base image); the writer keeps its OWN host image per tensor and
  advances it serially, so writer and planner share no mutable state.
  A half-written snapshot stays invisible: the manifest registers only
  after every object landed, and a write failure poisons the queue — the
  next snapshot re-bases from a fresh base image, exactly the
  ``_mirror.clear()`` invariant of the inline path.
* **Manifest v2** — each ``TensorEntry`` records per-block refs that are
  either raw hashes or ``"d:"`` delta refs.  v1 manifests (``hashes``)
  remain readable, so old snapshot directories restore unchanged.
* ``restore(sid)`` — resolve each ref through its base chain
  (``ChunkStore.resolve``) and rebuild the pytree; chains are bounded by
  the store's ``max_chain`` (deep chains rebase automatically).
* ``delete/gc`` — mark the *closure* of live refs from retained
  snapshots (a delta keeps its parents alive), sweep the rest.  The mark
  and the sweep hold the store's ``gc_lock`` so a concurrent background
  write can never have a just-written, not-yet-committed object swept.

Restore across meshes: manifests record logical tensors (path, shape,
dtype); ``restore`` re-shards onto whatever mesh the caller's shardings
dictate — this is what lets a capsule resume on a *different* volunteer
pod (elastic rescale).
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.core import telemetry as tlm
from repro.core.chunkstore import ChunkStore, Digested, dense_xor, sha256
from repro.core.writer import SnapshotWriter
from repro.kernels.delta_encode.ops import (DeviceMirror, chunk_records,
                                            probe_leaves)

MANIFEST_VERSION = 2

# threads that copy and hash a write's dense chunks while the store takes
# them in order; a quarter of the host's cores, the rest left to training
HASH_WORKERS = max(1, (os.cpu_count() or 1) // 4)


def _flatten(tree) -> list[tuple[str, Any]]:
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in leaves]


@dataclass
class TensorEntry:
    shape: tuple
    dtype: str
    refs: List[str]           # per-block: raw sha256 hex | "d:" delta ref

    # v1 manifests named this field "hashes"; keep the alias for callers
    @property
    def hashes(self) -> List[str]:
        return self.refs

    def to_json(self):
        return {"shape": list(self.shape), "dtype": self.dtype,
                "refs": self.refs}

    @classmethod
    def from_json(cls, d):
        return cls(tuple(d["shape"]), d["dtype"],
                   list(d.get("refs", d.get("hashes", []))))


@dataclass
class Manifest:
    snapshot_id: str
    parent: Optional[str]
    step: int
    created: float
    tensors: Dict[str, TensorEntry]
    aux: dict = field(default_factory=dict)      # cursor, rng seed, metrics
    kind: str = "diff"                            # base | diff
    version: int = MANIFEST_VERSION

    def all_refs(self) -> List[str]:
        return [r for ent in self.tensors.values() for r in ent.refs]

    def to_json(self) -> str:
        return json.dumps({
            "version": self.version,
            "snapshot_id": self.snapshot_id, "parent": self.parent,
            "step": self.step, "created": self.created, "kind": self.kind,
            "aux": self.aux,
            "tensors": {k: t.to_json() for k, t in self.tensors.items()},
        })

    @classmethod
    def from_json(cls, s: str) -> "Manifest":
        d = json.loads(s)
        return cls(d["snapshot_id"], d["parent"], d["step"], d["created"],
                   {k: TensorEntry.from_json(t)
                    for k, t in d["tensors"].items()},
                   d.get("aux", {}), d.get("kind", "diff"),
                   d.get("version", 1))


@dataclass
class SnapshotInfo:
    snapshot_id: str
    step: int
    kind: str
    new_bytes: int        # differencing-image cost (changed blocks)
    dedup_bytes: int      # blocks reused from the chain
    total_bytes: int      # logical state size
    changed_chunks: int = 0
    reused_chunks: int = 0


@dataclass
class _TensorPlan:
    """Per-tensor work captured synchronously at snapshot() time.

    Self-contained: either the full host image (``base``, re-base path) or
    the probe's compacted changed tiles + bitmap (delta path).  The writer
    folds tiles into its OWN host image (``SnapshotManager._mirror``, which
    only the writer advances), so planner and writer share no mutable
    state and the planner never touches host chunk layout at all."""
    key: str
    shape: tuple
    dtype: str
    nbytes: int
    base: Optional[np.ndarray] = None        # full host image (base path)
    tiles: Optional[np.ndarray] = None       # compacted changed 32 KiB tiles
    bitmap: Optional[np.ndarray] = None      # per-tile changed flags


class SnapshotManager:
    def __init__(self, store: ChunkStore,
                 root: Optional[Path] = None,
                 keep_last: int = 3,
                 async_mode: bool = False,
                 writer_depth: int = 2,
                 auto_gc: bool = True,
                 delta: bool = True,
                 delta_mode: str = "auto",
                 telemetry=None):
        self.store = store
        self.tel = tlm.resolve(telemetry)
        self.root = Path(root) if root is not None else None
        if self.root is not None:
            (self.root / "manifests").mkdir(parents=True, exist_ok=True)
        self.keep_last = keep_last
        # when the store is SHARED across managers (DiskSet), per-manager
        # sweeps would delete sibling disks' chunks — the owner must run a
        # global mark (DiskSet.gc_all) instead.
        self.auto_gc = auto_gc
        # delta=False falls back to the v1 full-hash path (every snapshot
        # re-hashes every chunk); delta_mode picks the diff backend:
        # "auto" (TPU kernel on TPU, numpy oracle elsewhere), "tpu",
        # "interpret", "ref".
        self.delta = delta
        self.delta_mode = delta_mode
        self.manifests: Dict[str, Manifest] = {}
        self.order: List[str] = []                 # snapshot chain
        self._writer = SnapshotWriter(self._write_inner, depth=writer_depth,
                                      telemetry=self.tel) \
            if async_mode else None
        self._futures: deque[Future] = deque()
        self.last_info: Optional[SnapshotInfo] = None
        self._counter = 0
        # host byte image per tensor, advanced ONLY by the write path
        # (writer thread in async mode) — the probing thread never reads it
        self._mirror: Dict[str, np.ndarray] = {}
        self._device_mirror = DeviceMirror()       # probe-side tiles (no H→D)
        self._prev_refs: Dict[str, List[str]] = {}
        self._hashers: Optional[ThreadPoolExecutor] = None

    @property
    def is_async(self) -> bool:
        return self._writer is not None

    @property
    def mirror_bytes(self) -> int:
        """Device bytes the probe-side mirror tiles hold."""
        return self._device_mirror.nbytes()

    @property
    def writer_stats(self) -> dict:
        return dict(self._writer.stats) if self._writer is not None else {}

    # ------------------------------------------------------------------
    def snapshot(self, state, *, step: int, aux: Optional[dict] = None,
                 block: bool = True) -> SnapshotInfo | Future:
        """Take a snapshot.  ``state`` is any pytree of arrays.

        Planning (device probe + changed-tile transfer) is synchronous;
        with ``async_mode`` chunk compaction and the store/manifest writes
        run on the background writer and ``block=False`` returns the
        write's Future immediately — the caller's stall is the probe plus
        queue backpressure, nothing else."""
        self._reap()             # surface any finished/failed async write
        try:
            with self.tel.span("snapshot.plan", step=step):
                plan = self._plan_state(state)
        except BaseException:
            # a partial plan has already advanced some tensors' mirrors
            # while _prev_refs still points at the old chunks; drop both so
            # the next snapshot re-bases instead of recording stale refs
            self._poison()
            raise
        if self._writer is not None:
            try:
                fut = self._writer.submit(plan, step, aux or {}, step=step)
            except BaseException:
                self._poison()
                raise
            self._futures.append(fut)
            return self.wait() if block else fut
        return self._write_sync(plan, step, aux or {})

    def wait(self) -> Optional[SnapshotInfo]:
        """Drain pending background writes; returns the last SnapshotInfo.
        Raises (once) if any pending write failed, after re-basing."""
        out = self.last_info if self._futures else None
        try:
            while self._futures:
                out = self._futures.popleft().result()
                self.last_info = out
        except BaseException:
            self._poison()
            raise
        return out

    def close(self) -> None:
        """Drain the writer and stop its threads."""
        try:
            self.wait()
        finally:
            if self._writer is not None:
                self._writer.close()
            if self._hashers is not None:
                self._hashers.shutdown()

    def _reap(self) -> None:
        """Non-blocking: collect already-finished async writes (keeps the
        future list bounded and surfaces failures at the next snapshot)."""
        while self._futures and self._futures[0].done():
            fut = self._futures.popleft()
            try:
                self.last_info = fut.result()
            except BaseException:
                self._poison()
                raise

    def _poison(self) -> None:
        """Re-base after a failure: drain valid queued writes, then drop
        every mirror so the next snapshot records a full base image rather
        than delta refs against parents that never landed."""
        if self._writer is not None:
            while self._futures:
                fut = self._futures.popleft()
                with contextlib.suppress(BaseException):
                    self.last_info = fut.result()
            self._writer.reset()
        self._mirror.clear()
        self._device_mirror.clear()
        self._prev_refs.clear()

    # ------------------------------------------------------------------
    def _plan_state(self, state) -> List[_TensorPlan]:
        """Probe the whole pytree in size-bucketed fused launches against
        the device-resident mirror slots — this is ALL the work the
        calling thread does per tensor.  Leaves the probe reports as
        un-probed (first snapshot, shape/dtype change, bucket membership
        change) fall back to full base images; the probe seeded their
        slots, so the next round diffs them."""
        flat = [(k, leaf if hasattr(leaf, "dtype") else np.asarray(leaf))
                for k, leaf in _flatten(state)]
        probes = {}
        if self.delta and flat:
            probes = probe_leaves(dict(flat), mode=self.delta_mode,
                                  mirror=self._device_mirror)
        plans = []
        for key, leaf in flat:
            pr = probes.get(key)
            if pr is None:
                plans.append(self._plan_base(key, leaf))
            else:
                tiles, bitmap, nbytes = pr
                plans.append(_TensorPlan(key, tuple(leaf.shape),
                                         str(leaf.dtype), nbytes,
                                         tiles=tiles, bitmap=bitmap))
        return plans

    def _plan_base(self, key: str, leaf) -> _TensorPlan:
        shape, dtype = tuple(leaf.shape), str(leaf.dtype)
        with self.tel.span("snapshot.d2h"):
            host = np.ascontiguousarray(np.asarray(leaf))
        if host.shape != shape:
            host = host.reshape(shape)   # ascontiguousarray 0-d -> 1-d
        if host is leaf or host.base is not None:
            host = host.copy()       # plan must not alias caller data
        return _TensorPlan(key, shape, dtype, host.nbytes, base=host)

    # ------------------------------------------------------------------
    def _write_sync(self, plan, step, aux) -> SnapshotInfo:
        try:
            info = self._write_inner(plan, step, aux)
        except BaseException:
            # the probe already swapped the device mirror forward; a
            # half-written store would make the NEXT diff record stale
            # parent refs.  Drop the mirrors so the next snapshot is a
            # full base image.
            self._poison()
            raise
        self.last_info = info
        return info

    def _write_inner(self, plan: List[_TensorPlan], step: int,
                     aux: dict) -> SnapshotInfo:
        """Persist one plan: per tensor, advance the host image by the
        probe's tiles and take the changed chunks' XOR views
        (``writer.records`` span), then store those chunks (``writer.put``;
        ``put_delta`` stores a dense one raw); then register the
        manifest."""
        before_put = self.store.stats["put_bytes"]
        before_dedup = self.store.stats["dedup_bytes"]
        cb = self.store.chunk_bytes
        tensors = {}
        total = changed = reused = reused_bytes = 0
        # hold the store's gc lock across write + manifest commit so a
        # concurrent mark/sweep can never see (and sweep) this snapshot's
        # objects while its manifest is still unregistered
        with self._gc_guard():
            for p in plan:
                total += p.nbytes
                if p.base is not None:
                    flat = np.asarray(p.base).reshape(-1).view(np.uint8)
                    with self.tel.span("writer.put"):
                        refs = self.store.put_buffer(memoryview(flat))
                    changed += len(refs)
                    # the writer advances this image in place from now on
                    self._mirror[p.key] = flat if flat.flags.writeable \
                        else flat.copy()
                else:
                    # advance the writer's host image by the probe's tiles
                    # and take per-chunk XOR views — off the hot path.  A
                    # failure past here leaves the image ahead of
                    # _prev_refs; _poison drops both.
                    prev_refs = self._prev_refs[p.key]
                    records: Dict[int, np.ndarray] = {}
                    new_flat = self._mirror[p.key]
                    if p.bitmap is not None and p.bitmap.any():
                        with self.tel.span("writer.records"):
                            records, new_flat = chunk_records(
                                new_flat, p.tiles, p.bitmap, p.nbytes, cb)
                    refs = []
                    with self.tel.span("writer.put"):
                        fulls = self._full_chunks(records, new_flat, cb)
                        for ci, pref in enumerate(prev_refs):
                            xor = records.get(ci)
                            if xor is None:
                                refs.append(pref)
                                reused += 1
                                reused_bytes += max(
                                    0, min((ci + 1) * cb, p.nbytes) - ci * cb)
                            else:
                                refs.append(self.store.put_delta(
                                    pref, memoryview(xor),
                                    full_bytes=next(fulls)))
                                changed += 1
                tensors[p.key] = TensorEntry(p.shape, p.dtype, refs)
                self._prev_refs[p.key] = refs
            # chain reuse counts as dedup, as the v1 hash-everything path did
            self.store.metrics.dedup_bytes.inc(reused_bytes)
            self.store.metrics.dedup_chunks.inc(reused)
            self._counter += 1
            sid = f"snap-{self._counter:06d}-{sha256(str(step).encode())[:8]}"
            parent = self.order[-1] if self.order else None
            man = Manifest(sid, parent, step, time.time(), tensors, aux,
                           kind="base" if parent is None else "diff")
            self.manifests[sid] = man
            self.order.append(sid)
            if self.root is not None:
                (self.root / "manifests" / f"{sid}.json") \
                    .write_text(man.to_json())
        self.gc() if self.auto_gc else self._trim_manifests()
        return SnapshotInfo(
            snapshot_id=sid, step=step, kind=man.kind,
            new_bytes=self.store.stats["put_bytes"] - before_put,
            dedup_bytes=self.store.stats["dedup_bytes"] - before_dedup,
            total_bytes=total,
            changed_chunks=changed, reused_chunks=reused)

    def _full_chunks(self, records: Dict[int, np.ndarray],
                     new_flat: np.ndarray, cb: int):
        """The changed chunks' new bytes, in chunk order, as ``put_delta``
        takes them: a dense chunk's (``dense_xor``, the store's own rule)
        copied and hashed on the hash pool, any other as a view."""
        if self._hashers is None:
            self._hashers = ThreadPoolExecutor(
                HASH_WORKERS, thread_name_prefix="snapshot-hash")

        def full(ci: int):
            chunk = memoryview(new_flat[ci * cb:(ci + 1) * cb])
            return Digested(chunk) if dense_xor(records[ci]) else chunk
        return self._hashers.map(full, sorted(records))

    def _gc_guard(self):
        lock = getattr(self.store, "gc_lock", None)
        return lock if lock is not None else contextlib.nullcontext()

    # ------------------------------------------------------------------
    def restore(self, snapshot_id: Optional[str] = None, *,
                target_tree=None, shardings=None):
        """Rebuild state (optionally re-sharded onto a new mesh).

        Returns (state, aux).  ``target_tree`` supplies the pytree structure
        (e.g. abstract state); flattened key paths must match the manifest.
        Handles v2 (delta-ref) and v1 (hash-list) manifests alike.
        """
        self.wait()
        sid = snapshot_id or (self.order[-1] if self.order else None)
        if sid is None:
            raise ValueError("no snapshots available")
        man = self.get_manifest(sid)
        arrays = {}
        for key, ent in man.tensors.items():
            data = self.store.resolve_buffer(ent.refs)
            arr = np.frombuffer(data, dtype=np.dtype(ent.dtype))
            arrays[key] = arr.reshape(ent.shape)
        if target_tree is None:
            return arrays, man.aux
        leaves, treedef = jax.tree_util.tree_flatten(target_tree)
        paths = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(target_tree)[0]]
        sh_leaves = (jax.tree_util.tree_flatten(shardings)[0]
                     if shardings is not None else [None] * len(leaves))
        out = []
        for path, leaf, sh in zip(paths, leaves, sh_leaves):
            if path not in arrays:
                raise KeyError(f"snapshot missing tensor {path}")
            a = arrays[path]
            out.append(jax.device_put(a, sh) if sh is not None else a)
        return jax.tree_util.tree_unflatten(treedef, out), man.aux

    def load_existing(self) -> int:
        """Adopt manifests already on disk under ``root`` (a previous
        process's chain) into this manager's order.

        Ordered by ``(step, created)``, NOT filename: snapshot ids restart
        per process, so a resumed run's newest snapshot can sort first by
        name.  v1 (``hashes``) and v2 (``refs``) manifests mix freely in
        one directory.  Returns the number of manifests adopted."""
        if self.root is None:
            raise ValueError("load_existing needs an on-disk root")
        mans = [Manifest.from_json(p.read_text())
                for p in sorted((self.root / "manifests").glob("*.json"))]
        adopted = 0
        for man in sorted(mans, key=lambda m: (m.step, m.created)):
            if man.snapshot_id in self.manifests:
                continue
            self.manifests[man.snapshot_id] = man
            self.order.append(man.snapshot_id)
            adopted += 1
        # new snapshots must not reuse an adopted id slot
        self._counter = max(self._counter, len(self.order))
        return adopted

    def get_manifest(self, sid: str) -> Manifest:
        """In-memory manifest, falling back to the on-disk copy."""
        man = self.manifests.get(sid)
        return man if man is not None else self._load_manifest(sid)

    def _load_manifest(self, sid: str) -> Manifest:
        if self.root is None:
            raise KeyError(sid)
        man = Manifest.from_json(
            (self.root / "manifests" / f"{sid}.json").read_text())
        self.manifests[sid] = man
        return man

    # ------------------------------------------------------------------
    def download_plan(self, client_refs: set[str],
                      snapshot_id: Optional[str] = None):
        """Block-level transfer accounting for a re-attaching volunteer.

        -> (missing refs, bytes to move, bytes saved) for the given (or
        latest) snapshot — the same ``ChunkStore.plan_send`` (Wire) the
        server's ``fetch_capsule`` uses."""
        self.wait()
        sid = snapshot_id or (self.order[-1] if self.order else None)
        if sid is None:
            raise ValueError("no snapshots available")
        return self.store.plan_send(self.get_manifest(sid).all_refs(),
                                    client_refs)

    # ------------------------------------------------------------------
    def _trim_manifests(self) -> None:
        while len(self.order) > self.keep_last:
            sid = self.order.pop(0)
            man = self.manifests.pop(sid, None)
            if man is not None and self.root is not None:
                p = self.root / "manifests" / f"{sid}.json"
                if p.exists():
                    p.unlink()

    def gc(self) -> int:
        """Keep the last ``keep_last`` snapshots; mark the closure of their
        refs (delta parents stay live) and sweep the store.  Mark + sweep
        run under the store's gc lock so an in-flight background write
        commits its manifest before the live set is collected."""
        with self._gc_guard():
            self._trim_manifests()
            live: set[str] = set()
            for man in self.manifests.values():
                live.update(man.all_refs())
            return self.store.gc(live)

    def latest(self) -> Optional[str]:
        return self.order[-1] if self.order else None
