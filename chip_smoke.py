"""One-chip smoke run: the volunteer training round on a TPU at
granite-3-2b's published widths.

    python chip_smoke.py

Everything runs in this one process (a child that touched JAX could not
reach the chip).  Phases, each of which must pass:

1. device  — JAX's first device is a TPU; there is no CPU fallback.
2. kernel  — the compiled fused snapshot kernel agrees bit for bit with
   the numpy oracle on small random inputs, at tile counts that do and do
   not fill whole bitmap blocks.
3. train   — ``repro.launch.train.main`` runs granite-3-2b with every
   width as published and depth cut to one layer: four rounds of
   scheduler -> grad step -> fold/apply -> differencing snapshot through
   the compiled kernel -> background writer.
4. resume  — the same run in a fresh directory, two rounds, then
   ``--resume`` for two more: the resumed losses equal the uninterrupted
   run's rounds 2-3 bit for bit.  Every snapshot probe ran the compiled
   kernel (``ref_passes == 0``).

Earlier lines report the device, peak device memory, state and mirror
bytes, set-up and per-round seconds.  The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import gc
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.jaxcache import use_compile_cache  # noqa: E402

TRAIN_ARGS = ["--arch", "granite-3-2b", "--preset", "full", "--layers", "1",
              "--seq", "1024", "--batch", "2", "--micro", "2",
              "--workers", "3", "--snapshot-every", "1", "--async-writer",
              "--seed", "0"]
KERNEL_TILE_COUNTS = (1, 1025, 12289)   # 12289: a 49155 x 2048 f32 leaf


class PhaseFailed(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def device_phase():
    import jax
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"JAX's first device is {dev.platform!r}, not a TPU")
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    return dev


def kernel_phase() -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.delta_encode.kernel import fused_delta_tiles
    from repro.kernels.delta_encode.ref import fused_tiles_ref
    rng = np.random.default_rng(0)
    for nblk in KERNEL_TILE_COUNTS:
        old = rng.integers(-2 ** 31, 2 ** 31 - 1, (nblk, 8, 1024), np.int32)
        new = old.copy()
        hit = np.unique(np.concatenate(
            [[0, nblk - 1], rng.integers(0, nblk, nblk // 3)]))
        new[hit, rng.integers(0, 8, hit.size),
            rng.integers(0, 1024, hit.size)] ^= 1
        bm, tiles = fused_delta_tiles(jnp.asarray(old), jnp.asarray(new))
        want_bm, want_tiles = fused_tiles_ref(old, new)
        k = int(want_bm.sum())
        check(np.array_equal(np.asarray(bm), want_bm),
              f"kernel bitmap differs from ref at {nblk} tiles")
        check(np.array_equal(np.asarray(tiles[:k]), want_tiles),
              f"kernel tiles differ from ref at {nblk} tiles")
        log(f"kernel fused_delta_tiles[{nblk}] == ref ({k} changed)")


def train_run(dev, outdir: Path, *extra: str) -> dict:
    from repro.kernels.delta_encode.ops import KERNEL_STATS, reset_kernel_stats
    from repro.launch import train
    reset_kernel_stats()
    t = time.perf_counter()
    summary = train.main(TRAIN_ARGS + ["--outdir", str(outdir), *extra])
    main_s = time.perf_counter() - t
    # the finished run's trainer and snapshot manager sit in reference
    # cycles; collect them so their device buffers are free for the next
    gc.collect()
    kstats = dict(KERNEL_STATS)
    losses = summary["losses"]
    writer = summary["snapshot_writer"]
    log(f"run {' '.join(extra)}: main_s {main_s:.2f} setup_s "
        f"{summary['setup_s']} step_s {summary['step_s']} losses {losses}")
    log(f"state_bytes {summary['state_bytes']} mirror_bytes "
        f"{summary['mirror_bytes']} kernel {kstats} writer {writer}")
    check(summary["reduced"] == {"n_layers": [1, 40]},
          f"unexpected cuts {summary['reduced']}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(kstats["ref_passes"] == 0,
          f"{kstats['ref_passes']} snapshot probes fell back to numpy ref")
    check(kstats["launches"] > 0, "no snapshot probe launched the kernel")
    check(writer["failed"] == 0 and writer["written"] == len(losses),
          f"snapshot writer did not land every snapshot: {writer}")
    report_memory(dev, f"run {' '.join(extra)}")
    return summary


def report_memory(dev, what: str) -> None:
    stats = dev.memory_stats() or {}
    log(f"memory after {what}: peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use')} bytes_in_use "
        f"{stats.get('bytes_in_use')} bytes_limit {stats.get('bytes_limit')}")


def main() -> int:
    use_compile_cache()
    import jax
    dev = device_phase()
    kernel_phase()
    report_memory(dev, "kernel")
    work = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    try:
        full = train_run(dev, work / "full", "--steps", "4")
        shutil.rmtree(work / "full")
        first = train_run(dev, work / "resume", "--steps", "2")
        second = train_run(dev, work / "resume", "--steps", "2", "--resume")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    check(first["losses"] == full["losses"][:2],
          f"rerun losses {first['losses']} != {full['losses'][:2]}")
    check(second["losses"] == full["losses"][2:],
          f"resumed losses {second['losses']} != {full['losses'][2:]}")
    log("resumed losses equal the uninterrupted run bit for bit")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except PhaseFailed as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
