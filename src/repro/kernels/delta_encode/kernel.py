"""Pallas TPU delta-encode kernel — the differencing-snapshot hot path.

A differencing snapshot (paper §III-E) must stream GBs of parameters and
emit (a) a lossless delta against the previous snapshot and (b) a per-block
changed bitmap so the host stores only written-to blocks.  This is a pure
memory-bound streaming op: read 2 tensors, write 1 + tiny bitmap, zero
FLOPs — ideal Pallas shape: 1-D grid over (8, 1024)-element VMEM tiles
(float32: 32 KiB/tile ×3 streams, deep pipelining, HBM-bound by design).

Deltas are XOR on the int32 bit pattern: exact for any float (including
NaN/Inf payloads), and unchanged blocks are all-zero → maximally
compressible downstream.  decode(old, delta) == new bit-for-bit.

The per-tile changed flags are written lane-dense: one (8, 128) int32
output block holds the flags of ``FLAGS_PER_BLOCK`` consecutive tiles and
stays resident in VMEM while the grid walks them (a revisited output
block, so every grid axis here is sequential — ``"arbitrary"``).  The
wrapper flattens the blocks and slices the first ``nblk`` flags, so any
tile count works and only one vector register is touched per tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 1024
SUB = 8
TILE = SUB * LANE   # 8192 elements per grid step
FLAG_LANES = 128
FLAGS_PER_BLOCK = SUB * FLAG_LANES   # tile flags per (8, 128) bitmap block

_TILE_SPEC = pl.BlockSpec((1, SUB, LANE), lambda i: (i, 0, 0))
_FLAG_SPEC = pl.BlockSpec((SUB, FLAG_LANES),
                          lambda i: (i // FLAGS_PER_BLOCK, 0))
_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _flag_shape(nblk: int) -> jax.ShapeDtypeStruct:
    nb = -(-nblk // FLAGS_PER_BLOCK)
    return jax.ShapeDtypeStruct((nb * SUB, FLAG_LANES), jnp.int32)


def _tile_changed(d) -> jax.Array:
    """0-d int32: 1 when any element of the XOR tile is nonzero."""
    return jnp.max(jnp.where(d != 0, 1, 0).astype(jnp.int32))


def _set_flag(flags_ref, changed) -> None:
    """Write this grid step's flag into its slot of the resident block."""
    j = pl.program_id(0) % FLAGS_PER_BLOCK

    @pl.when(j == 0)
    def _zero():
        flags_ref[...] = jnp.zeros(flags_ref.shape, jnp.int32)

    row = jax.lax.broadcasted_iota(jnp.int32, flags_ref.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, flags_ref.shape, 1)
    flags_ref[...] = jnp.where(row * FLAG_LANES + col == j, changed,
                               flags_ref[...])


def _flags_out(flags: jax.Array, nblk: int) -> jax.Array:
    return flags.reshape(-1)[:nblk]


def _delta_kernel(old_ref, new_ref, delta_ref, flags_ref):
    d = jax.lax.bitwise_xor(old_ref[...], new_ref[...])
    delta_ref[...] = d
    _set_flag(flags_ref, _tile_changed(d))


def _apply_kernel(old_ref, delta_ref, new_ref):
    new_ref[...] = jax.lax.bitwise_xor(old_ref[...], delta_ref[...])


def _bitmap_kernel(old_ref, new_ref, flags_ref):
    d = jax.lax.bitwise_xor(old_ref[...], new_ref[...])
    _set_flag(flags_ref, _tile_changed(d))


def _as_tiles(flat_i32: jax.Array):
    n = flat_i32.shape[0]
    pad = (-n) % TILE
    if pad:
        flat_i32 = jnp.pad(flat_i32, (0, pad))
    return flat_i32.reshape(-1, SUB, LANE), n


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_encode(old: jax.Array, new: jax.Array, *,
                 interpret: bool = False):
    """old/new: same-shape arrays -> (delta_i32 tiles, changed (nblocks,)).

    Bit-exact XOR delta over the int32 view, tiled (SUB, LANE)."""
    assert old.shape == new.shape and old.dtype == new.dtype
    o32, _ = _as_tiles(_bitcast_i32(old))
    n32, n = _as_tiles(_bitcast_i32(new))
    nblk = o32.shape[0]
    delta, flags = pl.pallas_call(
        _delta_kernel,
        grid=(nblk,),
        in_specs=[_TILE_SPEC, _TILE_SPEC],
        out_specs=[_TILE_SPEC, _FLAG_SPEC],
        out_shape=[jax.ShapeDtypeStruct((nblk, SUB, LANE), jnp.int32),
                   _flag_shape(nblk)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(o32, n32)
    return delta, _flags_out(flags, nblk), n


@functools.partial(jax.jit, static_argnames=("interpret",))
def changed_bitmap(old: jax.Array, new: jax.Array, *,
                   interpret: bool = False):
    """Probe pass: per-tile changed flags ONLY -> (changed (nblk,) i32, n).

    Unlike ``delta_encode`` the full delta never touches HBM — the kernel
    streams both tensors and emits one int32 per (8, 1024) tile.  For a
    mostly-unchanged state this is the whole device-side cost of a
    differencing snapshot; the host reads the tiny bitmap and gathers just
    the changed tiles afterwards (``gather_delta``)."""
    assert old.shape == new.shape and old.dtype == new.dtype
    o32, _ = _as_tiles(_bitcast_i32(old))
    n32, n = _as_tiles(_bitcast_i32(new))
    nblk = o32.shape[0]
    flags = pl.pallas_call(
        _bitmap_kernel,
        grid=(nblk,),
        in_specs=[_TILE_SPEC, _TILE_SPEC],
        out_specs=_FLAG_SPEC,
        out_shape=_flag_shape(nblk),
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(o32, n32)
    return _flags_out(flags, nblk), n


def _fused_kernel(old_ref, new_ref, flags_ref, tiles_ref,
                  cnt_ref, stage_ref, sem):
    """Probe + gather in one pass: XOR the tile, flag it, and — only when it
    changed — DMA the compacted tile into the next free output slot.

    The SMEM counter persists across grid steps (the grid axis is declared
    sequential), so compacted tiles land in ascending tile order and the
    host can recover tile indices from the bitmap alone."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cnt_ref[0] = 0

    d = jax.lax.bitwise_xor(old_ref[...], new_ref[...])
    changed = _tile_changed(d)
    _set_flag(flags_ref, changed)

    @pl.when(changed != 0)
    def _emit():
        c = cnt_ref[0]
        stage_ref[...] = d
        copy = pltpu.make_async_copy(stage_ref,
                                     tiles_ref.at[pl.ds(c, 1)], sem)
        copy.start()
        copy.wait()
        cnt_ref[0] = c + 1


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_delta_records(old: jax.Array, new: jax.Array, *,
                        interpret: bool = False):
    """Single-launch probe+gather -> (bitmap (nblk,) i32, tiles, n).

    ``tiles`` is (nblk, SUB, LANE) i32 with the k changed tiles compacted
    into slots [0, k) in ascending tile order (k = bitmap.sum()); slots
    past k are unwritten.  One kernel launch replaces the
    ``changed_bitmap`` + host sync + ``gather_delta`` pipeline, so the
    device-side cost of a snapshot probe is one pass over old/new and the
    only D2H traffic is the bitmap plus the k changed tiles."""
    assert old.shape == new.shape and old.dtype == new.dtype
    o32, _ = _as_tiles(_bitcast_i32(old))
    n32, n = _as_tiles(_bitcast_i32(new))
    bitmap, tiles = fused_delta_tiles(o32, n32, interpret=interpret)
    return bitmap, tiles, n


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_delta_tiles(o32: jax.Array, n32: jax.Array, *,
                      interpret: bool = False):
    """Tile-level fused probe+gather over pre-tiled (nblk, SUB, LANE) i32
    inputs — the launch the bucketed tree diff issues once per size bucket
    (inputs are per-leaf ``as_i32_tiles`` views concatenated on device)."""
    nblk = o32.shape[0]
    flags, tiles = pl.pallas_call(
        _fused_kernel,
        grid=(nblk,),
        in_specs=[_TILE_SPEC, _TILE_SPEC],
        out_specs=[_FLAG_SPEC, pl.BlockSpec(memory_space=pl.ANY)],
        out_shape=[_flag_shape(nblk),
                   jax.ShapeDtypeStruct((nblk, SUB, LANE), jnp.int32)],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((1, SUB, LANE), jnp.int32),
                        pltpu.SemaphoreType.DMA],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(o32, n32)
    return _flags_out(flags, nblk), tiles


def as_i32_tiles(x: jax.Array):
    """Public view helper: flat int32 image padded to whole (SUB, LANE)
    tiles -> ((nblk, SUB, LANE) i32, element count before padding).  The
    bucketed tree diff concatenates these per-leaf views so one fused
    launch probes many leaves."""
    return _as_tiles(_bitcast_i32(x))


@jax.jit
def gather_delta(old: jax.Array, new: jax.Array,
                 idx: jax.Array) -> jax.Array:
    """Second pass: XOR only the changed tiles, gathered on device.

    ``idx`` is the changed-tile index vector from ``changed_bitmap``; the
    result is the compacted (k, 8, 1024) i32 delta — the only payload that
    crosses the device→host boundary."""
    o32, _ = _as_tiles(_bitcast_i32(old))
    n32, _ = _as_tiles(_bitcast_i32(new))
    return jax.lax.bitwise_xor(jnp.take(o32, idx, axis=0),
                               jnp.take(n32, idx, axis=0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_apply(old: jax.Array, delta: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """Reconstruct: old ^ delta -> new (same shape/dtype as old)."""
    o32, n = _as_tiles(_bitcast_i32(old))
    new32 = pl.pallas_call(
        _apply_kernel,
        grid=(o32.shape[0],),
        in_specs=[_TILE_SPEC, _TILE_SPEC],
        out_specs=_TILE_SPEC,
        out_shape=jax.ShapeDtypeStruct(o32.shape, jnp.int32),
        interpret=interpret,
    )(o32, delta)
    flat = new32.reshape(-1)[:n]
    return _bitcast_back(flat, old.shape, old.dtype)


def _bitcast_i32(x: jax.Array) -> jax.Array:
    x = x.reshape(-1)
    if x.dtype == jnp.int32:
        return x
    if x.dtype in (jnp.float32,):
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    if x.dtype in (jnp.bfloat16, jnp.float16, jnp.int16):
        x16 = jax.lax.bitcast_convert_type(x, jnp.int16)
        pad = (-x16.shape[0]) % 2
        if pad:
            x16 = jnp.pad(x16, (0, pad))
        return jax.lax.bitcast_convert_type(x16.reshape(-1, 2), jnp.int32)
    raise TypeError(f"unsupported dtype {x.dtype}")


def _bitcast_back(flat_i32: jax.Array, shape, dtype) -> jax.Array:
    import numpy as np
    count = int(np.prod(shape)) if shape else 1
    if dtype == jnp.int32:
        return flat_i32[:count].reshape(shape)
    if dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(
            flat_i32, jnp.float32)[:count].reshape(shape)
    x16 = jax.lax.bitcast_convert_type(flat_i32, jnp.int16).reshape(-1)
    return jax.lax.bitcast_convert_type(
        x16[:count].reshape(shape), dtype)
