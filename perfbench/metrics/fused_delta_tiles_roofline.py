"""The snapshot probe kernel's share of its HBM roofline: the bytes its
launches must move (read old and new tiles, write changed tiles and flags,
from their shapes) at the chip's peak bandwidth, over the device time of
its ``fused_delta_tiles`` programs in the trace."""
from perfbench.yardstick import probe_bytes


def read(w):
    if w.trace is None or not w.probes:
        return None
    busy = w.trace.module_s("fused_delta_tiles")
    if busy <= 0:
        return None
    moved = sum(probe_bytes(n, k) for n, k in w.probes)
    return 100.0 * moved / w.peaks["hbm_bytes_per_s"] / busy
