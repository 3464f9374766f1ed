"""Model FLOP/s utilisation of the whole round on an expert share: the
operations the window's validated tokens required (``moe_yardstick``:
forward and backward, attention over each layer kind's visible keys, the
experts by the pairs the program's ``moe.pairs_here`` readings say were
routed here, recompute not counted) over window time x chip peak.
Nothing is read where the program keeps no such readings."""
from perfbench.moe_yardstick import dense_flops_per_token, flops_per_pair
from perfbench.program_readings import window_readings


def read(w):
    if not w.tokens or w.seconds <= 0:
        return None
    units = window_readings(w, ("moe.pairs_here",))
    if units is None:
        return None
    c, t = w.cell.config, w.cell.traffic
    # pairs a token sent here, over the units read (one per computed
    # result), applied to the window's validated tokens
    per_token = sum(u["moe.pairs_here"] for u in units.values()) \
        / (len(units) * t["batch"] * t["seq_len"])
    flops = (dense_flops_per_token(c, t["seq_len"])
             + per_token * flops_per_pair(c)) * w.tokens
    return 100.0 * flops / (w.seconds * w.peaks["bf16_flops_per_s"]
                            * w.chips)
