"""Model FLOP/s utilisation of the whole round: the operations the window's
validated tokens required (forward and backward, input embedding left out,
attention in, recompute not counted) over window time x chip peak."""
from perfbench.yardstick import train_flops_per_token


def read(w):
    if not w.tokens or w.seconds <= 0:
        return None
    c, t = w.cell.config, w.cell.traffic
    flops = train_flops_per_token(c, t["seq_len"]) * w.tokens
    return 100.0 * flops / (w.seconds * w.peaks["bf16_flops_per_s"]
                            * w.chips)
