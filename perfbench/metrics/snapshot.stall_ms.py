"""Trainer-visible time per snapshot (probe, changed-tile copy to the host
and any writer backpressure), as the program's ``RoundStats`` counts it."""


def read(w):
    stalls = [h.snapshot_stall_ms for h in w.history
              if h.snapshot_stall_ms > 0]
    return sum(stalls) / len(stalls) if stalls else None
