"""Host time per snapshot of the copies to the host (the probe's bitmaps and
changed tiles, base images): the program's ``delta_encode.d2h`` and
``snapshot.d2h`` spans under a ``snapshot`` span, per ``snapshot`` span.
The differ's copies for another caller (the uplink) sit under no snapshot
and are left out.  A bitmap's copy first waits for its probe launch; the
benchmark's blocks on the grad step and the new state end before it."""
from perfbench.program_spans import count, total_ms, under, window_spans

COPIES = ("snapshot.d2h", "delta_encode.d2h")


def read(w):
    spans = window_spans(w)
    if spans is None or not count(spans, "snapshot"):
        return None
    copies = under(spans, "snapshot", COPIES)
    return sum(total_ms(copies, n) for n in COPIES) / count(spans, "snapshot")
