"""Host time per round of the eager fold and AdamW, without the wait for the
device: the program's ``fold`` and ``optimizer`` spans.  ``fold`` closes
after ``_fold_round`` has returned, so it also holds the release of the unit
gradients the fold drops.  The benchmark's block on the new state runs after
``optimizer`` has closed, inside ``apply``, so neither span holds it."""
from perfbench.program_spans import total_ms, window_spans


def read(w):
    spans = window_spans(w)
    if spans is None:
        return None
    return (total_ms(spans, "fold") + total_ms(spans, "optimizer")) \
        / len(w.rounds)
