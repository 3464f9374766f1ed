"""Host time per round of folding the validated gradients and applying the
optimizer, to the new state being ready on the device."""


def read(w):
    spans = w.spans.get("fold_apply", [])
    if not spans or not w.rounds:
        return None
    return 1e3 * sum(spans) / len(w.rounds)
