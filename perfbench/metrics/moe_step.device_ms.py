"""Device time of the grad step per work unit on an expert share:
operations that ran inside the benchmark's ``grad_step`` span, which
blocks on the gradient."""


def read(w):
    units = len(w.spans.get("grad_step", []))
    if w.trace is None or not units or "grad_step" not in w.trace.by_span:
        return None
    return 1e3 * w.trace.by_span["grad_step"] / units
