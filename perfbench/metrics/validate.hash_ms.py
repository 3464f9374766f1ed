"""Host time of gradient validation per result: the span around
``elastic.grad_hash``, which in the traced run starts on a gradient the
device has finished, so it holds the copy to the host and the hash."""


def read(w):
    spans = w.spans.get("validate.hash", [])
    return 1e3 * sum(spans) / len(spans) if spans else None
