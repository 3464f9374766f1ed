"""Background writer time per snapshot written in the window (chunking,
hashing, store writes and manifest), from the writer's own counters."""


def read(w):
    written = w.writer.get("written", 0)
    if not written:
        return None
    return w.writer["write_ms"] / written
