"""Time per round the trainer blocked on the writer's full queue, from the
writer's own counter."""


def read(w):
    if not w.writer or not w.rounds:
        return None
    return w.writer.get("backpressure_ms", 0.0) / len(w.rounds)
