"""Host time per round outside every layer (the scheduler's loop, leases,
reports, each unit's batch, bookkeeping): each ``round`` span less the union
of its direct children (``grad_step``, ``validate``, ``fold``, ``apply``,
``snapshot``).  The benchmark's blocks sit inside ``grad_step`` and
``apply``, so none of them falls in this time."""
from perfbench.program_spans import self_ms, window_spans


def read(w):
    spans = window_spans(w)
    rounds = [s for s in spans or [] if s.name == "round"]
    if not rounds:
        return None
    return sum(self_ms(r, spans) for r in rounds) / len(rounds)
