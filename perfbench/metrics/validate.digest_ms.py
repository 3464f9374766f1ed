"""Host time per result of hashing the gradient's bytes (blake2b), leaf by
leaf: the program's ``validate.digest`` spans per ``validate`` span.  No
wait on the device falls in them: the benchmark's blocks sit inside
``grad_step`` and ``apply``."""
from perfbench.program_spans import per


def read(w):
    return per(w, "validate.digest", "validate")
