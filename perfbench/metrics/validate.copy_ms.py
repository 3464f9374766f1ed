"""Host time per result of copying the gradient to the host, leaf by leaf:
the program's ``validate.copy`` spans per ``validate`` span.  In the traced
run the benchmark blocks on the gradient inside ``grad_step``, so the copy
starts on a finished gradient and holds no wait for the grad step."""
from perfbench.program_spans import per


def read(w):
    return per(w, "validate.copy", "validate")
