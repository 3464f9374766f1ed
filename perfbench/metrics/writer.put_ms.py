"""Background writer time per snapshot encoding (zero-run RLE, sha256) and
storing each tensor's changed chunks: the program's ``writer.put`` spans per
``writer.write`` span, on the writer's thread, where no block of the
benchmark's runs."""
from perfbench.program_spans import per


def read(w):
    return per(w, "writer.put", "writer.write")
