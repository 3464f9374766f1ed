"""Background writer time per snapshot folding the probe's tiles into its
host image and cutting per-chunk XOR records: the program's
``writer.records`` spans per ``writer.write`` span, on the writer's thread,
where no block of the benchmark's runs."""
from perfbench.program_spans import per


def read(w):
    return per(w, "writer.records", "writer.write")
