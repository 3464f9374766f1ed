"""Load of the busiest held expert: per unit, the largest held expert's
pairs in any layer (the program's ``moe.max_expert_pairs`` reading) over
the mean pairs of a held expert in a layer (``moe.pairs_here`` over held
experts x layers), averaged over the window's units.  1 is an even load."""
from perfbench.program_readings import window_readings

NAMES = ("moe.max_expert_pairs", "moe.pairs_here")


def read(w):
    units = window_readings(w, NAMES)
    if units is None:
        return None
    c = w.cell.config
    slots = c["num_experts"] * c["num_hidden_layers"]
    ratios = [u["moe.max_expert_pairs"] * slots / u["moe.pairs_here"]
              for u in units.values() if u["moe.pairs_here"] > 0]
    return sum(ratios) / len(ratios) if ratios else None
