"""Readings of the control and of planted faults, for setting a cell's
limits.

    python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,3

The control is the plain reference computed one precision step below the
bfloat16 products the configuration states (float8 operands) and put in
the program's place: it follows the cell's first steps at the cell's own
sizes, on the chip, and is compared with the float32 reference exactly as
a run compares the program.  So are the training faults planted in the
reference (half of each batch, each answer 1% off).  Each is judged
against the cell's own limits, as a run is: every one must come out not
correct.  One JSON line per seed; the upper reading of each number is the
smallest the control (or a fault) gives.  The program's own readings (the
lower ones) are the ``checks`` a run of ``run.py`` prints.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from perfbench import registry  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    args = ap.parse_args(argv)
    cell = registry.load_cell(args.workload)
    from perfbench import check, harness, reference, run
    run.use_cache_dir()
    devs = run.device_or_exit(cell.chips)
    steps = harness.CHECK_STEPS
    for asked in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        seed = reference.stream_seed(asked, cell.traffic["data"],
                                     cell.config["vocab_size"])
        ref = check.follow(cell, seed, steps)
        line = {"workload": cell.name, "seed": asked, "stream_seed": seed}
        for name, kw in [("control", {"precision": "fp8"})] + [
                (f, {"fault": f}) for f in check.FAULTS]:
            numbers = check.gaps(check.follow(cell, seed, steps, **kw), ref)
            correct, _ = check.judge(numbers, cell.limits)
            line[name] = dict(numbers, correct=correct)
        line.update(seconds=time.perf_counter() - t,
                    device=devs[0].device_kind)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
