"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus section markers).  Scaled for
the CPU container; see EXPERIMENTS.md for the recorded runs + analysis.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from benchmarks import (edge_egress, fig3_overhead, fig4_sprint_pcor,
                            replica_failover, server_throughput,
                            table2_snapshots, telemetry_overhead)

    sections = [
        ("fig3 (benchmark overhead, 4 platforms)", fig3_overhead.run),
        ("fig4 (SPRINT pcor load/exec)", fig4_sprint_pcor.run),
        ("table2 (snapshot time/sizes)", table2_snapshots.run),
        ("server (§IV-C throughput)", server_throughput.run),
        ("replica (fan-out + failover)", replica_failover.run),
        ("edge (discovery + cache egress)", edge_egress.run),
        ("telemetry (tracing overhead)", telemetry_overhead.run),
    ]
    print("name,us_per_call,derived")
    ok = True
    for title, fn in sections:
        t0 = time.time()
        try:
            for line in fn():
                print(line)
        except Exception as e:  # keep the harness honest: report, fail exit
            ok = False
            print(f"{title.split()[0]}.ERROR,0,{type(e).__name__}: {e}")
        print(f"# section '{title}' took {time.time() - t0:.1f}s",
              file=sys.stderr)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
