"""Telemetry-overhead microbenchmark: dispatch p50, tracing off vs on.

The telemetry plane promises that the *disabled* path costs one attribute
check on the scheduler hot path (``core/telemetry.py``), and that even
the *enabled* path (latency histogram + submit/dispatch/lease/report
events per unit) stays within a small constant factor.  This benchmark
pins both claims to numbers CI can gate:

* ``disabled`` row — ``request_work`` p50 with a hub whose tracing flag
  is off (the default for every test and benchmark in the repo).  This
  is the figure the committed ``BENCH_scheduler.json`` flat-ratio gate
  implicitly depends on, so it also gates loosely against the committed
  ``BENCH_telemetry.json`` baseline;
* ``enabled`` row — same workload with ``tracing=True`` on an isolated
  hub (ring-buffer recorder + dispatch-latency histogram live);
* ``overhead_ratio`` — enabled p50 / disabled p50, gated *within* one
  run by ``check_regression.py --kind telemetry`` (default limit 3.0)
  so it is immune to runner speed.

    PYTHONPATH=src:. python -m benchmarks.telemetry_overhead \
        --json /tmp/tel.json
    PYTHONPATH=src:. python -m benchmarks.check_regression /tmp/tel.json

``--spans N`` instead prints the cost of one timed span (``Telemetry
.span``: two clock reads, a profiler annotation and a ring append) over N
nested pairs, with the profiler off and then recording.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import time
from pathlib import Path

import numpy as np

from benchmarks.common import csv_line
from repro.core import telemetry as tlm
from repro.core.scheduler import SimClock, VolunteerScheduler

BURST = 8                 # requests per sampled volunteer


def measure(tracing: bool, clients: int, samples: int,
            seed: int = 0) -> dict:
    """Steady-state ``request_work`` latency against an isolated hub.

    Mirrors ``server_throughput.measure_row``'s duty cycle (burst of
    requests, report each unit untimed) so the two benchmarks measure
    the same regime; the only variable is the hub's ``tracing`` flag."""
    rng = np.random.default_rng(seed)
    tel = tlm.Telemetry(tracing=tracing, clock=SimClock())
    sched = VolunteerScheduler(replication=1, quorum=1, deadline_s=3600.0,
                               clock=SimClock(), telemetry=tel)
    for i in range(clients):
        sched.join(f"v{i}")
    for uid in range(samples * 2 + BURST * 4):
        sched.submit(uid, {"batch_index": uid})
    h = hashlib.sha256(b"result").hexdigest()
    n_bursts = max(1, samples // BURST)
    pick = rng.integers(0, clients, size=n_bursts)
    lat = []
    for i in pick:
        w = f"v{i}"
        for _ in range(BURST):
            t0 = time.perf_counter()
            wu = sched.request_work(w)
            lat.append(time.perf_counter() - t0)
            assert wu is not None, "backlog drained mid-measurement"
            sched.report(w, wu.unit_id, h)      # untimed: keep churn real
    lat = np.asarray(lat)
    return {
        "name": "enabled" if tracing else "disabled",
        "tracing": tracing, "clients": clients, "samples": int(len(lat)),
        "p50_us": float(np.percentile(lat, 50) * 1e6),
        "p99_us": float(np.percentile(lat, 99) * 1e6),
        "events": len(tel.events),
    }


def run_curve(clients: int = 2000, samples: int = 400) -> dict:
    rows = [measure(False, clients, samples),
            measure(True, clients, samples)]
    by = {r["name"]: r for r in rows}
    ratio = (by["enabled"]["p50_us"] / by["disabled"]["p50_us"]
             if by["disabled"]["p50_us"] > 0 else None)
    return {"kind": "telemetry", "clients": clients, "samples": samples,
            "rows": rows, "overhead_ratio": ratio}


def span_cost_ns(n: int, profiler: bool) -> float:
    """Nanoseconds per span, over ``n`` pairs of a span nested in another
    (as ``validate.copy`` sits in ``validate``), on an isolated hub."""
    import tempfile

    import jax
    hub = tlm.Telemetry()
    with hub.span("warm"):
        pass
    tmp = tempfile.TemporaryDirectory() if profiler else None
    if profiler:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp.name, profiler_options=opts)
    t0 = time.perf_counter_ns()
    for i in range(n):
        with hub.span("outer", step=i):
            with hub.span("inner"):
                pass
    dt = time.perf_counter_ns() - t0
    if profiler:
        jax.profiler.stop_trace()
        tmp.cleanup()
    return dt / (2 * n)


def run(tiny: bool = True) -> list[str]:
    """Registry entry point (benchmarks/run.py): CSV lines."""
    curve = run_curve()
    lines = [csv_line(f"telemetry.{r['name']}", r["p50_us"],
                      f"p99_us={r['p99_us']:.1f};events={r['events']}")
             for r in curve["rows"]]
    lines.append(csv_line("telemetry.overhead_ratio", 0.0,
                          f"enabled_p50/disabled_p50="
                          f"{curve['overhead_ratio']:.2f}"))
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--clients", type=int, default=2000)
    ap.add_argument("--samples", type=int, default=400)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the machine-readable result here")
    ap.add_argument("--spans", type=int, default=0, metavar="N",
                    help="measure the cost of a span instead, over N pairs")
    args = ap.parse_args(argv)
    if args.spans:
        for profiler in (False, True):
            print(f"  span ns, profiler {'on ' if profiler else 'off'}: "
                  f"{span_cost_ns(args.spans, profiler):.1f}")
        return 0
    curve = run_curve(clients=args.clients, samples=args.samples)
    for r in curve["rows"]:
        print(f"  {r['name']:9s} p50 {r['p50_us']:8.2f}us  "
              f"p99 {r['p99_us']:8.2f}us  events {r['events']}")
    print(f"  overhead_ratio enabled/disabled = "
          f"{curve['overhead_ratio']:.2f}")
    if args.json:
        Path(args.json).write_text(json.dumps(curve, indent=2))
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
