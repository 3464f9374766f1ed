"""Run one benchmark cell on the chip it is started on.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Loads the cell's configuration and traffic by name, builds the program's
training round from the seed, warms it up, measures ``--seconds`` of
rounds and checks what the first steps produced against the plain
reference.  The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` with ``--trace 1``); the numbers compared, each beside its
limit, are the last lines of standard error and the result's last key.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits with code 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from perfbench import registry  # noqa: E402

NO_CHIP = 3


def device_or_exit(chips: int):
    """JAX's devices, or exit without a result: no fall-back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"perfbench: needs {chips} TPU chip(s), JAX has "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(NO_CHIP)
    return devs


def use_cache_dir() -> None:
    """The checkout's fixed compile cache, or the one the environment names;
    small programs (the eager optimizer's) are kept too."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(CHECKOUT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def measure(cell, seed: int, seconds: float, trace: bool, devs,
            peaks: dict, keep_trace: Path | None = None,
            t_start: float = T_START) -> dict:
    """Set-up, window, readings and check of one run -> the result object."""
    from perfbench import check, harness, reference, tracing
    asked = seed
    seed = reference.stream_seed(seed, cell.traffic["data"],
                                 cell.config["vocab_size"])
    print(f"perfbench: --seed {asked} runs the stream of seed {seed}",
          file=sys.stderr, flush=True)
    trace_dir = Path(tempfile.mkdtemp(prefix="perfbench-trace-")) \
        if trace else None
    s = harness.Session(cell, seed, seconds, trace, peaks, cell.chips,
                        trace_dir)
    s.run()
    win = s.win
    setup_s = s.t0 - t_start
    print(f"perfbench: setup {setup_s:.3f} s over {s.setup_rounds} rounds; "
          f"window {win.seconds:.3f} s, rounds "
          f"{[round(b - a, 3) for _, a, b in win.rounds]}; "
          f"writer {win.writer}; durable lags {win.durable_lags}; "
          f"check {sum(win.spans.get('check', [])):.4f} s",
          file=sys.stderr, flush=True)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:cell.chips])
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        win.trace = tracing.reduce_dir(trace_dir)
        if keep_trace is not None:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if win.trace is not None:
            device["busy_s"] = win.trace.busy_s
            device["window_s"] = win.trace.window_s
            breakdown = {"device_ops": win.trace.device_ops,
                         "idle_gaps": win.trace.idle_gaps}
        values = {m["name"]: cell.reader(m["name"]).read(win)
                  for m in cell.per_layer}
        wanted = cell.per_layer
    else:
        lags = win.durable_lags
        values = {"tokens_per_s": win.tokens / win.seconds,
                  "peak_hbm_gb": peak / 1e9, "setup_s": setup_s,
                  "durable_lag_s": sum(lags) / len(lags) if lags else None}
        wanted = cell.end_to_end
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if values.get(m["name"]) is not None}

    numbers = {}
    if s.snapshots_on:
        numbers["snapshot_gap"] = s.snapshot_gap()
    prog = s.program_readings()
    s.free_device()
    ref = check.follow(cell, seed, harness.CHECK_STEPS)
    numbers.update(check.gaps(prog, ref))
    correct, checks = check.judge(numbers, cell.limits)
    result = {"correct": correct,
              "attempted": len(win.rounds) * cell.traffic["units_per_round"],
              "failed": sum(h.invalid for h in win.history),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result.update(rounds=len(win.rounds), window_s=win.seconds,
                  stream_seed=seed, checks=checks)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="copy the raw profiler trace here")
    args = ap.parse_args(argv)
    cell = registry.load_cell(args.workload)
    use_cache_dir()
    devs = device_or_exit(cell.chips)
    peaks = registry.peaks(devs[0].device_kind)
    result = measure(cell, args.seed, args.seconds, bool(args.trace), devs,
                     peaks, args.keep_trace)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the window's snapshots are written by now; end the process without
    # joining the writer's idle thread or tearing JAX down
    os._exit(code)
