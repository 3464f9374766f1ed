"""Drive one cell: set-up, the measured window, the readings and the check.

The window drives ``VolunteerTrainer.round`` exactly as
``repro.launch.train.main`` wires it (grad step, validation, scheduler,
fold, optimizer, snapshot manager, writer).  ``main`` has no hook for a
time-bounded window, so the session runs it with a large fixed ``--steps``
and wraps the class's ``round``: the wrapper counts set-up rounds, opens
the window, times each round, and ends ``main`` by raising when the window
has run its length.  The round that is running then is finished, counted,
and the window closes at its end, so the rate covers all the work and all
the time of the window.  A snapshot taken in the window may become
restorable after it; the session then waits for the writer, so that the
snapshot's lag is counted and the check can read it.

The session reads what the check needs as set-up passes: the parameters
before the first step, the first moment after it, the parameters after
the cell's ``check_steps`` steps, and, in a cell that snapshots, the sums
of the state after every round that took a snapshot.  That work is the
check's, not the program's: it runs under a ``bench.check`` span, between
rounds, and never waits on the device (the sums stay there until the
window has closed).
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from perfbench import check


class WindowClosed(Exception):
    """Raised out of ``round`` to end ``train.main`` when the window is over."""


CHECK_STEPS = 3


def train_argv(cell, seed: int) -> list[str]:
    """``train.main``'s arguments for the cell's configuration and traffic."""
    c, t = cell.config, cell.traffic
    preset = c.get("program_preset", "full")
    argv = ["--arch", c["program_arch"], "--preset", preset]
    if preset == "full":
        argv += ["--layers", str(c["num_hidden_layers"])]
    argv += ["--steps", str(t["steps"]), "--seq", str(t["seq_len"]),
             "--batch", str(t["batch"]), "--micro", str(t["units_per_round"]),
             "--workers", str(t["volunteers"]),
             "--replication", str(t["replication"]),
             "--quorum", str(t["quorum"]),
             "--fail-prob", str(t["fail_prob"]),
             "--corrupt-prob", str(t["corrupt_prob"]),
             "--snapshot-every", str(t["snapshot_every"]),
             "--writer-depth", str(t["writer_depth"]),
             "--lr", str(t["optimizer"]["lr"]),
             "--seed", str(seed), "--log-every", str(t["steps"])]
    if t["async_writer"]:
        argv.append("--async-writer")
    return argv


@dataclass
class Window:
    """What the measured window read, handed to each metric's reader."""
    cell: object
    peaks: dict
    chips: int
    seconds: float = 0.0
    rounds: list = field(default_factory=list)     # (step, t0, t1)
    tokens: int = 0
    history: list = field(default_factory=list)    # RoundStats
    spans: dict = field(default_factory=dict)      # name -> [seconds]
    writer: dict = field(default_factory=dict)     # counter deltas
    probes: list = field(default_factory=list)     # (tiles, changed)
    durable_lags: list = field(default_factory=list)
    trace: object = None                           # tracing.Reduced


def _host_tree(tree) -> dict:
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): np.asarray(x) for p, x in flat}


class Session:
    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 peaks: dict, chips: int = 1, trace_dir: Path | None = None):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.tracing = trace
        self.trace_dir = trace_dir
        self.t = cell.traffic
        self.snapshots_on = self.t["snapshot_every"] > 0
        self.win = Window(cell, peaks, chips)
        self.trainer = None
        self.phase = "setup"
        self.setup_rounds = 0
        self.t0 = self.t_wall0 = None
        self.prog: dict = {}                 # readings for the check
        self.sums: dict = {}                 # step -> device sums per leaf
        self.snap_start: dict = {}           # step -> wall time of the call
        self.registered: dict = {}           # snapshot id -> (step, created)
        self._writer0: dict = {}
        self._annotation = None
        self._restore: list = []

    # ---------------- wrappers ----------------
    def _span(self, name: str, fn, block: bool = False):
        import jax

        def wrapped(*a, **k):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench." + name):
                out = fn(*a, **k)
                if block:
                    jax.block_until_ready(out)
            if self.phase == "window":
                self.win.spans.setdefault(name, []).append(
                    time.perf_counter() - t0)
            return out
        return wrapped

    @contextlib.contextmanager
    def _check(self):
        """The check's own work between rounds, timed apart from the program."""
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.check"):
            yield
        if self.phase == "window":
            self.win.spans.setdefault("check", []).append(
                time.perf_counter() - t0)

    def _patch(self, owner, attr: str, new) -> None:
        self._restore.append((owner, attr, getattr(owner, attr)))
        setattr(owner, attr, new)

    def _attach(self, trainer) -> None:
        """First round: install the per-instance wrappers."""
        self.trainer = trainer
        if self.snapshots_on:
            take = trainer.snapshots.snapshot

            def snapshot(state, *, step, **kw):
                self.snap_start[step] = time.time()
                return take(state, step=step, **kw)
            trainer.snapshots.snapshot = snapshot
            self._sums_fn = check.state_sums_fn()
        if not self.tracing:
            return
        trainer.grad_fn = self._span("grad_step", trainer.grad_fn, True)
        trainer.apply_fn = self._span("fold_apply", trainer.apply_fn, True)
        if hasattr(trainer, "_fold_round"):
            trainer._fold_round = self._span("fold_apply",
                                             trainer._fold_round)
        if self.snapshots_on:
            trainer.snapshots.snapshot = self._span(
                "snapshot", trainer.snapshots.snapshot)

    def _install_module_wrappers(self) -> None:
        from repro.core import elastic
        if self.tracing:
            self._patch(elastic, "grad_hash",
                        self._span("validate.hash", elastic.grad_hash))
            from repro.kernels.delta_encode import ops
            probe = ops.fused_delta_tiles

            def fused_delta_tiles(o32, n32, **kw):
                bm, tiles = probe(o32, n32, **kw)
                if self.phase == "window":
                    self.win.probes.append((int(o32.shape[0]), bm))
                return bm, tiles
            self._patch(ops, "fused_delta_tiles", fused_delta_tiles)
        session = self
        orig = elastic.VolunteerTrainer.round

        def round_(trainer, step):
            return session._round(orig, trainer, step)
        self._patch(elastic.VolunteerTrainer, "round", round_)

    # ---------------- the round ----------------
    def _round(self, orig, trainer, step: int):
        if self.trainer is None:
            self._attach(trainer)
        if step == 0:
            self.prog["p0"] = _host_tree(trainer.state.params)
        start = time.perf_counter()
        if self.phase == "window" and start - self.t0 >= self.seconds:
            raise WindowClosed
        stats = orig(trainer, step)
        end = time.perf_counter()
        self._after(trainer, step, stats, start, end)
        return stats

    def _after(self, trainer, step, stats, start, end) -> None:
        import jax
        import jax.numpy as jnp
        if self.snapshots_on:
            with self._check():
                if step in self.snap_start:
                    # the state the round ended with is the one it snapshotted
                    self.sums[step] = self._sums_fn(trainer.state)
                self._poll_registered()
        if self.phase == "window":
            self.win.rounds.append((step, start, end))
            self.win.history.append(stats)
            return
        self.setup_rounds += 1
        self.prog.setdefault("loss", []).append(stats.loss)
        if step == 0:
            m = trainer.state.opt.m
            norms = jax.jit(lambda t: jax.tree.map(
                lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), t))(m)
            beta1 = self.t["optimizer"]["beta1"]
            self.prog["grad"] = {k: float(v) / (1 - beta1)
                                 for k, v in _host_tree(norms).items()}
        if step == CHECK_STEPS - 1:
            self.prog["p_end"] = _host_tree(trainer.state.params)
        if self.setup_rounds >= max(self.t["warmup_rounds"], CHECK_STEPS):
            self._open_window(trainer)

    def _open_window(self, trainer) -> None:
        import jax
        if self.tracing:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self._annotation = jax.profiler.TraceAnnotation("bench.window")
            self._annotation.__enter__()
        if trainer.snapshots is not None:
            self._writer0 = dict(trainer.snapshots.writer_stats)
        self.phase = "window"
        self.t_wall0 = time.time()
        self.t0 = time.perf_counter()

    def _poll_registered(self) -> None:
        snaps = self.trainer.snapshots
        for sid, man in dict(snaps.manifests).items():
            self.registered.setdefault(sid, (man.step, man.created))

    # ---------------- the run ----------------
    def run(self) -> None:
        """Set-up and window; returns when the window has closed."""
        from repro.launch import train
        self._install_module_wrappers()
        try:
            train.main(train_argv(self.cell, self.seed))
        except WindowClosed:
            pass
        finally:
            for owner, attr, old in reversed(self._restore):
                setattr(owner, attr, old)
        if self.phase != "window" or not self.win.rounds:
            raise RuntimeError("train.main ended before the window closed")
        self._close_window()

    def _close_window(self) -> None:
        import jax
        win, t = self.win, self.t
        last = win.rounds[-1]
        win.seconds = last[2] - self.t0
        win.tokens = len(win.rounds) * t["units_per_round"] * t["batch"] \
            * t["seq_len"]
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()
        snaps = self.trainer.snapshots
        if self.snapshots_on:
            # a snapshot taken in the window that becomes restorable after
            # the close is late, not lost: wait for the writer, so that its
            # lag counts and the check reads the window's newest snapshot
            snaps.wait()
            self._poll_registered()
            win.durable_lags = [
                created - self.snap_start[step]
                for step, created in self.registered.values()
                if self.snap_start.get(step, 0.0) >= self.t_wall0]
        if snaps is not None and snaps.is_async:
            now = snaps.writer_stats
            win.writer = {k: now[k] - self._writer0.get(k, 0)
                          for k in now}
        win.probes = [(n, int(np.asarray(bm).sum())) for n, bm in win.probes]

    # ---------------- after the window ----------------
    def snapshot_gap(self) -> float:
        """Leaves of the newest restorable snapshot whose bytes do not sum
        to the program's state after that round (0 when all agree)."""
        snaps = self.trainer.snapshots
        store = snaps.store
        lock = getattr(store, "gc_lock", None) or contextlib.nullcontext()
        with lock:
            sid = snaps.latest()
            if sid is None:
                return float("inf")
            man = snaps.get_manifest(sid)
            if man.step not in self.sums:
                return float("inf")
            want = {k: tuple(int(x) for x in v) for k, v in
                    _host_tree(self.sums[man.step]).items()}
            bad = 0
            for key, ent in man.tensors.items():
                got = check.host_sums(store.resolve_buffer(ent.refs))
                bad += got != want.get(key)
            bad += len(set(want) - set(man.tensors))
        return float(bad)

    def program_readings(self) -> dict:
        p0, p1 = self.prog["p0"], self.prog["p_end"]
        return {
            "loss": self.prog["loss"][:CHECK_STEPS],
            "grad": self.prog["grad"],
            "change": {k: float(np.sqrt(np.sum(
                (p1[k].astype(np.float64) - p0[k]) ** 2))) for k in p0},
            "shapes": {k: tuple(v.shape) for k, v in p0.items()},
        }

    def free_device(self) -> None:
        """Drop every device buffer the program holds: the reference runs
        next, on a chip whose peak has been read."""
        import gc

        import jax
        self.trainer = None
        gc.collect()
        for a in jax.live_arrays():
            a.delete()
