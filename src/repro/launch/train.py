"""V-BOINC training launcher.

End-to-end driver: boots a capsule for ``--arch``, attaches Base/Dep disks,
runs volunteer-scheduled data-parallel training with periodic differencing
snapshots, and survives worker failures / restarts.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --preset smoke --steps 50 --workers 4 --fail-prob 0.05 \
        --snapshot-every 10 --outdir /tmp/run1
    # crash it, then:
    ... --resume --steps 50       # continues bit-exactly from the snapshot

``--preset full`` keeps the assigned architecture at every published
width; ``--layers N`` cuts its depth only (the summary's ``reduced``
records the cut, with a share arch's held experts and vocabulary, e.g.
``mellum2-12b-a2.5b-ep8``), which is how one chip holds it:

    python -m repro.launch.train --arch granite-3-2b --preset full \
        --layers 1 --seq 1024 --batch 2 --snapshot-every 1 --async-writer

``--preset smoke``/``--preset 100m`` build reduced same-family configs
sized for a CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs.base import get_arch, reduced, share_cuts
from repro.core import telemetry as tlm
from repro.core.chunkstore import ChunkStore
from repro.core.elastic import SimWorker, VolunteerTrainer
from repro.core.scheduler import SimClock, VolunteerScheduler
from repro.core.snapshots import SnapshotManager
from repro.data.pipeline import DataConfig, TokenStream
from repro.distributed.sharding import init_tree
from repro.launch.jaxcache import use_compile_cache
from repro.models import api
from repro.models.lm import RunConfig
from repro.optim import adamw


def build_arch(name: str, preset: str, layers: int = 0):
    """-> (config, ``reduced`` record of the cuts made to the full preset:
    {key: [here, published]}).

    ``layers`` cuts the depth of ``full`` only; every width stays as
    published.  A share arch (``configs.base.expert_share``) records the
    experts and vocabulary rows it holds of its source as well."""
    cfg = get_arch(name)
    if preset != "full":
        if layers:
            raise ValueError("--layers cuts the depth of --preset full only")
        if preset == "smoke":
            return reduced(cfg), {}
        if preset == "100m":
            # ~100M-param same-family config (example application scale)
            return reduced(cfg, n_layers=6, d_model=512, n_heads=8,
                           n_kv_heads=4, d_ff=2048, vocab_size=32768), {}
        raise ValueError(preset)
    cuts = share_cuts(cfg)
    if not layers or layers == cfg.n_layers:
        return cfg, cuts
    if not 0 < layers < cfg.n_layers:
        raise ValueError(f"--layers must be in 1..{cfg.n_layers}")
    return (dataclasses.replace(cfg, n_layers=layers),
            {"n_layers": [layers, cfg.n_layers], **cuts})


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--preset", default="smoke",
                    choices=["smoke", "100m", "full"])
    ap.add_argument("--layers", type=int, default=0,
                    help="cut --preset full to N layers (depth only; every "
                         "width stays as published)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8, help="per micro-batch")
    ap.add_argument("--micro", type=int, default=2,
                    help="work units per optimizer step")
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--fail-prob", type=float, default=0.0)
    ap.add_argument("--corrupt-prob", type=float, default=0.0)
    ap.add_argument("--replication", type=int, default=1)
    ap.add_argument("--quorum", type=int, default=1)
    ap.add_argument("--shards", type=int, default=1,
                    help="shard the scheduler plane by account-key range "
                         "across N VolunteerScheduler shards (watermark "
                         "refill + work stealing; dispatch stays O(1) as "
                         "the fleet grows)")
    ap.add_argument("--rebalance", action="store_true",
                    help="elastic shard policy: after each round, split "
                         "the hottest shard into the coldest when its "
                         "backlog runs 2x ahead (needs --shards > 1)")
    ap.add_argument("--watermark", type=int, default=2,
                    help="per-volunteer pending-queue low watermark "
                         "(sharded plane only)")
    ap.add_argument("--refill-batch", type=int, default=8,
                    help="leases pulled per watermark refill scan "
                         "(sharded plane only)")
    ap.add_argument("--snapshot-every", type=int, default=10)
    ap.add_argument("--compress-grads", action="store_true",
                    help="int8+error-feedback gradient compression (4x "
                         "smaller volunteer result uploads)")
    ap.add_argument("--uplink", action="store_true",
                    help="delta-aware upload path: volunteers stream "
                         "quantized gradient deltas through the server's "
                         "chunk store; only changed blocks move up")
    ap.add_argument("--edge-caches", type=int, default=0,
                    help="edge delta caches fronting the snapshot store; "
                         "restore_latest routes through their discovery "
                         "service instead of the primary")
    ap.add_argument("--edge-capacity", type=int, default=1 << 28,
                    help="per-cache capacity in bytes (LRU by closure)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="replicate snapshot chains to N peer stores "
                         "(async, bounded outbox); the run survives a "
                         "primary store loss")
    ap.add_argument("--async-writer", action="store_true",
                    help="zero-stall snapshots: the round pays only the "
                         "device probe + changed-tile transfer; hashing, "
                         "RLE, store writes and chain rebase run on a "
                         "background writer thread (per-round stall is "
                         "reported as snapshot_stall_ms; a half-written "
                         "snapshot is never visible)")
    ap.add_argument("--writer-depth", type=int, default=2,
                    help="bounded queue depth for --async-writer; when the "
                         "writer falls behind by this many snapshots the "
                         "trainer blocks (counted as backpressure_ms in "
                         "the writer stats, i.e. visible stall) instead of "
                         "queueing unboundedly")
    ap.add_argument("--telemetry", default=None, metavar="DIR",
                    help="enable lifecycle tracing; writes events.jsonl "
                         "(flight recorder), metrics.prom (Prometheus "
                         "text exposition) and trace_summary.txt "
                         "(trace_reduce post-mortem) into DIR at exit")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--outdir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=1)
    args = ap.parse_args(argv)
    use_compile_cache()
    t_setup = time.perf_counter()

    cfg, cuts = build_arch(args.arch, args.preset, args.layers)
    run = RunConfig(remat="none", block_kv=min(args.seq, 512), ssm_chunk=64)
    specs = api.state_specs(cfg)
    oc = adamw.AdamWConfig(lr=args.lr, warmup_steps=10,
                           total_steps=max(args.steps * 2, 100))
    grad_fn = api.make_grad_step(cfg, run)

    def apply_fn(state, grads):
        # eager AdamW: the span holds the host's dispatch of its per-leaf
        # programs (the jitted update inside would only trace a span)
        with tlm.span("optimizer"):
            p, o, _ = adamw.update(oc, grads, state.opt, state.params)
        return api.TrainState(p, o)

    stream = TokenStream(DataConfig(cfg.vocab_size, args.seq, args.batch,
                                    seed=args.seed))
    # one shared clock for the scheduler AND the telemetry hub: with a
    # fixed seed the flight-recorder stream is byte-identical across runs
    clock = SimClock()
    tel_dir = Path(args.telemetry) if args.telemetry else None
    if tel_dir is not None:
        tel_dir.mkdir(parents=True, exist_ok=True)
        tlm.set_default(tlm.Telemetry(tracing=True, clock=clock))
    root = Path(args.outdir) if args.outdir else None
    store = ChunkStore(root / "store" if root else None)
    replicas = None
    if args.replicas > 0:
        from repro.core.replica import ReplicaSet
        peers = [ChunkStore(root / f"replica{i}" if root else None)
                 for i in range(args.replicas)]
        # the set IS the snapshot store: writes land on the primary and
        # fan out through the bounded outbox the trainer pumps per round
        store = replicas = ReplicaSet(store, peers)
    snaps = SnapshotManager(store, root=root / "snaps" if root else None,
                            keep_last=3, async_mode=args.async_writer,
                            writer_depth=args.writer_depth)
    if args.shards > 1:
        from repro.core.shardplane import ShardedScheduler
        sched = ShardedScheduler(shards=args.shards,
                                 replication=args.replication,
                                 quorum=args.quorum, deadline_s=30.0,
                                 watermark=args.watermark,
                                 refill_batch=args.refill_batch,
                                 clock=clock)
    else:
        sched = VolunteerScheduler(replication=args.replication,
                                   quorum=args.quorum, deadline_s=30.0,
                                   clock=clock)
    edge = None
    if args.edge_caches > 0:
        from repro.core.edge import EdgeCache, EdgeTier
        # read-only delta caches fronting the snapshot store: the
        # trainer's restore path drains from their discovery service, and
        # they earn scheduler transfer credit for the bytes they serve
        edge = EdgeTier(store,
                        [EdgeCache(f"edge-{i}",
                                   capacity_bytes=args.edge_capacity)
                         for i in range(args.edge_caches)],
                        scheduler=sched)

    server = None
    if args.uplink:
        # the volunteer project server: results come back as delta refs
        # through its chunk store instead of bare hashes
        from repro.core.capsule import CapsuleSpec
        from repro.core.server import Project, VBoincServer
        server = VBoincServer(ChunkStore())
        spec = CapsuleSpec(args.arch, "train_4k", run, arch_override=cfg)
        server.publish(Project("train", spec, scheduler=sched))
        server.register_user("launcher")

    # the initial state is handed over, not kept: a local reference here
    # would pin a whole extra state image on the device for the run
    trainer = VolunteerTrainer(
        grad_fn=grad_fn, apply_fn=apply_fn,
        state=api.TrainState(
            init_tree(specs.params, jax.random.key(args.seed)),
            init_tree(specs.opt, jax.random.key(args.seed))),
        stream=stream,
        micro_batches=args.micro, scheduler=sched, snapshots=snaps,
        snapshot_every=args.snapshot_every, seed=args.seed,
        compress_grads=args.compress_grads,
        server=server, project="train" if server else None,
        uplink=args.uplink, replicas=replicas, edge=edge)

    start_step = 0
    if args.resume:
        if root is not None:
            # pick up on-disk manifests from the previous process; ordered
            # by (step, created), NOT filename — snapshot ids restart per
            # process, so a resumed run's newest snapshot can sort first
            snaps.load_existing()
        abstract = jax.eval_shape(
            lambda: api.TrainState(init_tree(specs.params, jax.random.key(0)),
                                   init_tree(specs.opt, jax.random.key(0))))
        start_step = trainer.restore_latest(abstract)
        print(f"resumed from snapshot at step {start_step}")

    next_id = [0]

    def spawn(n: int) -> None:
        for _ in range(n):
            w = next_id[0]
            next_id[0] += 1
            trainer.add_worker(SimWorker(
                f"vol-{w}", fail_prob=args.fail_prob,
                corrupt_prob=args.corrupt_prob,
                rng=np.random.default_rng((args.seed, w))))

    spawn(args.workers)
    # elastic membership: replacements keep arriving as volunteers churn
    trainer.respawn = lambda tr: spawn(1)

    t0 = time.time()
    setup_s = time.perf_counter() - t_setup
    rebalance_splits = 0
    step_s = []
    for s in range(start_step, start_step + args.steps):
        alive = sum(w.alive for w in trainer.workers.values())
        if alive < args.workers:
            spawn(args.workers - alive)
        ts = time.perf_counter()
        st = trainer.round(s)
        step_s.append(time.perf_counter() - ts)
        if args.rebalance and args.shards > 1:
            moved = sched.rebalance()
            if moved is not None:
                rebalance_splits += 1
                print(f"step {s:4d} rebalance: split shard "
                      f"{moved['split']} -> {moved['target']} "
                      f"({moved['slots']} slots, "
                      f"{moved['reassigned_open']} open units)")
        if s % args.log_every == 0:
            up = (f" up {st.uplink_moved}/{st.uplink_dense}"
                  if args.uplink else "")
            print(f"step {st.step:4d} loss {st.loss:.4f} "
                  f"units {st.units} reissued {st.reissued} "
                  f"dup {st.duplicates} invalid {st.invalid} "
                  f"snap_bytes {st.snapshot_bytes} "
                  f"stall_ms {st.snapshot_stall_ms:.1f}{up}")
    snaps.close()                    # drain pending background writes
    wall = time.time() - t0
    tokens = args.steps * args.micro * args.batch * args.seq
    summary = {
        "arch": cfg.name, "reduced": cuts,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "steps": args.steps, "wall_s": round(wall, 2),
        "setup_s": round(setup_s, 2),
        "step_s": [round(x, 3) for x in step_s],
        "tokens_per_s": round(tokens / wall, 1),
        "losses": [h.loss for h in trainer.history],
        "final_loss": trainer.history[-1].loss,
        "state_bytes": sum(int(x.nbytes)
                           for x in jax.tree.leaves(trainer.state)),
        "mirror_bytes": snaps.mirror_bytes,
        "scheduler": dict(trainer.sched.stats),
        "store": dict(store.stats),
        "alive_workers": sum(w.alive for w in trainer.workers.values()),
        "snapshot_stall_ms": round(sum(
            h.snapshot_stall_ms for h in trainer.history), 2),
    }
    if args.shards > 1:
        summary["shard_plane"] = sched.shard_report()
        if args.rebalance:
            summary["rebalance_splits"] = rebalance_splits
    if args.async_writer:
        summary["snapshot_writer"] = {
            k: round(v, 2) if isinstance(v, float) else v
            for k, v in snaps.writer_stats.items()}
    if replicas is not None:
        replicas.flush()             # durability: drain the outbox on exit
        summary["replication"] = {**dict(replicas.rstats),
                                  **replicas.replication_report()}
    if edge is not None:
        summary["edge"] = {**{k: int(v) for k, v in dict(edge.stats).items()},
                           "caches": edge.describe()}
    if server is not None:
        log = server.uplinks.get("train")
        hist = trainer.history
        summary["uplink"] = {
            "bytes_in": log.bytes_in if log else 0,
            "bytes_dedup": log.bytes_dedup if log else 0,
            "accepted": log.accepted if log else 0,
            "rejected": log.rejected if log else 0,
            "dense_bytes": sum(h.uplink_dense for h in hist),
            "worker_credit": {w: round(i.credit, 3) for w, i in
                              trainer.sched.workers.items()},
        }
    if tel_dir is not None:
        tel = tlm.get_default()
        n_events = trainer.dump_flight_recorder(tel_dir / "events.jsonl")
        (tel_dir / "metrics.prom").write_text(tel.prometheus())
        report = tlm.trace_reduce(tel)
        (tel_dir / "trace_summary.txt").write_text(report.summary() + "\n")
        summary["telemetry"] = {
            "dir": str(tel_dir), "events": n_events,
            "reissues": report.reissues,
            "attribution_rate": round(report.attribution_rate, 4),
            "anomalies": report.anomaly_kinds(),
        }
    print(json.dumps(summary, indent=2))
    if root is not None:
        (root / "summary.json").write_text(json.dumps(summary))
    return summary


if __name__ == "__main__":
    main()
