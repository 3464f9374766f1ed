"""End-to-end behaviour tests for the paper's system: full volunteer
training rounds with failures, quorum validation, differencing snapshots
and bit-exact crash recovery (the V-BOINC guarantees, on real jax compute).
"""
import jax
import numpy as np
import pytest

from repro.configs.base import get_arch, reduced
from repro.core.chunkstore import ChunkStore
from repro.core.elastic import SimWorker, VolunteerTrainer
from repro.core.scheduler import SimClock, VolunteerScheduler
from repro.core.snapshots import SnapshotManager
from repro.data.pipeline import DataConfig, TokenStream
from repro.distributed.sharding import init_tree
from repro.models import api
from repro.models.lm import RunConfig
from repro.optim import adamw

RUN = RunConfig(remat="none", block_kv=8, ssm_chunk=8)
OC = adamw.AdamWConfig(lr=5e-3, warmup_steps=5, total_steps=500)


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_arch("granite-3-2b"))
    specs = api.state_specs(cfg)
    loss_fn = api.make_eval_loss(cfg, RUN)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    def apply_fn(state, grads):
        p, o, _ = adamw.update(OC, grads, state.opt, state.params)
        return api.TrainState(p, o)

    stream = TokenStream(DataConfig(cfg.vocab_size, 32, 4, seed=0))
    return cfg, specs, grad_fn, apply_fn, stream


def _trainer(setup, seed=0, snap=None, sched=None, micro=4):
    cfg, specs, grad_fn, apply_fn, stream = setup
    state = api.TrainState(init_tree(specs.params, jax.random.key(0)),
                           init_tree(specs.opt, jax.random.key(0)))
    return VolunteerTrainer(grad_fn=grad_fn, apply_fn=apply_fn, state=state,
                            stream=stream, micro_batches=micro,
                            scheduler=sched, snapshots=snap,
                            snapshot_every=2, seed=seed)


def test_reference_training_learns(setup):
    tr = _trainer(setup)
    for i in range(3):
        tr.add_worker(SimWorker(f"w{i}"))
    hist = tr.run(8)
    assert hist[-1].loss < hist[0].loss - 0.05
    assert all(h.invalid == 0 for h in hist)


def test_faulty_fleet_matches_reference_bitwise(setup):
    ref = _trainer(setup)
    for i in range(3):
        ref.add_worker(SimWorker(f"w{i}"))
    ref_hist = ref.run(5)

    sched = VolunteerScheduler(replication=2, quorum=2, deadline_s=5.0,
                               clock=SimClock())
    tr = _trainer(setup, seed=1, sched=sched)
    tr.add_worker(SimWorker("good0"))
    tr.add_worker(SimWorker("good1"))
    tr.add_worker(SimWorker("liar", corrupt_prob=0.3,
                            rng=np.random.default_rng(7)))
    tr.add_worker(SimWorker("flaky", fail_prob=0.25,
                            rng=np.random.default_rng(8)))
    hist = tr.run(5)
    for a, b in zip(ref_hist, hist):
        assert abs(a.loss - b.loss) < 1e-6     # deterministic replay


def test_crash_restore_is_bit_exact(setup):
    cfg, specs, grad_fn, apply_fn, stream = setup
    store = ChunkStore(chunk_bytes=1 << 14)
    snap = SnapshotManager(store, keep_last=2)
    ref = _trainer(setup)
    for i in range(2):
        ref.add_worker(SimWorker(f"w{i}"))
    ref_hist = ref.run(6)

    tr = _trainer(setup, snap=snap)
    for i in range(2):
        tr.add_worker(SimWorker(f"w{i}"))
    tr.run(4)                                    # snapshots at steps 1,3
    # "host terminates"; a new trainer restores the latest snapshot
    abstract = jax.eval_shape(
        lambda: api.TrainState(init_tree(specs.params, jax.random.key(0)),
                               init_tree(specs.opt, jax.random.key(0))))
    tr2 = _trainer(setup, seed=9)
    tr2.snapshots = snap
    next_step = tr2.restore_latest(abstract)
    assert next_step == 4
    for i in range(2):
        tr2.add_worker(SimWorker(f"n{i}"))
    cont = tr2.run(2, start_step=next_step)
    for a, b in zip(ref_hist[next_step:], cont):
        assert abs(a.loss - b.loss) < 1e-6


def test_differencing_snapshots_dedup(setup):
    store = ChunkStore(chunk_bytes=1 << 12)
    snap = SnapshotManager(store, keep_last=3)
    tr = _trainer(setup, snap=snap)
    tr.add_worker(SimWorker("w0"))
    tr.snapshot_every = 1
    tr.run(3)
    assert any(m.kind == "base" for m in snap.manifests.values())
    assert any(m.kind == "diff" for m in snap.manifests.values())
    # opt.step & friends change but frozen-ish chunks dedup across snapshots
    assert store.stats["dedup_chunks"] >= 0
    # latest restore works
    got, aux = snap.restore(target_tree=None)
    assert "cursor" in aux


def test_elastic_respawn_keeps_training(setup):
    tr = _trainer(setup, seed=3)
    tr.add_worker(SimWorker("mortal", fail_prob=0.9,
                            rng=np.random.default_rng(1)))
    spawned = []

    def respawn(trainer):
        wid = f"fresh{len(spawned)}"
        spawned.append(wid)
        trainer.add_worker(SimWorker(wid))

    tr.respawn = respawn
    hist = tr.run(2)
    assert len(hist) == 2 and len(spawned) >= 1


# ---------------------------------------------------------------------------
# the launcher: depth-only cut of a published config, resume through main
# ---------------------------------------------------------------------------
WIDTHS = ("d_model", "n_heads", "n_kv_heads", "d_ff", "vocab_size",
          "head_dim", "moe", "ssm")


def test_full_preset_layers_cuts_depth_only():
    from repro.launch.train import build_arch
    pub = get_arch("granite-3-2b")
    cfg, cuts = build_arch("granite-3-2b", "full", layers=1)
    assert cfg.n_layers == 1 and cuts == {"n_layers": [1, pub.n_layers]}
    assert all(getattr(cfg, w) == getattr(pub, w) for w in WIDTHS)
    assert build_arch("granite-3-2b", "full") == (pub, {})
    with pytest.raises(ValueError):
        build_arch("granite-3-2b", "smoke", layers=1)
    with pytest.raises(ValueError):
        build_arch("granite-3-2b", "full", layers=pub.n_layers + 1)


@pytest.fixture
def cache_config(monkeypatch):
    """Give the compile-cache setting back as it was after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield monkeypatch
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_dir_env_then_fixed_checkout_path(cache_config,
                                                        tmp_path):
    from repro.launch import jaxcache
    cache_config.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jaxcache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    cache_config.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = jaxcache.CHECKOUT / ".jax_cache"
    assert jaxcache.use_compile_cache() == str(fixed)
    assert jax.config.jax_compilation_cache_dir == str(fixed)
    assert (jaxcache.CHECKOUT / "src" / "repro").is_dir()
    ignored = (jaxcache.CHECKOUT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_train_main_resume_matches_uninterrupted(cache_config, tmp_path):
    from repro.launch import train
    cache_config.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    args = ["--seq", "16", "--batch", "2", "--snapshot-every", "1",
            "--async-writer", "--seed", "0"]
    full = train.main(args + ["--steps", "4",
                              "--outdir", str(tmp_path / "a")])
    train.main(args + ["--steps", "2", "--outdir", str(tmp_path / "b")])
    resumed = train.main(args + ["--steps", "2", "--resume",
                                 "--outdir", str(tmp_path / "b")])
    assert resumed["losses"] == full["losses"][2:]
    assert full["reduced"] == {} and len(full["step_s"]) == 4
    assert full["snapshot_writer"]["written"] == 4
    assert 0 < full["state_bytes"] <= full["mirror_bytes"]
