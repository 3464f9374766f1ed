"""Mellum2-12B-A2.5B's chip share against its plain reference, on the CPU.

At a small size on seeded random weights the program's loss and gradients
equal the reference's (sliding windows shorter than the sequence, YaRN on
the full layer, 8 experts routed top-2 with 2 held); the reference takes
each layer's kind from the order of its calls; at the published widths
the program's parameter tree is the reference's; both new cells load, and
``train.main``'s ``build_arch`` accepts their arguments.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import check, harness, moe_yardstick, registry
from perfbench import reference as R
from perfbench.harness import Window
from repro.configs.base import LayerKind, MoEConfig, Rope, get_arch
from repro.core import telemetry as tlm
from repro.distributed.sharding import init_tree
from repro.launch.train import build_arch
from repro.models import api
from repro.models.lm import RunConfig

CELL = "mellum2-12b-a2.5b.train_4k"
SHARE = "mellum2-12b-a2.5b-ep8"
ROPE = {"sliding_attention": {"rope_type": "default", "rope_theta": 10000},
        "full_attention": {"rope_type": "yarn", "rope_theta": 10000,
                           "factor": 4, "original_max_position_embeddings": 64,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2}}
KINDS = ["sliding_attention"] * 3 + ["full_attention"]


def _small():
    """(program config, reference config): 4 layers, window 8 at 32
    positions, YaRN whose ramp spans dimensions 0-2 of 8."""
    sliding = LayerKind(8, Rope(10000.0))
    full = LayerKind(0, Rope(10000.0, factor=4.0, original_max_positions=64,
                             beta_fast=32.0, beta_slow=1.0,
                             attention_factor=1.2))
    cfg = dataclasses.replace(
        get_arch(SHARE), n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, vocab_size=256,
        moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, experts_held=2),
        layer_types=(sliding, sliding, sliding, full))
    c = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 16, "router_experts": 8, "num_experts": 2,
         "num_experts_per_tok": 2, "moe_intermediate_size": 32,
         "vocab_size": 256, "padded_vocab_size": 256, "num_hidden_layers": 4,
         "layer_types": KINDS, "sliding_window": 8, "rope_parameters": ROPE,
         "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
         "embedding_multiplier": 1.0, "logits_scaling": 1.0}
    return cfg, c


def _tokens(seed, b=2, t=32, vocab=256):
    r = np.random.default_rng(seed)
    return (r.integers(0, vocab, (b, t)).astype(np.int32),
            r.integers(0, vocab, (b, t)).astype(np.int32))


def test_program_matches_reference_loss_and_grads():
    cfg, c = _small()
    ref = registry.load_cell(CELL).reference()
    params = init_tree(api.param_specs(cfg), jax.random.key(7))
    tokens, labels = _tokens(7)
    run = RunConfig(remat="none", block_kv=8, compute_dtype=jnp.float32)
    (loss, routing), grads = api.make_grad_step(cfg, run)(
        params, {"tokens": tokens, "labels": labels})
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(
            lambda p: R.lm_loss(p, tokens, labels, c, R.Precision(), ref.block)
        )(params)
    # float32 on both sides: blocked online softmax against chunked full
    # softmax, grouped products against dense per-expert terms; the sums
    # run in other orders, so they agree to float32 round-off
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        w = want_grads
        for key in path:
            w = w[key.key]
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-3,
                                   atol=1e-6, err_msg=jax.tree_util.keystr(path))
    # every pair of every layer is counted, here or elsewhere
    np.testing.assert_array_equal(
        np.asarray(routing["moe_pairs_here"])
        + np.asarray(routing["moe_pairs_elsewhere"]), [2 * 32 * 2] * 4)


def test_reference_applies_layer_types_in_order(monkeypatch):
    _, c = _small()
    ref = registry.load_cell(CELL).reference()
    seen = []
    attention = ref.attention

    def spy(p, x, c, kind, pr):
        seen.append(kind)
        return attention(p, x, c, kind, pr)
    monkeypatch.setattr(ref, "attention", spy)
    params = R.init_params(ref.param_specs(c), 1, 0.02)
    tokens, labels = _tokens(1)
    for n in (4, 2, 4):
        seen.clear()
        R.lm_loss(params, tokens, labels, dict(c, num_hidden_layers=n),
                  R.Precision(), ref.block)
        assert seen == KINDS[:n]


def test_state_specs_match_reference_param_specs():
    cell = registry.load_cell(CELL)
    cfg, _ = build_arch(SHARE, "full", cell.config["num_hidden_layers"])
    prog = jax.tree_util.tree_flatten_with_path(
        api.state_specs(cfg).params, is_leaf=lambda x: hasattr(x, "axes"))[0]
    want = jax.tree_util.tree_flatten_with_path(
        cell.reference().param_specs(cell.config),
        is_leaf=lambda x: isinstance(x, R.Leaf))[0]
    assert [(jax.tree_util.keystr(p), s.shape) for p, s in prog] == \
        [(jax.tree_util.keystr(p), s.shape) for p, s in want]


@pytest.mark.parametrize("name", [CELL, "granite-3-2b.quorum2"])
def test_new_cells_load_and_build(name):
    cell = registry.load_cell(name)
    argv = harness.train_argv(cell, 2 ** 31 + 9)
    opts = dict(zip(argv[::2], argv[1::2]))
    cfg, cuts = build_arch(opts["--arch"], opts["--preset"],
                           int(opts["--layers"]))
    assert cfg.n_layers == cell.config["num_hidden_layers"]
    assert cfg.vocab_size == cell.config["vocab_size"]
    if name == CELL:
        assert cuts == {"n_layers": [4, 28], "experts_held": [8, 64],
                        "vocab_size": [12288, 98304]}
        assert cfg.moe.held == cell.config["num_experts"]
        assert cfg.moe.n_experts == cell.config["router_experts"]
        assert [k.window for k in cfg.layer_kinds()] == [1024, 1024, 1024, 0]
        # 4 x (attention 21,233,664 + router 147,456 + 8 experts x
        # 6,193,152) + embedding and head of 12,288 rows (norms not counted)
        assert cfg.param_count() == 340_328_448
        # of the 8 held experts, a token reaches 8 x 8 / 64 = 1 on average
        assert cfg.active_param_count() == 340_328_448 - 4 * 7 * 6_193_152


def test_whole_model_counts():
    whole = get_arch("mellum2-12b-a2.5b")
    # 28 x (21,233,664 + 147,456 + 64 x 6,193,152) + 2 x 98,304 x 2,304
    assert whole.param_count() == 12_149_784_576          # 12.15 B
    # 8 of the 64 experts a token
    assert whole.active_param_count() == \
        12_149_784_576 - 28 * 56 * 6_193_152               # 2.44 B


def test_flops_per_token_from_the_configuration():
    c = registry.load_cell(CELL).config
    # 6 x (4 x (21,233,664 + 147,456) + 12,288 x 2,304) + 3 x 2 x 2 x 32 x
    # 128 x (3 x 896.125 + 2,048.5) mean keys
    assert moe_yardstick.dense_flops_per_token(c, 4096) == 915_843_072.0
    # 6 x 3 x 2,304 x 896 a pair
    assert moe_yardstick.flops_per_pair(c) == 37_158_912.0
    assert moe_yardstick.mean_visible_keys(8, 0) == 4.5
    assert moe_yardstick.mean_visible_keys(8, 2) == (3 + 6 * 2) / 8


def test_round_mfu_counts_the_pairs_routed_here(monkeypatch):
    hub = tlm.Telemetry()
    monkeypatch.setattr(tlm, "_DEFAULT", hub)
    cell = registry.load_cell(CELL)
    w = Window(cell, {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1.0}, 1)
    reader = cell.reader("moe_round.mfu")
    w.rounds, w.seconds, w.tokens = [(5, 0.0, 1.0)], 2.0, 2 * 8192
    assert reader.read(w) is None                       # nothing recorded
    # two units of 8,192 tokens, 1 and 3 pairs here a token; a set-up
    # round's unit is left out
    for unit, step, pairs in ((8, 4, 10 ** 9), (10, 5, 8192), (11, 5, 24576)):
        hub.reading("moe.pairs_here", pairs, step=step, unit=unit)
    flops = (915_843_072.0 + 2 * 37_158_912.0) * 2 * 8192
    assert reader.read(w) == pytest.approx(100 * flops / (2.0 * 1e12))


def test_held_load_reads_the_window_units(monkeypatch):
    hub = tlm.Telemetry()
    monkeypatch.setattr(tlm, "_DEFAULT", hub)
    cell = registry.load_cell(CELL)
    w = Window(cell, {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}, 1)
    reader = cell.reader("moe.held_load_max")
    w.rounds = [(5, 0.0, 1.0)]
    assert reader.read(w) is None                       # nothing recorded
    # 32 slots (8 held x 4 layers); a set-up round's unit is left out
    for unit, step, top in ((8, 4, 9999), (10, 5, 2048), (11, 5, 1024)):
        hub.reading("moe.max_expert_pairs", top, step=step, unit=unit)
        hub.reading("moe.pairs_here", 32768, step=step, unit=unit)
    assert reader.read(w) == pytest.approx((2.0 + 1.0) / 2)


# smoke sizes of the harness's own run: the program's ``smoke`` preset of
# the share (2 layers, width 64, 4 experts of which 2 held, top-2) and the
# reference at the same sizes
SMOKE = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "router_experts": 4,
         "num_experts": 2, "moe_intermediate_size": 32, "vocab_size": 256,
         "padded_vocab_size": 256, "num_experts_per_tok": 2}
# between the largest gap of sound runs (seeds 1-3 and 2**31 + 11) and the
# smallest of the float8 control (seeds 1-3) at these sizes on the CPU:
# loss 9.4e-5 / 7.6e-5 (no gap between them: the control fails by the
# others), grad 2.9e-3 / 6.9e-3, change 1.9e-3 / 4.0e-3
SMOKE_LIMITS = {"loss_gap": 1e-3, "grad_gap": 5e-3, "change_gap": 3e-3}


def _smoke_cell():
    cell = registry.load_cell(CELL)
    cell.config = dict(cell.config, program_preset="smoke", **SMOKE)
    cell.limits = {k: {"limit": v} for k, v in SMOKE_LIMITS.items()}
    cell.traffic = dict(cell.traffic, seq_len=32, batch=4, warmup_rounds=3)
    return cell


def test_run_is_correct_on_the_cpu():
    from perfbench import run
    res = run.measure(_smoke_cell(), 2 ** 31 + 11, 0.2, False,
                      jax.devices(), {"bf16_flops_per_s": 1.0,
                                      "hbm_bytes_per_s": 1.0}, t_start=0.0)
    assert res["correct"], res["checks"]


def test_control_fails_the_smoke_limits():
    cell = _smoke_cell()
    for seed in (1, 2, 3):
        ref = check.follow(cell, seed, harness.CHECK_STEPS)
        ctl = check.follow(cell, seed, harness.CHECK_STEPS, "fp8")
        ok, checks = check.judge(check.gaps(ctl, ref), cell.limits)
        assert not ok, (seed, checks)
