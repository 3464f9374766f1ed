"""The benchmark finds every piece by name, and BENCHMARK.json keeps to the
shape the harness and its checker rely on."""
from __future__ import annotations

import json
import re

import pytest

from perfbench import registry, yardstick
from perfbench.harness import Window, train_argv

BENCH = json.loads((registry.CHECKOUT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_finds_its_files_by_name(name):
    cell = registry.load_cell(name)
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    assert cell.config["name"] == entry["config"]
    ref = cell.reference()
    assert callable(ref.param_specs) and callable(ref.block)
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                     "tokens_per_s"}
    assert cell.per_layer, "every cell reports a per-layer metric"
    assert {"loss_gap", "grad_gap", "change_gap"} <= set(cell.limits)


@pytest.mark.parametrize("name", CELLS)
def test_readers_return_nothing_on_an_empty_window(name):
    cell = registry.load_cell(name)
    empty = Window(cell, {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
                   1)
    for m in cell.per_layer:
        assert cell.reader(m["name"]).read(empty) is None, m["name"]


@pytest.mark.parametrize("kind,known", [("TPU v5 lite", True),
                                        ("TPU v4", False), ("cpu", False)])
def test_peaks_are_keyed_by_device_kind(kind, known):
    if known:
        p = registry.peaks(kind)
        assert p["bf16_flops_per_s"] == 197e12
        assert p["hbm_bytes_per_s"] == 819e9
        assert "cloud.google.com" in p["source"]
    else:
        with pytest.raises(registry.UnknownName):
            registry.peaks(kind)


@pytest.mark.parametrize("what", ["workload", "reader", "reference"])
def test_unknown_names_are_refused(what):
    if what == "workload":
        with pytest.raises(registry.UnknownName):
            registry.load_cell("no-such.cell")
        return
    cell = registry.load_cell(CELLS[0])
    with pytest.raises(registry.UnknownName):
        if what == "reader":
            cell.reader("no.such_metric")
        else:
            cell.config = dict(cell.config, name="no-such-config")
            cell.reference()


def test_benchmark_json_names_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    assert all(set(m.get("workloads", CELLS)) <= set(CELLS)
               for m in BENCH["end_to_end"] + BENCH["per_layer"])
    assert all(len(x) <= 64 and "\n" not in x for x in layers)
    for c in BENCH["configs"]:
        doc = json.loads((registry.CHECKOUT / c["file"]).read_text())
        assert set(doc["reduced"]) <= set(c["reduced"])
        assert all(k in doc for k in c["reduced"])


@pytest.mark.parametrize("name", CELLS)
def test_train_argv_states_the_traffic(name):
    cell = registry.load_cell(name)
    argv = train_argv(cell, 2 ** 31 + 5)
    t = cell.traffic
    pairs = dict(zip(argv[::2], argv[1::2]))
    assert pairs["--layers"] == str(cell.config["num_hidden_layers"])
    assert pairs["--batch"] == str(t["batch"])
    assert pairs["--seq"] == str(t["seq_len"])
    assert pairs["--snapshot-every"] == str(t["snapshot_every"])
    assert pairs["--seed"] == str(2 ** 31 + 5)
    assert ("--async-writer" in argv) == t["async_writer"]


@pytest.mark.parametrize("name,per_token", [
    # 6 x (one layer's 60,817,408 products + 100,669,440 output head)
    # + 3 x 2 x 2 x 32 heads x 512 mean causal span x 64
    ("granite-3-2b.snap_every_8", 981_504_000.0),
    # 6 x (48,732,800 + 51,201,600) + 3 x 2 x 2 x 25 x 512 x 64
    ("hymba-1.5b.train", 609_436_800.0)])
def test_flops_per_token_from_the_configuration(name, per_token):
    cell = registry.load_cell(name)
    assert yardstick.train_flops_per_token(
        cell.config, cell.traffic["seq_len"]) == per_token


@pytest.mark.parametrize("nblk,changed,want", [
    (1, 0, 2 * 32768 + 4096), (1024, 1024, 3 * 1024 * 32768 + 4096),
    (1025, 3, (2050 + 3) * 32768 + 2 * 4096)])
def test_probe_bytes_from_shapes(nblk, changed, want):
    assert yardstick.probe_bytes(nblk, changed) == want


@pytest.mark.parametrize("seed", [1, 2147484002, 2147484004])
@pytest.mark.parametrize("vocab", [256, 49155])
def test_stream_seed_keeps_long_cycled_chains(seed, vocab):
    import math

    import numpy as np

    from perfbench import reference as R
    data = {"markov_order": 1, "noise": 0.05}
    assert R.stream_seed(seed, data, vocab) == seed
    data["long_cycles"] = True
    got = R.stream_seed(seed, data, vocab)
    assert seed <= got < seed + R.MAX_SEED_TRIES
    assert R.stream_seed(got, data, vocab) == got
    head = np.random.default_rng(got)
    mix = int(head.integers(1, vocab, size=(1,), dtype=np.int64)[0])
    bias = int(head.integers(0, vocab))
    assert math.gcd(mix, vocab) == 1
    perm = (mix * np.arange(vocab) + bias) % vocab
    assert np.mean(R._cycle_lengths(perm) < 40) < 0.5
