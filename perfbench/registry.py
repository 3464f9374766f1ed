"""Find every piece of the benchmark by the name ``BENCHMARK.json`` gives it.

A cell names a configuration and a traffic mix; each lives in a file of its
own, as does each per-layer metric's reader and each cell's limits:

    perfbench/configs/<config>.json   sizes as run, source, cuts
    perfbench/configs/<config>.py     its plain float32 reference
    perfbench/traffic/<mix>.json      the training job one round is cut from
    perfbench/metrics/<metric>.py     ``read(ctx) -> float | None``
    perfbench/limits/<cell>.json      the limit of each number compared
    perfbench/peaks.json              chip peaks keyed by ``device_kind``

A later cell, configuration, mix or metric is a new file and a new entry;
no file here changes.  Nothing in this module touches JAX.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parent


class UnknownName(LookupError):
    """A name that no file or entry of the benchmark defines."""


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise UnknownName(f"no file {path.relative_to(CHECKOUT)}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str) -> ModuleType:
    """Import a file of the benchmark by path (its name may hold '.' or '-')."""
    if not path.is_file():
        raise UnknownName(f"no file {path.relative_to(CHECKOUT)}")
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = _load_json(BENCH_DIR / "peaks.json")
    kinds = table["kinds"]
    if device_kind not in kinds:
        raise UnknownName(f"device kind {device_kind!r} is not in "
                          f"peaks.json (known: {sorted(kinds)})")
    return dict(kinds[device_kind], source=table["source"])


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)

    def reference(self) -> ModuleType:
        return load_module(BENCH_DIR / "configs" / f"{self.config['name']}.py",
                           "ref_" + self.config["name"])

    def reader(self, metric: str) -> ModuleType:
        return load_module(BENCH_DIR / "metrics" / f"{metric}.py",
                           "metric_" + metric)


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in reported


def load_cell(name: str) -> Cell:
    bench = _load_json(CHECKOUT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise UnknownName(f"no workload {name!r} in BENCHMARK.json "
                          f"(known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise UnknownName(f"workload {name!r} names no configuration "
                          f"{w['config']!r}")
    config = _load_json(BENCH_DIR / "configs" / f"{w['config']}.json")
    if config.get("name") != w["config"]:
        raise UnknownName(f"configs/{w['config']}.json names itself "
                          f"{config.get('name')!r}")
    traffic = _load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(BENCH_DIR / "limits" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, reported)]
    return Cell(name, int(w["chips"]), config, traffic, limits, e2e,
                per_layer)
