"""Find each piece of a cell by its name in ``BENCHMARK.json``.

A cell names a configuration (its ``file``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix names its client
(``bench/clients/<client>.py``); the configuration names its generator
(``bench/gen/<generator>.py``); each per-layer metric is read by
``bench/metrics/<metric>.py``. Adding a cell, a configuration or a metric
adds files and entries and edits none of these.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list       # BENCHMARK.json entries this cell reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, name: str, root: Path = ROOT) -> Cell:
    """The cell called ``name``; raises ``KeyError`` for an unknown one."""
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} (known: {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(name, w["chips"], config, traffic,
                [m for m in bench["end_to_end"] if _reports(m, name)],
                [m for m in bench["per_layer"] if _reports(m, name)])


def _load(path: Path, modname: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def client(name: str, root: Path = ROOT):
    """The module that sends the calls of traffic mixes with
    ``"client": name``."""
    return _load(root / "bench" / "clients" / f"{name}.py",
                 f"bench_client_{name}")


def generator(name: str, root: Path = ROOT):
    """The module whose ``graph(config)`` makes the graphs of
    configurations with ``"generator": name``."""
    return _load(root / "bench" / "gen" / f"{name}.py",
                 f"bench_gen_{name}")


def metric_reader(name: str, root: Path = ROOT):
    """The module whose ``read(run)`` gives per-layer metric ``name``."""
    return _load(root / "bench" / "metrics" / f"{name}.py",
                 "bench_metric_" + name.replace(".", "_").replace("-", "_"))
