"""Find a cell of ``BENCHMARK.json`` by name, with the files that belong to
it: the configuration under ``bench/configs/<config>.json``, the traffic
mix under ``bench/traffic/<traffic>.json`` and one reader per per-layer
metric under ``bench/layer_metrics/<metric>.py``.  Nothing here names a
cell, a configuration or a metric: a new one is new files and entries."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class CellError(ValueError):
    """A cell, or a file it names, is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    moves: str | None = None     # per-layer: the end-to-end metric it moves


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict              # bench/configs/<config>.json
    traffic: dict             # bench/traffic/<traffic>.json
    end_to_end: tuple         # Metric, those this cell reports
    per_layer: tuple          # Metric, those this cell reports

    @property
    def config_name(self) -> str:
        return self.config["name"]

    @property
    def traffic_name(self) -> str:
        return self.traffic["name"]


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise CellError(f"missing file {os.path.relpath(path, ROOT)}") from e


def _applies(entry: dict, cell_name: str) -> bool:
    return "workloads" not in entry or cell_name in entry["workloads"]


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def load_config(name: str, root: str = ROOT) -> dict:
    cfg = _read_json(os.path.join(root, "bench", "configs", f"{name}.json"))
    if cfg.get("name") != name:
        raise CellError(f"bench/configs/{name}.json names itself "
                        f"{cfg.get('name')!r}")
    return cfg


def load_traffic(name: str, root: str = ROOT) -> dict:
    mix = _read_json(os.path.join(root, "bench", "traffic", f"{name}.json"))
    return dict(mix, name=name)


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json; "
                        f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    cfg = load_config(w["config"], root)
    if cfg.get("chips", 1) != w["chips"]:
        raise CellError(f"workload {name!r} asks for {w['chips']} chips, its "
                        f"config holds {cfg.get('chips', 1)}")
    e2e = tuple(Metric(m["name"], m["unit"])
                for m in bench["end_to_end"] if _applies(m, name))
    layer = tuple(Metric(m["name"], m["unit"], m["moves"])
                  for m in bench["per_layer"] if _applies(m, name))
    for m in layer:
        metric_reader(m.name, root)          # every reader must exist
    return Cell(name, w["chips"], cfg, load_traffic(w["traffic"], root),
                e2e, layer)


def metric_reader(name: str, root: str = ROOT):
    """The ``read(ctx)`` function of ``bench/layer_metrics/<name>.py``."""
    path = os.path.join(root, "bench", "layer_metrics", f"{name}.py")
    if not os.path.exists(path):
        raise CellError(f"missing reader bench/layer_metrics/{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
