"""What a cell is, read from data: ``BENCHMARK.json`` names the cells,
and each cell's configuration, traffic mix, limits and metrics live in
files of their own under ``bench/``, found by name, as does the family
of the configuration's architecture (``bench/families``).  Nothing here
lists a cell, a configuration, a family or a metric."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

import families

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    model: dict              # the configuration file, as run
    family: object           # its module under bench/families
    traffic: dict            # the traffic mix file
    limits: dict             # bench/limits/<cell>.json
    end_to_end: tuple        # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell in cells


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    model = load_json(root / conf["file"])
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]), model=model,
        family=families.load(model, root / "bench"),
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(root / "bench" / "limits" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)))


def metric_reader(name: str, root: Path = ROOT):
    """``read(run) -> float | None`` from ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
