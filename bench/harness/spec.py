"""What a cell is, read from data: ``BENCHMARK.json`` names the cells,
and each cell's configuration, traffic mix, limits and metrics live in
files of their own under ``bench/``, found by name.  Nothing here lists
a cell, a configuration or a metric."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    model: dict              # the configuration file, as run
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
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]),
        model=load_json(root / conf["file"]),
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


def arch_config(model: dict, name: str):
    """The program's ``ArchConfig`` for a configuration file in the
    Hugging Face ``config.json`` form (plus its ``equations`` block)."""
    from repro.configs.base import ArchConfig

    eq = model["equations"]
    if eq["norm"] != "rmsnorm" or eq["ffn"] != "swiglu" \
            or model["hidden_act"] != "silu":
        raise ValueError(f"{name}: the bench drives dense RMSNorm/SwiGLU "
                         f"models only, got {eq}")
    heads = model["num_attention_heads"]
    return ArchConfig(
        name=name, family="dense",
        n_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        n_heads=heads, n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        head_dim=model.get("head_dim") or model["hidden_size"] // heads,
        qk_norm=bool(eq["qk_norm"]),
        qkv_bias=bool(model.get("attention_bias", False)),
        attn_window=model.get("sliding_window"),
        rope_theta=float(model["rope_theta"]),
        act="silu", ffn_gated=True, norm_eps=float(model["rms_norm_eps"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        compute_dtype=model["torch_dtype"],
        source=f"bench/configs/{name}.json")
