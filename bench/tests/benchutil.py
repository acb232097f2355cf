"""Shared set-up of the benchmark's tests: import paths and tiny cells."""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import spec  # noqa: E402

TINY = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 512}


def tiny_model(config: str, **over) -> dict:
    """A configuration file shrunk to a size the CPU runs in seconds."""
    model = spec.load_json(BENCH / "configs" / f"{config}.json")
    model.update(TINY, **over)
    model["serve"] = {"n_slots": 4, "max_len": 256}
    return model


def tiny_cell(name: str, **mix_over) -> spec.Cell:
    """Cell ``name`` with its model and traffic shrunk: short prompts and
    outputs, a rate the CPU keeps up with; ``mix_over`` overrides keys of
    the shrunk mix."""
    cell = spec.load_cell(name)
    mix = {**cell.traffic}
    for k in ("prompt", "output"):
        mix[k] = {**mix[k], "median": min(mix[k]["median"], 24),
                  "max": min(mix[k]["max"], 80)}
        mix[k]["min"] = min(mix[k]["min"], mix[k]["median"])
    if mix["loop"] == "open":
        mix["rate_rps"] = 6.0
    else:
        mix |= {"clients": 8, "requests": 32}
    mix |= mix_over
    return spec.Cell(name=name, config_name=cell.config_name,
                     traffic_name=cell.traffic_name, chips=1,
                     model=tiny_model(cell.config_name),
                     family=cell.family, traffic=mix, limits=cell.limits,
                     end_to_end=cell.end_to_end, per_layer=cell.per_layer)
