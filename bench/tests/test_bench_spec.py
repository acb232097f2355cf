"""BENCHMARK.json against the benchmark's contract, and the benchmark's
data-driven layout: every cell, configuration, mix, limit and metric is a
file found by name."""
import dataclasses
import json
import re
import shutil

import jax
import pytest

from benchutil import BENCH, ROOT

import families
from harness import spec, weights

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_keys_names_and_units():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["bench"]
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCHMARK[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in BENCHMARK["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] == 1
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_every_cell_resolves_to_its_files():
    for w in BENCHMARK["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.limits["logit_gap"] > 0
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))


def test_per_layer_metrics_list_only_cells_reporting_what_they_move():
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", m["workloads"]))
    layers = {m["layer"] for m in BENCHMARK["per_layer"]}
    perf = (ROOT / "PERF.md").read_text()
    assert all(layer in perf for layer in layers)


@pytest.mark.parametrize("config", ["qwen3-0.6b", "h2o-danube-1.8b"])
def test_program_params_match_the_program_layout(config):
    """The weights the benchmark makes fill the program's own pytree,
    leaf for leaf, shape for shape."""
    from repro.models import lm

    model = spec.load_json(BENCH / "configs" / f"{config}.json")
    fam = families.load(model)
    cfg = fam.arch_config(model, config)
    dm = fam.dims(model)
    want = jax.eval_shape(lambda: lm.init_params(jax.random.key(0), cfg))
    got = jax.eval_shape(lambda: fam.program_params(
        weights._maker(fam, tuple(sorted(dm.items())), "float32")(
            jax.random.key(0)), dm))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [x.shape for x in jax.tree.leaves(got)] == \
        [x.shape for x in jax.tree.leaves(want)]
    # the shape arithmetic counts what the program's own count does: every
    # weight a token multiplies by, the embedding only as the (tied) head
    untied_embed = 0 if dm["tied"] else dm["vocab"] * dm["d"]
    assert fam.matmul_params(dm) == cfg.param_count() - untied_embed


def _arch(config):
    model = spec.load_json(BENCH / "configs" / f"{config}.json")
    return families.load(model).arch_config(model, config)


def test_configs_state_their_published_shapes():
    q = _arch("qwen3-0.6b")
    assert (q.n_layers, q.d_model, q.n_heads, q.n_kv_heads, q.head_dim_,
            q.d_ff, q.vocab_size) == (28, 1024, 16, 8, 128, 3072, 151936)
    assert q.qk_norm and q.tie_embeddings and q.rope_theta == 1e6
    d = _arch("h2o-danube-1.8b")
    assert (d.n_layers, d.d_model, d.n_heads, d.n_kv_heads, d.head_dim_,
            d.d_ff, d.vocab_size) == (24, 2560, 32, 8, 80, 6912, 32000)
    assert not d.qk_norm and not d.tie_embeddings and d.attn_window is None


def test_a_new_metric_needs_only_its_file_and_entry(tmp_path):
    """A later PR adds a metric by adding ``bench/metrics/<name>.py`` and
    an entry: no harness file changes."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "throwaway_ms", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "scheduler (serve/scheduler.py)",
        "moves": "ttft_p95_ms", "workloads": ["qwen3-chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "bench/metrics/throwaway_ms.py").write_text(
        "def read(run):\n    return 2.0 * run.setup_s\n")
    cell = spec.load_cell("qwen3-chat", root=tmp_path)
    assert "throwaway_ms" in [m["name"] for m in cell.per_layer]
    assert "throwaway_ms" not in [
        m["name"] for m in spec.load_cell("danube-batch",
                                          root=tmp_path).per_layer]
    read = spec.metric_reader("throwaway_ms", root=tmp_path)

    @dataclasses.dataclass
    class FakeRun:
        setup_s: float

    assert read(FakeRun(1.5)) == 3.0


def _run_bench(cwd, env_extra):
    import os
    import subprocess
    import sys

    env = {**os.environ, **env_extra}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    out = _run_bench(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout == ""
    assert "no TPU" in out.stderr


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = _run_bench(tmp_path, {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert out.returncode != 0 and out.stdout == ""
