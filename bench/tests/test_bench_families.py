"""Architecture families (``bench/families``): the dense family gives the
numbers the harness gave before families existed, pinned here as
literals at full widths; a new family is a file found by name, and a
whole run goes through it with no harness file naming it."""
import hashlib
import json
import shutil
import time
import types
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from benchutil import BENCH, ROOT, TINY, tiny_model

import families
from harness import cli, reading, spec, weights
from harness.xtrace import Summary

DENSE = families.named("dense")

# Literals computed before the dense family was moved out of the harness
# (harness/weights.py and harness/shapes.py at the parent commit).
PINNED = {
    "qwen3-0.6b": {
        "dims": {"layers": 28, "d": 1024, "heads": 16, "kv_heads": 8,
                 "head_dim": 128, "ff": 3072, "vocab": 151936,
                 "tied": True, "qk_norm": True},
        "leaves": {"embed": (151936, 1024), "final_norm": (1024,),
                   "k_norm": (28, 128), "ln1": (28, 1024), "ln2": (28, 1024),
                   "q_norm": (28, 128), "w_down": (28, 3072, 1024),
                   "w_gate": (28, 1024, 3072), "w_up": (28, 1024, 3072),
                   "wk": (28, 1024, 1024), "wo": (28, 2048, 1024),
                   "wq": (28, 1024, 2048), "wv": (28, 1024, 1024)},
        "matmul_params": 595984384,
        "weight_bytes": {"bfloat16": 1192099840, "float32": 2384199680},
        "kv_bytes_per_token": 114688,
        "prefill_flops": 109111738368,
        "decode_work": (123604041728, 80881909760),
        "tiny_sha256": "374179c14427c007e0afa730c902d180"
                       "3f6e88c3df82b0d695f6124a592288e0",
        "gaps": (16.382068634033203, 2004.5537109375, 1.4594788551330566),
    },
    "h2o-danube-1.8b": {
        "dims": {"layers": 24, "d": 2560, "heads": 32, "kv_heads": 8,
                 "head_dim": 80, "ff": 6912, "vocab": 32000,
                 "tied": False, "qk_norm": False},
        "leaves": {"embed": (32000, 2560), "final_norm": (2560,),
                   "lm_head": (2560, 32000), "ln1": (24, 2560),
                   "ln2": (24, 2560), "w_down": (24, 6912, 2560),
                   "w_gate": (24, 2560, 6912), "w_up": (24, 2560, 6912),
                   "wk": (24, 2560, 640), "wo": (24, 2560, 2560),
                   "wq": (24, 2560, 2560), "wv": (24, 2560, 640)},
        "matmul_params": 1749155840,
        "weight_bytes": {"bfloat16": 3498562560, "float32": 6997125120},
        "kv_bytes_per_token": 61440,
        "prefill_flops": 285716807680,
        "decode_work": (345668321280, 226365603840),
        "tiny_sha256": "aaf81db75e6b20e3540d5efd13f3226d"
                       "541786f1326abf15dfe45f54788f40dc",
        "gaps": (14.29094123840332, 1923.489990234375, 0.6946616172790527),
    },
}
CONFIGS = sorted(PINNED)


def _model(config):
    return spec.load_json(BENCH / "configs" / f"{config}.json")


@pytest.mark.parametrize("config", CONFIGS)
def test_dense_counts_are_the_parents(config):
    model, want = _model(config), PINNED[config]
    assert families.load(model) is DENSE
    dm = DENSE.dims(model)
    assert dm == want["dims"]
    assert DENSE.leaves(dm) == want["leaves"]
    assert DENSE.matmul_params(dm) == want["matmul_params"]
    for dtype, n in want["weight_bytes"].items():
        assert DENSE.weight_bytes(dm, dtype) == n
    assert DENSE.kv_bytes_per_token(dm, "bfloat16") == \
        want["kv_bytes_per_token"]
    assert DENSE.prefill_flops(dm, 1024, 1100) == want["prefill_flops"]
    assert DENSE.decode_work(dm, "bfloat16", 64, 40000, 96) == \
        want["decode_work"]


def _tiny_weights(config):
    model = tiny_model(config)
    return model, weights.make(DENSE, model, 2**31 + 5)


@pytest.mark.parametrize("config", CONFIGS)
def test_dense_weights_keep_the_parents_checksum(config):
    _, w = _tiny_weights(config)
    h = hashlib.sha256()
    for k in sorted(w):
        h.update(k.encode())
        h.update(np.asarray(w[k]).tobytes())
    assert h.hexdigest() == PINNED[config]["tiny_sha256"]


@pytest.mark.parametrize("config", CONFIGS)
def test_dense_reference_gaps_are_the_parents(config):
    """The family's reference is ``reference/model.py``, which gives the
    parent's gaps on the parent's weights (float sums on the CPU may
    reorder with its thread count, hence the relative 1e-6)."""
    from reference import model as plain

    model, w = _tiny_weights(config)
    ref = families.reference(DENSE)
    assert Path(ref.__file__) == Path(plain.__file__)
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, 512, 256),
                         jnp.int32)
    tgt = jnp.asarray(np.random.default_rng(6).integers(0, 512, 256),
                      jnp.int32)
    g, gc = ref.gaps(w, tokens, tgt, cfg=ref.config(model), control=True)
    got = (float(jnp.max(g)), float(jnp.sum(g)), float(jnp.max(gc)))
    assert got == pytest.approx(PINNED[config]["gaps"], rel=1e-6)


# A family that is not dense: every norm gain exactly 1, a per-lane state
# of STATE_BYTES read and written per token beside the KV, and a
# reference of its own that records its calls.
TOY_FAMILY = '''"""Test family: dense, unit norm gains, a per-lane state."""
from pathlib import Path

import families

_dense = families.named("dense", Path(__file__).resolve().parents[1])
REFERENCE = "toyref"
STATE_BYTES = 4096
arch_config = _dense.arch_config
dims = _dense.dims
leaves = _dense.leaves
program_params = _dense.program_params
prefill_flops = _dense.prefill_flops


def scale(name, shape, dm):
    mean, std = _dense.scale(name, shape, dm)
    return (mean, 0.0) if mean == 1.0 else (mean, std)


def decode_work(dm, dtype, iterations, kv_lens_sum, tokens):
    flops, byts = _dense.decode_work(dm, dtype, iterations, kv_lens_sum,
                                     tokens)
    return flops, byts + 2 * STATE_BYTES * tokens
'''
TOY_REFERENCE_TAIL = '''

CALLS = []
_plain_gaps = gaps


def gaps(*a, **kw):
    CALLS.append(kw.get("control", False))
    return _plain_gaps(*a, **kw)
'''


@pytest.fixture
def toy_root(tmp_path):
    """A checkout whose only cell runs a configuration of family ``toy``,
    added as files: the family, its reference, its configuration, a mix
    and a limit."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    (bench / "families" / "toy.py").write_text(TOY_FAMILY)
    (bench / "reference" / "toyref.py").write_text(
        (BENCH / "reference" / "model.py").read_text() + TOY_REFERENCE_TAIL)
    model = tiny_model("qwen3-0.6b")
    model["equations"] = {**model["equations"], "family": "toy"}
    (bench / "configs" / "toy-tiny.json").write_text(json.dumps(model))
    chat = spec.load_json(BENCH / "traffic" / "chat.json")
    chat |= {"rate_rps": 6.0,
             "prompt": {"median": 24, "sigma": 0.8, "min": 16, "max": 80},
             "output": {"median": 24, "sigma": 0.7, "min": 8, "max": 80}}
    (bench / "traffic" / "toychat.json").write_text(json.dumps(chat))
    (bench / "limits" / "toy-chat.json").write_text('{"logit_gap": 0.65}')
    e2e = json.loads((ROOT / "BENCHMARK.json").read_text())["end_to_end"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy-tiny", "file": "bench/configs/toy-tiny.json"}],
        "workloads": [{"name": "toy-chat", "config": "toy-tiny",
                       "traffic": "toychat", "chips": 1}],
        "end_to_end": [{**m, "workloads": ["toy-chat"]} for m in e2e
                       if m["name"] in ("tpot_p95_ms", "setup_s")],
        "per_layer": []}))
    return tmp_path


def test_a_new_family_runs_through_the_harness(toy_root):
    cell = spec.load_cell("toy-chat", root=toy_root)
    fam = cell.family
    assert Path(fam.__file__) == toy_root / "bench" / "families" / "toy.py"
    dm = fam.dims(cell.model)
    assert dm["d"] == TINY["hidden_size"]

    w = weights.make(fam, cell.model, 11)
    norms = [k for k in w if k.endswith("norm") or k in ("ln1", "ln2")]
    assert norms and all(bool(jnp.all(w[k] == 1.0)) for k in norms)
    dense_w = weights.make(DENSE, cell.model, 11)
    assert not bool(jnp.all(dense_w["ln1"] == 1.0))
    assert bool(jnp.all(dense_w["wq"] == w["wq"]))

    # the work counts the metric files read come from the family
    reqs = [{"prompt_len": 64, "generated0": 0, "generated": 9,
             "computed": 64, "prefilled": 64}]
    window = types.SimpleNamespace(requests=reqs, counters={
        "decode_loop_iters": 8, "decode_tokens": 8})
    trace = Summary(window_s=1.0, busy_s=1.0, n_devices=1,
                    programs={"fused": 1e-3}, program_runs={"fused": 1},
                    ops=[], idle_gaps=[])
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    def roofline(family):
        run = reading.Run(cell=types.SimpleNamespace(family=family), dims=dm,
                          dtype="bfloat16", n_slots=4, setup_s=0.0,
                          window=window, trace=trace, peaks=peaks)
        return reading.decode_roofline(run), reading.model_flops(run)

    (toy_share, toy_flops), (dense_share, dense_flops) = \
        roofline(fam), roofline(DENSE)
    assert toy_flops == dense_flops
    assert toy_share - dense_share == pytest.approx(
        100.0 * 2 * fam.STATE_BYTES * 8 / 819e9 / 1e-3)

    # a whole run checks its tokens against the family's reference
    ref = families.reference(fam)
    assert Path(ref.__file__).name == "toyref.py" and not ref.CALLS
    res = cli.run_cell(cell, 2**31 + 21, 4.0, False, time.perf_counter())
    assert res["correct"] and res["attempted"] > 10
    assert ref.CALLS and not any(ref.CALLS)


def test_no_harness_file_names_a_family():
    """spec, weights, correct, reading and the rest of the
    harness reach the architecture only through ``cell.family``."""
    for path in (BENCH / "harness").glob("*.py"):
        text = path.read_text()
        assert "dense" not in text and "reference import" not in text, path
