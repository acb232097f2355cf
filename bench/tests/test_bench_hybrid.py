"""The hybrid family (granite-4.0-h-micro) at a tiny size on the CPU: the
program served through ``ServeScheduler`` against the plain reference
(``reference/hybrid.py``), the reference against the program's own
forward, and the family's counts at full width, pinned."""
import dataclasses
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchutil import BENCH, tiny_model

import families
from harness import correct, spec, weights

CONFIG = "granite-4.0-h-micro"
SEED = 2**31 + 77
# f32 on both sides: the program's chunked SSD, bucketed chunks and
# decode steps against the reference's sequential scan over the whole
# sequence differ by float32 rounding, about 1e-5 at logits that spread
# by 2.5.  A state that absorbs pad tokens, a dropped multiplier or
# norm, or a slot that keeps its last request's state moves the served
# tokens' gaps by the logits' own scale.
SERVED_GAP = 1e-3
# Program and reference logits over one prompt, as above.
LOGITS_ATOL = 1e-4


def tiny_hybrid() -> dict:
    """The configuration at widths the CPU serves in seconds, with two
    repeats of a three-layer pattern that holds both kinds."""
    return tiny_model(CONFIG, shared_intermediate_size=128, mamba_d_head=16,
                      mamba_n_heads=8, mamba_d_state=16, num_hidden_layers=6,
                      layer_types=["mamba", "attention", "mamba"] * 2)


def _program(model):
    """The program's config in float32, and its weights: the
    configuration's bf16 values, held in float32."""
    fam = families.load(model)
    cfg = dataclasses.replace(fam.arch_config(model, CONFIG),
                              param_dtype="float32", compute_dtype="float32")
    w = weights.make(fam, model, SEED, "bfloat16")
    params = fam.program_params(
        {k: v.astype(jnp.float32) for k, v in w.items()}, fam.dims(model))
    return fam, cfg, params


def _serve(cfg, params, prompts, new_tokens):
    """Serve ``prompts`` through the scheduler as the benchmark builds it
    (fused loop, adaptive admission and depth), on 2 slots with buckets
    of 16 and 32, so long prompts prefill in several padded chunks and a
    later request reuses a slot.  Returns (tokens per request, slots
    used per request, pad positions prefilled)."""
    from repro.core.acc import AdaptiveCoreChunk
    from repro.core.adaptive import adaptive
    from repro.core.calibration import CalibrationCache
    from repro.core.executor import SequentialExecutor
    from repro.serve import ServeScheduler, spans

    acc = AdaptiveCoreChunk(cache=CalibrationCache())
    sched = ServeScheduler(
        cfg, params, n_slots=2, max_len=128, chunk_buckets=(16, 32),
        executor=adaptive(SequentialExecutor(), acc),
        dispatch_depth="auto", admission="adaptive")
    assert sched._pad_ok
    t0 = time.perf_counter()
    rids = [sched.submit(p, max_new_tokens=new_tokens) for p in prompts]
    slots = {}
    while sched.pending:
        sched.tick()
        for rid in rids:
            r = sched.requests[rid]
            if r.slot is not None:
                slots.setdefault(rid, r.slot)
    sched.flush()
    out = sched.results()
    pads = sum(s.attrs["pad"] for s in spans.RECORDER.between(
        t0, time.perf_counter()) if s.name == "serve.prefill.chunk")
    return [out[r] for r in rids], [slots[r] for r in rids], pads


def _served_gap(fam, model, prompts, served):
    clients = [types.SimpleNamespace(prompt=p, tokens=t)
               for p, t in zip(prompts, served, strict=True)]
    return correct.compare(fam, model, SEED, clients, 128, control=True)


@pytest.fixture(scope="module")
def served():
    model = tiny_hybrid()
    fam, cfg, params = _program(model)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (70, 37, 45)]
    toks, slots, pads = _serve(cfg, params, prompts, 12)
    return model, fam, prompts, toks, slots, pads


def test_served_tokens_match_the_reference(served):
    model, fam, prompts, toks, slots, pads = served
    assert all(len(t) == 12 for t in toks)
    assert pads > 0                       # chunks ran padded to buckets
    assert len(set(slots)) < len(slots)   # a later request reused a slot
    cmp = _served_gap(fam, model, prompts, toks)
    assert cmp["logit_gap"] < SERVED_GAP
    # the fp8 control departs from the program by the logits' scale
    assert cmp["control_logit_gap"] > 100 * SERVED_GAP


def test_pads_that_reach_the_state_are_caught(monkeypatch):
    """A Mamba layer that is not told its chunk's real length lets the
    pad tokens into its state: the served tokens then fail the
    reference."""
    from repro.models import mamba2

    real = mamba2.apply
    monkeypatch.setattr(mamba2, "apply", lambda p, x, cfg, *, state=None,
                        length=None: real(p, x, cfg, state=state))
    model = tiny_hybrid()
    fam, cfg, params = _program(model)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (70, 37)]
    toks, _, pads = _serve(cfg, params, prompts, 8)
    assert pads > 0
    assert _served_gap(fam, model, prompts, toks)["logit_gap"] > SERVED_GAP


def test_reference_matches_program_forward():
    from repro.models import lm

    model = tiny_hybrid()
    fam, cfg, params = _program(model)
    ref = families.reference(fam)
    c = dict(ref.config(model))
    tokens = np.random.default_rng(0).integers(0, 512, 256).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = lm.forward(params, {"tokens": jnp.asarray(tokens)[None]},
                             cfg)
    w = weights.make(fam, model, SEED)
    h = ref._hidden(w, jnp.asarray(tokens), c, False)
    got = np.asarray(jnp.matmul(h, ref._head(w, c), precision=ref.HI))
    want = np.asarray(want[0])
    assert np.std(want) > 1.0            # logits spread over units
    np.testing.assert_allclose(got, want, atol=LOGITS_ATOL, rtol=0)


def test_weights_decay_the_state_as_published():
    """A = exp(A_log) and softplus(dt_bias) are drawn around Mamba-2's
    initial ranges, so heads decay fast and slow and the state carries."""
    model = tiny_hybrid()
    fam = families.load(model)
    w = weights.make(fam, model, SEED)
    a = np.exp(np.concatenate([np.asarray(v).ravel() for k, v in w.items()
                               if k.endswith("a_log")]))
    dt = np.log1p(np.exp(np.concatenate([np.asarray(v).ravel()
                                         for k, v in w.items()
                                         if k.endswith("dt_bias")])))
    assert 1 < np.median(a) < 16 and 0.001 < np.median(dt) < 0.1
    assert a.min() < 3 and a.max() > 6


# Full-width counts, worked out by hand from the configuration
# (PERF.md §4): a Mamba layer with its MLP is 76.2 M parameters, an
# attention layer with its MLP 60.8 M, the tied embedding 205.5 M.
PINNED = {
    "dims": {"layers": 40, "d": 2048, "heads": 32, "kv_heads": 8,
             "head_dim": 64, "ff": 8192, "vocab": 100352, "tied": True,
             "pattern": ("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
             "ssm_heads": 64, "ssm_head_dim": 64, "ssm_state": 128,
             "conv": 4, "embedding_multiplier": 12.0,
             "residual_multiplier": 0.22, "logits_scaling": 8.0},
    "matmul_params": 3190919168,
    "weight_bytes": {"bfloat16": 6382792192},
    "kv_bytes_per_token": 8192,
    "state_bytes_per_lane": 76437504,
    "decode_work": (6218472488960, 555586387968),
}


def test_full_width_counts_are_pinned():
    model = spec.load_json(BENCH / "configs" / f"{CONFIG}.json")
    fam = families.load(model)
    assert fam.__name__.endswith("hybrid")
    dm = fam.dims(model)
    assert dm == PINNED["dims"]
    assert fam.matmul_params(dm) == PINNED["matmul_params"]
    assert fam.weight_bytes(dm, "bfloat16") == \
        PINNED["weight_bytes"]["bfloat16"]
    assert fam.kv_bytes_per_token(dm, "bfloat16") == \
        PINNED["kv_bytes_per_token"]
    assert fam.state_bytes_per_lane(dm, "bfloat16") == \
        PINNED["state_bytes_per_lane"]
    assert fam.decode_work(dm, "bfloat16", 64, 40000, 960) == \
        PINNED["decode_work"]
    # the program counts the same parameters (its count adds the small
    # per-layer vectors the matmul count leaves out)
    cfg = fam.arch_config(model, CONFIG)
    assert cfg.param_count() - fam.matmul_params(dm) == \
        36 * (3 * 64 + 4096 + 4352)


def test_program_params_match_the_program_layout():
    """The weights the benchmark makes fill the program's own pytree,
    leaf for leaf, shape for shape, and the family counts what the
    program's own count does, less the small per-layer vectors."""
    from repro.models import lm

    model = spec.load_json(BENCH / "configs" / f"{CONFIG}.json")
    fam = families.load(model)
    cfg = fam.arch_config(model, CONFIG)
    dm = fam.dims(model)
    want = jax.eval_shape(lambda: lm.init_params(jax.random.key(0), cfg))
    got = jax.eval_shape(lambda: fam.program_params(
        weights._maker(fam, tuple(sorted(dm.items())), "bfloat16")(
            jax.random.key(0)), dm))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(got)] == \
        [(x.shape, x.dtype) for x in jax.tree.leaves(want)]
    di = cfg.d_inner
    small = cfg.layer_kinds().count("mamba2") * (
        di + 2 * cfg.ssm_state + 3 * cfg.ssm_heads + di)
    assert fam.matmul_params(dm) == cfg.param_count() - small


def test_granite_states_its_published_shapes():
    model = spec.load_json(BENCH / "configs" / f"{CONFIG}.json")
    g = families.load(model).arch_config(model, CONFIG)
    assert (g.n_layers, g.d_model, g.n_heads, g.n_kv_heads, g.head_dim_,
            g.d_ff, g.vocab_size) == (40, 2048, 32, 8, 64, 8192, 100352)
    assert g.layer_kinds().count("attn") == 4 and g.layer_kinds()[5] == "attn"
    assert (g.ssm_heads, g.ssm_head_dim, g.ssm_state, g.d_inner,
            g.conv_width) == (64, 64, 128, 4096, 4)
    assert (g.embed_multiplier, g.residual_multiplier, g.attn_scale,
            g.logits_divisor) == (12.0, 0.22, 0.015625, 8.0)
    assert not g.rope and g.mamba_ffn and g.tie_embeddings
    assert g.param_dtype == g.compute_dtype == "bfloat16"


def test_the_cell_serves_its_mix_on_its_pool():
    cell = spec.load_cell("granite-batch")
    assert Path(cell.family.__file__).name == "hybrid.py"
    serve, mix = cell.model["serve"], cell.traffic
    assert serve == {"n_slots": 32, "max_len": 4096}
    assert mix["loop"] == "closed" and mix["requests"] == 128
    assert mix["clients"] == 2 * serve["n_slots"]
    assert mix["prompt"]["max"] + mix["output"]["max"] <= serve["max_len"]
    assert [m["name"] for m in cell.end_to_end] == ["output_tok_s",
                                                    "setup_s"]
    assert sorted(m["name"] for m in cell.per_layer) == sorted(
        p.stem for p in (BENCH / "metrics").glob("*.hybrid.py"))


def tiny_cell() -> spec.Cell:
    """granite-batch with the tiny configuration, short prompts and
    outputs, 8 callers on 4 slots."""
    cell = spec.load_cell("granite-batch")
    mix = {**cell.traffic, "clients": 8, "requests": 32}
    for k in ("prompt", "output"):
        mix[k] = {"median": 24, "sigma": mix[k]["sigma"], "min": 8,
                  "max": 80}
    return dataclasses.replace(cell, model=tiny_hybrid(), traffic=mix)


RUN_SEED, RUN_SECONDS = 2**31 + 4243, 4.0


def test_a_tiny_run_of_the_cell_is_correct():
    from harness import cli

    res = cli.run_cell(tiny_cell(), RUN_SEED, RUN_SECONDS, False,
                       time.perf_counter())
    assert res["correct"] and res["attempted"] > 8
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
    ctl = cli.run_cell(tiny_cell(), RUN_SEED, RUN_SECONDS, False,
                       time.perf_counter(), control=True)
    assert not ctl["correct"]


def test_the_span_readers_read_a_tiny_window():
    """The readers of the program's spans find pads and state resets in
    a tiny window; the readers of a device trace find nothing without
    one."""
    from harness import cli, reading, serving

    cell = tiny_cell()
    sched = cli.scheduler(cell, RUN_SEED)
    sched.warmup()
    clients, warm = cli.clients_and_warm_prompts(cell, RUN_SEED, RUN_SECONDS)
    win = serving.run_window(sched, cell.traffic, clients, warm, RUN_SECONDS,
                             serving.CompileCounter())
    run = reading.Run(cell=cell, dims=cell.family.dims(cell.model),
                      dtype="bfloat16", n_slots=sched.pool.n_slots,
                      setup_s=0.0, window=win, trace=None, peaks={})
    pad = spec.metric_reader("prefill_pad_share.hybrid")(run)
    reset = spec.metric_reader("state_reset_ms_per_admit.hybrid")(run)
    assert 0 < pad < 100 and 0 < reset < 1e3
    for name in ("decode_iter_ms.hybrid", "decode_roofline.hybrid",
                 "prefill_roofline.hybrid", "mfu.hybrid",
                 "device_idle_share.hybrid"):
        assert spec.metric_reader(name)(run) is None
