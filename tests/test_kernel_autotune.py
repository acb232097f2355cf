"""Measured Pallas block autotuner: candidate legality (property-style
sweeps), winner persistence/round-trip, hardware invalidation, and the
satellite fixes (plan_1d VMEM clamp, feedback size attribution, mesh
compat)."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels as K
from repro.core.calibration import CalibrationCache
from repro.core.feedback import OnlineFeedback, tag_workload
from repro.core.hardware import TPU_V5E
from repro.kernels import ref as R
from repro.kernels import tuning
from repro.kernels.autotune import (KernelTuner, attention_live_bytes,
                                    candidates_1d, candidates_attention,
                                    max_block_1d, shape_bucket)

RS = np.random.RandomState(0)


# ---------------------------------------------------------------------------
# candidate generation: tile alignment + VMEM budget (property sweeps)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 7, 128, 1000, 4096, 65536 + 3, 10**6])
@pytest.mark.parametrize("bytes_per_elem", [1, 2, 4])
@pytest.mark.parametrize("arrays_in_vmem", [1, 2, 3])
def test_candidates_1d_legal(n, bytes_per_elem, arrays_in_vmem):
    cands = candidates_1d(n, bytes_per_elem=bytes_per_elem,
                          arrays_in_vmem=arrays_in_vmem)
    cap = max_block_1d(bytes_per_elem=bytes_per_elem,
                       arrays_in_vmem=arrays_in_vmem)
    budget = TPU_V5E.vmem_bytes * 0.25 / (2.0 * arrays_in_vmem)
    assert cands, (n, bytes_per_elem)
    assert len(set(cands)) == len(cands)
    tile = tuning.TILE_1D
    for b in cands:
        assert b % tile == 0, (n, b)
        assert b <= cap
        # either inside the budget, or the single smallest legal tile
        assert b * bytes_per_elem <= budget or b == tile
        # never wider than the padded problem
        assert b <= tuning.round_up(n, tile)
    # the analytic prior leads the candidate list
    assert cands[0] == min(
        tuning.plan_1d(n, bytes_per_elem=bytes_per_elem,
                       arrays_in_vmem=arrays_in_vmem).block,
        tuning.round_up(max(n, 1), tile))


@pytest.mark.parametrize("align", [tuning.SUBLANE, tuning.LANE])
@pytest.mark.parametrize("prior", [8, 100, 4096])
def test_candidates_1d_alignment_override(align, prior):
    for b in candidates_1d(5000, align=align, prior=prior):
        assert b % align == 0 and b >= align


@pytest.mark.parametrize("sq,skv,d", [(8, 128, 32), (40, 100, 64),
                                      (512, 512, 64), (4096, 4096, 128),
                                      (64, 8192, 128)])
@pytest.mark.parametrize("bytes_per_elem", [2, 4])
def test_candidates_attention_legal(sq, skv, d, bytes_per_elem):
    budget = TPU_V5E.vmem_bytes * 0.5 / 2.0
    cands = candidates_attention(sq, skv, d, bytes_per_elem=bytes_per_elem)
    assert cands
    assert len(set(cands)) == len(cands)
    for bq, bk in cands:
        assert bq % tuning.SUBLANE == 0 and bk % tuning.LANE == 0
        assert attention_live_bytes(bq, bk, d, bytes_per_elem) <= budget
        assert bq <= ((sq + tuning.SUBLANE - 1) // tuning.SUBLANE) \
            * tuning.SUBLANE
        assert bk <= ((skv + tuning.LANE - 1) // tuning.LANE) * tuning.LANE


def test_shape_bucket():
    assert [shape_bucket(n) for n in (1, 2, 3, 1000, 1024, 1025)] \
        == [1, 2, 4, 1024, 1024, 2048]


# ---------------------------------------------------------------------------
# satellite: plan_1d respects a small VMEM budget (clamp ordering)
# ---------------------------------------------------------------------------

def test_plan_1d_small_budget_respects_vmem():
    tiny = dataclasses.replace(TPU_V5E, vmem_bytes=512 * 1024)
    for bytes_per_elem, arrays in [(4, 2), (4, 8), (8, 4)]:
        p = tuning.plan_1d(10**6, bytes_per_elem=bytes_per_elem,
                           arrays_in_vmem=arrays, hw=tiny)
        budget = tiny.vmem_bytes * 0.25 / (2.0 * arrays)
        assert p.block % tuning.LANE == 0
        assert p.block * bytes_per_elem <= max(budget,
                                               tuning.LANE * bytes_per_elem)
        assert p.padded >= 10**6


def test_plan_1d_normal_budget_unchanged():
    p = tuning.plan_1d(10**6, bytes_per_elem=4)
    assert p.block >= tuning.LANE * tuning.SUBLANE
    assert p.padded >= 10**6


# ---------------------------------------------------------------------------
# winner persistence + hardware invalidation
# ---------------------------------------------------------------------------

def _searching_tuner(path, hardware="hw-a"):
    return KernelTuner(CalibrationCache(path), repeats=1, hardware=hardware)


def test_winner_roundtrip_and_hw_invalidation(tmp_path):
    path = os.path.join(tmp_path, "cal.json")
    calls = []

    def run(block):
        calls.append(block)

    t1 = _searching_tuner(path)
    p1 = t1.plan_1d("k1", 5000, run, dtype="float32")
    assert t1.searches == 1 and calls  # measured every candidate
    assert p1.block % tuning.LANE == 0
    assert p1.padded >= 5000

    # same tuner, same bucket: answered from memory, no new measurements
    n_calls = len(calls)
    p1b = t1.plan_1d("k1", 5000, run, dtype="float32")
    assert len(calls) == n_calls and t1.cache_hits == 1
    assert p1b.block == p1.block

    # fresh cache over the same file: winner round-trips from disk
    t2 = _searching_tuner(path)
    p2 = t2.plan_1d("k1", 5000, run, dtype="float32")
    assert t2.searches == 0 and t2.cache_hits == 1
    assert len(calls) == n_calls
    assert p2.block == p1.block

    # a different hardware key invalidates the stored winner (keys
    # separately: hw-b must not inherit blocks measured on hw-a)
    t3 = _searching_tuner(path, hardware="hw-b")
    t3.plan_1d("k1", 5000, run, dtype="float32")
    assert t3.searches == 1 and len(calls) > n_calls
    # ... its re-measured record now serves hw-b processes
    t4 = _searching_tuner(path, hardware="hw-b")
    n_calls = len(calls)
    t4.plan_1d("k1", 5000, run, dtype="float32")
    assert t4.searches == 0 and len(calls) == n_calls
    # ... and hw-a's winner coexists (machines sharing one store must
    # not alternately overwrite each other)
    t5 = _searching_tuner(path)
    t5.plan_1d("k1", 5000, run, dtype="float32")
    assert t5.searches == 0 and len(calls) == n_calls


def test_distinct_keys_search_separately(tmp_path):
    path = os.path.join(tmp_path, "cal.json")
    t = _searching_tuner(path)

    def run(*_):
        pass

    t.plan_1d("k1", 5000, run, dtype="float32")
    t.plan_1d("k1", 5000, run, dtype="bfloat16")       # dtype in key
    t.plan_1d("k2", 5000, run, dtype="float32")        # kernel in key
    t.plan_1d("k1", 50000, run, dtype="float32")       # bucket in key
    t.plan_1d("k1", 4097, run, dtype="float32")        # same bucket as 5000
    assert t.searches == 4 and t.cache_hits == 1


def test_attention_winner_roundtrip(tmp_path):
    path = os.path.join(tmp_path, "cal.json")
    t1 = _searching_tuner(path)
    bq, bk = t1.plan_attention("fa", 64, 128, 32, lambda q, k: None)
    assert t1.searches == 1
    assert bq % tuning.SUBLANE == 0 and bk % tuning.LANE == 0
    t2 = _searching_tuner(path)
    assert t2.plan_attention("fa", 64, 128, 32, lambda q, k: None) == (bq, bk)
    assert t2.searches == 0 and t2.cache_hits == 1


def test_attention_cached_winner_capped_to_sequence(tmp_path):
    """A winner stored by a bucket-mate with longer sequences must be
    capped to the current call's padded lengths on reuse."""
    t = _searching_tuner(os.path.join(tmp_path, "cal.json"))
    run = lambda q, k: None  # noqa: E731
    t.plan_attention("fa", 1024, 1024, 32, run)      # bucket 1024
    bq, bk = t.plan_attention("fa", 513, 513, 32, run)  # same bucket
    assert t.searches == 1 and t.cache_hits == 1
    assert bq <= 520 and bk <= 640  # round_up(513, 8) / round_up(513, 128)


def test_attention_variant_keys_separately(tmp_path):
    """A winner measured under one masking config (causal/window) must
    not be reused for another — the work per tile differs."""
    t = _searching_tuner(os.path.join(tmp_path, "cal.json"))
    run = lambda q, k: None  # noqa: E731
    t.plan_attention("fa", 64, 128, 32, run, variant=(True, None))
    t.plan_attention("fa", 64, 128, 32, run, variant=(False, None))
    t.plan_attention("fa", 64, 128, 32, run, variant=(True, 64))
    t.plan_attention("fa", 64, 128, 32, run, variant=(True, None))
    assert t.searches == 3 and t.cache_hits == 1


def test_attention_key_matches_v2_on_disk_order(tmp_path):
    """Winners persisted by the pre-unification (schema v2) release used
    (ns, kernel, bsq, bskv, d, dtype, variant, hw) tuples; the engine's
    DecisionKey must keep that exact identity or every stored
    flash-attention winner would silently re-measure."""
    t = _searching_tuner(os.path.join(tmp_path, "cal.json"))
    legacy_key = ("pallas_block", "fa", 64, 128, 32, "bfloat16",
                  repr(()), t.hardware)
    t.cache.set_tuned(legacy_key, {"block_q": 16, "block_kv": 128,
                                   "hw": t.hardware})
    bq, bk = t.plan_attention("fa", 64, 128, 32,
                              lambda q, k: pytest.fail("must not measure"))
    assert (bq, bk) == (16, 128)
    assert t.searches == 0 and t.cache_hits == 1


def test_illegal_persisted_block_triggers_remeasure(tmp_path):
    """A record with a non-positive block (torn write, buggy peer) must
    fall through to re-measurement, not crash plan math."""
    path = os.path.join(tmp_path, "cal.json")
    t = _searching_tuner(path)
    key = ("pallas_block", "k1", 8192, "float32", t.hardware)
    t.cache.set_tuned(key, {"block": 0, "hw": t.hardware})
    p = t.plan_1d("k1", 5000, lambda b: None, dtype="float32")
    assert t.searches == 1 and p.block > 0


def test_plan_argument_on_pallas_entry_points():
    """The externally-chosen-blocks entry points the autotuner feeds."""
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.reduce_scan import (inclusive_scan_pallas,
                                           reduce_sum_pallas)
    from repro.kernels.rmsnorm import rmsnorm_pallas

    plan = tuning.BlockPlan(block=128, grid=2, padded=256)
    x = jnp.asarray(RS.randn(256).astype(np.float32))
    np.testing.assert_allclose(float(reduce_sum_pallas(x, plan=plan)),
                               float(R.reduce_sum_ref(x)), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(inclusive_scan_pallas(x, plan=plan)),
                               np.asarray(R.inclusive_scan_ref(x)),
                               rtol=1e-4, atol=1e-3)
    xr = jnp.asarray(RS.randn(16, 128).astype(np.float32))
    g = jnp.ones((128,))
    np.testing.assert_allclose(
        np.asarray(rmsnorm_pallas(xr, g, plan=tuning.BlockPlan(8, 2, 16))),
        np.asarray(R.rmsnorm_ref(xr, g)), rtol=1e-5, atol=1e-5)
    q = jnp.asarray(RS.randn(1, 2, 32, 32).astype(np.float32))
    k = jnp.asarray(RS.randn(1, 2, 128, 32).astype(np.float32))
    v = jnp.asarray(RS.randn(1, 2, 128, 32).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(flash_attention_pallas(q, k, v, causal=False,
                                          plan=(16, 128))),
        np.asarray(R.attention_ref(q, k, v, causal=False)),
        rtol=2e-4, atol=2e-4)


def test_schema_v1_files_still_load(tmp_path):
    """Schema bumps (v2 tuned table, v3 unified entries) must not
    discard a user's existing v1 t0/t_iter calibrations — old files
    load, and the first save migrates them to the current version."""
    import json

    from repro.core.calibration import SCHEMA_VERSION

    path = os.path.join(tmp_path, "cal.json")
    with open(path, "w") as f:
        json.dump({"version": 1, "t0": {"'a'": 1e-5},
                   "t_iter": {"'b'": 2e-6}}, f)
    c = CalibrationCache(path)
    assert c.peek_t_iter("b") == pytest.approx(2e-6)
    assert len(c) == 2
    c.set_tuned(("k",), {"block": 128})   # autosaves as current schema
    with open(path) as f:
        assert json.load(f)["version"] == SCHEMA_VERSION


def test_schema_roundtrip_through_save_load(tmp_path):
    path = os.path.join(tmp_path, "cal.json")
    c = CalibrationCache(path)
    c.set_tuned(("pallas_block", "k", 1024, "float32"),
                {"block": 256, "hw": "hw-a", "seconds": 1e-3})
    c.t_iter("w", lambda: 2e-6)   # scalar stores coexist with records
    c2 = CalibrationCache(path)
    rec = c2.tuned(("pallas_block", "k", 1024, "float32"))
    assert rec is not None and rec["block"] == 256 and rec["hw"] == "hw-a"
    assert c2.peek_t_iter("w") == pytest.approx(2e-6)
    assert len(c2) == 2


# ---------------------------------------------------------------------------
# tuned kernels stay correct (winner plans produce oracle outputs)
# ---------------------------------------------------------------------------

def test_tuned_ops_match_oracles(tmp_path):
    tuner = KernelTuner(CalibrationCache(os.path.join(tmp_path, "c.json")),
                        repeats=1)
    x = jnp.asarray(RS.randn(3000).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(K.adjacent_difference(x, tuner=tuner)),
        np.asarray(R.adjacent_difference_ref(x)), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        float(K.reduce_sum(x, tuner=tuner)), float(R.reduce_sum_ref(x)),
        rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(K.inclusive_scan(x, tuner=tuner)),
        np.asarray(R.inclusive_scan_ref(x)), rtol=1e-4, atol=1e-3)

    xr = jnp.asarray(RS.randn(100, 256).astype(np.float32))
    g = jnp.asarray(RS.randn(256).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(K.rmsnorm(xr, g, tuner=tuner)),
        np.asarray(R.rmsnorm_ref(xr, g)), rtol=1e-5, atol=1e-5)

    q = jnp.asarray(RS.randn(1, 2, 40, 32).astype(np.float32))
    k = jnp.asarray(RS.randn(1, 2, 100, 32).astype(np.float32))
    v = jnp.asarray(RS.randn(1, 2, 100, 32).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(K.flash_attention(q, k, v, causal=True, tuner=tuner)),
        np.asarray(R.attention_ref(q, k, v, causal=True)),
        rtol=2e-4, atol=2e-4)
    assert tuner.searches == 5


def test_measurement_is_eager_mid_trace(tmp_path):
    """Consumers resolve plans while tracing inside an outer jit (the
    scheduler's compiled steps, the train step): the harness must make
    the probes concrete and eager there, or it would wall-clock trace
    staging instead of kernel execution."""
    t = _searching_tuner(os.path.join(tmp_path, "cal.json"))
    concrete = []

    def run(block):
        concrete.append(not isinstance(jnp.zeros((block,)),
                                       jax.core.Tracer))

    def traced(y):
        t.plan_1d("probe", 1000, run, dtype="float32")
        return y * 2

    jax.jit(traced)(jnp.ones(3))
    assert t.searches == 1
    assert concrete and all(concrete)


def test_rmsnorm_pallas_grad_matches_reference():
    """The custom VJP (Pallas forward, closed-form backward) that the
    --kernel-autotune train path relies on."""
    from repro.kernels import ops as kops

    x = jnp.asarray(RS.randn(50, 128).astype(np.float32))
    g = jnp.asarray(RS.randn(128).astype(np.float32))

    def ref(x, g, eps=1e-6):
        xf = x.astype(jnp.float32)
        r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (xf * r) * g

    vp, (dxp, dgp) = jax.value_and_grad(
        lambda a, b: jnp.sum(kops.rmsnorm(a, b) ** 2), argnums=(0, 1))(x, g)
    vr, (dxr, dgr) = jax.value_and_grad(
        lambda a, b: jnp.sum(ref(a, b) ** 2), argnums=(0, 1))(x, g)
    assert float(vp) == pytest.approx(float(vr), rel=1e-5)
    np.testing.assert_allclose(np.asarray(dxp), np.asarray(dxr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dgp), np.asarray(dgr),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# satellite: feedback skips observations with unknown element counts
# ---------------------------------------------------------------------------

def test_timed_chunk_fn_skips_unknown_size():
    fb = OnlineFeedback()
    seen = []
    fn = tag_workload(lambda c: seen.append(c), "wk")
    timed = fb.timed_chunk_fn(fn)

    class Sized:
        size = 64

    class Unsized:
        pass

    timed(Unsized())          # passes through, no observation
    assert fb.count("wk") == 0 and fb.t_iter("wk") is None
    timed(Sized())            # real size: observed and smoothed
    assert fb.count("wk") == 1
    assert fb.observations[-1].elems == 64
    assert fb.t_iter("wk") is not None
    assert len(seen) == 2     # both calls executed the wrapped fn


# ---------------------------------------------------------------------------
# launch/mesh.make_mesh on the installed jax
# ---------------------------------------------------------------------------

def test_make_mesh_compat_current_jax():
    from repro.launch.mesh import make_mesh

    n = len(jax.devices())
    mesh = make_mesh((n, 1), ("data", "model"))
    assert dict(mesh.shape) == {"data": n, "model": 1}
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,) * 2


# ---------------------------------------------------------------------------
# scheduler opt-in: tuned serving produces the baseline tokens
# ---------------------------------------------------------------------------

def test_scheduler_kernel_tuner_same_tokens(tmp_path):
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve.scheduler import ServeScheduler

    cfg = get_config("qwen3-0.6b").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray(RS.randint(0, cfg.vocab_size, size=10), jnp.int32)

    def run(sched):
        sched.submit(prompt, max_new_tokens=3)
        return sched.run_until_idle()

    base = run(ServeScheduler(cfg, params, n_slots=1, max_len=16))
    tuner = KernelTuner(CalibrationCache(os.path.join(tmp_path, "c.json")),
                        repeats=1)
    tuned = run(ServeScheduler(cfg, params, n_slots=1, max_len=16,
                               kernel_tuner=tuner))
    assert list(base.values()) == list(tuned.values())
    assert tuner.searches > 0   # the tuned path actually engaged
