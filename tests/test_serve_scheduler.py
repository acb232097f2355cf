"""The adaptive serving runtime: scheduler, slot pool, online feedback,
calibration persistence, and prefill segmentation edge cases."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import SequentialExecutor, adaptive
from repro.core.acc import AdaptiveCoreChunk, StaticCoreChunk
from repro.core.calibration import SCHEMA_VERSION, CalibrationCache
from repro.core.executor import Chunk, HostParallelExecutor
from repro.core.feedback import OnlineFeedback, tag_workload
from repro.core.future import when_all
from repro.data import make_batch
from repro.models import init_params
from repro.serve import (RequestState, ServeEngine, ServeScheduler,
                         prefill_segments)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3-0.6b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_sched(cfg, params, *, n_slots=2, max_len=48, acc=None, clock=None):
    kwargs = {} if clock is None else {"clock": clock}
    return ServeScheduler(
        cfg, params, n_slots=n_slots, max_len=max_len,
        executor=adaptive(SequentialExecutor(),
                          acc or AdaptiveCoreChunk()), **kwargs)


# ---------------------------------------------------------------------------
# admission
# ---------------------------------------------------------------------------

def test_admission_fifo_and_deadline_order(setup):
    cfg, params = setup
    sched = make_sched(cfg, params, n_slots=2)
    prompt = jnp.arange(8, dtype=jnp.int32) % cfg.vocab_size
    # Arrivals 2, 0, 1 (explicit timestamps); no deadlines -> FIFO by
    # arrival, not by submission call order.
    r_late = sched.submit(prompt, max_new_tokens=2, arrival=2.0)
    r_first = sched.submit(prompt, max_new_tokens=2, arrival=0.0)
    r_mid = sched.submit(prompt, max_new_tokens=2, arrival=1.0)
    rec = sched.tick()
    assert rec.admitted == (r_first, r_mid)   # two slots, earliest two
    assert r_late not in rec.admitted         # latest arrival queued
    sched.run_until_idle()

    # A tight deadline jumps the arrival queue (EDF).
    sched2 = make_sched(cfg, params, n_slots=1)
    r_a = sched2.submit(prompt, max_new_tokens=2, arrival=0.0)
    r_urgent = sched2.submit(prompt, max_new_tokens=2, arrival=5.0,
                             deadline=1.0)
    rec = sched2.tick()
    assert rec.admitted == (r_urgent,)
    assert sched2.requests[r_a].state is RequestState.WAITING


def test_slot_exhaustion_queues_then_admits(setup):
    cfg, params = setup
    sched = make_sched(cfg, params, n_slots=2)
    prompt = jnp.arange(6, dtype=jnp.int32) % cfg.vocab_size
    rids = [sched.submit(prompt, max_new_tokens=2) for _ in range(3)]
    rec0 = sched.tick()
    # Pool exhausted: first two admitted, third queued (never dropped).
    assert rec0.admitted == tuple(rids[:2])
    assert sched.requests[rids[2]].state is RequestState.WAITING
    outs = sched.run_until_idle()
    assert sorted(outs) == sorted(rids)
    assert all(len(outs[r]) == 2 for r in rids)
    # The straggler was admitted only after a slot freed up.
    admit_tick = {r: rec.tick for rec in sched.trace for r in rec.admitted}
    finish_tick = {r: rec.tick for rec in sched.trace for r in rec.finished}
    assert admit_tick[rids[2]] >= min(finish_tick[r] for r in rids[:2])


# ---------------------------------------------------------------------------
# interleave determinism + concurrent mixed-length requests
# ---------------------------------------------------------------------------

def test_interleave_deterministic_with_sequential_executor(setup):
    cfg, params = setup
    tokens = make_batch(cfg, 2, 14, kind="prefill", seed=5)["tokens"]

    def run():
        sched = make_sched(cfg, params, n_slots=2, clock=lambda: 0.0)
        sched.submit(tokens[0], max_new_tokens=4, arrival=0.0)
        sched.submit(tokens[1][:9], max_new_tokens=3, arrival=0.0)
        outs = sched.run_until_idle()
        return outs, sched.trace

    outs1, trace1 = run()
    outs2, trace2 = run()
    assert outs1 == outs2
    assert trace1 == trace2          # tick-for-tick identical schedule
    # and the schedule genuinely interleaves: some tick both prefills a
    # chunk and decodes a running request
    assert any(rec.prefill_ops and rec.decoded for rec in trace1)


def test_mixed_length_requests_share_pool_without_realloc(setup):
    """Acceptance: two requests of different prompt lengths complete
    concurrently through the slot pool with no cache reallocation."""
    cfg, params = setup
    tokens = make_batch(cfg, 2, 16, kind="prefill", seed=7)["tokens"]
    long_p, short_p = tokens[0], tokens[1][:5]

    sched = make_sched(cfg, params, n_slots=2)
    r_long = sched.submit(long_p, max_new_tokens=6)
    r_short = sched.submit(short_p, max_new_tokens=6)
    outs = sched.run_until_idle()
    assert len(outs[r_long]) == 6 and len(outs[r_short]) == 6
    # one lm.init_caches for the pool's whole lifetime
    assert sched.pool.allocations == 1
    assert sched.pool.free_slots() == 2
    # both requests were in flight simultaneously (same tick decoded both)
    assert any(set(rec.decoded) >= {r_long, r_short} for rec in sched.trace)

    # per-request correctness: each equals the single-request reference
    for rid, prompt in ((r_long, long_p), (r_short, short_p)):
        solo = make_sched(cfg, params, n_slots=1)
        r = solo.submit(prompt, max_new_tokens=6)
        assert solo.run_until_idle()[r] == outs[rid]


def test_scheduler_matches_legacy_batch_generate(setup):
    cfg, params = setup
    prompts = make_batch(cfg, 2, 12, kind="prefill", seed=3)["tokens"]
    legacy = ServeEngine(cfg, params, batch=2, max_len=40)
    ref = np.asarray(legacy._generate_legacy(prompts, 5))
    engine = ServeEngine(cfg, params, batch=2, max_len=40)
    out = np.asarray(engine.generate(prompts, 5))   # scheduler path
    np.testing.assert_array_equal(out, ref)


def test_static_policy_runs_and_chunks_small(setup):
    cfg, params = setup
    tokens = make_batch(cfg, 1, 16, kind="prefill", seed=1)["tokens"]
    sched = ServeScheduler(
        cfg, params, n_slots=1, max_len=32,
        executor=adaptive(SequentialExecutor(),
                          StaticCoreChunk(cores=1, chunks_per_core=8)))
    rid = sched.submit(tokens[0], max_new_tokens=2)
    outs = sched.run_until_idle()
    assert len(outs[rid]) == 2
    # static split: the 16-token prompt went in pieces, not one chunk
    assert len([op for rec in sched.trace for op in rec.prefill_ops]) > 1


# ---------------------------------------------------------------------------
# online feedback
# ---------------------------------------------------------------------------

def test_feedback_smoothing_converges_on_drifting_t_iter():
    cache = CalibrationCache()
    fb = OnlineFeedback(cache, alpha=0.25)
    key = ("serve_prefill", "drift-test")
    # calibrated world: 1 us/elem; drifted world: 5 us/elem
    fb.observe(key, 1000, 1000 * 1e-6)
    assert cache.peek_t_iter(key) == pytest.approx(1e-6)
    for _ in range(40):
        fb.observe(key, 1000, 1000 * 5e-6)
    assert cache.peek_t_iter(key) == pytest.approx(5e-6, rel=1e-3)
    # and a single outlier cannot yank the estimate away
    fb.observe(key, 1000, 1000 * 500e-6)
    assert cache.peek_t_iter(key) < 130e-6


def test_adaptive_executor_records_bulk_timings():
    acc = AdaptiveCoreChunk(t0_override=1e-6)
    ex = adaptive(SequentialExecutor(), acc)

    def work(chunk):
        return chunk.size

    tag_workload(work, ("wl", "bulk"))
    when_all(ex.bulk_async_execute(
        work, [Chunk(0, 64), Chunk(64, 64)])).result()
    assert acc.cache.peek_t_iter(("wl", "bulk")) is not None
    assert ex.feedback.count(("wl", "bulk")) == 2
    # ... and the observation feeds the next decision's t_iter
    from repro.core.cost_model import WorkloadProfile

    t = acc.measure_iteration(ex, WorkloadProfile(1.0, 1.0), 128,
                              key=("wl", "bulk"))
    assert t == acc.cache.peek_t_iter(("wl", "bulk"))


def test_adaptive_executor_times_tagged_continuations():
    acc = AdaptiveCoreChunk(t0_override=1e-6)
    ex = adaptive(SequentialExecutor(), acc)
    from repro.core import Future

    def cont(value):
        return value + 1

    tag_workload(cont, ("wl", "then"), elems=32)
    assert ex.then_execute(cont, Future.ready(1)).result() == 2
    assert acc.cache.peek_t_iter(("wl", "then")) is not None


def test_scheduler_decisions_track_observed_drift(setup):
    """After ticks ran, the decision t_iter is the smoothed observation,
    not the analytic roofline seed."""
    cfg, params = setup
    sched = make_sched(cfg, params, n_slots=1, max_len=32)
    sched.warmup()   # cold (compiling) calls are deliberately untimed
    rid = sched.submit(jnp.arange(10, dtype=jnp.int32), max_new_tokens=2)
    sched.run_until_idle()
    assert len(sched.results()[rid]) == 2
    observed = sched.acc.cache.peek_t_iter(sched.prefill_key)
    assert observed is not None and observed > 0
    t = sched.acc.measure_iteration(sched.executor, sched.prefill_profile,
                                    100, key=sched.prefill_key)
    assert t == observed


# ---------------------------------------------------------------------------
# calibration persistence
# ---------------------------------------------------------------------------

def test_calibration_cache_persistence_roundtrip(tmp_path):
    path = str(tmp_path / "cal.json")
    c1 = CalibrationCache(path=path)
    c1.t0(("t0", "SequentialExecutor", 1), lambda: 3.5e-5)
    c1.smooth_t_iter(("serve_prefill", "qwen"), 2e-6)
    # autosaved on every update
    c2 = CalibrationCache(path=path)
    assert c2.t0(("t0", "SequentialExecutor", 1),
                 lambda: pytest.fail("must not re-measure")) == 3.5e-5
    assert c2.peek_t_iter(("serve_prefill", "qwen")) == pytest.approx(2e-6)

    blob = json.loads(open(path).read())
    assert blob["version"] == SCHEMA_VERSION

    # a stale schema version is ignored, not misread
    blob["version"] = SCHEMA_VERSION + 1
    with open(path, "w") as f:
        json.dump(blob, f)
    c3 = CalibrationCache(path=path)
    assert len(c3) == 0


def test_calibration_t0_key_stable_across_instances(tmp_path):
    """The t0 key no longer bakes in id(executor): a persisted entry is
    reused by a fresh, identical executor in a new 'process'."""
    path = str(tmp_path / "cal.json")
    acc1 = AdaptiveCoreChunk(cache=CalibrationCache(path=path))
    t0_first = acc1.calibrate_t0(SequentialExecutor())
    acc2 = AdaptiveCoreChunk(cache=CalibrationCache(path=path))
    t0_second = acc2.calibrate_t0(SequentialExecutor())
    assert t0_second == t0_first     # loaded, not re-measured


# ---------------------------------------------------------------------------
# prefill segmentation edge cases
# ---------------------------------------------------------------------------

def test_prefill_segments_tile_exactly():
    for s, chunk, pos, window in [(17, 5, 0, None), (40, 24, 0, 16),
                                  (1, 100, 3, 4), (33, 7, 13, 8),
                                  (64, 64, 0, 16)]:
        segs = prefill_segments(s, chunk, pos=pos, window=window)
        assert sum(step for _, step in segs) == s
        assert [start for start, _ in segs] == \
            list(np.cumsum([0] + [st for _, st in segs[:-1]]))
        if window:
            p = pos
            for _, step in segs:
                assert step <= window - p % window
                p += step


def test_prefill_segments_window_zero_means_no_window():
    # window=0 must not divide-by-zero nor clamp (regression)
    assert prefill_segments(10, 4, window=0) == [(0, 4), (4, 4), (8, 2)]
    assert prefill_segments(10, 4, window=None) == [(0, 4), (4, 4), (8, 2)]


def test_prefill_segments_pos_on_window_boundary():
    # pos exactly on a boundary gets a full-window first step
    assert prefill_segments(8, 8, pos=16, window=8)[0] == (0, 8)
    # one short of the boundary gets a 1-token step first
    assert prefill_segments(8, 8, pos=15, window=8)[0] == (0, 1)


def test_prefill_segments_validation():
    with pytest.raises(ValueError):
        prefill_segments(-1, 4)
    assert prefill_segments(0, 4) == []
    assert prefill_segments(5, 0) == [(i, 1) for i in range(5)]  # floor 1


def test_engine_windowed_prefill_uses_shared_segments(setup):
    cfg = get_config("h2o-danube-1.8b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, batch=1, max_len=64)
    segs = eng._prefill_segments(40, 24)
    assert sum(st for _, st in segs) == 40
    assert all(st <= (eng.window or 40) for _, st in segs)


# ---------------------------------------------------------------------------
# slot pool
# ---------------------------------------------------------------------------

def test_slot_pool_free_list_and_double_release(setup):
    from repro.serve import SlotKVCachePool

    cfg, _ = setup
    pool = SlotKVCachePool(cfg, n_slots=2, max_len=16)
    a = pool.acquire("a")
    b = pool.acquire("b")
    assert {a, b} == {0, 1} and pool.acquire("c") is None
    pool.release(a)
    assert pool.free_slots() == 1
    with pytest.raises(ValueError):
        pool.release(a)
    assert pool.acquire("d") == a
    assert pool.allocations == 1


def test_slot_pool_advance_overflow_is_typed(setup):
    """advance() past max_len must raise SlotOverflowError (a silent
    wraparound writes into other slots' cache rows mid-fused-dispatch)."""
    from repro.serve import SlotKVCachePool, SlotOverflowError

    cfg, _ = setup
    pool = SlotKVCachePool(cfg, n_slots=2, max_len=16)
    slot = pool.acquire("a")
    assert pool.advance(slot, 16) == 16         # exactly full is fine
    with pytest.raises(SlotOverflowError) as exc:
        pool.advance(slot, 1)
    assert exc.value.slot == slot
    assert exc.value.pos == 17 and exc.value.max_len == 16
    assert isinstance(exc.value, ValueError)    # old callers still catch
    assert pool.positions[slot] == 16           # overshoot not applied
    with pytest.raises(ValueError):
        pool.advance(slot, -1)


def test_slot_pool_adopt_rejects_layout_mismatch(setup):
    """adopt() is a blind rebind after a donated step — a tree from a
    step with different geometry must be rejected, not adopted."""
    from repro.serve import CacheLayoutError, SlotKVCachePool

    cfg, _ = setup
    pool = SlotKVCachePool(cfg, n_slots=2, max_len=16)
    other = SlotKVCachePool(cfg, n_slots=4, max_len=16)   # wrong n_slots
    with pytest.raises(CacheLayoutError):
        pool.adopt(other.caches)
    short = SlotKVCachePool(cfg, n_slots=2, max_len=8)    # wrong max_len
    with pytest.raises(CacheLayoutError):
        pool.adopt(short.caches)
    pool.adopt(pool.caches)                               # matching: fine
    assert pool.allocations == 1


# ---------------------------------------------------------------------------
# fused decode loop (serve/decode_loop.py)
# ---------------------------------------------------------------------------

def _run_wave(sched, tokens, spec):
    """Submit (prompt_prefix_len, max_new) requests, drain, return outs."""
    rids = [sched.submit(tokens[i][:plen], max_new_tokens=n)
            for i, (plen, n) in enumerate(spec)]
    outs = sched.run_until_idle()
    return [outs[r] for r in rids]


@pytest.mark.parametrize("depth", [1, 2, 4, 8])
def test_fused_decode_token_identity(setup, depth):
    """Fused decode at any depth emits byte-identical output to the
    legacy per-tick path — including a request completing mid-loop
    (max_new smaller than the dispatch depth) and slot reuse after its
    early exit (3 requests through 2 slots)."""
    cfg, params = setup
    tokens = make_batch(cfg, 3, 14, kind="prefill", seed=11)["tokens"]
    spec = [(14, 9), (9, 3), (6, 7)]   # 3-token request exits mid-loop
    ref = _run_wave(make_sched(cfg, params, n_slots=2), tokens, spec)
    sched = ServeScheduler(
        cfg, params, n_slots=2, max_len=48,
        executor=adaptive(SequentialExecutor(), AdaptiveCoreChunk()),
        dispatch_depth=depth)
    got = _run_wave(sched, tokens, spec)
    assert got == ref
    # every dispatched token was drained, every budget exactly honoured
    assert all(r.pending_out == 0 and r.finished_at is not None
               for r in sched.requests.values())
    assert sched.decode_dispatches < sum(n for _, n in spec)


def test_fused_auto_depth_identity_and_trace(setup):
    """dispatch_depth='auto': identical tokens, serve_dispatch_depth
    decisions in the engine trace, and online provenance once the loop
    has timed a real dispatch (warmup keeps the cold compile out)."""
    cfg, params = setup
    tokens = make_batch(cfg, 2, 12, kind="prefill", seed=13)["tokens"]
    spec = [(12, 8), (7, 8)]
    ref = _run_wave(make_sched(cfg, params, n_slots=2), tokens, spec)
    sched = ServeScheduler(
        cfg, params, n_slots=2, max_len=48,
        executor=adaptive(SequentialExecutor(), AdaptiveCoreChunk()),
        dispatch_depth="auto")
    sched.warmup()
    assert _run_wave(sched, tokens, spec) == ref
    entries = sched.decision_model().trace.entries("serve_dispatch_depth")
    assert entries, "auto depth must be decided through the engine"
    assert all(e.decision.chunk >= 1 for e in entries)
    assert entries[-1].decision.provenance in ("measured", "online")
    # host round-trips stay sub-one-per-token on the fused path
    gen = sum(n for _, n in spec)
    assert sched.host_roundtrips < gen


def test_fused_donation_safety_across_waves(setup):
    """No use-after-donate on the slot pool: the same scheduler serves
    two waves (slot release + reacquire between fused dispatches), the
    pool is never reallocated, and outputs match the legacy path both
    times."""
    cfg, params = setup
    tokens = make_batch(cfg, 2, 10, kind="prefill", seed=17)["tokens"]
    legacy = make_sched(cfg, params, n_slots=2)
    sched = ServeScheduler(
        cfg, params, n_slots=2, max_len=32,
        executor=adaptive(SequentialExecutor(), AdaptiveCoreChunk()),
        dispatch_depth=4)
    sched.warmup()
    for _ in range(2):
        spec = [(10, 5), (6, 4)]
        assert _run_wave(sched, tokens, spec) == \
            _run_wave(legacy, tokens, spec)
        sched.clear_finished()
        legacy.clear_finished()
    # one lm.init_caches ever, donation notwithstanding
    assert sched.pool.allocations == 1
    assert sched.pool.free_slots() == 2


def test_fused_tickrecords_and_positions(setup):
    """Dispatch accounting is host-authoritative: positions advance by
    <= depth at dispatch time and the TickRecord carries the decided
    depth."""
    cfg, params = setup
    tokens = make_batch(cfg, 1, 8, kind="prefill", seed=19)["tokens"]
    sched = ServeScheduler(
        cfg, params, n_slots=1, max_len=32,
        executor=adaptive(SequentialExecutor(), AdaptiveCoreChunk()),
        dispatch_depth=3)
    rid = sched.submit(tokens[0], max_new_tokens=7)
    outs = sched.run_until_idle()
    assert len(outs[rid]) == 7
    dec_ticks = [rec for rec in sched.trace if rec.decoded]
    assert dec_ticks and all(rec.depth == 3 for rec in dec_ticks)
    # 6 decode tokens (first comes from prefill) at depth 3 -> 2 dispatches
    assert sched.decode_dispatches == 2
    assert sched.decode_tokens == 6


def _bits(x):
    x = np.asarray(x)
    return x.view(f"u{x.dtype.itemsize}")


@pytest.mark.parametrize("depth", [1, 4])
@pytest.mark.parametrize("arch", ["qwen3-0.6b", "h2o-danube-1.8b",
                                  "zamba2-1.2b"])
def test_fused_dispatch_writes_only_active_rows(arch, depth):
    """One fused dispatch leaves an inactive lane's cache bit-identical
    (a free slot, a slot mid-prefill, and the positions a lane did not
    reach because its budget ran out mid-loop), and changes an active
    lane's K/V rows exactly at the positions it wrote.  h2o-danube's
    reduced window makes the pool a 16-row ring, which the deep lane
    wraps; zamba2's mamba2 states keep the row merge, so only active
    lanes' states move."""
    from repro.models import init_caches
    from repro.serve.decode_loop import make_fused_decode_step

    cfg = get_config(arch).reduced()
    window = cfg.attn_window
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(7)
    caches = jax.tree.map(
        lambda x: jnp.asarray(rng.standard_normal(x.shape), x.dtype),
        init_caches(cfg, 4, 64, window=window))
    before = jax.tree.map(lambda x: np.array(_bits(x)), caches)
    # lane 0 decodes the whole dispatch; lane 1 has one token of budget;
    # lane 2 is a free slot, lane 3 a slot mid-prefill
    poss = np.array([30, 20, 0, 10], np.int32)
    steps = np.array([depth, 1, 0, 0], np.int32)
    step = make_fused_decode_step(cfg, window=window, max_depth=depth)
    after, _, _ = step(params, caches, jnp.asarray([3, 5, 7, 9], jnp.int32),
                       jnp.asarray(poss), jnp.asarray(steps))
    for kind, old, new in zip(cfg.layer_kinds(), before, after,
                              strict=True):
        for key in old:
            got = _bits(new[key])
            for lane in range(4):
                same = got[lane] == old[key][lane]
                if kind not in ("attn", "shared_attn"):
                    assert same.all() == (steps[lane] == 0), (kind, lane)
                    continue
                s_cache = same.shape[1]
                wrote = {int(poss[lane] + j) % s_cache
                         for j in range(steps[lane])}
                moved = set(np.nonzero(~same.all(axis=(0, 2)))[0].tolist())
                assert moved == wrote, (kind, key, lane)


def test_scheduler_on_host_parallel_executor(setup):
    """Prefill chunks may run on pool threads; cache writes stay on the
    scheduler thread — results must match the sequential schedule."""
    cfg, params = setup
    tokens = make_batch(cfg, 2, 10, kind="prefill", seed=9)["tokens"]
    ref_sched = make_sched(cfg, params, n_slots=2, max_len=32)
    r0 = ref_sched.submit(tokens[0], max_new_tokens=3)
    r1 = ref_sched.submit(tokens[1], max_new_tokens=3)
    ref = ref_sched.run_until_idle()
    with HostParallelExecutor(max_workers=2) as ex:
        sched = ServeScheduler(cfg, params, n_slots=2, max_len=32,
                               executor=adaptive(ex))
        s0 = sched.submit(tokens[0], max_new_tokens=3)
        s1 = sched.submit(tokens[1], max_new_tokens=3)
        outs = sched.run_until_idle()
    assert outs[s0] == ref[r0] and outs[s1] == ref[r1]
