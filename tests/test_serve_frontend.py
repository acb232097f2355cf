"""Serving front end: streaming, cancellation/slot lifecycle, deadline
enforcement, backpressure, adaptive admission, and the load generators."""
import asyncio
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import SequentialExecutor, adaptive
from repro.core.acc import AdaptiveCoreChunk, StaticCoreChunk
from repro.data import make_batch
from repro.models import init_params
from repro.serve import (PromptTooLongError, QueueFullError, RequestState,
                         ServeFrontend, ServeScheduler, SLOModel,
                         bursty_trace, heavy_tailed_trace, materialize,
                         poisson_trace, trace_summary)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3-0.6b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_sched(cfg, params, *, n_slots=2, max_len=48, acc=None,
               clock=None, **kw):
    if clock is not None:
        kw["clock"] = clock
    return ServeScheduler(
        cfg, params, n_slots=n_slots, max_len=max_len,
        executor=adaptive(SequentialExecutor(),
                          acc or AdaptiveCoreChunk()), **kw)


class FakeClock:
    """Deterministic scheduler clock for deadline tests."""

    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# typed submit errors
# ---------------------------------------------------------------------------

def test_prompt_too_long_is_typed(setup):
    cfg, params = setup
    sched = make_sched(cfg, params, n_slots=1, max_len=16)
    long_prompt = jnp.arange(20, dtype=jnp.int32) % cfg.vocab_size
    with pytest.raises(PromptTooLongError) as ei:
        sched.submit(long_prompt, max_new_tokens=2)
    assert ei.value.prompt_len == 20 and ei.value.max_len == 16
    # subclasses ValueError: pre-existing callers keep catching it
    assert isinstance(ei.value, ValueError)


def test_frontend_rejects_long_prompt_without_dying(setup):
    """A bad request is the caller's structured error; the serve loop
    keeps serving everyone else."""
    cfg, params = setup
    sched = make_sched(cfg, params, n_slots=1, max_len=16)
    long_prompt = jnp.arange(20, dtype=jnp.int32) % cfg.vocab_size
    ok_prompt = jnp.arange(6, dtype=jnp.int32) % cfg.vocab_size

    async def go():
        async with ServeFrontend(sched) as fe:
            with pytest.raises(PromptTooLongError):
                await fe.submit(long_prompt, 2)
            stream = await fe.submit(ok_prompt, 3)
            toks = [t async for t in stream]
            return toks, stream.record.status

    toks, status = asyncio.run(go())
    assert len(toks) == 3 and status == "completed"


# ---------------------------------------------------------------------------
# cancellation and the slot lifecycle
# ---------------------------------------------------------------------------

def test_cancel_waiting_request(setup):
    cfg, params = setup
    sched = make_sched(cfg, params, n_slots=1)
    prompt = jnp.arange(6, dtype=jnp.int32) % cfg.vocab_size
    r_run = sched.submit(prompt, max_new_tokens=2)
    r_wait = sched.submit(prompt, max_new_tokens=2)
    sched.tick()
    assert sched.requests[r_wait].state is RequestState.WAITING
    assert sched.cancel(r_wait)
    assert sched.requests[r_wait].state is RequestState.CANCELLED
    assert not sched.cancel(r_wait)          # idempotent
    outs = sched.run_until_idle()
    assert len(outs[r_run]) == 2 and r_wait not in outs
    assert sched.pool.free_slots() == 1
    assert sched.cancelled == 1


def test_cancel_mid_prefill_releases_slot(setup):
    """Cancel while the prompt is partially prefilled: the slot returns
    to the pool with no reallocation, and its next occupant decodes
    exactly like a solo reference run."""
    cfg, params = setup
    tokens = make_batch(cfg, 2, 16, kind="prefill", seed=23)["tokens"]
    # Static chunks of 2: a 16-token prompt takes several ticks, so one
    # tick deterministically leaves it mid-prefill.
    sched = make_sched(cfg, params, n_slots=1, max_len=32,
                       acc=StaticCoreChunk(cores=1, chunks_per_core=8))
    r_victim = sched.submit(tokens[0], max_new_tokens=4)
    sched.tick()
    victim = sched.requests[r_victim]
    assert victim.state is RequestState.PREFILL
    assert victim.remaining_prefill > 0
    assert sched.cancel(r_victim)
    assert victim.slot is None and sched.pool.free_slots() == 1

    r_next = sched.submit(tokens[1][:10], max_new_tokens=4)
    outs = sched.run_until_idle()
    assert sched.pool.allocations == 1
    solo = make_sched(cfg, params, n_slots=1, max_len=32,
                      acc=StaticCoreChunk(cores=1, chunks_per_core=8))
    r_ref = solo.submit(tokens[1][:10], max_new_tokens=4)
    assert outs[r_next] == solo.run_until_idle()[r_ref]


def test_cancel_mid_fused_dispatch(setup):
    """Cancel with tokens already dispatched on the device: the dispatch
    drains without emitting them (out is frozen, pending_out returns to
    0), the slot is back in the pool with ``allocations==1``, and the
    surviving request's stream is byte-identical to an uncancelled run."""
    cfg, params = setup
    tokens = make_batch(cfg, 2, 10, kind="prefill", seed=29)["tokens"]
    spec = [(10, 8), (7, 12)]

    def submit_pair(sched):
        return [sched.submit(tokens[i][:p], max_new_tokens=n)
                for i, (p, n) in enumerate(spec)]

    ref_sched = make_sched(cfg, params, n_slots=2, max_len=32,
                           dispatch_depth=4)
    ref_sched.warmup()
    ref_ids = submit_pair(ref_sched)
    ref = ref_sched.run_until_idle()

    sched = make_sched(cfg, params, n_slots=2, max_len=32,
                       dispatch_depth=4)
    sched.warmup()
    r_keep, r_cancel = submit_pair(sched)
    victim = sched.requests[r_cancel]
    for _ in range(200):
        sched.tick()
        if victim.state is RequestState.DECODE and victim.pending_out > 0:
            break
    assert victim.pending_out > 0, "no in-flight dispatch to cancel into"
    frozen = list(victim.out)
    assert sched.cancel(r_cancel)
    assert sched.pool.free_slots() >= 1
    outs = sched.run_until_idle()

    assert victim.out == frozen           # dispatched tokens dropped
    assert victim.pending_out == 0        # ...but the drain balanced
    assert victim.state is RequestState.CANCELLED
    assert outs[r_keep] == ref[ref_ids[0]]
    assert sched.pool.allocations == 1
    assert sched.pool.free_slots() == 2


def test_frontend_cancel_stream(setup):
    """Streaming consumer cancels after two tokens: the stream ends, the
    record says cancelled (not an SLO miss), and the slot is free."""
    cfg, params = setup
    sched = make_sched(cfg, params, n_slots=1, max_len=48)
    prompt = jnp.arange(8, dtype=jnp.int32) % cfg.vocab_size

    async def go():
        async with ServeFrontend(sched) as fe:
            stream = await fe.submit(prompt, 24)
            got = []
            async for tok in stream:
                got.append(tok)
                if len(got) == 2:
                    await stream.cancel()
            return got, stream.record

    got, rec = asyncio.run(go())
    assert rec.status == "cancelled" and rec.missed is False
    assert len(got) < 24                  # generation genuinely stopped
    assert sched.pool.free_slots() == 1
    assert sched.pool.allocations == 1
    assert sched.requests[rec.rid].state is RequestState.CANCELLED


# ---------------------------------------------------------------------------
# deadline enforcement
# ---------------------------------------------------------------------------

def test_shed_expired_before_prefill(setup):
    """shed_expired: a request whose deadline passed while waiting is
    dropped before its prefill burns compute, and the TickRecord carries
    the miss and the queue depth."""
    cfg, params = setup
    clock = FakeClock()
    sched = make_sched(cfg, params, n_slots=1, clock=clock,
                       shed_expired=True)
    prompt = jnp.arange(6, dtype=jnp.int32) % cfg.vocab_size
    r_ok = sched.submit(prompt, max_new_tokens=2, deadline=100.0)
    r_dead = sched.submit(prompt, max_new_tokens=2, deadline=5.0)
    r_queued = sched.submit(prompt, max_new_tokens=2, deadline=50.0)
    clock.t = 10.0                        # r_dead's deadline passed
    rec = sched.tick()
    dead = sched.requests[r_dead]
    assert dead.state is RequestState.SHED
    assert dead.finished_at == 10.0
    assert rec.deadline_misses == 1
    assert rec.admitted == (r_queued,)    # EDF among the survivors
    assert rec.queue_depth == 1           # r_ok still waiting
    assert sched.shed == 1 and sched.deadline_misses == 1
    outs = sched.run_until_idle()
    assert sorted(outs) == sorted([r_ok, r_queued])


def test_late_completion_counts_as_miss(setup):
    """A request that finishes past its deadline is a miss (counted once,
    in the tick where its tokens landed)."""
    cfg, params = setup
    clock = FakeClock()
    sched = make_sched(cfg, params, n_slots=1, clock=clock)
    prompt = jnp.arange(6, dtype=jnp.int32) % cfg.vocab_size
    rid = sched.submit(prompt, max_new_tokens=2, deadline=5.0)
    clock.t = 10.0                        # already late, but admitted
    sched.run_until_idle()
    assert sched.requests[rid].state is RequestState.DONE
    assert sched.deadline_misses == 1
    assert sum(rec.deadline_misses for rec in sched.trace) == 1


def test_frontend_marks_late_completion_missed(setup):
    cfg, params = setup
    clock = FakeClock()
    sched = make_sched(cfg, params, n_slots=1, clock=clock)
    prompt = jnp.arange(6, dtype=jnp.int32) % cfg.vocab_size

    async def go():
        async with ServeFrontend(sched, enforce_deadlines=False) as fe:
            stream = await fe.submit(prompt, 2, deadline=5.0)
            clock.t = 10.0
            async for _ in stream:
                pass
            return stream.record, fe.stats()

    rec, stats = asyncio.run(go())
    assert rec.status == "completed" and rec.missed is True
    assert stats["completed"] == 1 and stats["completed_in_slo"] == 0
    assert stats["missed"] == 1


# ---------------------------------------------------------------------------
# backpressure
# ---------------------------------------------------------------------------

def test_frontend_backpressure(setup):
    """The bounded queue rejects (wait=False) or suspends (wait=True)
    instead of queueing without limit."""
    cfg, params = setup
    sched = make_sched(cfg, params, n_slots=1, max_len=48)
    prompt = jnp.arange(6, dtype=jnp.int32) % cfg.vocab_size

    async def go():
        fe = ServeFrontend(sched, max_queue=1)
        async with fe:
            streams = [await fe.submit(prompt, 2)]
            while fe.queue_depth() > 0:     # let the serve loop admit it
                await asyncio.sleep(0)
            streams.append(await fe.submit(prompt, 2))
            # queue bound 1 and one request already waiting: the next
            # non-waiting submit bounces (no await since the last one,
            # so the serve loop cannot have drained the queue).
            with pytest.raises(QueueFullError):
                await fe.submit(prompt, 2)
            assert fe.rejected == 1
            # wait=True parks until the queue drains, then succeeds
            streams.append(await fe.submit(prompt, 2, wait=True))
            for s in streams:
                async for _ in s:
                    pass
            return fe.stats()

    stats = asyncio.run(go())
    assert stats["completed"] == 3 and stats["rejected"] == 1


# ---------------------------------------------------------------------------
# streaming identity + adaptive admission
# ---------------------------------------------------------------------------

def test_streaming_tokens_match_batch_path(setup):
    """Streamed tokens are the same tokens run_until_idle returns —
    streaming changes delivery, never content."""
    cfg, params = setup
    tokens = make_batch(cfg, 2, 12, kind="prefill", seed=31)["tokens"]
    ref_sched = make_sched(cfg, params, n_slots=2, max_len=32)
    ref_ids = [ref_sched.submit(tokens[0], max_new_tokens=5),
               ref_sched.submit(tokens[1][:8], max_new_tokens=5)]
    ref = ref_sched.run_until_idle()

    sched = make_sched(cfg, params, n_slots=2, max_len=32)

    async def go():
        async with ServeFrontend(sched) as fe:
            s0 = await fe.submit(tokens[0], 5)
            s1 = await fe.submit(tokens[1][:8], 5)
            out = []
            for s in (s0, s1):
                out.append([t async for t in s])
            return out

    got = asyncio.run(go())
    assert got[0] == ref[ref_ids[0]]
    assert got[1] == ref[ref_ids[1]]


def test_burst_drain_spreads_inter_token_times(setup):
    """Each token is stamped with the time its drain landed it on the
    host, never with an invented one: the k tokens of one fused
    dispatch land in one fetch and share its stamp, the next dispatch's
    land later, and the first token keeps the prefill's own fetch time
    (an earlier front end spread a burst linearly over the dispatch
    interval and fell back to the pump's time for the first token)."""
    cfg, params = setup
    tokens = make_batch(cfg, 1, 10, kind="prefill", seed=33)["tokens"]
    n_new = 8
    sched = make_sched(cfg, params, n_slots=1, max_len=32,
                       dispatch_depth=4)    # fused: 4-token drain bursts

    async def go():
        async with ServeFrontend(sched) as fe:
            stream = await fe.submit(tokens[0], n_new)
            return [t async for t in stream], stream.record

    out, rec = asyncio.run(go())
    req = sched.requests[rec.rid]
    assert len(out) == n_new and rec.token_times == req.landed_at
    # the prefill's first token, then two drains: 4 tokens, then 3
    bursts = [len(list(g)) for _, g in itertools.groupby(rec.token_times)]
    assert bursts == [1, 4, 3], rec.token_times
    assert rec.token_times == sorted(rec.token_times)
    assert rec.token_times[0] == rec.first_token_at == req.first_token_at
    assert rec.first_token_at >= req.admitted_at >= req.arrival


def test_adaptive_admission_decisions_in_trace(setup):
    """admission='adaptive': every throttled admission round is a
    serve_admission engine decision with its inputs on the record."""
    cfg, params = setup
    sched = make_sched(cfg, params, n_slots=2, max_len=48,
                       admission="adaptive")
    sched.warmup()
    prompt = jnp.arange(8, dtype=jnp.int32) % cfg.vocab_size
    for _ in range(5):
        sched.submit(prompt, max_new_tokens=2)
    outs = sched.run_until_idle()
    assert len(outs) == 5                 # throttling never starves
    entries = sched.decision_model().trace.entries("serve_admission")
    assert entries, "adaptive admission must go through the engine"
    for e in entries:
        inputs = dict(e.decision.inputs)
        assert "queue_depth" in inputs and "free_slots" in inputs
        assert 1 <= e.decision.cores <= 2
    # explain() renders them (the --explain-decisions surface)
    assert "serve_admission" in sched.decision_model().explain()


def test_adaptive_admission_urgency_override(setup):
    """A head-of-queue request inside two admission rounds of its
    deadline opens the width to every free slot."""
    cfg, params = setup
    clock = FakeClock(t=100.0)
    sched = make_sched(cfg, params, n_slots=2, max_len=48,
                       admission="adaptive", clock=clock)
    sched.warmup()
    prompt = jnp.arange(8, dtype=jnp.int32) % cfg.vocab_size
    # Deadline exactly now: zero slack is inside any urgency bound, so
    # the width opens to every free slot regardless of the prior.
    sched.submit(prompt, max_new_tokens=2, deadline=100.0)
    sched.submit(prompt, max_new_tokens=2, deadline=100.0)
    rec = sched.tick()
    assert len(rec.admitted) == 2
    e = sched.decision_model().trace.entries("serve_admission")[-1]
    assert dict(e.decision.inputs)["urgent"] is True
    sched.run_until_idle()


# ---------------------------------------------------------------------------
# load generators
# ---------------------------------------------------------------------------

def test_traces_deterministic_and_shaped():
    for name, mk in (("poisson", lambda s: poisson_trace(
            200, rate_rps=50.0, seed=s)),
            ("bursty", lambda s: bursty_trace(
                200, base_rate_rps=10.0, burst_rate_rps=200.0, seed=s)),
            ("heavy", lambda s: heavy_tailed_trace(
                200, rate_rps=50.0, seed=s))):
        a, b, c = mk(0), mk(0), mk(1)
        assert a == b, f"{name}: same seed must replay identically"
        assert a != c, f"{name}: different seed must differ"
        assert len(a) == 200
        arr = [t.arrival_s for t in a]
        assert arr == sorted(arr) and arr[0] >= 0.0
        for t in a:
            assert t.prompt_len >= 1 and t.new_tokens >= 1
            assert t.deadline_s > t.arrival_s      # SLO is future-dated


def test_heavy_tail_is_heavy():
    trace = heavy_tailed_trace(2000, rate_rps=50.0, seed=3)
    s = trace_summary(trace)
    assert s["prompt_p99"] >= 3 * s["prompt_p50"]
    assert max(t.prompt_len for t in trace) <= 96   # clipped to geometry
    assert max(t.new_tokens for t in trace) <= 48


def test_slo_model_scales_with_length():
    slo = SLOModel(ttft_s=0.5, per_token_s=0.1)
    assert slo.deadline_offset(10) == pytest.approx(1.5)
    assert slo.deadline_offset(20) > slo.deadline_offset(10)
    trace = poisson_trace(10, rate_rps=100.0, seed=0, slo=None)
    assert all(t.deadline_s is None for t in trace)


def test_materialize_seeded_prompts():
    trace = poisson_trace(50, rate_rps=50.0, seed=2)
    m1 = materialize(trace, vocab=128, seed=2)
    m2 = materialize(trace, vocab=128, seed=2)
    for (_, p1), (_, p2) in zip(m1, m2, strict=True):
        np.testing.assert_array_equal(p1, p2)
        assert p1.dtype == np.int32
        assert p1.min() >= 0 and p1.max() < 128
    assert [p.shape[0] for _, p in m1] == [t.prompt_len for t in trace]
