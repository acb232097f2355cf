"""The serving program's spans (serve/spans.py): the bounded ring, the
lane counts each fused dispatch carries, the shared clock with the
profiler's trace, the request records, and the named scopes of the
fused step."""
import glob
import re
import tempfile
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import SequentialExecutor, adaptive
from repro.core.acc import AdaptiveCoreChunk
from repro.models import init_params, lm
from repro.serve import ServeScheduler, spans
from repro.serve.decode_loop import make_fused_decode_step
from repro.serve.scheduler import LANES


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3-0.6b").reduced()
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def make_sched(cfg, params, **kw):
    return ServeScheduler(
        cfg, params, executor=adaptive(SequentialExecutor(),
                                       AdaptiveCoreChunk()), **kw)


def serve_mix(sched, n=6):
    """Prompts longer than the largest chunk bucket (prefill spans
    ticks while other lanes decode), budgets that clamp lanes inside a
    dispatch, more requests than slots (a queue)."""
    for i in range(n):
        prompt = (jnp.arange(6 + 5 * i, dtype=jnp.int32) * (i + 3)) \
            % sched.cfg.vocab_size
        sched.submit(prompt, max_new_tokens=3 + 4 * (i % 3))
    return sched.run_until_idle()


def test_ring_is_bounded_and_reports_a_lost_window_start():
    rec = spans.Recorder(size=4)
    assert rec.earliest_start() is None and rec.between(0.0, 1.0) == []
    t0 = time.perf_counter()
    for _ in range(3):
        with rec.span("serve.tick"):
            pass
    held = rec.between(t0, time.perf_counter())
    assert [s.name for s in held] == ["serve.tick"] * 3
    t_mid = time.perf_counter()
    for i in range(4):
        with rec.span("serve.admit", admitted=i) as sp:
            sp.attrs["queue"] = 10 - i
    # the oldest spans fell out: the ring holds four, and the earliest
    # start it holds is past both t0 and t_mid
    assert len(rec.held()) == 4 and rec.earliest_start() > t_mid > t0
    assert rec.lost_since(t0) and rec.lost_since(t_mid)
    assert rec.between(t0, time.perf_counter()) is None
    assert rec.between(t_mid, time.perf_counter()) is None
    assert [s.attrs for s in rec.held()] == [
        {"admitted": i, "queue": 10 - i} for i in range(4)]
    # a window that begins after the earliest span held is whole
    t_late = time.perf_counter()
    with rec.span("serve.tick"):
        pass
    assert [s.name for s in rec.between(t_late, time.perf_counter())] \
        == ["serve.tick"]
    assert spans.RECORDER._ring.maxlen == spans.RING_SIZE == 1 << 17


def test_spans_nest_by_parent_and_hold_only_host_values():
    rec = spans.Recorder()
    with rec.span("serve.tick", tick=7) as tick:
        with rec.span("serve.prefill") as pf:
            with rec.span("serve.prefill.chunk", parent=pf.id):
                pass
        rec.record("serve.request", tick.start, time.perf_counter(), rid=1)
    by = {s.name: s for s in rec.held()}
    assert by["serve.tick"].parent is None
    assert by["serve.prefill"].parent == by["serve.tick"].id
    assert by["serve.prefill.chunk"].parent == by["serve.prefill"].id
    assert by["serve.request"].attrs == {"rid": 1}
    for s in rec.held():
        assert s.start <= s.end
        assert all(isinstance(v, (int, float, str, bool, type(None)))
                   for v in s.attrs.values())


@pytest.mark.parametrize("speculate", [None, 2])
def test_lane_counts_cover_every_slot_every_iteration(setup, speculate):
    """At each fused dispatch (speculative: at its drain) the five lane
    counts sum to iters x n_slots; over the run the decoding count is
    the scheduler's decode_tokens (occupancy's numerator), or under
    speculation its verify events."""
    cfg, params = setup
    sched = make_sched(cfg, params, n_slots=3, max_len=128,
                       dispatch_depth=4, chunk_buckets=(8, 16),
                       speculate=speculate)
    sched.warmup()
    spans.RECORDER.clear()
    serve_mix(sched)
    carried = [s for s in spans.RECORDER.held() if "lane_decode" in s.attrs]
    assert carried
    for s in carried:
        counts = [s.attrs[f"lane_{k}"] for k in LANES]
        assert min(counts) >= 0
        assert sum(counts) == s.attrs["iters"] * sched.pool.n_slots
        if speculate is None:
            assert s.name == "serve.dispatch"
            assert s.attrs["lane_decode"] == s.attrs["tokens"]
        else:
            assert s.name in ("serve.dispatch", "serve.drain")
    n = sched.pool.n_slots
    assert sum(sched.lane_iters.values()) == sched.decode_loop_iters * n
    if speculate is None:
        assert sched.lane_iters["decode"] == sched.decode_tokens
        assert sched.lane_iters["decode"] / (sched.decode_loop_iters * n) \
            == sched.decode_tokens / (sched.decode_loop_iters * n)
    else:
        assert sched.lane_iters["decode"] == sched.spec_verifies
    # the mix exercises the lanes beside decoding
    assert sched.lane_iters["clamped"] > 0
    assert sched.lane_iters["prefill"] > 0


def test_counters_are_sums_over_the_spans(setup):
    """``host_overhead_s`` is the tick spans less the device waits in
    them; the waits are the blocking drains, the synced launches and
    the stretch of each prefill's chunk thunks."""
    cfg, params = setup
    sched = make_sched(cfg, params, n_slots=2, max_len=96,
                       dispatch_depth=4, chunk_buckets=(8, 16))
    sched.warmup()
    spans.RECORDER.clear()
    sched.host_overhead_s = 0.0
    serve_mix(sched, n=4)
    held = spans.RECORDER.held()
    ticks = [s for s in held if s.name == "serve.tick"]
    assert [s.attrs["tick"] for s in ticks] == [r.tick for r in sched.trace]
    by_id = {s.id: s for s in held}

    def in_tick(s):
        while s.parent is not None:
            s = by_id[s.parent]
        return s.name == "serve.tick"

    waits = sum(s.end - s.start for s in held if in_tick(s) and (
        (s.name == "serve.drain" and s.attrs["blocked"])
        or (s.name == "serve.dispatch.launch" and s.attrs["synced"])))
    for s in held:
        if s.name == "serve.prefill" and in_tick(s):
            thunks = [c for c in held if c.parent == s.id and c.name in (
                "serve.prefill.read_slot", "serve.prefill.chunk")]
            waits += max(c.end for c in thunks) - min(c.start for c in thunks)
    total = sum(s.end - s.start for s in ticks)
    assert sched.host_overhead_s == pytest.approx(total - waits, abs=1e-6)
    pf = [s for s in held if s.name == "serve.prefill"]
    assert sum(s.attrs["chunks"] for s in pf) == sum(
        len(r.prefill_ops) for r in sched.trace)
    for s in pf:
        kids = [c.name for c in held if c.parent == s.id]
        assert kids.count("serve.prefill.chunk") == s.attrs["chunks"]
        assert kids.count("serve.prefill.read_slot") == s.attrs["chunks"]
        assert kids.count("serve.prefill.write_slot") == s.attrs["chunks"]


def test_request_spans_carry_queue_wait(setup):
    cfg, params = setup
    sched = make_sched(cfg, params, n_slots=2, max_len=96,
                       dispatch_depth=2)
    sched.warmup()
    spans.RECORDER.clear()
    outs = serve_mix(sched, n=5)
    reqs = [s for s in spans.RECORDER.held() if s.name == "serve.request"]
    assert sorted(s.attrs["rid"] for s in reqs) == sorted(outs)
    for s in reqs:
        a = s.attrs
        assert a["state"] == "done" and a["tokens"] == len(outs[a["rid"]])
        assert a["submitted"] <= a["admitted"] <= a["first_token"] \
            <= a["finished"] == s.end
        req = sched.requests[a["rid"]]
        assert len(req.landed_at) == len(req.out)
        assert req.landed_at == sorted(req.landed_at)
    # two slots for five requests: some waited for admission
    assert max(s.attrs["admitted"] - s.attrs["submitted"]
               for s in reqs) > 0


def test_spans_share_the_profiler_clock(setup):
    """Every span the ring holds from a traced run is on the trace's host
    plane under the same name, in the same order, with a duration within
    1 ms.  ``serve.request`` is written at finish from earlier stamps,
    not annotated, so it has no event there."""
    from jax.profiler import ProfileData

    cfg, params = setup
    sched = make_sched(cfg, params, n_slots=2, max_len=96,
                       dispatch_depth=2, chunk_buckets=(8, 16))
    sched.warmup()
    spans.RECORDER.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            serve_mix(sched, n=3)
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
        traced = sorted(
            (float(e.start_ns), e.name, float(e.duration_ns) / 1e9)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("serve."))
    held = sorted((s.start, s.name, s.end - s.start)
                  for s in spans.RECORDER.held()
                  if s.name != "serve.request")
    assert len(held) > 20
    assert [n for _, n, _ in traced] == [n for _, n, _ in held]
    for (_, name, a), (_, _, b) in zip(traced, held, strict=True):
        assert abs(a - b) < 1e-3, (name, a, b)


def test_fused_step_carries_named_scopes(setup):
    """The fused decode program's operations say which part of the step
    they belong to: attention, mlp, head, and the lane merge."""
    cfg, params = setup
    caches = lm.init_caches(cfg, 2, 32)
    z = jnp.zeros(2, jnp.int32)
    text = make_fused_decode_step(cfg, max_depth=2).lower(
        params, caches, z, z, z).as_text(debug_info=True)
    scopes = set(re.findall(r"[/(](attention|mlp|head|merge)[/)]", text))
    assert scopes == {"attention", "mlp", "head", "merge"}


def test_serve_cli_summarises_the_spans(setup, capsys):
    from repro.launch.serve import print_span_summary

    cfg, params = setup
    sched = make_sched(cfg, params, n_slots=2, max_len=96,
                       dispatch_depth=2)
    sched.warmup()
    spans.RECORDER.clear()
    serve_mix(sched, n=4)
    print_span_summary(sched)
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("host seconds by span: serve.tick ")
    assert "serve.request" not in out[0]
    assert out[1].startswith("queue wait over 4 requests: p50=")
    assert out[2].startswith("decode lanes: decode ")
    shares = [float(x.rstrip("%")) for x in out[2].split()[3::2]]
    assert len(shares) == 5 and abs(sum(shares) - 100.0) < 0.5
