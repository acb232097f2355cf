"""Drive the program's normal serving entry for one measured window.

Client coroutines call ``ServeFrontend.submit`` and consume each
``TokenStream``; the front end drives ``ServeScheduler`` with the fused
decode loop (``dispatch_depth="auto"``), ``admission="adaptive"``, no
speculation and no kernel tuner, over the contiguous KV pool.  Every
time here is the client's: when a request was due, when it was sent,
when each token reached the client.  Nothing the front end stamps is
read.
"""
from __future__ import annotations

import asyncio
import collections
import contextlib
import dataclasses
import logging
import time

import numpy as np

from . import traffic

WINDOW_SPAN = "bench.window"
# A traced run traces the window's last seconds only (see run_window).
TRACE_SECONDS = 8.0
# Warm-up requests decode this many tokens: enough for the decode loop
# to run and be timed, short enough that warm-up is mostly prefill.
WARM_NEW_TOKENS = 2


def now() -> float:
    return time.perf_counter()


@dataclasses.dataclass
class Client:
    req: traffic.Request
    prompt: np.ndarray
    due: float | None = None          # perf_counter seconds
    sent: float | None = None
    rid: int | None = None
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    status: str = "unsent"            # unsent|failed|completed|cut|...
    error: str = ""


class CompileCounter(logging.Handler):
    """Backend compiles and persistent-cache hits, from JAX's own
    monitoring events; between ``watch()`` and ``unwatch()`` also the
    name of every program compiled or loaded from the cache."""

    _LOGGER = "jax._src.interpreters.pxla"

    def __init__(self):
        import jax

        super().__init__(logging.DEBUG)
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.names: collections.Counter = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def emit(self, record):
        if record.msg.startswith("Compiling") and record.args:
            self.names[str(record.args[0])] += 1

    def watch(self):
        log = logging.getLogger(self._LOGGER)
        self._saved = log.level, log.propagate
        log.setLevel(logging.DEBUG)
        log.propagate = False
        log.addHandler(self)

    def unwatch(self):
        log = logging.getLogger(self._LOGGER)
        log.removeHandler(self)
        log.setLevel(self._saved[0])
        log.propagate = self._saved[1]

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.compiles, self.compile_s, self.cache_hits


def build_scheduler(cfg, params, model: dict):
    from repro.core.acc import AdaptiveCoreChunk
    from repro.core.adaptive import adaptive
    from repro.core.calibration import CalibrationCache
    from repro.core.executor import SequentialExecutor
    from repro.serve import ServeScheduler

    acc = AdaptiveCoreChunk(cache=CalibrationCache())   # memory only
    return ServeScheduler(
        cfg, params, n_slots=model["serve"]["n_slots"],
        max_len=model["serve"]["max_len"],
        executor=adaptive(SequentialExecutor(), acc),
        dispatch_depth="auto", admission="adaptive")


COUNTERS = ("decode_tokens", "decode_dispatches", "decode_loop_iters",
            "host_roundtrips")


def counters(sched) -> dict:
    return {k: getattr(sched, k) for k in COUNTERS}


def annotate(sched) -> None:
    """Host spans around the scheduler's layers (traced runs only): the
    trace then says what the host did in each device idle gap."""
    import jax

    for name in ("tick", "_admit", "_run_prefill", "_dispatch_decode",
                 "_drain"):
        fn = getattr(sched, name, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _span=f"sched.{name.lstrip('_')}", **kw):
            with jax.profiler.TraceAnnotation(_span):
                return _fn(*a, **kw)

        setattr(sched, name, wrapped)


async def _consume(fe, c: Client, new_tokens: int) -> None:
    from repro.serve import QueueFullError, PromptTooLongError

    c.sent = now()
    try:
        stream = await fe.submit(c.prompt, max_new_tokens=new_tokens)
    except (QueueFullError, PromptTooLongError) as e:
        c.status, c.error = "failed", repr(e)
        return
    c.rid = stream.rid
    async for tok in stream:
        c.times.append(now())
        c.tokens.append(int(tok))
    c.status = stream.record.status
    if c.status == "aborted":
        c.status = "cut"                 # the window closed under it


async def warm_up(fe, prompts: list, new_tokens: int) -> None:
    """Serve every prompt once (``new_tokens`` each) through the front
    end: compiles the window's prompt shapes and lets the scheduler's
    decisions see real ticks."""
    streams = [await fe.submit(p, max_new_tokens=new_tokens, wait=True)
               for p in prompts]
    for s in streams:
        async for _ in s:
            pass


async def open_loop(fe, clients: list, t0: float, t_end: float,
                    tasks: list) -> None:
    for c in clients:
        c.due = t0 + c.req.due_s
        if c.due >= t_end:
            break
        delay = c.due - now()
        if delay > 0:
            await asyncio.sleep(delay)
        if now() >= t_end:
            break
        tasks.append(asyncio.create_task(
            _consume(fe, c, c.req.new_tokens)))


async def closed_loop(fe, clients: list, n_callers: int, t_end: float,
                      tasks: list) -> None:
    """Callers draw the set's requests in turn, and from its start again
    once it is spent (a new ``Client`` with the same request and prompt,
    appended to ``clients``), so a faster program never runs dry."""
    n_set, drawn = len(clients), 0

    async def caller():
        nonlocal drawn
        while now() < t_end:
            if drawn < len(clients):
                c = clients[drawn]
            else:
                base = clients[drawn % n_set]
                c = Client(base.req, base.prompt)
                clients.append(c)
            drawn += 1
            c.due = now()
            await _consume(fe, c, c.req.new_tokens)

    tasks.extend(asyncio.create_task(caller()) for _ in range(n_callers))


@dataclasses.dataclass
class Window:
    t0: float                           # perf_counter time the window opened
    seconds: float
    clients: list
    span_s: float                       # the counted span (see run_window)
    counters: dict                      # deltas over the span
    compiles: tuple                     # (compiles, seconds, cache hits)
    setup_compiles: tuple               # the same, from start to window
    lateness_s: list                    # open loop: sent - due
    requests: list                      # span work per request (span_work)


def snapshot(sched) -> dict:
    """Counters, tick count and tokens generated per request, taken where
    no dispatch is in flight."""
    return {"counters": counters(sched), "ticks": len(sched.trace),
            "generated": {rid: len(r.out) for rid, r in sched.requests.items()}}


def span_work(sched, start: dict) -> list[dict]:
    """Per request, the work of the span since ``start``: prompt length,
    prompt tokens prefilled in the span (``computed``, ending at
    ``prefilled``), and tokens generated before the span
    (``generated0``) and by its end (the first comes from prefill)."""
    steps: dict = {}
    for t in sched.trace[start["ticks"]:]:
        for rid, step in t.prefill_ops:
            steps[rid] = steps.get(rid, 0) + step
    return [{"prompt_len": r.prompt_len, "prefilled": r.prefilled,
             "computed": steps.get(rid, 0),
             "generated0": start["generated"].get(rid, 0),
             "generated": len(r.out)}
            for rid, r in sched.requests.items()]


def run_window(sched, mix: dict, clients: list, warm_prompts: list,
               seconds: float, counter: CompileCounter, *,
               trace_dir: str | None = None) -> Window:
    """Warm up, then measure ``seconds``.

    The counted span is the whole window, from its opening until the
    last dispatched token has landed.  A traced run counts and traces
    only the window's last ``TRACE_SECONDS``: a longer trace overflows
    the device's trace buffer and silently loses events.  The pipeline
    is flushed where the trace starts, so the span's counters and the
    device work in the trace cover the same dispatches."""
    import jax

    from repro.serve import ServeFrontend

    async def main():
        fe = ServeFrontend(sched)
        await fe.start()
        await warm_up(fe, warm_prompts, WARM_NEW_TOKENS)
        sched.flush()
        sched.clear_finished()
        fe.records.clear()
        k0 = counter.snapshot()
        counter.watch()
        tasks: list = []
        t0 = now()
        t_end = t0 + seconds
        start, t_span = snapshot(sched), t0
        if mix["loop"] == "open":
            sender = asyncio.create_task(
                open_loop(fe, clients, t0, t_end, tasks))
        else:
            sender = asyncio.create_task(
                closed_loop(fe, clients, mix["clients"], t_end, tasks))
        span = contextlib.nullcontext()
        if trace_dir is not None:
            annotate(sched)
            await asyncio.sleep(max(t_end - TRACE_SECONDS - now(), 0.0))
            sched.flush()
            start, t_span = snapshot(sched), now()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans, not every call
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        with span:
            await asyncio.sleep(max(t_end - now(), 0.0))
            await fe.stop()              # lands in-flight work, ends streams
            t_stop = now()
        counter.unwatch()
        if trace_dir is not None:
            jax.profiler.stop_trace()
        await sender
        await asyncio.gather(*tasks)
        end = snapshot(sched)
        return Window(
            t0=t0, seconds=seconds, clients=clients, span_s=t_stop - t_span,
            counters={k: v - start["counters"][k]
                      for k, v in end["counters"].items()},
            compiles=tuple(b - a for a, b in
                           zip(k0, counter.snapshot(), strict=True)),
            setup_compiles=k0,
            lateness_s=[c.sent - c.due for c in clients
                        if c.sent is not None and mix["loop"] == "open"],
            requests=span_work(sched, start))

    return asyncio.run(main())
