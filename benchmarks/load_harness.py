"""Trace-replay load harness: SLO-goodput is the headline number.

    PYTHONPATH=src python benchmarks/load_harness.py [--smoke]

Replays seeded request traces (serve/loadgen.py: Poisson, bursty MMPP,
heavy-tailed lognormal lengths, shared-prefix system-prompt mix)
through the asyncio ``ServeFrontend``
with streaming, cancellation (a seeded fraction of clients abandon
mid-stream), deadline shedding and bounded-queue backpressure enabled —
sustained open-loop traffic, not the 8-request makespan smoke that
``BENCH_serve.json`` reports.

Two configurations per trace, identical load:

* **adaptive** — ``AdaptiveCoreChunk`` + fused auto-depth decode +
  ``admission="adaptive"`` (the ``serve_admission`` ExecutionModel
  decision throttles burst admission from queue depth and measured
  tick time);
* **static**   — ``StaticCoreChunk`` on the per-tick decode path with
  greedy fill-every-slot admission: no measurement anywhere.

Reported per configuration (into ``BENCH_load.json``): **SLO-goodput**
(tokens/s from requests that completed within their deadline — the
number we quote), p50/p99 TTFT, p99 inter-token latency, deadline-miss
rate, shed/cancelled/rejected counts, and the admission-decision
provenance mix.  The ``shared_prefix`` trace adds a third
configuration — **paged** (the adaptive config on the
``PagedKVCachePool`` with copy-on-write prefix reuse) — and reports
its prefix-cache hit rate, prefill-tokens-avoided and per-tick
prefill-stall time alongside the goodput comparison against the
contiguous pool.  ``--speculate`` adds a **speculative** configuration
(adaptive + ``speculate="auto"``) on the ``templated`` trace (motif-
tiled, high n-gram self-overlap — where the prompt-lookup drafter gets
real acceptance) and on the ``heavy`` trace (low overlap — where the
``serve_spec_depth`` decision must back off to depth 1), reporting
acceptance rate, tokens-per-verify and decision provenance.
``--smoke`` runs small fixed-seed heavy-tailed and shared-prefix
traces and exits non-zero if adaptive SLO-goodput falls below static,
if the shared-prefix hit rate is zero, if paged goodput falls below
0.9x contiguous, or (with ``--speculate``) if speculative goodput
falls below non-speculative on the templated trace or below 0.95x on
the heavy trace (the CI regression guards).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.acc import AdaptiveCoreChunk, StaticCoreChunk  # noqa: E402
from repro.core.adaptive import adaptive  # noqa: E402
from repro.core.executor import SequentialExecutor  # noqa: E402
from repro.launch.compile_cache import place_compile_cache  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serve import (GENERATORS, QueueFullError,  # noqa: E402
                         ServeFrontend, ServeScheduler, SLOModel,
                         materialize, percentile, trace_summary)


def make_trace(kind: str, n: int, seed: int, slo: SLOModel):
    """One seeded trace per (kind, n, seed): every configuration replays
    the identical load."""
    if kind == "poisson":
        return GENERATORS[kind](n, rate_rps=40.0, new_tokens=10,
                                seed=seed, slo=slo)
    if kind == "bursty":
        return GENERATORS[kind](n, base_rate_rps=15.0, burst_rate_rps=150.0,
                                mean_dwell_s=(1.0, 0.3), new_tokens=10,
                                seed=seed, slo=slo)
    if kind == "heavy":
        return GENERATORS[kind](n, rate_rps=40.0, seed=seed, slo=slo)
    if kind == "templated":
        # High n-gram self-overlap (motif-tiled prompts, cyclic greedy
        # continuations) — the workload where the prompt-lookup drafter
        # gets real acceptance, so the speculative configuration's win
        # is measurable under the full async front end.
        return GENERATORS[kind](n, rate_rps=40.0, motif_len=6,
                                median_prompt=16, prompt_sigma=0.3,
                                max_prompt=32, median_new=32,
                                new_sigma=0.3, max_new=64,
                                seed=seed, slo=slo)
    if kind == "shared_prefix":
        # Shaped like the production case for prefix reuse — a long
        # shared system prompt, short per-request suffixes and answers
        # — and driven hard enough, under a tight TTFT-dominated SLO,
        # to *deeply* saturate the contiguous pool (which must prefill
        # all 512 shared tokens per request) while the paged pool,
        # skipping them on every prefix hit, stays clear.  Both ends
        # matter: at a rate every policy absorbs the avoided prefill
        # becomes idle time instead of goodput and the comparison
        # ties, and a baseline only marginally over its cliff flips
        # with run-to-run machine noise.  Short answers keep the
        # comparison about prefill (what the cache avoids) rather
        # than decode volume.
        tight = SLOModel(ttft_s=0.25, per_token_s=0.015)
        return GENERATORS[kind](n, rate_rps=150.0, prefix_len=512,
                                median_new=2, max_new=4,
                                seed=seed, slo=tight)
    raise ValueError(f"unknown trace kind {kind!r}")


def build_sched(policy: str, cfg, params, *, n_slots: int,
                max_len: int) -> ServeScheduler:
    if policy in ("adaptive", "paged", "speculative"):
        return ServeScheduler(
            cfg, params, n_slots=n_slots, max_len=max_len,
            executor=adaptive(SequentialExecutor(), AdaptiveCoreChunk()),
            dispatch_depth="auto", admission="adaptive",
            paged=policy == "paged",
            speculate="auto" if policy == "speculative" else None)
    return ServeScheduler(
        cfg, params, n_slots=n_slots, max_len=max_len,
        executor=adaptive(SequentialExecutor(),
                          StaticCoreChunk(cores=1, chunks_per_core=8)),
        admission="greedy")


async def replay(frontend: ServeFrontend, mat_trace, *,
                 cancel_frac: float, seed: int) -> float:
    """Open-loop replay: every request is submitted at its trace time
    regardless of system state; a seeded ``cancel_frac`` of clients
    abandon their stream mid-generation.  Returns the makespan."""
    rng = np.random.RandomState(seed + 7919)
    cancel_at = {}
    for i, (tr, _) in enumerate(mat_trace):
        if rng.random_sample() < cancel_frac and tr.new_tokens >= 2:
            cancel_at[i] = int(rng.randint(1, tr.new_tokens))
    clock = frontend.clock      # deadlines are on the scheduler's clock
    t0 = clock()

    async def one(i, tr, prompt):
        delay = tr.arrival_s - (clock() - t0)
        if delay > 0:
            await asyncio.sleep(delay)
        deadline = None if tr.deadline_s is None else t0 + tr.deadline_s
        try:
            stream = await frontend.submit(prompt, tr.new_tokens,
                                           deadline=deadline)
        except QueueFullError:
            return          # backpressure: shed at the door, counted
        k = cancel_at.get(i)
        got = 0
        async for _tok in stream:
            got += 1
            if k is not None and got >= k:
                await stream.cancel()

    await asyncio.gather(*(one(i, tr, p)
                           for i, (tr, p) in enumerate(mat_trace)))
    return clock() - t0


def run_config(name: str, cfg, params, mat_trace, *, n_slots: int,
               max_len: int, max_queue: int, cancel_frac: float,
               seed: int) -> tuple[dict, ServeScheduler]:
    sched = build_sched(name, cfg, params, n_slots=n_slots,
                        max_len=max_len)
    sched.warmup()
    # Untimed prewarm: compile every distinct prompt-length host op so
    # the timed replay measures serving, not the process's one-time
    # compiles (same discipline as benchmarks/serve_throughput.py).
    by_len = {}
    for tr, prompt in mat_trace:
        by_len.setdefault(int(tr.prompt_len), prompt)
    for prompt in by_len.values():
        sched.submit(prompt, max_new_tokens=4)
    sched.run_until_idle()
    sched.clear_finished()
    sched.decode_dispatches = sched.decode_tokens = 0
    sched.host_roundtrips = 0
    sched.host_overhead_s = 0.0
    sched.deadline_misses = sched.shed = sched.cancelled = 0
    sched.spec_verifies = sched.spec_emitted = sched.spec_rounds = 0
    if sched.paged:
        # Cached prefix entries from the prewarm stay live (that's the
        # steady state a hot system prompt reaches); only the counters
        # reset so the reported hit rate covers the replayed trace.
        sched.pool.reset_prefix_stats()
        sched.prefill_stall_s = 0.0
    model = sched.decision_model()
    admit_seen = len(model.trace.entries("serve_admission")) \
        if model is not None else 0
    spec_seen = len(model.trace.entries("serve_spec_depth")) \
        if model is not None else 0

    frontend = ServeFrontend(sched, max_queue=max_queue)

    async def go():
        async with frontend:
            return await replay(frontend, mat_trace,
                                cancel_frac=cancel_frac, seed=seed)

    makespan = asyncio.run(go())

    recs = list(frontend.records.values())
    completed = [r for r in recs if r.status == "completed"]
    in_slo = [r for r in completed if not r.missed]
    cancelled = sum(1 for r in recs if r.status == "cancelled")
    shed = sum(1 for r in recs if r.status == "shed")
    late = sum(1 for r in completed if r.missed)
    eligible = max(len(mat_trace) - cancelled, 1)
    ttfts = [r.first_token_at - r.submitted_at for r in recs
             if r.first_token_at is not None]
    itls = [b - a for r in recs
            for a, b in zip(r.token_times, r.token_times[1:], strict=False)]
    gen = sum(r.tokens for r in recs)
    report = {
        "policy": name,
        "requests": len(mat_trace),
        "completed": len(completed),
        "completed_in_slo": len(in_slo),
        "generated_tokens": gen,
        "makespan_s": round(makespan, 3),
        # The headline: tokens that arrived in time, per second.
        "slo_goodput_tok_s": round(
            sum(r.tokens for r in in_slo) / makespan, 2) if makespan
        else 0.0,
        "tokens_per_s": round(gen / makespan, 2) if makespan else 0.0,
        "ttft_p50_ms": round(percentile(ttfts, 50) * 1e3, 1),
        "ttft_p99_ms": round(percentile(ttfts, 99) * 1e3, 1),
        "itl_p99_ms": round(percentile(itls, 99) * 1e3, 1),
        "deadline_miss_rate": round(
            (late + shed + frontend.rejected) / eligible, 4),
        "late_completions": late,
        "shed": shed,
        "cancelled": cancelled,
        "rejected": frontend.rejected,
        "ticks": len(sched.trace),
        "host_overhead_ms_per_token":
            round(sched.host_overhead_s / gen * 1e3, 3) if gen else 0.0,
    }
    if sched.paged:
        stats = sched.pool.prefix_stats()
        stats["prefix_hit_rate"] = round(stats["prefix_hit_rate"], 4)
        report["prefix"] = stats
        report["prefill_stall_s"] = round(sched.prefill_stall_s, 4)
    if sched._spec:
        st = sched.spec_stats()
        report["speculate"] = {
            "final_depth": st["depth"],
            "verifies": st["verifies"],
            "emitted": st["emitted"],
            "tokens_per_verify": round(st["tokens_per_verify"], 3),
            "acceptance_rate": round(st["acceptance_rate"], 4),
        }
        if model is not None:
            entries = model.trace.entries("serve_spec_depth")[spec_seen:]
            report["spec_decisions"] = len(entries)
            report["spec_provenance"] = sorted(
                {e.decision.provenance for e in entries})
    if model is not None:
        entries = model.trace.entries("serve_admission")[admit_seen:]
        report["admission_decisions"] = len(entries)
        report["admission_provenance"] = sorted(
            {e.decision.provenance for e in entries})
        widths = [e.decision.cores for e in entries]
        report["mean_admission_width"] = round(
            float(np.mean(widths)), 2) if widths else 0.0
        if sched.paged:
            for label, kind in (("page_size", "serve_page_size"),
                                ("interleave", "serve_prefill_interleave")):
                es = model.trace.entries(kind)
                report[f"{label}_provenance"] = sorted(
                    {e.decision.provenance for e in es})
    extra = ""
    if sched.paged:
        extra = (f" | prefix hits {report['prefix']['prefix_hit_rate']:.0%}"
                 f" avoided {report['prefix']['prefill_tokens_avoided']} tok"
                 f" | stall {report['prefill_stall_s'] * 1e3:.0f}ms")
    if sched._spec:
        sp = report["speculate"]
        extra = (f" | spec depth={sp['final_depth']} "
                 f"{sp['tokens_per_verify']:.2f} tok/verify "
                 f"(acceptance {sp['acceptance_rate']:.0%})")
    print(f"  {name:9s} goodput {report['slo_goodput_tok_s']:8.1f} tok/s "
          f"| ttft p99 {report['ttft_p99_ms']:7.1f}ms "
          f"| itl p99 {report['itl_p99_ms']:6.1f}ms "
          f"| miss {report['deadline_miss_rate']:.1%} "
          f"| shed {shed} cancelled {cancelled} rejected "
          f"{frontend.rejected}{extra}")
    return report, sched


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small fixed-seed heavy-tailed trace; exits "
                         "non-zero if adaptive SLO-goodput < static")
    ap.add_argument("--requests", type=int, default=None,
                    help="requests per trace (default: 1000 heavy / "
                         "256 others; 64 with --smoke)")
    ap.add_argument("--traces", default=None,
                    help="comma list from {heavy,poisson,bursty,"
                         "shared_prefix,templated} (default: all four "
                         "random kinds; heavy + shared_prefix with "
                         "--smoke, plus templated with --speculate)")
    ap.add_argument("--speculate", action="store_true",
                    help="additionally run the speculative "
                         "configuration (adaptive + speculate='auto') "
                         "on the templated and heavy traces; with "
                         "--smoke, fails if speculative goodput falls "
                         "below non-speculative on the templated trace "
                         "or below 0.95x on the heavy (low-overlap) "
                         "trace — the backoff guard")
    ap.add_argument("--seed", type=int, default=0,
                    help="single seed for arrivals, lengths, prompt "
                         "tokens and cancellation choices")
    ap.add_argument("--cancel-frac", type=float, default=0.05,
                    help="fraction of clients that abandon mid-stream")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-queue", type=int, default=128)
    ap.add_argument("--slo-ttft-ms", type=float, default=750.0)
    ap.add_argument("--slo-per-token-ms", type=float, default=60.0)
    ap.add_argument("--trace-out", default=None,
                    help="write the adaptive run's ExecutionModel "
                         "decision trace to this file")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_load.json"))
    args = ap.parse_args()
    place_compile_cache()

    kinds = (args.traces.split(",") if args.traces
             else (["heavy", "shared_prefix"] if args.smoke
                   else ["heavy", "poisson", "bursty", "shared_prefix"]))
    if args.speculate and "templated" not in kinds:
        kinds.append("templated")
    slo = SLOModel(ttft_s=args.slo_ttft_ms / 1e3,
                   per_token_s=args.slo_per_token_ms / 1e3)

    cfg = get_config("qwen3-0.6b").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)

    blob: dict = {"traces": {}, "smoke": bool(args.smoke),
                  "seed": args.seed,
                  "slo": {"ttft_ms": args.slo_ttft_ms,
                          "per_token_ms": args.slo_per_token_ms}}
    guard_ok = True
    explain_dump = None
    for kind in kinds:
        # The shared_prefix smoke needs enough sustained arrivals for
        # the contiguous pool's prefill queue to actually build — a
        # 64-request burst is absorbed by every policy and the paged
        # comparison degenerates to parity noise.
        n = args.requests or ((256 if kind == "shared_prefix" else 64)
                              if args.smoke
                              else (1000 if kind in ("heavy",
                                                     "shared_prefix")
                                    else 256))
        trace = make_trace(kind, n, args.seed, slo)
        max_len = max(t.prompt_len + t.new_tokens for t in trace) + 1
        # The shared-prefix trace additionally runs the paged pool with
        # copy-on-write prefix reuse against the contiguous adaptive
        # config — same load, same policy, only the cache layout
        # differs — so the goodput delta isolates what paging buys.
        # --speculate adds the speculative configuration on the
        # templated trace (where the drafter gets real acceptance) and
        # the heavy trace (low overlap: the backoff tax measurement).
        policies = (("paged", "adaptive", "static")
                    if kind == "shared_prefix" else ("adaptive", "static"))
        if args.speculate and kind in ("templated", "heavy"):
            policies = ("speculative",) + policies
            # Reserved draft margin: the last spec_d - 1 cache
            # positions are unusable under speculation (scheduler
            # docstring); every policy gets the same geometry so the
            # comparison stays layout-for-layout.
            max_len += 8
        mat = materialize(trace, cfg.vocab_size, seed=args.seed)
        print(f"{kind}: {trace_summary(trace)}")
        reports = {}
        for policy in policies:
            reports[policy], sched = run_config(
                policy, cfg, params, mat, n_slots=args.slots,
                max_len=max_len, max_queue=args.max_queue,
                cancel_frac=args.cancel_frac, seed=args.seed)
            if policy == "adaptive" and args.trace_out:
                model = sched.decision_model()
                if model is not None:
                    explain_dump = model.explain()
        ratio = (reports["adaptive"]["slo_goodput_tok_s"]
                 / reports["static"]["slo_goodput_tok_s"]) \
            if reports["static"]["slo_goodput_tok_s"] else float("inf")
        blob["traces"][kind] = {
            "trace": trace_summary(trace),
            **{p: reports[p] for p in policies},
            "adaptive_over_static_goodput": round(ratio, 3)
            if ratio != float("inf") else None,
        }
        print(f"  adaptive/static SLO-goodput: "
              f"{'inf' if ratio == float('inf') else f'{ratio:.2f}x'}")
        if reports["adaptive"]["slo_goodput_tok_s"] \
                < reports["static"]["slo_goodput_tok_s"]:
            guard_ok = False
        if kind == "shared_prefix":
            pr = (reports["paged"]["slo_goodput_tok_s"]
                  / reports["adaptive"]["slo_goodput_tok_s"]) \
                if reports["adaptive"]["slo_goodput_tok_s"] else float("inf")
            blob["traces"][kind]["paged_over_contiguous_goodput"] = \
                round(pr, 3) if pr != float("inf") else None
            hit = reports["paged"]["prefix"]["prefix_hit_rate"]
            print(f"  paged/contiguous SLO-goodput: "
                  f"{'inf' if pr == float('inf') else f'{pr:.2f}x'} "
                  f"(prefix hit rate {hit:.0%})")
            if hit <= 0.0:
                print("FAIL: shared-prefix trace produced a zero "
                      "prefix-cache hit rate — reuse is not engaging")
                guard_ok = False
            # Smoke guard: the paged pool must not lose to contiguous.
            # A small tolerance keeps run-to-run parity noise (the two
            # policies tie when neither saturates on a fast runner)
            # from flaking CI; a real regression — lost prefix cache,
            # donation bug, recompile per dispatch — lands far below.
            if reports["paged"]["slo_goodput_tok_s"] \
                    < 0.9 * reports["adaptive"]["slo_goodput_tok_s"]:
                print("FAIL: paged SLO-goodput below the contiguous "
                      "adaptive baseline on the shared-prefix trace")
                guard_ok = False
        if "speculative" in policies:
            sr = (reports["speculative"]["slo_goodput_tok_s"]
                  / reports["adaptive"]["slo_goodput_tok_s"]) \
                if reports["adaptive"]["slo_goodput_tok_s"] else float("inf")
            blob["traces"][kind]["speculative_over_adaptive_goodput"] = \
                round(sr, 3) if sr != float("inf") else None
            print(f"  speculative/adaptive SLO-goodput: "
                  f"{'inf' if sr == float('inf') else f'{sr:.2f}x'} "
                  f"({kind} trace)")
            if args.smoke and sr != float("inf"):
                if kind == "templated" and sr < 0.95:
                    # Open-loop goodput is arrival-bound here: both
                    # configurations absorb the offered rate and tie,
                    # so the guard is "must not lose" with the same
                    # noise tolerance as the paged guard — the raw
                    # speculative throughput multiplier (1.2x) is
                    # guarded in benchmarks/serve_throughput.py where
                    # the replay is device-bound.
                    print("FAIL: speculative SLO-goodput below "
                          "non-speculative on the templated trace")
                    guard_ok = False
                if kind == "heavy" and sr < 0.95:
                    # Low-overlap trace: acceptance collapses, the
                    # serve_spec_depth decision must back off to depth
                    # 1 and keep the speculation tax within noise.
                    print("FAIL: speculative SLO-goodput below 0.95x "
                          "adaptive on the heavy (low-overlap) trace — "
                          "acceptance backoff is not engaging")
                    guard_ok = False

    out = os.path.abspath(args.out)
    with open(out, "w") as f:
        json.dump(blob, f, indent=1)
    print(f"-> {out}")
    if explain_dump is not None and args.trace_out:
        os.makedirs(os.path.dirname(os.path.abspath(args.trace_out)),
                    exist_ok=True)
        with open(args.trace_out, "w") as f:
            f.write(explain_dump + "\n")
        print(f"-> {args.trace_out}")
    if args.smoke and not guard_ok:
        print("FAIL: adaptive SLO-goodput below the static baseline — "
              "serving-front-end regression")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
