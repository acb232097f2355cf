"""Serving driver: the continuous-batching scheduler under a request load.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --reduced --requests 8 --prompt-len 32 --new-tokens 16 --slots 4

Requests (synthetic prompts of jittered lengths) go through the
``ServeScheduler``: admission into cache slots, acc-decided prefill
chunking/batching per tick, slot-batched decode.  Reports throughput and
per-request latency percentiles.  T0/t_iter calibrations persist across
runs under ``--cal-cache-dir`` unless ``--no-cal-cache``.

``--frontend`` switches to the asyncio ``ServeFrontend`` path: a seeded
open-loop arrival trace (serve/loadgen.py) replayed with per-request
token streaming, deadline shedding and adaptive admission — the report
leads with SLO-goodput and the deadline-miss rate instead of raw
throughput.  ``--print-launch-profile`` emits the recommended process
environment (shell-sourceable) for production runs.
"""
from __future__ import annotations

import argparse
import asyncio
import collections
import time

import jax

from ..configs import ARCH_NAMES, get_config
from ..core.acc import AdaptiveCoreChunk
from ..core.adaptive import adaptive
from ..core import strict
from ..core.calibration import CalibrationCache
from ..core.executor import SequentialExecutor
from ..data import make_batch
from ..models import lm
from ..serve import (ServeEngine, ServeFrontend, ServeScheduler, SLOModel,
                     heavy_tailed_trace, materialize, percentile, spans)
from .compile_cache import place_compile_cache

# Recommended process environment for serving runs — (var, value, why).
# Source it with:  eval "$(python -m repro.launch.serve --print-launch-profile)"
# The malloc and logging lines follow the launch scripts of production
# JAX training rigs (SNIPPETS §1-2).  The compilation cache itself is
# placed by launch/compile_cache (JAX_COMPILATION_CACHE_DIR if set, else
# <repo>/.jax_cache); the threshold line decides what goes into it.
LAUNCH_PROFILE = (
    ("LD_PRELOAD", "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
     "tcmalloc: faster malloc under slot-pool churn (skip if absent)"),
    ("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD", "60000000000",
     "silence large-alloc warnings for cache-pool buffers"),
    ("TF_CPP_MIN_LOG_LEVEL", "4",
     "quiet XLA/TSL startup chatter on the serving console"),
    ("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1",
     "cache anything that took >=1s to compile"),
)


def print_launch_profile() -> None:
    for var, value, why in LAUNCH_PROFILE:
        print(f"export {var}={value}  # {why}")


def serve_cross_attention(cfg, params, args, executor, tuner=None) -> None:
    """Cross-attention (VLM) archs carry per-request frontend feats the
    scheduler does not model — they serve through the engine's lock-step
    batch path instead (kernel tuning applies there too)."""
    batch = make_batch(cfg, args.requests, args.prompt_len, kind="prefill")
    engine = ServeEngine(cfg, params, batch=args.requests,
                         max_len=args.prompt_len + args.new_tokens + 1,
                         executor=executor, kernel_tuner=tuner)
    t0 = time.monotonic()
    out = engine.generate(batch["tokens"], args.new_tokens,
                          frontend_feats=batch.get("frontend_feats"))
    dt = time.monotonic() - t0
    gen = int(out.shape[0] * out.shape[1])
    print(f"arch={cfg.name} (cross-attention: lock-step batch path) "
          f"requests={args.requests}")
    print(f"generated {gen} tokens in {dt:.2f}s ({gen / dt:.1f} tok/s)")
    print("sample:", out[0].tolist())


def print_span_summary(sched: ServeScheduler) -> None:
    """What the program's own spans (serve/spans.py) saw this run: host
    seconds by span name (nested spans count in each), queue wait
    (admission less submission) over the requests ``serve.request``
    recorded (it spans a request's life, not host work), and
    the decode loop's lane-iterations by what each slot did."""
    held = spans.RECORDER.held()
    by_name: collections.Counter = collections.Counter()
    for s in held:
        if s.name != "serve.request":
            by_name[s.name] += s.end - s.start
    print("host seconds by span: " + ", ".join(
        f"{name} {sec:.3f}" for name, sec in by_name.most_common()))
    waits = [s.attrs["admitted"] - s.attrs["submitted"] for s in held
             if s.name == "serve.request" and s.attrs["admitted"] is not None]
    print(f"queue wait over {len(waits)} requests: "
          f"p50={percentile(waits, 50) * 1e3:.1f}ms "
          f"p95={percentile(waits, 95) * 1e3:.1f}ms")
    lanes = sum(sched.lane_iters.values())
    if lanes:
        print("decode lanes: " + " ".join(
            f"{kind} {100.0 * n / lanes:.1f}%"
            for kind, n in sched.lane_iters.items()))


def serve_frontend(sched: ServeScheduler, args) -> None:
    """Async front-end replay: a seeded heavy-tailed open-loop trace
    with streaming consumers and per-request SLO deadlines — the mode
    whose headline is goodput, not throughput."""
    slo = SLOModel()
    trace = heavy_tailed_trace(
        args.requests, rate_rps=args.rate_rps,
        max_prompt=max(args.prompt_len, 8), max_new=args.new_tokens,
        seed=args.seed, slo=slo)
    mat = materialize(trace, sched.cfg.vocab_size, seed=args.seed)
    frontend = ServeFrontend(sched, max_queue=args.max_queue)

    async def replay():
        async with frontend:
            clock = frontend.clock   # deadlines: the scheduler's clock
            t0 = clock()

            async def one(tr, prompt):
                delay = tr.arrival_s - (clock() - t0)
                if delay > 0:
                    await asyncio.sleep(delay)
                deadline = None if tr.deadline_s is None \
                    else t0 + tr.deadline_s
                stream = await frontend.submit(prompt, tr.new_tokens,
                                               deadline=deadline, wait=True)
                async for _tok in stream:
                    pass

            await asyncio.gather(*(one(tr, p) for tr, p in mat))
            return clock() - t0

    makespan = asyncio.run(replay())
    stats = frontend.stats()
    recs = list(frontend.records.values())
    ttfts = [r.first_token_at - r.submitted_at for r in recs
             if r.first_token_at is not None]
    goodput = stats["goodput_tokens"] / makespan if makespan else 0.0
    eligible = max(stats["submitted"] - stats["cancelled"], 1)
    print(f"arch={sched.cfg.name} frontend requests={args.requests} "
          f"slots={sched.pool.n_slots} admission={sched.admission} "
          f"ticks={len(sched.trace)}")
    print(f"SLO-goodput {goodput:.1f} tok/s over {makespan:.2f}s | "
          f"completed {stats['completed']} "
          f"(in-SLO {stats['completed_in_slo']}) shed {stats['shed']} "
          f"rejected {stats['rejected']} | "
          f"miss rate {stats['missed'] / eligible:.1%} | "
          f"ttft p50={percentile(ttfts, 50) * 1e3:.0f}ms "
          f"p99={percentile(ttfts, 99) * 1e3:.0f}ms")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_NAMES), required=False)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--no-cal-cache", action="store_true",
                    help="do not persist T0/t_iter calibrations to disk")
    ap.add_argument("--cal-cache-dir", default=None,
                    help="calibration cache dir (default: "
                         "$REPRO_CAL_CACHE_DIR or ~/.cache/repro-acc)")
    ap.add_argument("--kernel-autotune", action="store_true",
                    help="measured Pallas blocks for prefill/decode "
                         "(winners persist in the calibration cache)")
    ap.add_argument("--mesh", default="off",
                    help="mesh-sharded serving: 'DATA,MODEL' device "
                         "counts (e.g. '4,2': 4 data-parallel replicas "
                         "x 2-way tensor parallel), or 'off' (single "
                         "device).  Slots round up to a multiple of the "
                         "replica count; per-device batch width becomes "
                         "a serve_mesh_batch engine decision")
    ap.add_argument("--dispatch-depth", default="auto",
                    help="fused decode tokens per device dispatch: "
                         "'auto' (adaptive serve_dispatch_depth decision, "
                         "default), an integer (fixed depth), or 'off' "
                         "(legacy per-tick decode, one round-trip per "
                         "token)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: fixed-size pages + on-device "
                         "page tables instead of contiguous per-slot "
                         "rows, with copy-on-write prefix reuse (a hot "
                         "system prompt is prefilled once and shared "
                         "read-only) and chunked prefill interleaved "
                         "into the fused decode loop.  Requires a fused "
                         "--dispatch-depth")
    ap.add_argument("--page-size", default="auto",
                    help="tokens per KV page: 'auto' (serve_page_size "
                         "engine decision from the Overhead-Law prior, "
                         "default) or an integer")
    ap.add_argument("--prefill-interleave", default="auto",
                    help="max prefill chunk-ops interleaved per fused "
                         "decode tick: 'auto' (serve_prefill_interleave "
                         "engine decision, default) or an integer")
    ap.add_argument("--speculate", default="off",
                    help="self-speculative decoding inside the fused "
                         "loop (n-gram prompt-lookup drafts, one "
                         "batched verify, device-side rollback): 'auto' "
                         "(adaptive serve_spec_depth decision with "
                         "backoff when acceptance collapses), an "
                         "integer draft window, or 'off' (default).  "
                         "Requires a fused --dispatch-depth; output is "
                         "byte-identical to non-speculative decoding")
    ap.add_argument("--explain-decisions", action="store_true",
                    help="dump the ExecutionModel decision trace: every "
                         "serve-tick, admission and kernel-block choice "
                         "with the policy and inputs that produced it")
    ap.add_argument("--frontend", action="store_true",
                    help="serve through the asyncio ServeFrontend: "
                         "seeded open-loop trace, streaming consumers, "
                         "SLO deadlines; reports SLO-goodput")
    ap.add_argument("--admission", choices=("greedy", "adaptive"),
                    default=None,
                    help="admission width policy (default: adaptive "
                         "with --frontend, greedy otherwise)")
    ap.add_argument("--rate-rps", type=float, default=40.0,
                    help="--frontend arrival rate (requests/s)")
    ap.add_argument("--max-queue", type=int, default=128,
                    help="--frontend bounded admission queue")
    ap.add_argument("--seed", type=int, default=0,
                    help="--frontend trace seed (arrivals, lengths, "
                         "prompt tokens)")
    ap.add_argument("--strict", action="store_true",
                    help="strict runtime mode (same guards as "
                         "REPRO_STRICT=1): donated cache pools poison "
                         "on read-after-donation and the serve tick "
                         "disallows implicit device->host transfers")
    ap.add_argument("--print-launch-profile", action="store_true",
                    help="print the recommended serving environment "
                         "(shell-sourceable) and exit")
    args = ap.parse_args()

    if args.strict:
        strict.enable()
    if args.print_launch_profile:
        print_launch_profile()
        return
    if args.arch is None:
        ap.error("--arch is required (unless --print-launch-profile)")
    place_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)

    cache = CalibrationCache() if args.no_cal_cache \
        else CalibrationCache.persistent(args.cal_cache_dir)
    acc = AdaptiveCoreChunk(cache=cache)
    executor = adaptive(SequentialExecutor(), acc)
    tuner = None
    if args.kernel_autotune:
        from ..train.autotune import make_kernel_tuner

        tuner = make_kernel_tuner(cache)   # shared store with acc/train
    if "cross_attn" in cfg.layer_kinds():
        serve_cross_attention(cfg, params, args, executor, tuner)
        if tuner is not None:
            print(f"kernel autotune: {tuner.searches} measured searches, "
                  f"{tuner.cache_hits} persisted winners reused")
        if args.explain_decisions:
            from ..core.model import ExecutionModel

            print(ExecutionModel.of(cache).explain())
        return
    max_len = args.prompt_len + args.new_tokens + 1
    depth = args.dispatch_depth.strip().lower()
    depth = None if depth in ("off", "none", "0") else \
        depth if depth == "auto" else int(depth)
    admission = args.admission or \
        ("adaptive" if args.frontend else "greedy")
    mesh, n_slots = None, args.slots
    if args.mesh.strip().lower() not in ("off", "none", ""):
        from .mesh import make_serve_mesh, n_data_replicas

        data, model_par = (int(x) for x in args.mesh.split(","))
        mesh = make_serve_mesh(data, model_par)
        reps = n_data_replicas(mesh)
        if n_slots % reps:      # slot dim must split into replica groups
            n_slots = -(-n_slots // reps) * reps
            print(f"mesh: rounding --slots {args.slots} up to {n_slots} "
                  f"({reps} data replicas)")
        print(f"mesh {data}x{model_par} over {mesh.devices.size} of "
              f"{len(jax.devices())} {jax.default_backend()} devices | "
              f"{reps} replicas x {n_slots // reps} slots")
    page_size = args.page_size.strip().lower()
    page_size = "auto" if page_size == "auto" else int(page_size)
    interleave = args.prefill_interleave.strip().lower()
    interleave = "auto" if interleave == "auto" else int(interleave)
    speculate = args.speculate.strip().lower()
    speculate = None if speculate in ("off", "none", "0") else \
        speculate if speculate == "auto" else int(speculate)
    sched = ServeScheduler(cfg, params, n_slots=n_slots, max_len=max_len,
                           executor=executor, kernel_tuner=tuner,
                           dispatch_depth=depth, admission=admission,
                           mesh=mesh, paged=args.paged,
                           page_size=page_size,
                           prefill_interleave=interleave,
                           speculate=speculate)
    sched.warmup()

    def print_paged_stats():
        if sched._spec:
            st = sched.spec_stats()
            print(f"speculate: depth={st['depth']} "
                  f"verifies={st['verifies']} emitted={st['emitted']} | "
                  f"{st['tokens_per_verify']:.2f} tok/verify "
                  f"(acceptance {st['acceptance_rate']:.1%})")
        if not args.paged:
            return
        st = sched.pool.prefix_stats()
        print(f"paged: page_size={st['page_size']} pages "
              f"{st['pages_in_use']}/{st['n_pages']} | prefix hits "
              f"{st['prefix_hits']}/{st['prefix_lookups']} avoided "
              f"{st['prefill_tokens_avoided']} tok | cow "
              f"{st['cow_copies']} | prefill stall "
              f"{sched.prefill_stall_s * 1e3:.0f}ms")

    if args.frontend:
        serve_frontend(sched, args)
        print_paged_stats()
        print_span_summary(sched)
        if args.explain_decisions:
            model = sched.decision_model()
            if model is not None:
                print(model.explain())
        if not args.no_cal_cache:
            cache.save()
            print(f"calibration cache: {cache.path} "
                  f"({len(cache)} entries)")
        return

    # Jittered prompt lengths: requests join and leave the batch at
    # different ticks — the continuous-batching case, not lock-step.
    tokens = make_batch(cfg, args.requests, args.prompt_len,
                        kind="prefill")["tokens"]
    t_start = time.monotonic()
    rids = []
    for i in range(args.requests):
        plen = max(args.prompt_len - (i % 3) * (args.prompt_len // 4), 1)
        rids.append(sched.submit(tokens[i, :plen],
                                 max_new_tokens=args.new_tokens))
    outs = sched.run_until_idle()
    dt = time.monotonic() - t_start

    lats = [sched.requests[rid].finished_at - sched.requests[rid].arrival
            for rid in rids]
    ttfts = [sched.requests[rid].first_token_at - sched.requests[rid].arrival
             for rid in rids]
    gen = sum(len(outs[rid]) for rid in rids)
    print(f"arch={cfg.name} requests={args.requests} slots={sched.pool.n_slots} "
          f"ticks={len(sched.trace)} dispatch-depth={args.dispatch_depth} "
          f"({sched.decode_dispatches} decode dispatches, "
          f"{sched.host_roundtrips} host round-trips, "
          f"{gen and sched.host_overhead_s / gen * 1e3:.2f}ms host "
          f"overhead/token)")
    print(f"generated {gen} tokens in {dt:.2f}s ({gen / dt:.1f} tok/s) | "
          f"latency p50={percentile(lats, 50) * 1e3:.0f}ms "
          f"p95={percentile(lats, 95) * 1e3:.0f}ms | "
          f"ttft p50={percentile(ttfts, 50) * 1e3:.0f}ms")
    print("sample:", outs[rids[0]])
    print_paged_stats()
    print_span_summary(sched)
    if tuner is not None:
        print(f"kernel autotune: {tuner.searches} measured searches, "
              f"{tuner.cache_hits} persisted winners reused")
    if args.explain_decisions:
        # acc, scheduler ticks and the kernel tuner all share the engine
        # bound to `cache`, so one dump attributes every decision made
        # this run — serve ticks, train-style plans, kernel blocks.
        model = sched.decision_model()
        if model is not None:
            print(model.explain())
    if not args.no_cal_cache:
        cache.save()   # flush any write-throttled smoothing updates
        print(f"calibration cache: {cache.path} ({len(cache)} entries)")


if __name__ == "__main__":
    main()
