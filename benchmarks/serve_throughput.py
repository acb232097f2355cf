"""Serving throughput benchmark: fused adaptive-depth decode vs the
per-tick path, adaptive vs static policies.

    PYTHONPATH=src python benchmarks/serve_throughput.py [--smoke]

A synthetic **open-loop** arrival trace (seeded Poisson interarrivals,
jittered prompt lengths) is replayed against three scheduler
configurations over the same slot pool geometry:

* **fused**    — ``AdaptiveCoreChunk`` + ``dispatch_depth="auto"``: the
  fused on-device decode loop (serve/decode_loop.py) advances the slot
  pool up to ``k`` tokens per dispatch with donated cache buffers, ``k``
  decided per tick from the measured host-overhead/device-step ratio
  (``serve_dispatch_depth`` decisions in the ExecutionModel trace);
* **per-tick** — same adaptive policy, legacy decode granularity: one
  device round-trip (``block_until_ready`` + ``device_get``) per token;
* **static**   — ``StaticCoreChunk`` (OpenMP-static / HPX-default
  semantics) on the per-tick path: fixed core count and chunks-per-core,
  no measurement anywhere.

Open-loop means arrivals do not wait for the system: a request is
submitted as soon as the wall clock passes its timestamp, so a slow
policy builds queue depth and pays for it in p95 latency.  Emits
``BENCH_serve.json`` with tokens/sec, latency percentiles, the
dispatch-granularity accounting (host-overhead-per-token,
dispatches-per-token, host-round-trips-per-token), and achieved
per-device rates (TFLOP/s, HBM GB/s and roofline bandwidth utilization,
from the decode step's XLA cost analysis x the scheduler's
decode-loop-iteration counter) per configuration.

``--mesh DATA,MODEL`` additionally replays the trace against the fused
adaptive configuration sharded over a device mesh (tensor-parallel
within a replica, ``DATA`` data-parallel slot groups) with
``n_replicas x slots`` lanes and per-device batch width decided by
``serve_mesh_batch`` — the ``mesh`` section of the report.

``--smoke`` doubles as the CI regression guard: it exits non-zero if
the fused adaptive configuration fails to beat the static baseline,
and (with ``--mesh``) if the sharded run collapses below
``MESH_SMOKE_FLOOR`` of the single-device fused run or its
``serve_mesh_batch`` decisions never reach online provenance.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.acc import AdaptiveCoreChunk, StaticCoreChunk  # noqa: E402
from repro.core.adaptive import adaptive  # noqa: E402
from repro.core.executor import SequentialExecutor  # noqa: E402
from repro.core.hardware import TPU_V5E  # noqa: E402
from repro.launch.compile_cache import place_compile_cache  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serve import (ServeScheduler, materialize,  # noqa: E402
                         percentile, templated_trace, trace_summary)

# Mesh smoke guard floor: host-emulated devices
# (--xla_force_host_platform_device_count) time-share ONE cpu, so the
# sharded run cannot beat the single-device run in wall clock — global
# mesh throughput lands well under 1x and per-device throughput under
# 1/n_devices.  What the guard can catch on such hosts is a sharding
# regression that tanks the path (bad layouts forcing per-step
# resharding, a lost donation recompiling every dispatch): those show up
# as order-of-magnitude collapses, not percents.  On real accelerator
# meshes the per-device column in ``device_metrics`` is the scaling
# metric; here we assert the global ratio stays above this floor.
MESH_SMOKE_FLOOR = 0.05

# Paged smoke guard floor: the synthetic trace has no shared prefixes,
# so the paged pool buys nothing here and pays the per-dispatch page
# table upload plus the gather indirection.  On CPU the interpret-mode
# Pallas paged kernel also pays a per-page grid step (page_size-sized
# tiles instead of one contiguous block_kv), which lands the honest
# ratio around 0.3-0.4x of contiguous fused — on real accelerators the
# tile DMA is the only difference.  The guard catches collapses (a
# lost donation or a recompile per dispatch is an order of magnitude,
# not percents); the prefix-reuse *win* is guarded in
# benchmarks/load_harness.py on the shared_prefix trace.
PAGED_SMOKE_FLOOR = 0.25

# Speculative smoke guard target: on the templated (motif-tiled,
# high n-gram self-overlap) trace the prompt-lookup drafter gets real
# acceptance, so speculative-adaptive must deliver at least this
# multiple of the non-speculative fused run's tokens/s — and its
# serve_spec_depth decisions must reach online provenance (the
# acceptance EMA actually fed back).  Low-overlap traces are guarded in
# benchmarks/load_harness.py (backoff keeps spec within 0.95x there).
SPEC_SMOKE_TARGET = 1.2


def synthetic_trace(n_requests: int, *, mean_interarrival_s: float,
                    prompt_lens: tuple[int, ...], new_tokens: int,
                    vocab: int, seed: int = 0):
    """[(arrival_offset_s, prompt, max_new_tokens)] — one seeded draw so
    every configuration replays the identical load."""
    rng = np.random.RandomState(seed)
    t = 0.0
    trace = []
    for _ in range(n_requests):
        t += float(rng.exponential(mean_interarrival_s))
        # Arrival gaps, prompt lengths and prompt tokens all come from
        # the one seeded stream: a single --seed pins the whole load.
        plen = int(prompt_lens[rng.randint(0, len(prompt_lens))])
        prompt = rng.randint(0, vocab, size=plen).astype(np.int32)
        trace.append((t, prompt, new_tokens))
    return trace


def run_policy(name: str, policy, cfg, params, trace, *, n_slots: int,
               max_len: int, dispatch_depth=None, mesh=None,
               paged=False, speculate=None):
    sched = ServeScheduler(cfg, params, n_slots=n_slots, max_len=max_len,
                           executor=adaptive(SequentialExecutor(), policy),
                           dispatch_depth=dispatch_depth, mesh=mesh,
                           paged=paged, speculate=speculate)
    sched.warmup()
    # Untimed steady-state warm: one request per distinct prompt length
    # compiles every shape-dependent host op (token slice / pad per
    # length) and seeds the online calibrations, so the timed replay
    # below measures the serving loop — not whichever configuration
    # runs first paying the process's one-time compiles.
    by_len = {}
    for _, prompt, _ in trace:
        by_len.setdefault(prompt.shape[0], prompt)
    for prompt in by_len.values():
        sched.submit(prompt, max_new_tokens=4)
    sched.run_until_idle()
    sched.clear_finished()
    sched.decode_dispatches = sched.decode_tokens = 0
    sched.host_roundtrips = 0
    sched.host_overhead_s = 0.0
    sched.decode_loop_iters = 0
    sched.prefill_stall_s = 0.0
    sched.spec_verifies = sched.spec_emitted = sched.spec_rounds = 0
    # Snapshot the engine trace so the report covers only the timed
    # replay's depth decisions, not the warm phase's seeded ones.
    model = sched.decision_model()
    depth_seen = len(model.trace.entries("serve_dispatch_depth")) \
        if model is not None else 0
    mesh_seen = len(model.trace.entries("serve_mesh_batch")) \
        if model is not None else 0
    spec_seen = len(model.trace.entries("serve_spec_depth")) \
        if model is not None else 0

    t0 = time.monotonic()
    # deque: the arrival trace is consumed strictly front-first, and a
    # list.pop(0) here is O(n) per arrival — O(n^2) over the replay,
    # pure host overhead charged to whichever policy is being measured.
    pending = collections.deque(trace)
    rids = []
    while pending or sched.pending:
        now = time.monotonic() - t0
        while pending and pending[0][0] <= now:
            offset, prompt, n_new = pending.popleft()
            rids.append(sched.submit(prompt, max_new_tokens=n_new,
                                     arrival=t0 + offset))
        if sched.pending:
            sched.tick()
        elif pending:
            time.sleep(min(pending[0][0] - now, 0.01))
    outs = sched.results()    # drains any in-flight fused dispatches
    makespan = time.monotonic() - t0

    lats = [sched.requests[r].finished_at - sched.requests[r].arrival
            for r in rids]
    ttfts = [sched.requests[r].first_token_at - sched.requests[r].arrival
             for r in rids]
    gen = sum(len(outs[r]) for r in rids)
    chunks = [rec.chunk for rec in sched.trace if rec.prefill_ops]
    depths = [rec.depth for rec in sched.trace if rec.depth > 0]
    report = {
        "policy": name,
        "requests": len(rids),
        "generated_tokens": gen,
        "makespan_s": round(makespan, 4),
        "tokens_per_s": round(gen / makespan, 2) if makespan else 0.0,
        "latency_p50_ms": round(percentile(lats, 50) * 1e3, 1),
        "latency_p95_ms": round(percentile(lats, 95) * 1e3, 1),
        "ttft_p50_ms": round(percentile(ttfts, 50) * 1e3, 1),
        "ticks": len(sched.trace),
        "mean_prefill_chunk": round(float(np.mean(chunks)), 1)
        if chunks else 0.0,
        "mean_dispatch_depth": round(float(np.mean(depths)), 1)
        if depths else 0.0,
        # Dispatch-granularity accounting: the quantities the
        # serve_dispatch_depth decision trades against each other.
        "host_overhead_ms_per_token":
            round(sched.host_overhead_s / gen * 1e3, 3) if gen else 0.0,
        "dispatches_per_token":
            round(sched.decode_dispatches / gen, 3) if gen else 0.0,
        "host_roundtrips_per_token":
            round(sched.host_roundtrips / gen, 3) if gen else 0.0,
        "smoothed_t_iter_s":
            sched.acc.cache.peek_t_iter(sched.prefill_key)
            if hasattr(sched.acc, "cache") else None,
        # Decode-lane time lost to prefill chunks with nothing in
        # flight to hide them behind — what serve_prefill_interleave
        # trades against admission starvation.
        "prefill_stall_s": round(sched.prefill_stall_s, 4),
        "prefill_stall_ms_per_tick":
            round(sched.prefill_stall_s / len(sched.trace) * 1e3, 4)
            if sched.trace else 0.0,
    }
    if paged:
        report["prefix"] = sched.pool.prefix_stats()
    if dispatch_depth is not None and model is not None:
        entries = model.trace.entries("serve_dispatch_depth")[depth_seen:]
        report["depth_decisions"] = len(entries)
        report["depth_provenance"] = sorted(
            {e.decision.provenance for e in entries})
    if mesh is not None and model is not None:
        entries = model.trace.entries("serve_mesh_batch")[mesh_seen:]
        report["mesh_decisions"] = len(entries)
        report["mesh_provenance"] = sorted(
            {e.decision.provenance for e in entries})
        report["mesh_trace"] = [e.decision.explain() for e in entries[-6:]]
    if speculate is not None:
        st = sched.spec_stats()
        report["speculate"] = {
            "mode": str(speculate),
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
            report["spec_trace"] = [e.decision.explain()
                                    for e in entries[-4:]]
    # Achieved per-device rates from the decode step's XLA cost analysis
    # (analysis/roofline.py).  cost_analysis counts a fori_loop body
    # ONCE, so the figures are per loop iteration per device — the
    # scheduler's decode_loop_iters counter is the multiplier.  The
    # bandwidth-utilization column anchors to the TPU v5e roofline spec
    # so runs on different hosts stay comparable.
    costs = sched.decode_cost_analysis()
    iters = sched.decode_loop_iters
    if costs is not None and makespan > 0:
        hbm_bps = costs["hbm_bytes_per_device"] * iters / makespan
        report["device_metrics"] = {
            "n_devices": costs["n_devices"],
            "decode_loop_iters": iters,
            "decode_flops_per_device_per_iter": costs["flops_per_device"],
            "decode_hbm_bytes_per_device_per_iter":
                costs["hbm_bytes_per_device"],
            "collective_wire_bytes_per_device_per_iter":
                costs["collective_wire_bytes_per_device"],
            "tflops_per_device":
                round(costs["flops_per_device"] * iters / makespan / 1e12,
                      9),
            "hbm_gb_per_s_per_device": round(hbm_bps / 1e9, 4),
            "hbm_bw_utilization_tpu_v5e": round(hbm_bps / TPU_V5E.mem_bw,
                                                9),
        }
    print(f"  {name:9s} {report['tokens_per_s']:8.1f} tok/s | "
          f"p50 {report['latency_p50_ms']:7.1f}ms | "
          f"host {report['host_overhead_ms_per_token']:6.2f}ms/tok | "
          f"{report['dispatches_per_token']:.2f} dispatches/tok | "
          f"{report['host_roundtrips_per_token']:.2f} round-trips/tok | "
          f"stall {report['prefill_stall_ms_per_tick']:.2f}ms/tick | "
          f"{report['ticks']} ticks")
    dm = report.get("device_metrics")
    if dm:
        print(f"  {'':9s} {dm['tflops_per_device'] * 1e3:8.4f} GFLOP/s/dev"
              f" | hbm {dm['hbm_gb_per_s_per_device']:7.3f} GB/s/dev "
              f"({dm['hbm_bw_utilization_tpu_v5e']:.2e} of v5e bw) | "
              f"{dm['n_devices']} device(s) x "
              f"{dm['decode_loop_iters']} decode iters")
    sp = report.get("speculate")
    if sp:
        print(f"  {'':9s} spec depth={sp['final_depth']} "
              f"{sp['tokens_per_verify']:.2f} tok/verify "
              f"(acceptance {sp['acceptance_rate']:.0%}) | provenance "
              f"{report.get('spec_provenance')}")
    return report, sched


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace for CI; exits non-zero if the fused "
                         "adaptive path loses to the static baseline")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--new-tokens", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0,
                    help="single seed for the arrival and prompt-length "
                         "RNGs (every configuration replays the same "
                         "draw)")
    ap.add_argument("--paged", action="store_true",
                    help="also run the fused adaptive configuration on "
                         "the paged KV pool (and shard the --mesh run's "
                         "pool the same way); with --smoke, fails if "
                         "the paged run collapses below "
                         "PAGED_SMOKE_FLOOR of the contiguous fused run")
    ap.add_argument("--mesh", default="off",
                    help="also run the fused adaptive configuration "
                         "sharded over a 'DATA,MODEL' device mesh "
                         "(launch/mesh.make_serve_mesh) with "
                         "n_replicas x slots lanes; emits the 'mesh' "
                         "section of BENCH_serve.json.  Pair with "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N on CPU hosts")
    ap.add_argument("--trace-out", default=None,
                    help="write the mesh run's (or, without --mesh, the "
                         "fused run's) ExecutionModel decision trace to "
                         "this file")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_serve.json"))
    args = ap.parse_args()
    place_compile_cache()

    n_requests = args.requests or (8 if args.smoke else 16)
    new_tokens = args.new_tokens or (24 if args.smoke else 48)
    prompt_lens = (8, 12, 16) if args.smoke else (16, 32, 64, 96)
    n_slots = 2 if args.smoke else 4
    max_len = max(prompt_lens) + new_tokens + 1

    cfg = get_config("qwen3-0.6b").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    # Arrivals dense enough to keep the queue non-empty for every
    # configuration: an open-loop trace that starves the scheduler
    # measures the arrival process, not the serving loop.
    trace = synthetic_trace(
        n_requests, mean_interarrival_s=0.002,
        prompt_lens=prompt_lens, new_tokens=new_tokens,
        vocab=cfg.vocab_size, seed=args.seed)

    print(f"serve throughput: {n_requests} requests, slots={n_slots}, "
          f"prompts {prompt_lens}, +{new_tokens} tokens each")
    fused_rep, fused_sched = run_policy(
        "fused", AdaptiveCoreChunk(), cfg, params, trace,
        n_slots=n_slots, max_len=max_len, dispatch_depth="auto")
    paged_rep = None
    if args.paged:
        paged_rep, _ = run_policy(
            "paged", AdaptiveCoreChunk(), cfg, params, trace,
            n_slots=n_slots, max_len=max_len, dispatch_depth="auto",
            paged=True)
    per_tick_rep, _ = run_policy(
        "per-tick", AdaptiveCoreChunk(), cfg, params, trace,
        n_slots=n_slots, max_len=max_len)
    static_rep, _ = run_policy(
        "static", StaticCoreChunk(cores=1, chunks_per_core=8), cfg, params,
        trace, n_slots=n_slots, max_len=max_len)

    def ratio(a, b):
        return round(a["tokens_per_s"] / b["tokens_per_s"], 3) \
            if b["tokens_per_s"] else float("nan")

    # Speculative section: fused-adaptive with and without
    # self-speculation, replaying a *templated* trace
    # (loadgen.templated_trace: motif-tiled prompts with high n-gram
    # self-overlap) where the prompt-lookup drafter gets real
    # acceptance.  The delta isolates what speculation buys; the random
    # traces above stay speculation-free so the other ratios are
    # unchanged.  The section runs speculation's home configuration —
    # a SINGLE decode lane (latency-bound serving, no batch to fill the
    # width; one lane also makes loop rounds equal per-lane verifies,
    # so the tokens-per-verify win is not diluted by the max() over
    # lanes) on a model a step up from reduced(): with 2 layers at
    # d_model 64 the per-round fixed costs (draft gather, history
    # shift, write-out) dominate the forward and drown the win, while
    # at 4 layers x d_model 128 the step is weight-bound and the wider
    # verify rides the same weight stream.  Generation long enough for
    # the drafter's bigram table to lock onto the motif cycle.
    spec_cfg = dataclasses.replace(cfg, n_layers=4, d_model=128,
                                   d_ff=256, head_dim=32)
    spec_params = lm.init_params(jax.random.PRNGKey(0), spec_cfg)
    spec_new = 128
    spec_reqs = templated_trace(
        n_requests, rate_rps=200.0, motif_len=6, median_prompt=16,
        prompt_sigma=0.3, max_prompt=32, median_new=spec_new,
        new_sigma=0.0, max_new=spec_new, seed=args.seed, slo=None)
    spec_mat = materialize(spec_reqs, spec_cfg.vocab_size, seed=args.seed)
    spec_trace = [(tr.arrival_s, toks, tr.new_tokens)
                  for tr, toks in spec_mat]
    # Headroom for the reserved draft margin (the last spec_d - 1 cache
    # positions are unusable under speculation — scheduler docstring).
    spec_max_len = max(tr.prompt_len + tr.new_tokens
                       for tr in spec_reqs) + 9
    print(f"templated trace (speculation section): "
          f"{trace_summary(spec_reqs)}")
    specoff_rep, _ = run_policy(
        "spec-off", AdaptiveCoreChunk(), spec_cfg, spec_params, spec_trace,
        n_slots=1, max_len=spec_max_len, dispatch_depth=12)
    spec_rep, _ = run_policy(
        "spec-auto", AdaptiveCoreChunk(), spec_cfg, spec_params, spec_trace,
        n_slots=1, max_len=spec_max_len, dispatch_depth=12,
        speculate="auto")

    fused_over_per_tick = ratio(fused_rep, per_tick_rep)
    adaptive_over_static = ratio(fused_rep, static_rep)
    spec_over_non_spec = ratio(spec_rep, specoff_rep)
    blob = {"adaptive": fused_rep, "per_tick": per_tick_rep,
            "static": static_rep,
            "fused_over_per_tick": fused_over_per_tick,
            "adaptive_over_static": adaptive_over_static,
            "speculative": {
                "templated_trace": trace_summary(spec_reqs),
                "spec_off": specoff_rep,
                "spec_auto": spec_rep,
                "spec_over_non_spec": spec_over_non_spec,
            },
            "smoke": bool(args.smoke)}
    print(f"  spec-auto/spec-off on templated trace: "
          f"{spec_over_non_spec:.2f}x")
    spec_ok = True
    if args.smoke:
        if spec_over_non_spec < SPEC_SMOKE_TARGET:
            print(f"FAIL: speculative-adaptive {spec_over_non_spec:.2f}x "
                  f"non-speculative on the templated trace (target "
                  f"{SPEC_SMOKE_TARGET}x) — speculation regression")
            spec_ok = False
        if "online" not in spec_rep.get("spec_provenance", []):
            print("FAIL: serve_spec_depth decisions never reached online "
                  "provenance during the timed replay: "
                  f"{spec_rep.get('spec_provenance')}")
            spec_ok = False

    paged_ok = True
    if paged_rep is not None:
        paged_over_fused = ratio(paged_rep, fused_rep)
        blob["paged"] = paged_rep
        blob["paged_over_fused"] = paged_over_fused
        print(f"  paged/fused: {paged_over_fused:.2f}x on a "
              "no-shared-prefix trace (page-table tax only)")
        if args.smoke and paged_over_fused < PAGED_SMOKE_FLOOR:
            print(f"FAIL: paged fused decode {paged_over_fused:.3f}x "
                  f"contiguous (floor {PAGED_SMOKE_FLOOR}) — paged-path "
                  "regression")
            paged_ok = False

    mesh_ok = True
    trace_sched = fused_sched
    if args.mesh.strip().lower() not in ("off", "none", ""):
        from repro.launch.mesh import make_serve_mesh, n_data_replicas

        data, model_par = (int(x) for x in args.mesh.split(","))
        mesh = make_serve_mesh(data, model_par)
        reps = n_data_replicas(mesh)
        mesh_slots = n_slots * reps    # same per-replica pool geometry
        print(f"mesh {data}x{model_par} over {mesh.devices.size} "
              f"{jax.default_backend()} devices: {reps} replicas x "
              f"{mesh_slots // reps} slots = {mesh_slots} lanes")
        mesh_rep, trace_sched = run_policy(
            "mesh", AdaptiveCoreChunk(), cfg, params, trace,
            n_slots=mesh_slots, max_len=max_len, dispatch_depth="auto",
            mesh=mesh, paged=args.paged)
        n_dev = int(mesh.devices.size)
        per_dev = round(mesh_rep["tokens_per_s"] / n_dev, 2)
        mesh_over_single = ratio(mesh_rep, fused_rep)
        blob["mesh"] = {
            "mesh_shape": {"data": data, "model": model_par},
            "n_devices": n_dev,
            "n_replicas": reps,
            "n_slots": mesh_slots,
            "paged": bool(args.paged),
            "backend": jax.default_backend(),
            "tokens_per_s_per_device": per_dev,
            "mesh_over_single_fused": mesh_over_single,
            "report": mesh_rep,
        }
        print(f"  mesh/single-fused: {mesh_over_single:.2f}x global | "
              f"{per_dev:.1f} tok/s/device over {n_dev} devices")
        if args.smoke:
            # See MESH_SMOKE_FLOOR: emulated devices share one cpu, so
            # the guard is the global ratio (a sharding regression shows
            # as a collapse) plus the decision loop having gone online.
            if mesh_over_single < MESH_SMOKE_FLOOR:
                print("FAIL: mesh-sharded throughput "
                      f"{mesh_over_single:.3f}x single-device fused "
                      f"(floor {MESH_SMOKE_FLOOR}) — sharded-serving "
                      "regression")
                mesh_ok = False
            if "online" not in mesh_rep.get("mesh_provenance", []):
                print("FAIL: serve_mesh_batch decisions never reached "
                      "online provenance during the timed replay: "
                      f"{mesh_rep.get('mesh_provenance')}")
                mesh_ok = False

    out = os.path.abspath(args.out)
    with open(out, "w") as f:
        json.dump(blob, f, indent=1)
    print(f"fused/per-tick throughput: {fused_over_per_tick:.2f}x | "
          f"adaptive/static: {adaptive_over_static:.2f}x -> {out}")
    if args.trace_out:
        model = trace_sched.decision_model()
        if model is not None:
            path = os.path.abspath(args.trace_out)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w") as f:
                f.write(model.explain() + "\n")
            print(f"-> {path}")
    if args.smoke and adaptive_over_static < 1.0:
        print("FAIL: fused adaptive below the static baseline "
              f"({adaptive_over_static:.2f}x) — dispatch-granularity "
              "regression")
        return 1
    if not mesh_ok or not paged_ok or not spec_ok:
        return 1
    if not args.smoke and fused_over_per_tick < 1.3:
        print("WARNING: fused decode below the 1.3x target over the "
              "per-tick path on this host")
    return 0


if __name__ == "__main__":
    sys.exit(main())
