"""Executor-API dispatch-overhead and decision-engine microbenchmarks.

Empty-task latency of each v2 execution function, per backend, plus the
per-decision overhead of the unified ``ExecutionModel`` engine — the
dispatch and decision costs the Overhead Law's T0 ultimately pays for.
Rows follow the harness CSV convention: ``name,us_per_call,derived``.

The engine numbers also land in ``BENCH_decision_engine.json`` so the
unification itself shows up in the benchmark artifacts and cannot
silently regress the hot path (a serve tick makes one engine decision;
a kernel call resolves one tuned plan).

    PYTHONPATH=src python benchmarks/executor_overhead.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.core import (HostParallelExecutor, SequentialExecutor, adaptive,
                        make_chunks, when_all)
from repro.core.calibration import CalibrationCache
from repro.core.model import DecisionKey, ExecutionModel
from repro.launch.compile_cache import place_compile_cache

N_CHUNKS = 16
REPEATS = 200

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_decision_engine.json")


def _empty(_chunk) -> None:
    return None


def _per_call(fn, repeats: int = REPEATS) -> float:
    fn()  # warm (pool threads, code paths)
    start = time.perf_counter()
    for _ in range(repeats):
        fn()
    return (time.perf_counter() - start) / repeats


def _bench_backend(name: str, ex) -> list[str]:
    chunks = make_chunks(N_CHUNKS, 1)
    rows = []
    t = _per_call(lambda: ex.sync_execute(_empty, chunks[0]))
    rows.append(f"exec/{name}/sync_execute,{t*1e6:.2f},empty_task")
    t = _per_call(lambda: ex.async_execute(_empty, chunks[0]).result())
    rows.append(f"exec/{name}/async_execute,{t*1e6:.2f},empty_task")
    t = _per_call(
        lambda: when_all(ex.bulk_async_execute(_empty, chunks)).result())
    rows.append(f"exec/{name}/bulk_async_execute,{t*1e6:.2f},"
                f"n_chunks={N_CHUNKS}")

    def chain():
        f = ex.async_execute(_empty, chunks[0])
        for _ in range(4):
            f = ex.then_execute(lambda _v: None, f)
        return f.result()

    t = _per_call(chain)
    rows.append(f"exec/{name}/then_execute_chain4,{t*1e6:.2f},empty_task")
    return rows


def bench_decision_engine(repeats: int = REPEATS, *,
                          smoke: bool = False) -> tuple[list[str], dict]:
    """Per-decision overhead of the unified engine, per query type.

    ``cores_chunk`` is the serve-tick / algorithm-plan hot path;
    ``observe`` runs once per timed chunk on the feedback path;
    ``tuned_blocks`` (store-hit) is what every kernel call pays once a
    winner is persisted.  The tuned sweep itself is measured work, not
    engine overhead, so the benchmark pre-seeds the store and reports
    the hit rate to prove the lookups stay hits.
    """
    model = ExecutionModel(CalibrationCache(), hardware="bench")
    rows: list[str] = []

    key = DecisionKey("bench_tick", ("engine",))
    t_decide = _per_call(
        lambda: model.cores_chunk(key, t_iter=2e-9, count=1 << 20,
                                  t0=1e-5, max_cores=16), repeats)
    rows.append(f"engine/cores_chunk,{t_decide*1e6:.2f},ns_per_decision="
                f"{t_decide*1e9:.0f}")

    obs_key = DecisionKey("bench_obs", ("engine",))
    t_observe = _per_call(
        lambda: model.observe(obs_key, 1024, 1e-3), repeats)
    rows.append(f"engine/observe,{t_observe*1e6:.2f},ns_per_observation="
                f"{t_observe*1e9:.0f}")

    tuned_key = DecisionKey("pallas_block", ("bench_kernel", 8192),
                            dtype="float32", hardware="bench")
    model.tuned_blocks(tuned_key, [(256,), (512,)], lambda b: None,
                       ("block",))   # one seed search, then all hits
    before = model.cache_hits
    t_tuned = _per_call(
        lambda: model.tuned_blocks(tuned_key, [(256,), (512,)],
                                   lambda b: None, ("block",)), repeats)
    hits = model.cache_hits - before
    hit_rate = hits / max(repeats + 1, 1)   # +1: the warm call
    rows.append(f"engine/tuned_blocks_hit,{t_tuned*1e6:.2f},"
                f"hit_rate={hit_rate:.3f}")

    report = {
        "ns_per_decision": t_decide * 1e9,
        "ns_per_observation": t_observe * 1e9,
        "ns_per_tuned_lookup": t_tuned * 1e9,
        "tuned_hit_rate": hit_rate,
        "decisions": model.decisions,
        "observations": model.observations,
        "searches": model.searches,
        "cache_hits": model.cache_hits,
        "trace_len": len(model.trace),
        # Same convention as BENCH_serve.json: a smoke-produced file is
        # self-identifying, never mistaken for a full run.
        "smoke": smoke,
        "repeats": repeats,
    }
    return rows, report


def _bench_all(*, smoke: bool = False) -> tuple[list[str], dict]:
    """Every suite: executor dispatch per backend + decision engine.
    Smoke runs skip the backend sweeps and use few engine repeats."""
    rows: list[str] = []
    if not smoke:
        rows += _bench_backend("seq", SequentialExecutor())
        with HostParallelExecutor(max_workers=2) as host:
            rows += _bench_backend("host2", host)
            # The adaptive wrapper should add only delegation cost.
            rows += _bench_backend("adaptive(host2)", adaptive(host))
    engine_rows, report = bench_decision_engine(
        repeats=20 if smoke else REPEATS, smoke=smoke)
    return rows + engine_rows, report


def bench_executor_overhead() -> list[str]:
    """benchmarks/run.py suite entry point (full run)."""
    rows, report = _bench_all()
    _write_report(report)
    return rows


def _write_report(report: dict, out: str = DEFAULT_OUT) -> None:
    try:
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
    except OSError:  # pragma: no cover - read-only checkout
        pass


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="engine-only, few repeats: prove the benchmark "
                         "runs and emit a smoke-flagged "
                         "BENCH_decision_engine.json")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args()
    place_compile_cache()

    print("name,us_per_call,derived")
    rows, report = _bench_all(smoke=args.smoke)
    for row in rows:
        print(row)
    _write_report(report, args.out)
    print(f"# wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    main()
