"""Benchmark harness: one function per paper table/figure plus the
framework's own microbenches.  Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run [--skip-wallclock]
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-wallclock", action="store_true",
                    help="model-based figures only (fast)")
    args = ap.parse_args()

    from repro.launch.compile_cache import place_compile_cache

    from . import executor_overhead, figures

    place_compile_cache()

    suites = [
        ("executor API v2 + decision-engine overhead (empty tasks; "
         "writes BENCH_decision_engine.json)",
         executor_overhead.bench_executor_overhead),
        ("fig1 (chunks/core sweep)", figures.fig1_chunks_per_core),
        ("fig2 (adjacent-difference, static vs acc)",
         figures.fig2_adjacent_difference),
        ("fig3 (artificial work, Intel)", figures.fig3_artificial_intel),
        ("fig4 (artificial work, AMD)", figures.fig4_artificial_amd),
        ("T0 calibration (measured on this host)", figures.table_t0_this_host),
        ("straggler mitigation (beyond paper)",
         figures.table_straggler_mitigation),
    ]
    if not args.skip_wallclock:
        from . import wallclock

        suites += [
            ("kernel wall-clock (interpret mode)", wallclock.bench_kernels),
            ("algorithm wall-clock", wallclock.bench_algorithms),
            ("train-step wall-clock (reduced)", wallclock.bench_train_step),
        ]

    print("name,us_per_call,derived")
    failed = 0
    for title, fn in suites:
        print(f"# --- {title} ---")
        try:
            for row in fn():
                print(row)
        except Exception:  # noqa: BLE001
            failed += 1
            traceback.print_exc()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
