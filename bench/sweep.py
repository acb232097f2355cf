#!/usr/bin/env python3
"""Find an open-loop cell's knee once, when the cell is defined: run the
cell at each of several offered rates, in one process, and print each
run's latencies and completions.  The cell's mix then fixes its rate at
0.8 x the highest rate sustained (PERF.md records the sweep).  The
benchmark's runs never run it.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 0.5,1,1.5
"""
import argparse
import dataclasses
import json
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import cli, spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    dev = cli.check_chips(cell)
    if dev is None:
        return 1
    cli.place_cache()
    peaks = spec.load_json(spec.BENCH / "peaks.json")[dev["kind"]]
    for rate in (float(r) for r in args.rates.split(",")):
        mix = {**cell.traffic, "rate_rps": rate}
        swept = dataclasses.replace(cell, traffic=mix)
        res = cli.run_cell(swept, args.seed, args.seconds, False,
                           time.perf_counter(), peaks=peaks)
        print(json.dumps({"rate_rps": rate, **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
