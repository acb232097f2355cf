#!/usr/bin/env python3
"""Run one benchmark cell once (see ``harness/cli.py``):

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import cli  # noqa: E402

if __name__ == "__main__":
    sys.exit(cli.main(sys.argv[1:], T_START))
