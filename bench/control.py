#!/usr/bin/env python3
"""One run of a cell with the control computed beside the program: the
reference at fp8, read at the positions of the served tokens.  Used to
set a cell's limit (``bench/limits``); the benchmark's runs never run it.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>
"""
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import cli  # noqa: E402

if __name__ == "__main__":
    sys.exit(cli.main(sys.argv[1:], T_START, control=True))
