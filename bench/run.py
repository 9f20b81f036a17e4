#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result (see ``bench/harness.py``).
Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result.
"""
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path

    checkout = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(checkout), str(checkout / "src")]
    from bench.harness import main

    sys.exit(main(sys.argv[1:], T_START))
