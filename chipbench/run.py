"""Run one benchmark cell and print its result as one JSON line.

Usage, from the root of a checkout on a machine with a TPU:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Diagnostics go to standard error and to ``results/chipbench/``; the last
lines on standard error give each number that decided ``correct`` beside
its limit. Without a TPU, or without the program (``src/repro``) beside
this directory, it exits nonzero and prints no result.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[0] = str(Path(__file__).resolve().parents[1])

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:], T_START))
