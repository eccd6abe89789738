#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the
numbers compared for ``correct`` are the last lines of standard error.
Without a TPU, or with fewer chips than the cell needs, it exits
non-zero and prints no result.
"""
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
