#!/usr/bin/env python3
"""Run one cell of the benchmark.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the run's result as one JSON object;
the numbers compared for ``correct`` are the last lines of standard error.
Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
