#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once on the chip; see chipbench/harness.py.

    python3 benchmarks/chip/run.py --workload rebuild1-p5 --seed 7 \
        --seconds 20 --trace 0
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
