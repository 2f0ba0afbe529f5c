#!/usr/bin/env python3
"""Run one cell once under the profiler, with the program's spans reduced
to per-layer metrics (``span_metrics.json``), idle gaps named by them, the
spans' own checks and the end-to-end metrics; see chipbench/spans.py.

    python3 benchmarks/chip/spans.py --workload rebuild1-p5 --seed 7 \
        --seconds 30
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench.spans import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
