#!/usr/bin/env python3
"""Run a cell under the control or a planted fault; see chipbench/control.py.

    python3 benchmarks/chip/control.py --workload rebuild1-p5 \
        --plant control --seeds 11,12,13 --seconds 5
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench.control import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
