#!/usr/bin/env python3
"""Find the knee of an open-loop read cell: one set-up, one window per rate.

    python3 benchmarks/chip/sweep.py --workload degraded-read-p5 --seed 5 \
        --seconds 8 --rates 250,500,1000,2000

For each offered rate it prints one JSON line: completed reads per second,
p50 and p99 latency from due time, the generator's p99 lag, and the median
latency of the window's last quarter of requests over its first quarter,
which grows with a backlog. The knee is the highest rate that completes
what it offers with no growing backlog; the cell's traffic runs at about
four fifths of it.
"""
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main(argv=None) -> int:
    import argparse
    import tempfile

    import jax
    import numpy as np

    from chipbench import harness, spec
    from chipbench.generator import Traffic
    from chipbench.stats import percentile

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated, per s")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        harness.require_chips(jax.devices(), cell.chips)
    except harness.NoChip as e:
        print(f"sweep.py: {e}; nothing was run", file=sys.stderr)
        return 1
    harness.use_compile_cache(spec.ROOT)
    work = spec.ROOT / harness.WORK_DIR
    work.mkdir(exist_ok=True)
    with tempfile.TemporaryDirectory(dir=work) as tmp:
        gen = Traffic(cell.config, cell.traffic, seed=args.seed,
                      seconds=args.seconds, workdir=Path(tmp),
                      chips=cell.chips)
        gen.setup()
        harness.freeze_heap()
        for rate in (float(r) for r in args.rates.split(",")):
            gen.schedule(rate, args.seconds)
            t0 = time.perf_counter()
            gen.window()
            lat = gen.run.requests["latency_s"] * 1e3
            quarter = max(1, len(lat) // 4)
            print(json.dumps({
                "rate_per_s": rate, "requests": len(lat),
                "completed_per_s": int(gen.run.requests["ok"].sum())
                / gen.run.window_s["reads"],
                "p50_ms": percentile(lat, 50), "p99_ms": percentile(lat, 99),
                "degraded_p99_ms": percentile(
                    lat[gen.run.requests["degraded"]], 99),
                "gen_lag_p99_ms": percentile(
                    gen.run.requests["lag_s"] * 1e3, 99),
                "backlog_growth": float(np.median(lat[-quarter:])
                                        / np.median(lat[:quarter])),
                "errors": len(gen.errors), "unfinished": gen.unfinished,
                "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
