"""Run one cell once and print the result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The run finds the cell in ``BENCHMARK.json``, refuses any first device that
is not a TPU and any host with fewer chips than the cell asks for, keeps
JAX's persistent compilation cache at a fixed path in the checkout (or where
``JAX_COMPILATION_CACHE_DIR`` says), sets up, warms up, measures for
``--seconds`` and then compares what the window produced with the plain
reference. With ``--trace 1`` the window runs under the profiler and the
result carries the per-layer metrics instead of the end-to-end ones.

The last lines on standard error name each number compared beside its
limit; the last line on standard output is the result, one JSON object.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

from . import spec as spec_lib
from .compile_clock import CompileClock
from .generator import Traffic
from .peaks import peaks_for

WORK_DIR = ".chipbench"            # in the checkout, and in .gitignore
CACHE_DIR = ".jax_cache"           # a fixed path: part of the cache key


class NoChip(RuntimeError):
    """The host has no TPU, or fewer chips than the cell asks for."""


def require_chips(devices, chips: int):
    """The first ``chips`` devices, all of them TPUs, or ``NoChip``."""
    if not devices or devices[0].platform != "tpu":
        platform = devices[0].platform if devices else "none"
        raise NoChip(f"the first JAX device is a {platform}, not a TPU")
    if len(devices) < chips:
        raise NoChip(f"{len(devices)} chips here, the cell needs {chips}")
    return list(devices[:chips])


def use_compile_cache(root: Path) -> str:
    """The persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``.jax_cache/`` in the checkout; every program is written to
    it, however short its compile."""
    import os

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(root / CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def freeze_heap() -> None:
    """Move every object set-up made out of the collector's reach (until
    ``Traffic.release``): a full collection inside the window then scans
    what the window made, not the whole process with JAX and the store in
    it, which stalls every thread for tens of milliseconds."""
    import gc

    gc.collect()
    gc.freeze()


def measure(cell, *, seed: int, seconds: float, trace: bool, devices,
            t_start: float, root: Path = spec_lib.ROOT) -> dict:
    """Everything after the look for a chip: set-up, window, check."""
    import jax

    from .trace_reduce import find_xplane, reduce_trace

    clock = CompileClock().install()
    work = root / WORK_DIR
    work.mkdir(exist_ok=True)
    with tempfile.TemporaryDirectory(dir=work) as tmp:
        tmp = Path(tmp)
        gen = Traffic(cell.config, cell.traffic, seed=seed, seconds=seconds,
                      workdir=tmp, chips=cell.chips)
        gen.setup()
        freeze_heap()
        run = gen.run
        run.peaks = peaks_for(devices[0].device_kind)
        compiles0 = clock.compiles
        run.setup_s = time.perf_counter() - t_start
        if trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0      # host spans, not every call
            jax.profiler.start_trace(str(tmp / "trace"),
                                     profiler_options=options)
        try:
            gen.window()
        finally:
            if trace:
                jax.profiler.stop_trace()
        run.compiles_in_window = clock.compiles - compiles0
        memory_peak = gen.memory_peak_bytes()
        gen.release()
        t_check = time.perf_counter()
        checks = gen.check()
        gen.timings["check_s"] = time.perf_counter() - t_check
        for err in gen.errors[:3]:
            print(f"error in the window: {err}", file=sys.stderr)
        if trace:
            run.trace = reduce_trace(find_xplane(str(tmp / "trace")),
                                     cell.chips)
            shutil.rmtree(tmp / "trace")
        attempted, failed = gen.attempted_failed(checks)
    print("timings " + " ".join(
        f"{k}={v:.3f}" for k, v in dict(
            setup_s=run.setup_s, **gen.timings,
            **{f"window_{p}_s": s for p, s in run.window_s.items()}).items())
        + f" compiles_in_window={run.compiles_in_window}", file=sys.stderr)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec_lib.reader(m["name"])(run)
        if value is None and not trace:
            raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = all(value <= limit for value, limit in checks.values())
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_mean_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.device_ops,
                               "idle_gaps": run.trace.idle_gaps}
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, (value, limit) in checks.items()}
    return result


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec_lib.load_cell(args.workload)
    import jax

    try:
        devices = require_chips(jax.devices(), cell.chips)
    except NoChip as e:
        print(f"run.py: {e}; nothing was run", file=sys.stderr)
        return 1
    use_compile_cache(spec_lib.ROOT)
    print_result(measure(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), devices=devices,
                         t_start=t_start))
    return 0
