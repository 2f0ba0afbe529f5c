"""The program's own spans in a profiler trace, reduced to per-layer numbers.

The store marks its stages with ``repro.<layer>.<stage>`` host spans
(``repro.obs``), which land in the same ``.xplane.pb`` as the device's
operations and the benchmark's ``bench.*`` spans, on the same clock. From
that file this module takes, inside the ``bench.window`` span:

- per ``repro.*`` span name: the count, every duration in seconds, the
  summed ``bytes`` arg and the count with ``degraded`` set (a
  ``TraceAnnotation``'s keyword args arrive as the event's stats; a bool as
  0 or 1);
- ``kernel_s``: the device time of the operations whose name carries the
  kernels' stable prefix, ``%gf_kernel_``, averaged over the cell's chips;
- the longest idle gaps of the first chip, each named by the innermost
  ``bench.*`` or ``repro.*`` span around the gap's midpoint;
- checks of the spans themselves: how much of ``bench.repair_all`` the
  coordinator's top-level ``repro.*`` spans cover and where the rest
  falls, how long the gathers took end to end, how much of the pipeline's
  launch span the h2d, device and d2h spans account for, whether every
  ``repro.serve.park`` overlaps a ``repro.serve.decode`` of the same
  block, and the names of the programs the device ran.

``trace_reduce.reduce_trace`` does not call this yet; ``program_spans()``
adds it around a run (``benchmarks/chip/spans.py``), and the readers of
``span_metrics.json``'s metrics read what it adds.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
from pathlib import Path

from .trace_reduce import (_DEVICE, OP_LINES, WINDOW_SPAN, _events, gaps,
                           union)

PREFIXES = ("bench.", "repro.")
KERNEL_OP_PREFIX = "%gf_kernel_"
METRICS_FILE = Path(__file__).resolve().parents[1] / "span_metrics.json"

Span = collections.namedtuple("Span", "name start end thread args")


@dataclasses.dataclass
class SpanSummary:
    """What the window's spans of one name add up to."""
    count: int = 0
    seconds: list = dataclasses.field(default_factory=list)
    bytes: int = 0
    degraded: int = 0

    @property
    def total_s(self) -> float:
        return sum(self.seconds)


def host_spans(planes) -> list:
    """Every ``bench.*`` and ``repro.*`` span of the host planes, with the
    thread (plane, line) it ran on and its args."""
    out = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for t, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append(Span(ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    (plane.name, t), dict(ev.stats)))
    return out


def window_of(spans) -> tuple:
    """``(lo, hi)`` of the longest ``bench.window`` span."""
    windows = [(s.start, s.end) for s in spans if s.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span")
    return max(windows, key=lambda w: w[1] - w[0])


def summarize(spans, lo: int, hi: int) -> dict:
    """``{name: SpanSummary}`` over the ``repro.*`` spans that start in
    ``[lo, hi)``."""
    out = collections.defaultdict(SpanSummary)
    for s in spans:
        if s.name.startswith("repro.") and lo <= s.start < hi:
            row = out[s.name]
            row.count += 1
            row.seconds.append((s.end - s.start) / 1e9)
            row.bytes += int(s.args.get("bytes", 0))
            row.degraded += int(bool(s.args.get("degraded", 0)))
    return dict(out)


def name_gaps(idle, spans, top: int = 10) -> list:
    """The ``top`` longest of the ``(start, end)`` ``idle`` intervals, each
    ``[name, seconds]``, named by the shortest ``bench.*`` or ``repro.*``
    span (other than the window) around its midpoint, else the window."""
    inner = [s for s in spans if s.name != WINDOW_SPAN]
    named = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        around = [(sp.end - sp.start, sp.name) for sp in inner
                  if sp.start <= mid < sp.end]
        named.append([min(around)[1] if around else WINDOW_SPAN,
                      (e - s) / 1e9])
    return named


def device_planes(planes, chips: int) -> list:
    devices = {int(m.group(1)): p for p in planes
               if (m := _DEVICE.match(p.name))}
    used = [devices[i] for i in sorted(devices)[:chips]]
    if len(used) < chips:
        raise ValueError(f"{len(used)} TPU planes, want {chips}")
    return used


def kernel_seconds(used, lo: int, hi: int) -> float | None:
    """Device seconds of the kernels (by their stable name) inside
    ``[lo, hi)``, averaged over the planes ``used``; None where no
    operation carries the name (a program whose kernels are unnamed)."""
    times = [min(e, hi) - max(s, lo) for plane in used
             for name, s, e in _events(plane, OP_LINES)
             if name.startswith(KERNEL_OP_PREFIX) and e > lo and s < hi]
    return sum(times) / 1e9 / len(used) if times else None


def module_names(used) -> list:
    """The programs the device ran, by name (``jit_gf_launch_mxu``, ...),
    from the ``XLA Modules`` line, without the fingerprint."""
    return sorted({name.split("(")[0]
                   for name, _, _ in _events(used[0], ("XLA Modules",))})


def first_chip_gaps(used, lo: int, hi: int) -> list:
    busy = union((max(s, lo), min(e, hi))
                 for _, s, e in _events(used[0], OP_LINES) if e > lo and s < hi)
    return gaps(busy, lo, hi)


def coverage(spans, parent: str = "bench.repair_all") -> float | None:
    """Share of the summed ``parent`` time that the ``repro.*`` spans on
    the parent's own thread cover (their union, so nested spans count once
    and the union is that of the top-level ones)."""
    covered = total = 0
    by_thread = collections.defaultdict(list)
    for s in spans:
        if s.name.startswith("repro."):
            by_thread[s.thread].append((s.start, s.end))
    for p in (s for s in spans if s.name == parent):
        inside = [(s, e) for s, e in by_thread[p.thread]
                  if p.start <= s and e <= p.end]
        covered += sum(e - s for s, e in union(inside))
        total += p.end - p.start
    return covered / total if total else None


def holes(spans, parent: str = "bench.repair_all", top: int = 6) -> list:
    """Where the ``parent`` thread's time falls outside its ``repro.*``
    spans: ``[[span before, span after, seconds, count], ...]`` summed over
    every ``parent`` span, longest first (``start``/``end`` at its edges)."""
    by_thread = collections.defaultdict(list)
    for s in spans:
        if s.name.startswith("repro."):
            by_thread[s.thread].append(s)
    total = collections.defaultdict(lambda: [0, 0])
    for p in (s for s in spans if s.name == parent):
        t, before = p.start, "start"
        for s in sorted((s for s in by_thread[p.thread]
                         if p.start <= s.start and s.end <= p.end),
                        key=lambda s: s.start):
            if s.start > t:
                row = total[(before, s.name)]
                row[0] += s.start - t
                row[1] += 1
            if s.end > t:
                t, before = s.end, s.name
        if p.end > t:
            row = total[(before, "end")]
            row[0] += p.end - t
            row[1] += 1
    rows = sorted(total.items(), key=lambda kv: -kv[1][0])[:top]
    return [[a, b, ns / 1e9, n] for (a, b), (ns, n) in rows]


def gather_wall(spans, parent: str = "bench.repair_all") -> float | None:
    """Seconds the repair gathers took end to end: per ``parent``, from its
    first ``repro.repair.prefetch`` to the end of its last
    ``repro.repair.gather_wait`` (the reader pools work through the windows
    in order, so this is when they read), summed."""
    total, seen = 0, False
    for p in (s for s in spans if s.name == parent):
        mine = [s for s in spans if s.thread == p.thread
                and p.start <= s.start and s.end <= p.end]
        first = [s.start for s in mine if s.name == "repro.repair.prefetch"]
        last = [s.end for s in mine if s.name == "repro.repair.gather_wait"]
        if first and last:
            total += max(last) - min(first)
            seen = True
    return total / 1e9 if seen else None


def launch_split(summary: dict) -> float | None:
    """(h2d + device + d2h seconds) over the pipeline's launch seconds."""
    launch = summary.get("repro.repair.launch")
    if launch is None or launch.total_s <= 0:
        return None
    return sum(summary[n].total_s for n in
               ("repro.launch.h2d", "repro.launch.device", "repro.launch.d2h")
               if n in summary) / launch.total_s


def unmatched_parks(spans) -> int | None:
    """``repro.serve.park`` spans with no ``repro.serve.decode`` of the
    same ``sid``/``block`` overlapping them; None without parks."""
    parks = [s for s in spans if s.name == "repro.serve.park"]
    if not parks:
        return None
    decodes = collections.defaultdict(list)
    for s in spans:
        if s.name == "repro.serve.decode":
            decodes[(s.args.get("sid"), s.args.get("block"))].append(s)
    return sum(not any(d.start < p.end and p.start < d.end
                       for d in decodes[(p.args.get("sid"),
                                         p.args.get("block"))])
               for p in parks)


def attach(reduced, path: str, chips: int, top: int = 10):
    """Add ``spans``, ``kernel_s`` and ``span_checks`` to a
    ``trace_reduce.ReducedTrace`` of ``path``, and name its idle gaps by the
    program's spans too. Busy time, the window and the op table stay as
    ``reduce_trace`` made them."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    spans = host_spans(planes)
    lo, hi = window_of(spans)
    used = device_planes(planes, chips)
    inside = [s for s in spans if s.start < hi and s.end > lo]
    reduced.spans = summarize(spans, lo, hi)
    reduced.kernel_s = kernel_seconds(used, lo, hi)
    reduced.idle_gaps = name_gaps(first_chip_gaps(used, lo, hi), inside, top)
    reduced.span_checks = {
        "repair_all_covered": coverage(inside),
        "repair_all_holes": holes(inside),
        "gather_wall_s": gather_wall(inside),
        "launch_split_of_launch": launch_split(reduced.spans),
        "unmatched_parks": unmatched_parks(inside),
        "modules": module_names(used),
    }
    return reduced


@contextlib.contextmanager
def program_spans():
    """Within the block, ``trace_reduce.reduce_trace`` also attaches the
    program's spans (:func:`attach`) to what it returns; the block gets
    the list of the traces so reduced."""
    from . import trace_reduce

    original = trace_reduce.reduce_trace
    reduced = []

    def reduce_with_spans(path, chips, top=10):
        reduced.append(attach(original(path, chips, top), path, chips, top))
        return reduced[-1]

    trace_reduce.reduce_trace = reduce_with_spans
    try:
        yield reduced
    finally:
        trace_reduce.reduce_trace = original


def span_metrics() -> list:
    """The per-layer metrics that read the program's spans, in
    ``BENCHMARK.json``'s form."""
    return json.loads(METRICS_FILE.read_text())


def table(summary: dict) -> dict:
    """A JSON-able digest of :func:`summarize`: per name the count, total
    seconds, mean, median and longest milliseconds, bytes and degraded
    count."""
    from .stats import percentile

    return {name: {"count": s.count, "total_s": s.total_s,
                   "mean_ms": s.total_s / s.count * 1e3,
                   "p50_ms": percentile(s.seconds, 50) * 1e3,
                   "max_ms": max(s.seconds) * 1e3,
                   "bytes": s.bytes, "degraded": s.degraded}
            for name, s in sorted(summary.items())}


def main(argv=None) -> int:
    """Run one cell once under the profiler, with the span metrics and the
    cell's end-to-end metrics as tracing leaves them."""
    import argparse
    import sys
    import time

    import jax

    from . import harness, spec

    ap = argparse.ArgumentParser(
        description="Run one cell once, traced, with the program's spans.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    cell = spec.load_cell(args.workload)
    reported = {m["name"] for m in cell.end_to_end}
    extra = [m for m in span_metrics() if cell.name in m["workloads"]
             and m["moves"] in reported]
    # a traced run reads per-layer metrics only; the end-to-end ones are
    # read too, to show what tracing costs them
    cell = dataclasses.replace(
        cell, per_layer=cell.per_layer + extra + cell.end_to_end)
    try:
        devices = harness.require_chips(jax.devices(), cell.chips)
    except harness.NoChip as e:
        print(f"spans.py: {e}; nothing was run", file=sys.stderr)
        return 1
    harness.use_compile_cache(spec.ROOT)
    with program_spans() as reduced:
        result = harness.measure(cell, seed=args.seed, seconds=args.seconds,
                                 trace=True, devices=devices, t_start=t_start)
    checks = result.pop("checks")
    result.update(spans=table(reduced[-1].spans),
                  kernel_s=reduced[-1].kernel_s,
                  span_checks=reduced[-1].span_checks, checks=checks)
    harness.print_result(result)
    return 0
