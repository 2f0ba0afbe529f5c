"""Reduce a profiler trace to device busy time, an op table and idle gaps.

The benchmark wraps its measured window in a host span named
``bench.window`` and every call into the program in a ``bench.*`` span
(``jax.profiler.TraceAnnotation``). From the ``.xplane.pb`` the profiler
writes, this module takes:

- the window: the ``bench.window`` span on the host plane;
- each device's busy time: the union of the intervals of its operations
  (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane) inside the window;
- the device operations that took most time, averaged over the devices;
- the longest idle gaps of the first device, each named by the innermost
  ``bench.*`` host span around the gap's midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OP_LINES = ("XLA Ops",)
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_HLO = re.compile(r"^(%\S+) = \(?(\w+\[[\d,]*\])")


@dataclasses.dataclass
class ReducedTrace:
    window_s: float
    busy_s: list                      # per device used, seconds
    device_ops: list                  # [[name, seconds], ...], longest first
    idle_gaps: list                   # [[host span, seconds], ...]

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy, lo: float, hi: float) -> list:
    """The idle intervals of ``[lo, hi)`` between merged ``busy`` ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def op_name(hlo: str) -> str:
    """``%copy.46 s32[24,8,8,131072]`` from the HLO text of a trace event."""
    m = _HLO.match(hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:120]


def _events(plane, line_names=None):
    for line in plane.lines:
        if line_names is None or line.name in line_names:
            for ev in line.events:
                yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def reduce_trace(path: str, chips: int, top: int = 10) -> ReducedTrace:
    """Reduce the ``.xplane.pb`` at ``path`` over the first ``chips`` TPU
    devices (by id)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_spans, devices = [], {}
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
        elif plane.name.startswith("/host:"):
            host_spans += [ev for ev in _events(plane)
                           if ev[0].startswith(SPAN_PREFIX)]
    windows = [(s, e) for name, s, e in host_spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    used = [devices[i] for i in sorted(devices)[:chips]]
    if len(used) < chips:
        raise ValueError(f"{len(used)} TPU planes in {path}, want {chips}")
    busy, op_ns, first_busy = [], {}, None
    for plane in used:
        ops = [(name, max(s, lo), min(e, hi))
               for name, s, e in _events(plane, OP_LINES) if e > lo and s < hi]
        merged = union((s, e) for _, s, e in ops)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, s, e in ops:
            op_ns[op_name(name)] = op_ns.get(op_name(name), 0.0) + (e - s)
        if first_busy is None:
            first_busy = merged
    device_ops = sorted(([name, ns / 1e9 / chips] for name, ns in op_ns.items()),
                        key=lambda kv: -kv[1])[:top]
    inner = [(s, e, name) for name, s, e in host_spans if name != WINDOW_SPAN]
    named = []
    for s, e in sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        around = [(ee - ss, name) for ss, ee, name in inner if ss <= mid < ee]
        named.append([min(around)[1] if around else WINDOW_SPAN, (e - s) / 1e9])
    return ReducedTrace(window_s=(hi - lo) / 1e9, busy_s=busy,
                        device_ops=device_ops, idle_gaps=named)
