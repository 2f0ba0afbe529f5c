"""Encode-kernel microbenchmarks: Pallas gf256 (interpret on CPU) vs CRS vs
MXU-mod2 vs jnp table reference. On-TPU the interesting comparison is the
roofline-level one in EXPERIMENTS.md §Perf; here we verify relative CPU
costs and record bytes/s for the codec default path."""
from __future__ import annotations

import numpy as np

from repro.kernels.ops import encode_batch_op

from ._util import csv, timed


def run(fast: bool = False) -> dict:
    rng = np.random.default_rng(0)
    # CPU container: Pallas paths run in interpret mode (python per tile) —
    # keep byte counts modest; the jnp ref path is XLA-compiled.
    cases = [(4, 24, 1 << 13)] if fast else [
        (4, 24, 1 << 13), (4, 24, 1 << 15), (9, 96, 1 << 14)]
    out = {}
    for (m, k, b) in cases:
        coef = rng.integers(1, 256, (m, k), dtype=np.uint8)
        data = rng.integers(0, 256, (1, k, b), dtype=np.uint8)
        for backend in ("ref", "gf", "crs", "mxu"):
            try:
                _, us = timed(lambda: np.asarray(
                    encode_batch_op(coef, data, backend=backend)), repeats=2)
                mbps = k * b / (us / 1e6) / 1e6
                out[f"{backend}/{m}x{k}x{b}"] = {"us": us, "MBps": mbps}
                csv(f"kernels/{backend}/{m}x{k}x{b}", us, f"{mbps:.1f}MB/s")
            except Exception as e:  # pragma: no cover
                out[f"{backend}/{m}x{k}x{b}"] = {"error": str(e)}
                csv(f"kernels/{backend}/{m}x{k}x{b}", -1, f"error={e}")
    return out
