"""The control and the planted faults that ``correct`` has to catch.

``control``
    The reference put in the program's place: every repair or decode launch
    (``BatchedCodecEngine.execute``) is computed by plain integer arithmetic
    here, with one guarantee of the configuration broken: the products are
    carry-less and truncated to 8 bits, without the reduction by the field's
    polynomial. This is the "lower precision" of a byte code: the arithmetic
    a kernel that drops the reduction step would do.
``unchanged``
    A repair that leaves the store as it was: rebuilt blocks are never
    written back.
``half_batch``
    Each launch rebuilds the first half of its stripes and leaves the rest
    out (zeros).
``no_exchange``
    A sharded launch keeps the first device's shard and leaves out the
    others' (zeros), as if the results never crossed between chips.
``altered``
    Every launch's output has one byte in each 4 KiB flipped where it is
    produced, so any range a client reads holds an altered byte.
``altered_encode``
    The same for every encode launch (``BatchedCodecEngine.encode``), so
    every ingested block holds an altered byte.

Run a cell under a plant on the chip, several seeds in one process:

    python3 benchmarks/chip/control.py --workload rebuild1-p5 \\
        --plant control --seeds 11,12,13 --seconds 5
"""
from __future__ import annotations

import contextlib
import functools

import numpy as np

PLANTS = ("none", "control", "unchanged", "half_batch", "no_exchange",
          "altered", "altered_encode")
# the faults each kind of cell can have, besides the control
FAULTS = {"rebuild": ("unchanged", "half_batch", "altered"),
          "reads": ("altered",), "writes": ("altered_encode",)}


@functools.lru_cache(maxsize=None)
def _truncated_fn():
    import jax
    import jax.numpy as jnp

    def run(bits, stacked):            # (m, 8, t) bool, (S, t, B) uint8
        s, t, b = stacked.shape
        words = jax.lax.bitcast_convert_type(
            stacked.reshape(s, t, b // 4, 4), jnp.uint32)
        # x * 2^i without the reduction: each byte shifted up by i, what
        # spills out of the byte dropped
        planes = jnp.stack([
            (words << i) & np.uint32(((0xFF << i) & 0xFF) * 0x01010101)
            for i in range(8)])                        # (8, S, t, B/4)
        out = jax.vmap(lambda take: jax.lax.reduce(
            jnp.where(take[:, None, :, None], planes, np.uint32(0)),
            np.uint32(0), jax.lax.bitwise_xor, (0, 2)))(bits)  # (m, S, B/4)
        out = jnp.moveaxis(out, 0, 1)
        return jax.lax.bitcast_convert_type(out, jnp.uint8).reshape(s, -1, b)

    return jax.jit(run)


def _truncated_execute(self, plan, stacked, mesh_rules=None):
    import jax
    import jax.numpy as jnp

    coeffs = np.asarray(plan.coeffs, np.uint8)                   # (m, t)
    bits = ((coeffs[:, None, :] >> np.arange(8)[None, :, None]) & 1) > 0
    out = _truncated_fn()(jnp.asarray(bits),
                          jnp.asarray(np.asarray(stacked, np.uint8)))
    jax.block_until_ready(out)
    self.last_span = 1
    self.effective_backend = "control"
    return out


def _wrap(original, edit):
    def execute(self, plan, stacked, mesh_rules=None):
        out = np.array(original(self, plan, stacked, mesh_rules))
        edit(self, out)
        return out
    return execute


def _half(self, out):
    if out.shape[0] > 1:
        out[-(-out.shape[0] // 2):] = 0


def _no_exchange(self, out):
    if self.last_span > 1:
        out[out.shape[0] // self.last_span:] = 0


def _altered(self, out):
    out[..., ::4096] ^= 1


@contextlib.contextmanager
def planted(kind: str):
    """Run the program with ``kind`` planted underneath (see the module)."""
    from repro.core.engine import BatchedCodecEngine
    from repro.ftx.stripestore import StripeStore

    if kind not in PLANTS:
        raise ValueError(f"unknown plant {kind!r}; choose from {PLANTS}")
    target, name = BatchedCodecEngine, "execute"
    original = target.execute
    if kind == "none":
        yield
        return
    if kind == "control":
        replacement = _truncated_execute
    elif kind == "altered_encode":
        name, original = "encode", target.encode

        def replacement(self, data, mesh_rules=None):
            out = np.array(original(self, data, mesh_rules))
            _altered(self, out)
            return out
    elif kind == "unchanged":
        target, name = StripeStore, "_finish_repair"
        original = target._finish_repair
        replacement = lambda self, *args, **kwargs: None  # noqa: E731
    else:
        edit = {"half_batch": _half, "no_exchange": _no_exchange,
                "altered": _altered}[kind]
        replacement = _wrap(original, edit)
    setattr(target, name, replacement)
    try:
        yield
    finally:
        setattr(target, name, original)


def main(argv=None) -> int:
    import argparse
    import json
    import sys
    import time

    import jax

    from . import harness, spec

    ap = argparse.ArgumentParser(description="Run a cell under a plant.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plant", choices=PLANTS, required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        devices = harness.require_chips(jax.devices(), cell.chips)
    except harness.NoChip as e:
        print(f"control.py: {e}; nothing was run", file=sys.stderr)
        return 1
    harness.use_compile_cache(spec.ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        with planted(args.plant):
            result = harness.measure(cell, seed=seed, seconds=args.seconds,
                                     trace=False, devices=devices,
                                     t_start=time.perf_counter())
        print(json.dumps({"plant": args.plant, "seed": seed,
                          "correct": result["correct"],
                          "checks": result["checks"],
                          "metrics": result["metrics"]}), flush=True)
    return 0
