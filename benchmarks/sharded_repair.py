"""Repair throughput vs. virtual device count (the PR-2 tentpole numbers).

Times batched multi-node repair through ``BatchedCodecEngine`` at a fixed
stripe count S while the stripe axis is sharded over 1 / 2 / 4 / 8 forced
host devices (``repro.dist.stripes``). Each device count runs in its own
subprocess — jax locks the device topology at first init, so the sweep
cannot run in-process.

On a CPU container the per-device work is the fused table path; virtual
devices share the same silicon, so perfect scaling is not expected — the
benchmark's value is (a) the scaling *trend* as the per-device S shrinks
and (b) a regression guard proving the sharded path stays bit-identical
(each worker checksums its output against the unsharded result).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from ._util import csv

GEOM = (24, 2, 2)  # the paper's P5
SCHEME = "cp-azure"


def _worker(devices: int, S: int, B: int) -> dict:
    """Runs in a fresh process with ``devices`` forced host devices."""
    import numpy as np

    import jax

    from repro.core.engine import BatchedCodecEngine
    from repro.core.schemes import make_scheme
    from repro.dist.sharding import with_rules
    from repro.launch.mesh import make_mesh

    from benchmarks._util import timed

    assert len(jax.devices()) == devices
    k, r, p = GEOM
    scheme = make_scheme(SCHEME, k, r, p)
    engine = BatchedCodecEngine(scheme)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (S, k, B), dtype=np.uint8)
    stripes = np.asarray(engine.encode(data))
    pattern = frozenset({0, k})  # data block + first local parity (cascade)
    avail = {i: stripes[:, i, :] for i in range(scheme.n) if i not in pattern}

    base, _ = engine.repair_multi(pattern, avail)
    base = {b: np.asarray(v) for b, v in base.items()}

    mesh = make_mesh((devices, 1), ("data", "model"))
    with with_rules(mesh) as mr:
        def sharded():
            out, _ = engine.repair_multi(pattern, avail, mesh_rules=mr)
            return {b: np.asarray(v) for b, v in out.items()}

        got, us = timed(sharded)
    span = engine.last_span
    for b in pattern:
        assert (got[b] == base[b]).all(), "sharded repair not bit-identical"
    return {"devices": devices, "span": span, "S": S, "B": B,
            "us_per_stripe": us / S,
            "stripe_mb_per_s": S * B * len(avail) / max(us, 1e-9)}


def _spawn(devices: int, S: int, B: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.setdefault("JAX_PLATFORMS", "cpu")
    root = Path(__file__).resolve().parents[1]
    src = str(root / "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src, str(root)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.sharded_repair",
         "--worker", str(devices), str(S), str(B)],
        env=env, cwd=root, capture_output=True, text=True, timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(f"worker devices={devices} failed:\n{out.stderr}")
    return json.loads(out.stdout.splitlines()[-1])


def run(fast: bool = False) -> dict:
    S, B = (32, 4096) if fast else (64, 16384)
    counts = (1, 4) if fast else (1, 2, 4, 8)
    print("bench,devices,S,B,us_per_stripe,derived")
    rows = [_spawn(d, S, B) for d in counts]
    base = rows[0]["us_per_stripe"]
    for r in rows:
        r["speedup_vs_1dev"] = base / r["us_per_stripe"]
        csv(f"sharded,{r['devices']},S={r['S']},B={r['B']}",
            r["us_per_stripe"],
            f"span={r['span']} speedup={r['speedup_vs_1dev']:.2f}x")
    return {"geometry": GEOM, "scheme": SCHEME, "rows": rows}


if __name__ == "__main__":
    if len(sys.argv) >= 5 and sys.argv[1] == "--worker":
        devices, S, B = map(int, sys.argv[2:5])
        print(json.dumps(_worker(devices, S, B)))
    else:
        print(json.dumps(run(fast="--fast" in sys.argv), indent=1))
