"""Per-shard gather scaling x locality cost (the PR-4 tentpole numbers).

Times fleet repair through the placement-aware read stack while the stripe
axis is sharded over 1 / 2 / 4 / 8 forced host devices: each device shard's
slice of the batched ``(S, |reads|, B)`` input is prefetched by that
shard's own reader pool (its simulated host's disks) into its own buffer
and device_put directly onto its shard — the single-host gather stack is
gone. ``io_stall_scale`` makes the per-read link model wall-real, so the
measured gather span is the simulated I/O actually being paid.

Two sweeps:

* **devices** (at ``remote_read_multiplier=1.0``): per-stripe gather span
  must *scale down* with the device count — the gather leaving the
  single-host critical path. The headline ``gather_speedup_at_max_devices``
  is CI-gated (``benchmarks.check_regression``).
* **locality ratio** (at the max device count): sweeping the cross-shard
  read multiplier shows the locality cost model charging remote traffic —
  ``sim_seconds`` inflates with the multiplier while disk bytes and output
  stay identical.

Every worker also repairs a twin store through the unsharded synchronous
path and asserts every rebuilt block file is bit-identical — the sharded
gather is a pure data-movement refactor, GF(2^8) bytes never change.

Each device count runs in its own subprocess (jax locks the topology at
first init, like ``sharded_repair``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from ._util import csv

GEOM = (6, 2, 2)
SCHEME = "cp-azure"


def _worker(devices: int, stripes: int, block: int, stall: float,
            mult: float) -> dict:
    """Runs in a fresh process with ``devices`` forced host devices."""
    import tempfile

    import numpy as np

    import jax

    from repro.dist.sharding import with_rules
    from repro.ftx import (RepairOptions, StoreConfig, StripeStore,
                           repair_failed_nodes)
    from repro.launch.mesh import make_mesh

    assert len(jax.devices()) == devices
    k, r, p = GEOM
    cfg = StoreConfig(scheme=SCHEME, k=k, r=r, p=p, block_size=block,
                      batch_stripes=max(devices, 8),
                      pipeline_window=max(devices, 8), prefetch_threads=2,
                      io_stall_scale=stall, remote_read_multiplier=mult)

    def build(root):
        store = StripeStore(root, cfg)
        payload = np.random.default_rng(11).integers(
            0, 256, stripes * k * block, dtype=np.uint8)
        store.put("blob", payload.tobytes())
        store.seal()
        assert len(store.stripes) == stripes
        return store

    with tempfile.TemporaryDirectory() as tmp:
        sa = build(Path(tmp) / "a")
        sb = build(Path(tmp) / "b")
        node = sa.stripes[0].node_of_block[0]
        mesh = make_mesh((devices, 1), ("data", "model"))
        with with_rules(mesh):
            rep = repair_failed_nodes(sa, [node], options=RepairOptions(pipeline=True))
        assert rep.devices == devices, (rep.devices, devices)
        base = repair_failed_nodes(sb, [node], options=RepairOptions(pipeline=False))
        for sid in sa.stripes:
            for b in range(sa.scheme.n):
                assert sa._block_path(sid, b).read_bytes() == \
                    sb._block_path(sid, b).read_bytes(), \
                    f"sharded gather not bit-identical at ({sid}, {b})"
        assert rep.blocks_read == base.blocks_read
        gbs = rep.gather_bytes_per_shard
        return {
            "devices": devices, "S": stripes, "B": block,
            "remote_multiplier": mult,
            "stripes_repaired": rep.stripes_repaired,
            "gather_seconds": rep.read_seconds,
            "gather_us_per_stripe": 1e6 * rep.read_seconds
            / max(1, rep.stripes_repaired),
            "wall_seconds": rep.wall_seconds,
            "sim_seconds": rep.sim_seconds,
            "local_reads": rep.local_reads,
            "remote_reads": rep.remote_reads,
            "local_fraction": rep.local_read_fraction,
            "shards": len(gbs),
            # 1.0 = every shard gathered the same byte count
            "shard_balance": (sum(gbs.values())
                              / (max(gbs.values()) * len(gbs))
                              if gbs else 1.0),
        }


def _spawn(devices: int, stripes: int, block: int, stall: float,
           mult: float) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.setdefault("JAX_PLATFORMS", "cpu")
    root = Path(__file__).resolve().parents[1]
    src = str(root / "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src, str(root)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.sharded_gather",
         "--worker", str(devices), str(stripes), str(block), str(stall),
         str(mult)],
        env=env, cwd=root, capture_output=True, text=True, timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(f"worker devices={devices} failed:\n{out.stderr}")
    return json.loads(out.stdout.splitlines()[-1])


def run(fast: bool = False) -> dict:
    # S is a multiple of n * 8 so round-robin placement yields pattern
    # groups whose windows stay divisible across every device count.
    S, B, stall = (80, 4096, 0.05) if fast else (160, 16384, 0.1)
    counts = (1, 4, 8) if fast else (1, 2, 4, 8)
    mults = (2.0,) if fast else (2.0, 4.0)
    print("bench,devices,S,B,us_per_stripe,derived")
    rows = [_spawn(d, S, B, stall, 1.0) for d in counts]
    base = rows[0]["gather_us_per_stripe"]
    for r in rows:
        r["gather_speedup_vs_1dev"] = base / max(r["gather_us_per_stripe"],
                                                 1e-9)
        csv(f"gather,{r['devices']},S={r['S']},B={r['B']}",
            r["gather_us_per_stripe"],
            f"speedup={r['gather_speedup_vs_1dev']:.2f}x "
            f"local={r['local_fraction']:.2f} "
            f"balance={r['shard_balance']:.2f}")
    # Locality-ratio sweep at the widest mesh: the cost model must charge
    # cross-shard traffic (sim time inflates with the multiplier).
    loc_rows = [_spawn(counts[-1], S, B, stall, m) for m in mults]
    sim_base = rows[-1]["sim_seconds"]
    for r in loc_rows:
        r["sim_inflation"] = r["sim_seconds"] / max(sim_base, 1e-9)
        csv(f"locality,{r['devices']},mult={r['remote_multiplier']}",
            r["gather_us_per_stripe"],
            f"sim_inflation={r['sim_inflation']:.2f}x "
            f"remote={1 - r['local_fraction']:.2f}")
    speedup = rows[-1]["gather_speedup_vs_1dev"]
    print(f"gather speedup at {counts[-1]} devices: {speedup:.2f}x")
    return {"geometry": GEOM, "scheme": SCHEME, "rows": rows,
            "locality_rows": loc_rows,
            "max_devices": counts[-1],
            "gather_speedup_at_max_devices": speedup,
            "min_shard_balance": min(r["shard_balance"] for r in rows)}


if __name__ == "__main__":
    if len(sys.argv) >= 7 and sys.argv[1] == "--worker":
        devices, stripes, block = map(int, sys.argv[2:5])
        stall, mult = map(float, sys.argv[5:7])
        print(json.dumps(_worker(devices, stripes, block, stall, mult)))
    else:
        print(json.dumps(run(fast="--fast" in sys.argv), indent=1))
