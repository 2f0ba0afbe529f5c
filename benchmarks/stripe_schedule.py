"""Locality-aware stripe scheduling: policy x devices x failure pattern.

The PR-5 tentpole numbers: fleet repair through the locality-aware stripe
scheduler (``repro.dist.schedule``) vs the contiguous stripe->device-shard
assignment, under each block-placement policy (``repro.dist.topology``) —
the experiment that turns the placement cost model into a measured win.

Every scenario repairs twin stores built identically (same topology, same
seeded placement): store *a* pipelined with ``schedule="locality"``, store
*b* synchronous with ``schedule="none"``, then asserts every rebuilt block
file bit-identical — the scheduler is a pure permutation of which shard
reads which stripes; GF(2^8) bytes never change.

Three sweeps (each device count in its own subprocess; jax locks the
topology at first init, like ``sharded_repair``/``sharded_gather``):

* **devices** (spread policy, single-node failure): the scheduled local
  fraction vs the contiguous one as the stripe axis widens — domains track
  the device count, so each device slice reads through its own rack.
* **policy** (at the max device count): ``contiguous`` arcs make every
  pattern group share one node set (nothing to schedule, uplift exactly
  1.0); ``round_robin`` disperses every stripe over all domains (flat
  affinity, nothing to win); ``spread``/copyset concentrates each stripe
  in few domains — the skewed scenario where scheduling pays.
* **failure pattern** (spread, max devices): single-node and cross-domain
  two-node repair.

Locality fractions are *deterministic* (seeded placement, counted reads —
no timing in the metric), so the CI gate on the spread-policy uplift
(``min_local_uplift``, ``min_scheduled_local_fraction`` via
``benchmarks.check_regression``) is machine-independent, unlike the
throughput gates. ``remote_read_multiplier=4`` also surfaces the win in
``sim_seconds`` (reported as ``sim_speedup``): fewer cross-domain reads is
simulated repair time saved, the paper's Figs 6/9 metric under placement.
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
NODES_PER_DOMAIN = 10
SPREAD_WIDTH = 2
BATCH = 8                 # stripes per window: one full-span launch at 8 dev
REMOTE_MULT = 4.0
SEED = 7


def _worker(devices: int, stripes: int, block: int, policy: str,
            pattern: str) -> dict:
    """Runs in a fresh process with ``devices`` forced host devices."""
    import tempfile

    import numpy as np

    import jax

    from repro.dist.sharding import with_rules
    from repro.dist.topology import Topology
    from repro.ftx import (RepairOptions, StoreConfig, StripeStore,
                           repair_failed_nodes)
    from repro.launch.mesh import make_mesh

    assert len(jax.devices()) == devices
    k, r, p = GEOM
    domains = max(1, devices)
    num_nodes = NODES_PER_DOMAIN * max(domains, 2)
    topo = Topology(num_nodes=num_nodes, num_domains=domains,
                    spread_width=SPREAD_WIDTH, seed=SEED)
    cfg = StoreConfig(scheme=SCHEME, k=k, r=r, p=p, block_size=block,
                      batch_stripes=BATCH, pipeline_window=BATCH,
                      prefetch_threads=2, placement_policy=policy,
                      remote_read_multiplier=REMOTE_MULT)

    def build(root):
        store = StripeStore(root, cfg, num_nodes=num_nodes, topology=topo)
        payload = np.random.default_rng(11).integers(
            0, 256, stripes * k * block, dtype=np.uint8)
        store.put("blob", payload.tobytes())
        store.seal()
        assert len(store.stripes) == stripes
        return store

    with tempfile.TemporaryDirectory() as tmp:
        sa = build(Path(tmp) / "a")
        sb = build(Path(tmp) / "b")
        n0 = sa.stripes[0].node_of_block[0]
        nodes = [n0]
        if pattern == "double":
            # second failure in a different domain, so the two-node groups
            # keep per-stripe diversity instead of collapsing onto one rack
            d0 = topo.domain_of(n0)
            nodes.append(next(
                n for n in range(num_nodes) if topo.domain_of(n) != d0
                and any(n in sa.stripes[s].node_of_block for s in sa.stripes)))
        mesh = make_mesh((devices, 1), ("data", "model"))
        with with_rules(mesh):
            rep = repair_failed_nodes(
                sa, nodes, options=RepairOptions(pipeline=True,
                                                 schedule="locality"))
            # like-for-like baseline: same mesh, same sharded gather, the
            # contiguous stripe->shard assignment — only the scheduler off
            base = repair_failed_nodes(
                sb, nodes, options=RepairOptions(pipeline=False,
                                                 schedule="none"))
        for sid in sa.stripes:
            for b in range(sa.scheme.n):
                assert sa._block_path(sid, b).read_bytes() == \
                    sb._block_path(sid, b).read_bytes(), \
                    f"scheduled repair not bit-identical at ({sid}, {b})"
        assert rep.blocks_read == base.blocks_read
        assert rep.schedule == "locality" and base.schedule == "none"
        return {
            "devices": devices, "S": stripes, "B": block,
            "policy": policy, "pattern": pattern, "domains": domains,
            "nodes": num_nodes,
            "stripes_repaired": rep.stripes_repaired,
            "scheduled_local_fraction": rep.local_read_fraction,
            "contiguous_local_fraction": base.local_read_fraction,
            "predicted_scheduled_fraction": rep.scheduled_local_read_fraction,
            "predicted_contiguous_fraction":
                rep.contiguous_local_read_fraction,
            "local_uplift": rep.local_read_fraction
            / max(base.local_read_fraction, 1e-9),
            "sim_seconds_scheduled": rep.sim_seconds,
            "sim_seconds_contiguous": base.sim_seconds,
            "sim_speedup": base.sim_seconds / max(rep.sim_seconds, 1e-9),
            "wall_us_per_stripe": 1e6 * rep.wall_seconds
            / max(1, rep.stripes_repaired),
        }


def _spawn(devices: int, stripes: int, block: int, policy: str,
           pattern: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.setdefault("JAX_PLATFORMS", "cpu")
    root = Path(__file__).resolve().parents[1]
    src = str(root / "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src, str(root)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.stripe_schedule",
         "--worker", str(devices), str(stripes), str(block), policy, pattern],
        env=env, cwd=root, capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(
            f"worker devices={devices} policy={policy} pattern={pattern} "
            f"failed:\n{out.stderr}")
    return json.loads(out.stdout.splitlines()[-1])


def run(fast: bool = False) -> dict:
    S, B = (640, 1024) if fast else (960, 4096)
    counts = (1, 8) if fast else (1, 2, 4, 8)
    print("bench,policy,devices,us_per_stripe,derived")
    rows = []

    def show(r):
        rows.append(r)
        csv(f"schedule,{r['policy']},{r['devices']}dev,{r['pattern']}",
            r["wall_us_per_stripe"],
            f"local={r['scheduled_local_fraction']:.3f} "
            f"contig={r['contiguous_local_fraction']:.3f} "
            f"uplift={r['local_uplift']:.2f}x "
            f"sim_speedup={r['sim_speedup']:.2f}x")

    # devices sweep: the skewed (spread) placement, single-node failure
    for d in counts:
        show(_spawn(d, S, B, "spread", "single"))
    # policy sweep at the widest mesh
    for policy in ("contiguous", "round_robin"):
        show(_spawn(counts[-1], S, B, policy, "single"))
    # failure-pattern sweep: cross-domain two-node repair under spread
    show(_spawn(counts[-1], S, B, "spread", "double"))

    gated = [r for r in rows if r["policy"] == "spread"
             and r["devices"] == counts[-1]]
    uplift = min(r["local_uplift"] for r in gated)
    frac = min(r["scheduled_local_fraction"] for r in gated)
    sim = min(r["sim_speedup"] for r in gated)
    print(f"skewed-placement uplift at {counts[-1]} devices: "
          f"{uplift:.2f}x (scheduled local fraction >= {frac:.3f}, "
          f"sim speedup >= {sim:.2f}x)")
    return {"geometry": GEOM, "scheme": SCHEME, "rows": rows,
            "max_devices": counts[-1],
            "min_local_uplift": uplift,
            "min_scheduled_local_fraction": frac,
            "min_sim_speedup": sim}


if __name__ == "__main__":
    if len(sys.argv) >= 7 and sys.argv[1] == "--worker":
        devices, stripes, block = map(int, sys.argv[2:5])
        print(json.dumps(_worker(devices, stripes, block,
                                 sys.argv[5], sys.argv[6])))
    else:
        print(json.dumps(run(fast="--fast" in sys.argv), indent=1))
