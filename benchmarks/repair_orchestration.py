"""Global repair orchestration on a replayed correlated-failure trace.

The PR-10 tentpole numbers, all driven by the committed trace fixture
(``tests/data/correlated_trace.json`` — same-timestamp node bursts plus a
whole-rack loss, replayed through ``repro.ftx.failures.replay_trace`` so
each correlated arrival repairs as one batch):

* **assignment** — the cross-window min-cost assignment
  (``schedule="global"``) vs the per-chunk greedy (``"locality"``) vs the
  contiguous stripe->shard order (``"none"``), on twin stores under a
  forced 8-device mesh. The metric is *counted* shard-local gather reads,
  and the in-bench assert pins the strict dominance chain
  ``global > greedy > contiguous`` on this trace; every rebuilt block is
  verified bit-identical across all three stores (assignment is a pure
  permutation).
* **destinations** — topology-aware rebuild destinations
  (``destinations="topology"``) vs write-back-in-place, with failed nodes
  *not* revived (the permanent-loss case destination selection exists
  for). In-place leaves every rebuilt block on a dead address (live
  fraction 0 for the first batch); topology relocates all of them onto UP
  nodes of least-loaded surviving domains (live fraction 1.0) while
  preserving the placement policy's invariants (asserted via
  ``placement_ok``).
* **rebalance** — after the full trace the store has lost six nodes and
  relocation has piled load onto the survivors; the fleet then *expands*
  by one rack (``StripeStore.expand``) and one ``repro.ftx.rebalance``
  pass migrates blocks through the windowed double-buffer loop. Metrics:
  planned == committed move count and the strict imbalance drop.

Every gated number is a deterministic count (seeded placement, fixed
trace), so the CI floors (``benchmarks.check_regression``:
``assignment_uplift_global_vs_greedy``, ``destination_live_fraction``,
``rebalance_moves``) hold machine-independently.
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
NODES = 24
DOMAINS = 12              # 2-node racks: a rack loss stays inside the
#                           scheme's universal 2-erasure decodability
SPREAD_WIDTH = 2
BATCH = 8
REMOTE_MULT = 4.0
SEED = 7
DEVICES = 8
TRACE = Path(__file__).resolve().parents[1] / "tests" / "data" / \
    "correlated_trace.json"


def _worker(devices: int, stripes: int, block: int) -> dict:
    """Runs in a fresh process with ``devices`` forced host devices."""
    import tempfile

    import numpy as np

    import jax

    from repro.dist.placement import block_loads
    from repro.dist.sharding import with_rules
    from repro.dist.topology import Topology, placement_ok
    from repro.ftx import RepairOptions, StoreConfig, StripeStore, rebalance
    from repro.ftx.events import NodeFailEvent, RackFailEvent, load_trace
    from repro.ftx.failures import replay_trace
    from repro.launch.mesh import make_mesh

    assert len(jax.devices()) == devices
    k, r, p = GEOM
    topo = Topology(num_nodes=NODES, num_domains=DOMAINS,
                    spread_width=SPREAD_WIDTH, seed=SEED)
    cfg = StoreConfig(scheme=SCHEME, k=k, r=r, p=p, block_size=block,
                      batch_stripes=BATCH, pipeline_window=BATCH,
                      prefetch_threads=2, placement_policy="spread",
                      remote_read_multiplier=REMOTE_MULT)
    events = load_trace(TRACE)

    def build(root):
        store = StripeStore(root, cfg, num_nodes=NODES, topology=topo)
        payload = np.random.default_rng(11).integers(
            0, 256, stripes * k * block, dtype=np.uint8)
        store.put("blob", payload.tobytes())
        store.seal()
        assert len(store.stripes) == stripes
        return store

    mesh = make_mesh((devices, 1), ("data", "model"))
    out: dict = {"devices": devices, "S": stripes, "B": block,
                 "nodes": NODES, "domains": DOMAINS,
                 "trace_events": len(events)}

    with tempfile.TemporaryDirectory() as tmp:
        # ---- assignment: global vs greedy vs contiguous, bit-identical
        stores, totals = {}, {}
        for sched in ("global", "locality", "none"):
            s = build(Path(tmp) / sched)
            with with_rules(mesh):
                res = replay_trace(s, events, options=RepairOptions(
                    schedule=sched, pipeline=True))
            stores[sched], totals[sched] = s, res["totals"]
        ref = stores["global"]
        for sid in ref.stripes:
            for b in range(ref.scheme.n):
                blob = ref._block_path(sid, b).read_bytes()
                for other in ("locality", "none"):
                    assert stores[other]._block_path(sid, b).read_bytes() \
                        == blob, f"not bit-identical at ({sid}, {b})"
        g, l, c = (totals[s]["scheduled_local"]
                   for s in ("global", "locality", "none"))
        assert g > l > c, f"dominance chain broken: {g} > {l} > {c}"
        assert totals["global"]["schedule_total"] \
            == totals["none"]["schedule_total"]
        out.update({
            "scheduled_local_global": g,
            "scheduled_local_greedy": l,
            "contiguous_local": c,
            "schedule_total": totals["global"]["schedule_total"],
            "assignment_uplift_global_vs_greedy": g / max(l, 1),
            "assignment_uplift_global_vs_contiguous": g / max(c, 1),
            "sim_seconds_global": totals["global"]["sim_seconds"],
            "sim_seconds_contiguous": totals["none"]["sim_seconds"],
        })

        # ---- destinations: topology vs write-back-in-place (first batch,
        # permanent loss — no revive), then the full trace under topology
        first_t = min(e.t for e in events)
        first = [e for e in events if e.t == first_t]
        live = {}
        for dest in ("topology", "in_place"):
            s = build(Path(tmp) / f"dest_{dest}")
            lost_nodes = set()
            for e in first:
                lost_nodes.update([e.node] if isinstance(e, NodeFailEvent)
                                  else topo.nodes_in(e.rack)
                                  if isinstance(e, RackFailEvent) else [])
            lost = sum(nodes.count(n) for st in s.stripes.values()
                       for nodes in [st.node_of_block] for n in lost_nodes)
            with with_rules(mesh):
                replay_trace(s, first, options=RepairOptions(
                    destinations=dest), revive=False)
            up = {n for n, state in s.nodes.items() if state.name == "UP"}
            total_blocks = sum(len(st.node_of_block)
                               for st in s.stripes.values())
            on_up = sum(1 for st in s.stripes.values()
                        for n in st.node_of_block if n in up)
            live[dest] = {"lost_blocks": lost,
                          "live_fraction": on_up / total_blocks}
        assert live["topology"]["live_fraction"] \
            > live["in_place"]["live_fraction"]

        # Full trace under topology destinations. On this fleet every
        # copyset is *saturated* (10 blocks fill five 2-node racks), so a
        # rack loss forces the width up — the hard invariants here are
        # liveness + distinctness + readable bytes; width *preservation*
        # under spare capacity is pinned by the property tests.
        sd = build(Path(tmp) / "dest_full")
        widths = {sid: len({topo.domain_of(n) for n in st.node_of_block})
                  for sid, st in sd.stripes.items()}
        with with_rules(mesh):
            full = replay_trace(sd, events, options=RepairOptions(
                destinations="topology"), revive=False)
        up = {n for n, state in sd.nodes.items() if state.name == "UP"}
        growth = 0
        for sid, st in sd.stripes.items():
            assert all(n in up for n in st.node_of_block), sid
            assert placement_ok("contiguous", topo, st.node_of_block), sid
            growth = max(growth, len({topo.domain_of(n)
                                      for n in st.node_of_block})
                         - widths[sid])
        blob = np.asarray(sd.get("blob"))
        assert blob.tobytes() == np.asarray(
            stores["global"].get("blob")).tobytes()
        out.update({
            "first_batch_lost_blocks": live["topology"]["lost_blocks"],
            "destination_live_fraction": live["topology"]["live_fraction"],
            "in_place_live_fraction": live["in_place"]["live_fraction"],
            "blocks_relocated": full["totals"]["blocks_relocated"],
            "max_width_growth": growth,
        })

        # ---- rebalance after expansion by one rack (2 nodes)
        topo2 = Topology(num_nodes=NODES + 2, num_domains=DOMAINS + 1,
                         spread_width=SPREAD_WIDTH, seed=SEED)
        assert all(topo.domain_of(i) == topo2.domain_of(i)
                   for i in range(NODES))
        sd.expand(topo2)
        rep = rebalance(sd)
        alive = [n for n, state in sd.nodes.items() if state.name == "UP"]
        loads = block_loads((s.node_of_block for s in sd.stripes.values()),
                            sd.num_nodes)
        assert rep.moved == rep.planned and rep.moved > 0
        assert rep.imbalance_after < rep.imbalance_before
        assert all(loads[n] == 0 or n in alive for n in loads)
        out.update({
            "rebalance_moves": rep.moved,
            "rebalance_windows": rep.windows,
            "rebalance_bytes": rep.bytes_moved,
            "imbalance_before": rep.imbalance_before,
            "imbalance_after": rep.imbalance_after,
        })
    return out


def _spawn(devices: int, stripes: int, block: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.setdefault("JAX_PLATFORMS", "cpu")
    root = Path(__file__).resolve().parents[1]
    src = str(root / "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src, str(root)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH")
                            else []))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.repair_orchestration",
         "--worker", str(devices), str(stripes), str(block)],
        env=env, cwd=root, capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"worker devices={devices} failed:\n{out.stderr}")
    return json.loads(out.stdout.splitlines()[-1])


def run(fast: bool = False) -> dict:
    S, B = (160, 1024) if fast else (320, 2048)
    print("bench,leg,devices,metric,derived")
    r = _spawn(DEVICES, S, B)
    csv(f"orchestration,assignment,{DEVICES}dev",
        r["assignment_uplift_global_vs_greedy"],
        f"global={r['scheduled_local_global']} "
        f"greedy={r['scheduled_local_greedy']} "
        f"contig={r['contiguous_local']} of {r['schedule_total']}")
    csv(f"orchestration,destinations,{DEVICES}dev",
        r["destination_live_fraction"],
        f"in_place={r['in_place_live_fraction']:.3f} "
        f"relocated={r['blocks_relocated']}")
    csv(f"orchestration,rebalance,{DEVICES}dev", r["rebalance_moves"],
        f"imbalance {r['imbalance_before']} -> {r['imbalance_after']} "
        f"windows={r['rebalance_windows']}")
    print(f"global-vs-greedy local-read uplift: "
          f"{r['assignment_uplift_global_vs_greedy']:.3f}x; "
          f"destination live fraction {r['destination_live_fraction']:.3f} "
          f"vs in-place {r['in_place_live_fraction']:.3f}; "
          f"{r['rebalance_moves']} rebalance moves")
    return {"geometry": GEOM, "scheme": SCHEME, "trace": str(TRACE),
            "row": r,
            "assignment_uplift_global_vs_greedy":
                r["assignment_uplift_global_vs_greedy"],
            "assignment_uplift_global_vs_contiguous":
                r["assignment_uplift_global_vs_contiguous"],
            "destination_live_fraction": r["destination_live_fraction"],
            "blocks_relocated": r["blocks_relocated"],
            "rebalance_moves": r["rebalance_moves"]}


if __name__ == "__main__":
    if len(sys.argv) >= 5 and sys.argv[1] == "--worker":
        devices, stripes, block = map(int, sys.argv[2:5])
        print(json.dumps(_worker(devices, stripes, block)))
    else:
        print(json.dumps(run(fast="--fast" in sys.argv), indent=1))
