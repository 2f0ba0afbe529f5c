"""Placement-policy demo: how block placement + stripe scheduling change
repair locality (DESIGN.md §9).

Builds one store per block-placement policy (repro.dist.topology) on an
80-node / 8-domain fleet, fails a node, and repairs twice on an 8-device
mesh: once with the locality-aware stripe scheduler and once with the
contiguous stripe->device-shard assignment. The table shows the realized
shard-local read fraction per (policy, schedule) — identical rebuilt
bytes, very different traffic:

* contiguous arcs: every stripe of a pattern group lives on the same
  nodes — nothing to schedule, uplift exactly 1x;
* round_robin: blocks disperse over all domains — locality capped at 1/D
  for any assignment;
* spread (copyset-style): each stripe's blocks concentrate in ~2 domains —
  the scheduler routes each stripe to a domain that owns its blocks.

PYTHONPATH=src python examples/placement_demo.py
"""
import os

# Force an 8-virtual-device CPU topology before jax initializes.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import shutil                                              # noqa: E402
import tempfile                                            # noqa: E402

import numpy as np                                         # noqa: E402

from repro.dist.sharding import with_rules                 # noqa: E402
from repro.dist.topology import POLICIES, Topology         # noqa: E402
from repro.ftx import (StoreConfig, StripeStore,           # noqa: E402
                       repair_failed_nodes)
from repro.launch.mesh import make_mesh                    # noqa: E402

S, B, NODES, DOMAINS = 640, 1024, 80, 8
topo = Topology(num_nodes=NODES, num_domains=DOMAINS, spread_width=2, seed=7)
mesh = make_mesh((8, 1), ("data", "model"))
payload = np.random.default_rng(0).integers(0, 256, S * 6 * B,
                                            dtype=np.uint8).tobytes()

print(f"{NODES} nodes / {DOMAINS} domains, {S} stripes, 8-device mesh")
print(f"{'policy':12s} {'scheduled':>10s} {'contiguous':>11s} {'uplift':>7s}")
for policy in POLICIES:
    fracs = {}
    for schedule in ("locality", "none"):
        tmp = tempfile.mkdtemp()
        cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=B,
                          batch_stripes=8, pipeline_window=8,
                          placement_policy=policy, stripe_schedule=schedule)
        store = StripeStore(tmp, cfg, num_nodes=NODES, topology=topo)
        store.put("blob", payload)
        store.seal()
        node = store.stripes[0].node_of_block[0]
        with with_rules(mesh):
            report = repair_failed_nodes(store, [node])
        fracs[schedule] = report.local_read_fraction
        shutil.rmtree(tmp, ignore_errors=True)
    uplift = fracs["locality"] / max(fracs["none"], 1e-9)
    print(f"{policy:12s} {fracs['locality']:10.3f} {fracs['none']:11.3f} "
          f"{uplift:6.2f}x")
