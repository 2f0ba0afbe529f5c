"""Quickstart: build CP-LRCs, inspect repair plans, run a real repair.

PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import make_scheme, metrics
from repro.core.engine import BatchedCodecEngine
from repro.core.repair import multi_repair_plan, single_repair_plan

print("== CP-Azure (24,2,2) vs Azure LRC (24,2,2) ==")
cp = make_scheme("cp-azure", 24, 2, 2)
az = make_scheme("azure", 24, 2, 2)

gr = cp.n - 1  # the last global parity, G_r
for name, s in (("azure", az), ("cp-azure", cp)):
    plan = single_repair_plan(s, gr)
    print(f"{name:9s} repair G_r: read {plan.cost} blocks via {plan.method}")

d1, l1 = 0, cp.k
plan = multi_repair_plan(cp, [d1, l1])
print(f"cp-azure  repair D1+L1: {plan.cost} blocks, all_local={plan.all_local}"
      f" (paper: 13 vs 24 for Azure)")

print("\n== metrics (paper Table III, P5 column) ==")
for name, s in (("azure", az), ("cp-azure", cp)):
    print(f"{name:9s} ADRC={metrics.adrc(s):6.2f} ARC1={metrics.arc1(s):6.2f}")

print("\n== bytes-level repair through the JAX/Pallas engine ==")
engine = BatchedCodecEngine(make_scheme("cp-azure", 6, 2, 2))
rng = np.random.default_rng(0)
data = rng.integers(0, 256, (1, 6, 1024), dtype=np.uint8)  # one stripe, S=1
stripe = engine.encode(data)
lost = {0, 7}  # D1 and L2
avail = {i: stripe[:, i] for i in range(engine.scheme.n) if i not in lost}
rebuilt, plan = engine.repair_multi(lost, avail)
ok = all((rebuilt[b] == stripe[:, b]).all() for b in lost)
print(f"lost D1+L2 -> read {plan.meta.cost} blocks, bit-exact={ok}")
