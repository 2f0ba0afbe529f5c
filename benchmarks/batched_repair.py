"""Batched vs per-stripe repair throughput (the PR-1 tentpole numbers).

Sweeps S (stripes per batch) x B (block bytes) x scheme and times
single-node and two-node repair through:

  looped   — one stripe at a time: ``BatchedCodecEngine.repair_single`` /
             ``repair_multi`` at S=1 per stripe, one kernel dispatch each
             (plan cache warm, so this measures pure per-stripe execution
             overhead, not planning).
  batched  — ``BatchedCodecEngine``: one compiled plan + one launch for the
             whole batch.

Reports per-stripe microseconds for both and the speedup. Acceptance: the
batched path sustains >= 3x per-stripe throughput at S >= 32 (interpret-mode
CPU numbers; the TPU Mosaic grid widens the gap). Results are bit-identical
by construction — tests/test_engine.py asserts it on every path.
"""
from __future__ import annotations

import numpy as np

from repro.core.engine import BatchedCodecEngine
from repro.core.schemes import make_scheme

from ._util import csv, timed

SCHEMES = ("cp-azure", "cp-uniform", "azure")
GEOM = (24, 2, 2)  # the paper's P5


def _bench_one(name: str, S: int, B: int, rng) -> dict:
    k, r, p = GEOM
    scheme = make_scheme(name, k, r, p)
    engine = BatchedCodecEngine(scheme)
    data = rng.integers(0, 256, (S, k, B), dtype=np.uint8)
    stripes = np.asarray(engine.encode(data))

    failed = 0  # a data block: local-group repair
    batch_avail = {i: stripes[:, i, :] for i in range(scheme.n) if i != failed}
    per_stripe_avail = [{i: stripes[s:s + 1, i, :] for i in range(scheme.n)
                         if i != failed} for s in range(S)]

    def looped():
        return [engine.repair_single(failed, a)[0][0]
                for a in per_stripe_avail]

    def batched():
        out, _ = engine.repair_single(failed, batch_avail)
        return np.asarray(out)

    got_loop, us_loop = timed(looped)
    got_batch, us_batch = timed(batched)
    assert (np.stack(got_loop) == got_batch).all(), "batched != looped"

    # Two-node (cascading) pattern: data block + first local parity.
    pattern = frozenset({0, k})
    mb_avail = {i: stripes[:, i, :] for i in range(scheme.n)
                if i not in pattern}
    ms_avail = [{i: stripes[s:s + 1, i, :] for i in range(scheme.n)
                 if i not in pattern} for s in range(S)]

    def looped2():
        return [{b: v[0] for b, v in
                 engine.repair_multi(pattern, a)[0].items()} for a in ms_avail]

    def batched2():
        out, _ = engine.repair_multi(pattern, mb_avail)
        return {b: np.asarray(v) for b, v in out.items()}

    got_loop2, us_loop2 = timed(looped2)
    got_batch2, us_batch2 = timed(batched2)
    for b in pattern:
        assert (np.stack([g[b] for g in got_loop2]) == got_batch2[b]).all()

    row = {
        "scheme": name, "S": S, "B": B,
        "single_looped_us_per_stripe": us_loop / S,
        "single_batched_us_per_stripe": us_batch / S,
        "single_speedup": us_loop / us_batch,
        "multi_looped_us_per_stripe": us_loop2 / S,
        "multi_batched_us_per_stripe": us_batch2 / S,
        "multi_speedup": us_loop2 / us_batch2,
    }
    csv(f"single,{name},S={S},B={B}", us_batch / S,
        f"speedup={row['single_speedup']:.1f}x")
    csv(f"multi,{name},S={S},B={B}", us_batch2 / S,
        f"speedup={row['multi_speedup']:.1f}x")
    return row


def run(fast: bool = False) -> dict:
    rng = np.random.default_rng(0)
    schemes = SCHEMES[:1] if fast else SCHEMES
    sweep_s = (8, 32) if fast else (8, 32, 64)
    sweep_b = (4096,) if fast else (4096, 16384)
    print("bench,scheme,S,B,us_per_stripe,derived")
    rows = [_bench_one(name, S, B, rng)
            for name in schemes for S in sweep_s for B in sweep_b]
    worst = min(r["single_speedup"] for r in rows if r["S"] >= 32)
    print(f"min single-repair speedup at S>=32: {worst:.1f}x "
          f"(acceptance: >= 3x)")
    return {"geometry": GEOM, "rows": rows,
            "min_single_speedup_at_S32": worst}
