"""RepairPlanner LRU cache under concurrency.

The planner is shared by the store's engine and every pipeline
reader/writer thread of a store — and fleet repair may run from multiple
coordinator threads at once. The cache contract under that load: counters
stay consistent (hits + misses == lookups, evictions never exceed
insertions), the LRU bound holds, and no plan is ever lost or corrupted
(every returned CompiledPlan matches a fresh single-threaded solve).
"""
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.core.planner import RepairPlanner
from repro.core.schemes import make_scheme
from repro.ftx import (RepairOptions, StoreConfig, StripeStore,
                       repair_failed_nodes)


def _build(root, stripes=20):
    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=256,
                      batch_stripes=8, pipeline_window=4, prefetch_threads=2)
    store = StripeStore(root, cfg)
    payload = np.random.default_rng(3).integers(
        0, 256, stripes * cfg.k * cfg.block_size, dtype=np.uint8)
    store.put("blob", payload.tobytes())
    store.seal()
    return store


def test_concurrent_repair_all_shares_planner_consistently(tmp_path):
    """Four threads drive repair_all on the same store at once (idempotent:
    every thread rebuilds the same blocks to the same bytes). The shared
    planner's stats stay consistent and every pattern stays cached."""
    store = _build(tmp_path / "s")
    truth = {(sid, b): store._block_path(sid, b).read_bytes()
             for sid in store.stripes for b in range(store.scheme.n)}
    node = store.stripes[0].node_of_block[0]
    store.fail_node(node)
    patterns = {store._down_blocks(sid) for sid in store.stripes
                if store._down_blocks(sid)}
    assert patterns
    store.engine.planner.cache_clear()

    barrier = threading.Barrier(4)

    def worker():
        barrier.wait()
        return store.repair_all(options=RepairOptions(pipeline=False))

    with ThreadPoolExecutor(4) as pool:
        futures = [pool.submit(worker) for _ in range(4)]
        results = [f.result() for f in futures]    # raises on any failure
    store.revive_node(node)

    assert all(r["stripes_repaired"] > 0 for r in results)
    assert {(sid, b): store._block_path(sid, b).read_bytes()
            for sid in store.stripes
            for b in range(store.scheme.n)} == truth

    stats = store.engine.planner.stats
    assert stats.lookups == stats.hits + stats.misses
    # duplicate concurrent builds are allowed (solve runs outside the
    # lock), but nothing may be lost: every pattern is now a pure hit
    assert stats.misses >= len(patterns)
    before = stats.snapshot()
    for down in patterns:
        store.engine.planner.multi_plan(down)
    after = stats.snapshot()
    assert after["misses"] == before["misses"]
    assert after["hits"] == before["hits"] + len(patterns)


def test_repair_all_concurrent_with_degraded_reads(tmp_path):
    """repair_all races 8 serving threads on the same store: every byte
    served during the race is bit-identical (write-back invalidation never
    exposes a stale cache entry), and the serving + planner counters stay
    consistent — every read is accounted exactly once."""
    store = _build(tmp_path / "s")
    truth = {(sid, b): store._block_path(sid, b).read_bytes()
             for sid in store.stripes for b in range(store.scheme.n)}
    node = store.stripes[0].node_of_block[0]
    store.fail_node(node)
    keys = sorted(truth)
    reads_per_thread = 150
    barrier = threading.Barrier(9)
    errors = []

    def reader(seed):
        rng = np.random.default_rng(seed)
        barrier.wait()
        for _ in range(reads_per_thread):
            sid, b = keys[int(rng.integers(len(keys)))]
            got = store.read(sid, b).tobytes()
            if got != truth[(sid, b)]:
                errors.append((sid, b))

    def repairer():
        barrier.wait()
        return store.repair_all(options=RepairOptions(pipeline=False))

    with ThreadPoolExecutor(9) as pool:
        futures = [pool.submit(reader, seed) for seed in range(8)]
        repair = pool.submit(repairer)
        for f in futures:
            f.result()                       # raises on any reader failure
        rep = repair.result()
    assert rep["stripes_repaired"] > 0
    assert not errors, f"stale/corrupt serves: {errors[:3]}"
    t = store.telemetry
    assert t.direct_reads + t.degraded_reads == 8 * reads_per_thread
    # every degraded read either hit the hot cache or was counted a miss
    # (coalesced waiters are misses too — they paid for the shared decode)
    assert t.cache_hits + t.cache_misses == t.degraded_reads
    stats = store.engine.planner.stats
    assert stats.lookups == stats.hits + stats.misses
    # post-repair, post-revive: the written-back blocks serve direct and
    # bit-identical — no reconstruction artifacts survived the race
    store.revive_node(node)
    assert {k: store.read(*k).tobytes() for k in keys} == truth


def test_lru_eviction_consistent_under_thread_hammer():
    """16 threads hammer a maxsize-8 planner with 3x as many distinct
    patterns: the LRU bound holds, counters add up, and every plan handed
    out equals the single-threaded solve (no lost/corrupt plans)."""
    scheme = make_scheme("cp-azure", 24, 2, 2)
    planner = RepairPlanner(scheme, maxsize=8)
    oracle = RepairPlanner(scheme, maxsize=512)
    patterns = [frozenset({b}) for b in range(24)]
    rounds = 4
    errors = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        for _ in range(rounds):
            for i in rng.permutation(len(patterns)):
                plan = planner.multi_plan(patterns[i])
                ref = oracle.multi_plan(patterns[i])
                if (plan.targets != ref.targets or plan.reads != ref.reads
                        or not (plan.coeffs == ref.coeffs).all()):
                    errors.append(patterns[i])

    with ThreadPoolExecutor(16) as pool:
        list(pool.map(worker, range(16)))

    assert not errors, f"lost/corrupt plans for {errors[:3]}"
    stats = planner.stats
    assert stats.lookups == stats.hits + stats.misses == \
        16 * rounds * len(patterns)
    assert stats.misses >= len(patterns)
    assert stats.evictions <= stats.misses
    assert len(planner) <= 8


def test_pipelined_repair_threads_share_planner(tmp_path):
    """The pipeline's reader/writer threads re-plan through the same
    planner mid-repair; a serial second repair of the same pattern set is
    then all hits (plans survived the concurrent phase)."""
    store = _build(tmp_path / "s")
    node = store.stripes[0].node_of_block[0]
    rep = repair_failed_nodes(store, [node], options=RepairOptions(pipeline=True))
    assert rep.pipelined and rep.stripes_repaired > 0
    assert rep.plan_cache["hits"] + rep.plan_cache["misses"] > 0
    rep2 = repair_failed_nodes(store, [node], options=RepairOptions(pipeline=True))
    assert rep2.plan_cache["misses"] == 0
    assert rep2.plan_cache["hits"] > 0


def test_concurrent_byte_and_bit_plans_share_one_expansion():
    """Byte plans and their bit-matrix expansions requested concurrently
    for the same down-sets: LRU stats count only plan lookups (bit
    expansions ride on the cached plan, never the planner cache), every
    thread sees one identical expansion per plan, and the process-wide
    expansion counter grows by exactly the number of distinct plans —
    the once-per-pattern-chunk amortization contract (DESIGN.md §11)."""
    from repro.core.gf import matrix_to_bitmatrix
    from repro.core.planner import bitmatrix_expansions

    scheme = make_scheme("cp-azure", 12, 2, 2)
    planner = RepairPlanner(scheme)
    patterns = [frozenset({b}) for b in range(8)]
    # Warm the byte plans serially so the race below is over *one* cached
    # plan object per pattern (racing solves legitimately build duplicate
    # plan objects; only the published one matters for expansion counting).
    for down in patterns:
        planner.multi_plan(down)
    base = planner.stats.snapshot()
    assert base["misses"] == len(patterns)
    before = bitmatrix_expansions()
    barrier = threading.Barrier(16)
    seen: list[dict] = []
    errors = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        got = {}
        barrier.wait()
        for i in rng.permutation(len(patterns)):
            down = patterns[i]
            plan = planner.multi_plan(down)       # byte-plan lookup (hit)
            bits = plan.bit_coeffs()              # bit-plan request
            if bits.shape != (plan.coeffs.shape[0] * 8,
                              plan.coeffs.shape[1] * 8):
                errors.append(down)
            got[down] = (id(plan.bit_coeffs()), bits)
        seen.append(got)

    with ThreadPoolExecutor(16) as pool:
        list(pool.map(worker, range(16)))

    assert not errors
    # Bit requests never touch the planner cache: lookups grew only by the
    # byte-plan hits, and hits+misses still add up.
    stats = planner.stats
    assert stats.lookups == stats.hits + stats.misses
    assert stats.misses == base["misses"]
    assert stats.hits == base["hits"] + 16 * len(patterns)
    # Every thread got the same cached expansion object, with the right bits.
    for down in patterns:
        plan = planner.multi_plan(down)
        ids = {got[down][0] for got in seen}
        assert ids == {id(plan.bit_coeffs())}, down
        want = matrix_to_bitmatrix(plan.coeffs)
        for got in seen:
            assert (got[down][1] == want).all(), down
    # Counter: one expansion per plan — never per call or per thread.
    assert bitmatrix_expansions() - before == len(patterns)


def test_bit_expansion_cached_once_per_pattern_chunk(tmp_path):
    """End-to-end counter test: a fleet repair through a bit-plane backend
    expands each pattern's coefficient matrix exactly once, no matter how
    many chunked launches the pattern's stripe group takes."""
    from repro.core.planner import bitmatrix_expansions

    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=256,
                      backend="crs", batch_stripes=4, pipeline_window=0)
    store = StripeStore(tmp_path / "s", cfg)
    payload = np.random.default_rng(5).integers(
        0, 256, 80 * cfg.k * cfg.block_size, dtype=np.uint8)
    store.put("blob", payload.tobytes())
    store.seal()
    node = store.stripes[0].node_of_block[0]
    store.fail_node(node)
    patterns = {store._down_blocks(sid) for sid in store.stripes
                if store._down_blocks(sid)}
    before = bitmatrix_expansions()
    tele = store.repair_all()
    assert tele["effective_backend"] == "crs"
    # chunking (batch_stripes=4 over 80 stripes) guarantees each pattern
    # group takes multiple launches — yet each pattern expands once
    assert tele["launches"] > len(patterns)
    assert bitmatrix_expansions() - before == len(patterns)


def test_eviction_counter_matches_cache_size_single_thread():
    """Deterministic counterpart: distinct patterns streamed through a
    small cache evict exactly (misses - maxsize) times."""
    scheme = make_scheme("cp-azure", 24, 2, 2)
    planner = RepairPlanner(scheme, maxsize=4)
    for b in range(12):
        planner.multi_plan(frozenset({b}))
    stats = planner.stats
    assert stats.misses == 12 and stats.hits == 0
    assert len(planner) == 4
    assert stats.evictions == 8
    with pytest.raises(RuntimeError):
        planner.multi_plan(frozenset(range(10)))   # > r+p: not decodable
