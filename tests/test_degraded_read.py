"""Degraded-read serving path: bit-identity, planning economy, caching,
coalescing telemetry, mid-read failure injection, and the front end.

The 1-device cases always run; the mesh-context cases run in the
forced-8-device CI leg (``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
import threading
import time

import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.repair import single_repair_plan
from repro.ftx import (DegradedReadReport, StoreConfig, StripeStore,
                       read_report, repair_failed_nodes)
from repro.launch.mesh import make_mesh
from repro.serve.blocks import BlockServer, zipf_requests
from repro.serve.telemetry import LatencyRecorder

multidevice = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

SCHEMES = ("cp-azure", "cp-uniform")


def _build(root, *, scheme="cp-azure", stripes=12, block_size=256, **kw):
    cfg = StoreConfig(scheme=scheme, k=6, r=2, p=2, block_size=block_size,
                      pipeline_window=0, **kw)
    store = StripeStore(root, cfg)
    payload = np.random.default_rng(7).integers(
        0, 256, stripes * cfg.k * block_size, dtype=np.uint8)
    store.put("blob", payload.tobytes())
    store.seal()
    assert len(store.stripes) == stripes
    return store


def _healthy(store):
    return {(sid, b): store.read(sid, b).tobytes()
            for sid in store.stripes for b in range(store.scheme.n)}


# ----------------------------------------------------------- bit-identity
@pytest.mark.parametrize("scheme", SCHEMES)
def test_single_failure_reads_bit_identical(tmp_path, scheme):
    store = _build(tmp_path / "s", scheme=scheme)
    truth = _healthy(store)
    node = store.stripes[0].node_of_block[0]
    store.fail_node(node)
    assert {k: store.read(*k).tobytes() for k in truth} == truth
    rep = read_report(store)
    assert rep.degraded_reads > 0 and rep.direct_reads > 0
    # Single failures repair at local-group bandwidth for every data and
    # local-parity block; only a lost cascade parity may need the global
    # tier (its cheapest recompute reads all k data blocks).
    assert rep.global_decodes <= 1
    assert rep.local_decode_fraction >= 0.9


@pytest.mark.parametrize("scheme", SCHEMES)
def test_double_failure_reads_bit_identical(tmp_path, scheme):
    store = _build(tmp_path / "s", scheme=scheme)
    truth = _healthy(store)
    read_report(store, reset=True)
    # Two data-block nodes: same-group stripes force the multi/global
    # fallback, cross-group stripes stay local — both must serve.
    store.fail_node(store.stripes[0].node_of_block[0])
    store.fail_node(store.stripes[0].node_of_block[1])
    assert {k: store.read(*k).tobytes() for k in truth} == truth
    rep = read_report(store)
    assert rep.degraded_reads > 0
    assert rep.decode_launches > 0


def test_unrecoverable_pattern_raises_ioerror(tmp_path):
    store = _build(tmp_path / "s", stripes=4)
    sid = next(iter(store.stripes))
    nodes = {store.stripes[sid].node_of_block[b]
             for b in range(store.scheme.r + store.cfg.p + 1)}
    for n in nodes:
        store.fail_node(n)
    down = store._down_blocks(sid)
    if len(down) <= store.scheme.r + store.cfg.p:
        pytest.skip("placement folded the failed nodes onto fewer blocks")
    with pytest.raises(IOError):
        store.read(sid, sorted(down)[0])


# ------------------------------------------------------- mesh context (CI)
@multidevice
@pytest.mark.parametrize("scheme", SCHEMES)
def test_degraded_reads_bit_identical_under_mesh(tmp_path, scheme):
    """Serving decodes issued inside an active 8-device mesh context return
    the same bytes (S=1 launches degrade to a single device — the
    divisibility rule — but must stay correct)."""
    from repro.dist.sharding import with_rules

    store = _build(tmp_path / "s", scheme=scheme)
    truth = _healthy(store)
    store.fail_node(store.stripes[0].node_of_block[0])
    store.fail_node(store.stripes[0].node_of_block[1])
    with with_rules(make_mesh((8, 1), ("data", "model"))):
        got = {k: store.read(*k).tobytes() for k in truth}
    assert got == truth


# ------------------------------------------------ planning economy (prop)
@settings(max_examples=12, deadline=None)
@given(st.integers(0, 5), st.sampled_from(SCHEMES))
def test_degraded_read_never_exceeds_planned_cost(block, scheme):
    """A cold degraded read touches exactly the chosen plan's source blocks,
    and for a single failure that plan never costs more than the paper's
    single-repair plan (local-group bandwidth, not k reads)."""
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        store = _build(f"{tmp}/s", scheme=scheme, stripes=4)
        sid = next(iter(store.stripes))
        store.fail_node(store.stripes[sid].node_of_block[block])
        down = store._down_blocks(sid)
        if block not in down:
            pytest.skip("another stripe's block landed on that node")
        plan = store.engine.planner.serving_plan(block, down)
        before = store.telemetry.blocks_read
        data = store.read(sid, block)
        touched = store.telemetry.blocks_read - before
        assert touched == plan.cost == len(plan.reads)
        assert plan.cost <= single_repair_plan(store.scheme, block).cost
        assert data.nbytes == store.cfg.block_size


def test_serving_plan_tiers_and_validation(tmp_path):
    store = _build(tmp_path / "s", stripes=2)
    planner = store.engine.planner
    # lone failure: a local-tier plan (group members, never a global decode)
    plan = planner.serving_plan(0, frozenset({0}))
    assert plan.meta.method in ("group", "recompute")
    assert plan.cost < store.scheme.k
    # block not in the down-set: ValueError
    with pytest.raises(ValueError):
        planner.serving_plan(1, frozenset({0}))
    # two data blocks of one local group down: no single-block candidate
    # survives the down-set, so the plan falls back to the flattened
    # multi-node decode — its targets cover the whole pattern and its reads
    # avoid every down block
    down = frozenset({0, 1})
    plan = planner.serving_plan(0, down)
    assert 0 in plan.targets
    assert not (set(plan.reads) & down)
    # repeated queries are pure cache hits
    before = planner.stats.snapshot()
    planner.serving_plan(0, down)
    after = planner.stats.snapshot()
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"]


# ------------------------------------------------------------- hot cache
def test_cache_hit_miss_and_eviction_bound(tmp_path):
    store = _build(tmp_path / "s", read_cache_blocks=2)
    store.fail_node(store.stripes[0].node_of_block[0])
    lost = [(sid, b) for sid in store.stripes
            for b in store._down_blocks(sid)]
    assert len(lost) > 2
    first = lost[0]
    store.read(*first)                      # miss -> decode
    store.read(*first)                      # hit
    t = store.telemetry
    assert t.cache_hits == 1 and t.cache_misses == 1
    assert t.serve_decode_launches == 1
    for key in lost:                         # stream past the capacity
        store.read(*key)
    assert len(store._hot_cache) <= 2        # LRU bound holds
    # evicted entries decode again rather than serving stale/absent data
    assert store.telemetry.serve_decode_launches >= len(lost) - 2


def test_cache_disabled_decodes_every_time(tmp_path):
    store = _build(tmp_path / "s", read_cache_blocks=0)
    store.fail_node(store.stripes[0].node_of_block[0])
    sid = next(s for s in store.stripes if store._down_blocks(s))
    block = next(iter(store._down_blocks(sid)))
    for _ in range(3):
        store.read(sid, block)
    t = store.telemetry
    assert t.serve_decode_launches == 3
    assert t.cache_hits == 0


def test_repair_invalidates_cached_reconstructions(tmp_path):
    store = _build(tmp_path / "s")
    truth = _healthy(store)
    read_report(store, reset=True)
    node = store.stripes[0].node_of_block[0]
    store.fail_node(node)
    lost = [(sid, b) for sid in store.stripes
            for b in store._down_blocks(sid)]
    for key in lost:
        store.read(*key)                     # populate the hot cache
    assert len(store._hot_cache) == len(lost)  # default cap (64) holds all
    rep = repair_failed_nodes(store, [node])  # write-back invalidates
    assert rep.stripes_repaired > 0
    assert store.telemetry.cache_invalidations == len(lost)
    assert not any(k in store._hot_cache for k in lost)
    # post-repair reads are direct (node revived) and still bit-identical
    before = store.telemetry.direct_reads
    assert {k: store.read(*k).tobytes() for k in lost} == \
        {k: truth[k] for k in lost}
    assert store.telemetry.direct_reads == before + len(lost)


def test_multi_plan_fallback_caches_sibling_blocks(tmp_path):
    """When both failures share a local group, the multi-plan decode
    rebuilds the whole pattern in one launch; the sibling's first read must
    be a cache hit, not a second launch."""
    store = _build(tmp_path / "s")
    store.fail_node(store.stripes[0].node_of_block[0])
    store.fail_node(store.stripes[0].node_of_block[1])
    sid = 0
    down = sorted(store._down_blocks(sid))
    assert down == [0, 1]                   # same local group at (6,2,2)
    store.read(sid, down[0])
    launches = store.telemetry.serve_decode_launches
    store.read(sid, down[1])
    assert store.telemetry.serve_decode_launches == launches
    assert store.telemetry.cache_hits >= 1


# --------------------------------------------------------- read_range API
def test_read_range_slices_live_and_degraded(tmp_path):
    store = _build(tmp_path / "s")
    sid = next(iter(store.stripes))
    whole = store.read(sid, 0).tobytes()
    assert store.read_range(sid, 0, 10, 50).tobytes() == whole[10:50]
    store.fail_node(store.stripes[sid].node_of_block[0])
    assert store.read_range(sid, 0, 10, 50).tobytes() == whole[10:50]
    assert store.read_range(sid, 0).tobytes() == whole  # hi=None -> full


def test_read_api_validation(tmp_path):
    store = _build(tmp_path / "s", stripes=2)
    with pytest.raises(KeyError):
        store.read(999, 0)
    with pytest.raises(IndexError):
        store.read(0, store.scheme.n)
    with pytest.raises(ValueError):
        store.read_range(0, 0, 50, 10)
    with pytest.raises(ValueError):
        store.read_range(0, 0, 0, store.cfg.block_size + 1)


def test_served_bytes_counts_range_not_block(tmp_path):
    store = _build(tmp_path / "s", stripes=2)
    read_report(store, reset=True)
    store.read_range(0, 0, 0, 10)
    assert store.telemetry.served_bytes == 10
    store.fail_node(store.stripes[0].node_of_block[0])
    store.read_range(0, 0, 0, 10)
    assert store.telemetry.served_bytes == 20


# ------------------------------------------------- mid-read node failure
def test_node_death_between_plan_and_gather_replans(tmp_path):
    """A source node dying after plan selection surfaces as an IOError on
    the gather; the read re-plans against the fresh down-set and still
    returns correct bytes (mirrors the pipeline's mid-repair re-plan)."""
    store = _build(tmp_path / "s")
    truth = _healthy(store)
    read_report(store, reset=True)
    node = store.stripes[0].node_of_block[0]
    store.fail_node(node)
    sid = next(s for s in store.stripes if store._down_blocks(s))
    block = next(iter(store._down_blocks(sid)))
    plan = store.engine.planner.serving_plan(block, store._down_blocks(sid))
    victim_block = sorted(plan.reads)[0]
    victim_node = store.stripes[sid].node_of_block[victim_block]
    fired = []

    def hook(stage, s, b):
        if stage == "gather" and not fired:
            fired.append((s, b))
            store.fail_node(victim_node)    # dies between plan and gather

    store.read_hook = hook
    try:
        data = store.read(sid, block)
    finally:
        store.read_hook = None
    assert data.tobytes() == truth[(sid, block)]
    assert store.telemetry.serve_replans >= 1
    rep = read_report(store)
    assert rep.replans >= 1


def test_replan_gives_up_when_pattern_unrecoverable(tmp_path):
    store = _build(tmp_path / "s", stripes=4)
    node = store.stripes[0].node_of_block[0]
    store.fail_node(node)
    sid = next(s for s in store.stripes if store._down_blocks(s))
    block = next(iter(store._down_blocks(sid)))

    def hook(stage, s, b):
        if stage == "gather":
            for n in range(store.num_nodes):   # kill everything mid-read
                store.fail_node(n)

    store.read_hook = hook
    try:
        with pytest.raises(IOError):
            store.read(sid, block)
    finally:
        store.read_hook = None


# ----------------------------------------------------- coalescing (serve)
def test_concurrent_reads_coalesce_to_one_launch(tmp_path):
    """8 threads race onto one lost block with the cache off: exactly one
    decode launch, 7 coalesced waiters, all bytes identical."""
    store = _build(tmp_path / "s", read_cache_blocks=0)
    truth = _healthy(store)
    read_report(store, reset=True)
    store.fail_node(store.stripes[0].node_of_block[0])
    sid = next(s for s in store.stripes if store._down_blocks(s))
    block = next(iter(store._down_blocks(sid)))
    n_threads = 8
    gate = threading.Event()

    def hook(stage, s, b):
        if stage == "gather":
            gate.wait(timeout=30)           # hold the leader's decode ...

    store.read_hook = hook
    results = [None] * n_threads
    errors = []

    def reader(i):
        try:
            results[i] = store.read(sid, block).tobytes()
        except BaseException as e:          # pragma: no cover - diagnostics
            errors.append(e)
            gate.set()

    threads = [threading.Thread(target=reader, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    # ... until every follower has attached to the in-flight decode: the
    # leader (whichever thread won the registration race) is parked in the
    # hook, so once waiters == 7 all eight requests are accounted for.
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        entry = store._inflight.get((sid, block))
        if entry is not None and entry.waiters == n_threads - 1:
            break
        time.sleep(0.002)
    else:                                    # pragma: no cover - diagnostics
        gate.set()
        pytest.fail("followers never coalesced onto the in-flight decode")
    gate.set()
    for t in threads:
        t.join(timeout=60)
    store.read_hook = None
    assert not errors, errors
    assert all(r == truth[(sid, block)] for r in results)
    t = store.telemetry
    assert t.serve_decode_launches == 1
    assert t.coalesced_reads == n_threads - 1
    assert t.degraded_reads == n_threads
    assert not store._inflight                # future retired


def test_coalescing_disabled_launches_per_request(tmp_path):
    store = _build(tmp_path / "s", read_cache_blocks=0, coalesce_reads=False)
    store.fail_node(store.stripes[0].node_of_block[0])
    sid = next(s for s in store.stripes if store._down_blocks(s))
    block = next(iter(store._down_blocks(sid)))
    results = BlockServer(store, clients=4).run([(sid, block)] * 8)
    assert len({r.tobytes() for r in results}) == 1
    assert store.telemetry.serve_decode_launches == 8
    assert store.telemetry.coalesced_reads == 0


def test_decode_error_propagates_to_waiters_and_retires_future(tmp_path):
    """A failing decode must release every coalesced waiter with the error
    and retire the in-flight entry so later reads start fresh."""
    store = _build(tmp_path / "s", read_cache_blocks=0)
    store.fail_node(store.stripes[0].node_of_block[0])
    sid = next(s for s in store.stripes if store._down_blocks(s))
    block = next(iter(store._down_blocks(sid)))

    def hook(stage, s, b):
        if stage == "gather":
            for n in range(store.num_nodes):
                store.fail_node(n)

    store.read_hook = hook
    with pytest.raises(IOError):
        store.read(sid, block)
    store.read_hook = None
    assert not store._inflight
    for n in range(store.num_nodes):
        store.revive_node(n)
    store.fail_node(store.stripes[sid].node_of_block[block])
    assert store.read(sid, block).nbytes == store.cfg.block_size


# --------------------------------------------------- report + front end
def test_read_report_fields_and_reset(tmp_path):
    store = _build(tmp_path / "s")
    read_report(store, reset=True)
    store.fail_node(store.stripes[0].node_of_block[0])
    sid = next(s for s in store.stripes if store._down_blocks(s))
    block = next(iter(store._down_blocks(sid)))
    store.read(sid, block)
    store.read(sid, block)
    live = next(b for b in range(store.scheme.n)
                if b not in store._down_blocks(sid))
    store.read(sid, live)
    rep = read_report(store)
    assert isinstance(rep, DegradedReadReport)
    assert rep.direct_reads == 1 and rep.degraded_reads == 2
    assert rep.decode_launches == 1 and rep.cache_hits == 1
    assert rep.coalescing_ratio == 2.0
    assert rep.cache_hit_rate == 0.5
    assert rep.local_decode_fraction == 1.0
    assert rep.latency["count"] == 3
    assert rep.p99_ms >= rep.p50_ms >= 0.0
    assert rep.served_bytes == 3 * store.cfg.block_size
    # reset zeroes serving counters but not repair telemetry
    blocks_read = store.telemetry.blocks_read
    read_report(store, reset=True)
    assert store.telemetry.degraded_reads == 0
    assert store.telemetry.blocks_read == blocks_read
    assert store.read_latency.snapshot()["count"] == 0


def test_zipf_requests_deterministic_and_skewed(tmp_path):
    store = _build(tmp_path / "s")
    a = zipf_requests(store, 500, alpha=1.2, seed=9)
    b = zipf_requests(store, 500, alpha=1.2, seed=9)
    assert a == b                            # same seed, same stream
    assert a != zipf_requests(store, 500, alpha=1.2, seed=10)
    assert all(0 <= blk < store.cfg.k for _, blk in a)   # data pool only
    counts = {}
    for key in a:
        counts[key] = counts.get(key, 0) + 1
    top = max(counts.values())
    assert top >= 5 * (500 / (len(store.stripes) * store.cfg.k))  # skew
    full = zipf_requests(store, 100, block_pool="all")
    assert any(blk >= store.cfg.k for _, blk in full)
    with pytest.raises(ValueError):
        zipf_requests(store, 10, block_pool="bogus")


def test_block_server_preserves_order_and_latency(tmp_path):
    store = _build(tmp_path / "s")
    truth = _healthy(store)
    store.fail_node(store.stripes[0].node_of_block[0])
    requests = zipf_requests(store, 64, seed=3)
    server = BlockServer(store, clients=4)
    before = store.read_latency.snapshot()["count"]
    out = server.run(requests)
    assert [d.tobytes() for d in out] == [truth[k] for k in requests]
    assert store.read_latency.snapshot()["count"] - before == len(requests)
    timed = server.run(requests[:8], timed=True)
    assert all(dt >= 0.0 for _, dt in timed)
    assert server.report().degraded_reads >= 0
    with pytest.raises(ValueError):
        BlockServer(store, clients=0)


# ------------------------------------------------------ latency recorder
def test_latency_recorder_quantiles_and_ring():
    rec = LatencyRecorder(max_samples=64)
    assert rec.snapshot() == {"count": 0, "bytes": 0, "p50_ms": 0.0,
                              "p99_ms": 0.0, "mean_ms": 0.0, "max_ms": 0.0}
    for ms in range(1, 101):                 # 100 samples through a 64-ring
        rec.record(ms / 1e3, nbytes=10)
    snap = rec.snapshot()
    assert snap["count"] == 100 and snap["bytes"] == 1000
    # ring keeps the most recent 64 samples: 37..100 ms
    assert snap["max_ms"] == pytest.approx(100.0)
    assert snap["p50_ms"] == pytest.approx(68.5, abs=1.0)
    assert snap["p99_ms"] <= 100.0
    prev = rec.reset()
    assert prev["count"] == 100
    assert rec.snapshot()["count"] == 0


def test_latency_recorder_thread_safe_counts():
    rec = LatencyRecorder(max_samples=128)

    def worker(_):
        for _ in range(200):
            rec.record(0.001, nbytes=1)

    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(8) as pool:
        list(pool.map(worker, range(8)))
    snap = rec.snapshot()
    assert snap["count"] == 8 * 200 and snap["bytes"] == 8 * 200
