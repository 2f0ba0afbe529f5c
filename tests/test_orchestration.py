"""Global repair orchestration (DESIGN.md §14): the cross-window min-cost
assignment's dominance chain, topology-aware rebuild destinations, the
golden failure-trace fixture + replay determinism, and the background
rebalancer — the property layer that pins PR 10's tentpole.

The 1-device cases always run; the multi-device cases run in the
forced-8-device CI leg (``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.dist.placement import PlacementMap, block_loads
from repro.dist.schedule import (greedy_assign, optimize_assignment,
                                 schedule_group)
from repro.dist.sharding import with_rules
from repro.dist.topology import Topology, placement_ok
from repro.ftx import (RepairOptions, StoreConfig, StripeStore, plan_moves,
                       rebalance)
from repro.ftx.events import (NodeFailEvent, dump_trace, from_doc,
                              load_trace, sort_events, to_doc)
from repro.ftx.failures import replay_trace
from repro.launch.mesh import make_mesh

REPO = Path(__file__).resolve().parent.parent
TRACE = Path(__file__).resolve().parent / "data" / "correlated_trace.json"

multidevice = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _mesh(shape=(8, 1)):
    return make_mesh(shape, ("data", "model"))


def _trace_store(root, *, stripes=40, block=512, num_nodes=24, domains=12,
                 spread_width=2, scheme="cp-azure", policy="spread"):
    """A store on the geometry the committed trace fixture targets:
    2-node racks, so every correlated batch stays within the scheme's
    universal 2-erasure decodability."""
    topo = Topology(num_nodes=num_nodes, num_domains=domains,
                    spread_width=spread_width, seed=7)
    cfg = StoreConfig(scheme=scheme, k=6, r=2, p=2, block_size=block,
                      batch_stripes=8, pipeline_window=8,
                      prefetch_threads=2, placement_policy=policy)
    store = StripeStore(root, cfg, num_nodes=num_nodes, topology=topo)
    payload = np.random.default_rng(3).integers(
        0, 256, stripes * cfg.k * block, dtype=np.uint8)
    store.put("blob", payload.tobytes())
    store.seal()
    assert len(store.stripes) == stripes
    return store


def _all_blocks(store):
    return {(sid, b): store._block_path(sid, b).read_bytes()
            for sid in store.stripes for b in range(store.scheme.n)}


def _loads(store):
    return block_loads((s.node_of_block for s in store.stripes.values()),
                       store.num_nodes)


def _fake_placement(num_nodes, shards, reads, sids, seed):
    """A synthetic PlacementMap: seeded random node->shard and block->node."""
    rng = np.random.default_rng(seed)
    shard_of = tuple(int(s) for s in rng.integers(0, shards, num_nodes))
    table = {(sid, b): int(rng.integers(num_nodes))
             for sid in sids for b in reads}
    return PlacementMap(shard_of_node=shard_of,
                        node_of=lambda sid, b: table[(sid, b)])


# ----------------------------------------------------- assignment solver
@settings(max_examples=60, deadline=None)
@given(st.integers(2, 6), st.integers(1, 8), st.integers(1, 10),
       st.integers(0, 99999))
def test_global_assignment_dominates_greedy_and_contiguous(span, cap, amax,
                                                           seed):
    """The tentpole dominance chain at the solver level, over random
    affinity matrices: the cycle-canceled assignment is never below the
    greedy or the contiguous one, preserves every column capacity, and
    reaches the same optimum from either warm start (it is exact, not just
    monotone)."""
    rng = np.random.default_rng(seed)
    n = span * cap
    a = rng.integers(0, amax + 1, size=(n, span)).astype(np.int64)

    def total(assign):
        return int(sum(int(a[i, int(d)]) for i, d in enumerate(assign)))

    contiguous = [i // cap for i in range(n)]
    greedy = greedy_assign(a, cap)
    assert sorted(greedy) == contiguous          # capacity: cap per column
    # schedule_chunk's floor: keep the contiguous order unless greedy
    # strictly beats it — the chain's middle link is max(greedy, contig).
    floor = max(total(greedy), total(contiguous))
    opt_g = optimize_assignment(a, greedy)
    opt_c = optimize_assignment(a, contiguous)
    for opt in (opt_g, opt_c):
        assert sorted(int(d) for d in opt) == contiguous
    assert total(opt_g) == total(opt_c)          # warm-start independent
    assert total(opt_g) >= floor                 # global >= greedy >= contig


def test_optimize_assignment_edge_cases():
    empty = optimize_assignment(np.zeros((0, 3), dtype=np.int64), [])
    assert empty.size == 0
    one_col = optimize_assignment(np.ones((4, 1), dtype=np.int64),
                                  [0, 0, 0, 0])
    assert one_col.tolist() == [0, 0, 0, 0]
    # already-optimal start is returned unchanged
    a = np.array([[5, 0], [0, 5]], dtype=np.int64)
    assert optimize_assignment(a, [0, 1]).tolist() == [0, 1]
    # a 2-cycle that pays: both stripes start on their worst column
    assert optimize_assignment(a, [1, 0]).tolist() == [0, 1]


@multidevice
@settings(max_examples=12, deadline=None)
@given(st.integers(2, 4), st.integers(1, 6), st.integers(2, 9),
       st.integers(0, 999))
def test_schedule_group_global_dominates_per_chunk(windows, num_reads,
                                                   shards, seed):
    """Store-free property on random placements: pooling every window into
    one transportation problem never predicts fewer shard-local reads than
    per-chunk greedy, which never predicts fewer than contiguous; the
    output stays a permutation of the group with per-window capacity."""
    with with_rules(_mesh()) as mr:
        sids = [100 + 7 * i for i in range(8 * windows)]
        reads = tuple(range(num_reads))
        pm = _fake_placement(32, shards, reads, sids, seed)
        outs = {mode: schedule_group(sids, reads, pm, mr, step=8, mode=mode)
                for mode in ("none", "locality", "global")}
        tot = {m: sum(c.scheduled_local for c in cs)
               for m, cs in outs.items()}
        assert tot["global"] >= tot["locality"] >= tot["none"]
        for cs_list in outs.values():
            assert sorted(s for cs in cs_list for s in cs.sids) \
                == sorted(sids)                 # group-wide permutation
            assert all(len(cs.sids) == 8 for cs in cs_list)
        # contiguous predictions compare like for like across modes
        assert sum(c.contiguous_local for c in outs["global"]) \
            == tot["none"]
        assert all(c.total_reads == 8 * num_reads for c in outs["global"])


@multidevice
def test_schedule_group_keeps_degraded_tail_chunks():
    """A tail chunk the span does not divide launches degraded and is
    excluded from the pooled assignment under every mode."""
    with with_rules(_mesh()) as mr:
        sids = list(range(20))                  # chunks of 8, 8, 4
        reads = (0, 1, 2)
        pm = _fake_placement(32, 4, reads, sids, 5)
        for mode in ("none", "locality", "global"):
            out = schedule_group(sids, reads, pm, mr, step=8, mode=mode)
            assert len(out) == 3
            assert out[-1].is_identity and out[-1].span == 1
            assert out[-1].sids == tuple(range(16, 20))


# --------------------------------------------------- golden trace fixture
def test_trace_fixture_golden_roundtrip(tmp_path):
    """The committed fixture is byte-stable: doc round-trips are identity,
    dump(load(fixture)) reproduces the exact committed bytes, canonical
    ordering is input-order independent, and the bare-list form loads to
    the same events."""
    committed = TRACE.read_bytes()
    events = load_trace(TRACE)
    assert len(events) == 6
    assert events == sort_events(events)        # loads canonically sorted
    for e in events:
        assert from_doc(to_doc(e)) == e
    out = tmp_path / "again.json"
    dump_trace(events, out)
    assert out.read_bytes() == committed
    dump_trace(list(reversed(events)), out)     # order-independent dump
    assert out.read_bytes() == committed
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps([to_doc(e) for e in reversed(events)]))
    assert load_trace(bare) == events


def test_replay_trace_batches_correlated_failures(tmp_path):
    """Same-timestamp failures repair as one batch: the fixture's six
    events collapse to four batches (two node bursts, one rack, one
    singleton), rack events expand through the topology, and revived
    nodes leave the fleet whole."""
    store = _trace_store(tmp_path / "s", stripes=24)
    events = load_trace(TRACE)
    res = replay_trace(store, events, options=RepairOptions())
    rows = res["batches"]
    assert [r["t"] for r in rows] == [10.0, 250.5, 400.25, 612.75]
    assert rows[0]["nodes"] == [7, 17]
    assert rows[1]["nodes"] == store.topology.nodes_in(2) == [4, 5]
    assert rows[2]["nodes"] == [3]
    assert rows[3]["nodes"] == [20, 21]
    assert all(r["blocks_read"] > 0 for r in rows)
    assert all(s.name == "UP" for s in store.nodes.values())  # revived
    for key in ("blocks_read", "blocks_relocated", "repairs_local"):
        assert res["totals"][key] == sum(r[key] for r in rows)
    # every NodeFailEvent earns a RepairDoneEvent in the emitted log
    fails = [e for e in res["events"] if isinstance(e, NodeFailEvent)]
    assert sorted(e.node for e in fails) == [3, 4, 5, 7, 17, 20, 21]
    bad = [NodeFailEvent(t=1.0, node=99)]
    with pytest.raises(ValueError):
        replay_trace(store, bad)


def test_replay_trace_schedule_modes_bit_identical_one_device(tmp_path):
    """Without a mesh the scheduler is inert (span 1): the global and
    disabled schedules replay to byte-identical stores with coinciding
    predictions."""
    events = load_trace(TRACE)
    stores, res = {}, {}
    for mode in ("global", "none"):
        s = _trace_store(tmp_path / mode, stripes=24)
        res[mode] = replay_trace(s, events,
                                 options=RepairOptions(schedule=mode))
        stores[mode] = s
    assert _all_blocks(stores["global"]) == _all_blocks(stores["none"])
    ga, na = res["global"]["totals"], res["none"]["totals"]
    assert ga["blocks_read"] == na["blocks_read"]
    assert ga["scheduled_local"] == ga["contiguous_local"]
    assert na["scheduled_local"] == na["contiguous_local"]


@multidevice
def test_replay_trace_dominance_chain_8dev(tmp_path):
    """The tentpole acceptance on the committed trace: global strictly
    beats per-chunk greedy strictly beats contiguous on counted scheduled
    shard-local reads, with all three replays byte-identical (assignment
    is a pure permutation; write-back is keyed by sid)."""
    events = load_trace(TRACE)
    stores, totals = {}, {}
    with with_rules(_mesh()):
        for mode in ("global", "locality", "none"):
            s = _trace_store(tmp_path / mode, stripes=160)
            totals[mode] = replay_trace(
                s, events, options=RepairOptions(schedule=mode,
                                                 pipeline=True))["totals"]
            stores[mode] = s
    blocks = _all_blocks(stores["global"])
    assert _all_blocks(stores["locality"]) == blocks
    assert _all_blocks(stores["none"]) == blocks
    g, l, c = (totals[m]["scheduled_local"]
               for m in ("global", "locality", "none"))
    assert g > l > c
    assert totals["global"]["schedule_total"] \
        == totals["none"]["schedule_total"] > 0


def _replay_cli(tmp, tag):
    env = dict(os.environ, PYTHONPATH="src")
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.simulate",
         "--replay", str(TRACE), "--nodes", "24", "--domains", "12",
         "--policy", "spread", "--schedule", "global",
         "--destinations", "topology", "--rebalance",
         "--replay-store", str(tmp / tag)],
        cwd=REPO, env=env, capture_output=True, text=True)


def test_replay_cli_deterministic(tmp_path):
    """Two ``--replay`` runs over the committed trace print byte-identical
    JSON — every reported field is an exact count (simulated seconds are
    rounded to a stable precision)."""
    a = _replay_cli(tmp_path, "a")
    b = _replay_cli(tmp_path, "b")
    assert a.returncode == 0, a.stderr
    assert b.returncode == 0, b.stderr
    assert a.stdout == b.stdout
    doc = json.loads(a.stdout)
    assert doc["trace_events"] == 6
    assert doc["schedule"] == "global"
    assert doc["destinations"] == "topology"
    assert len(doc["batches"]) == 4
    assert doc["totals"]["blocks_read"] > 0
    assert doc["rebalance"]["moved"] == doc["rebalance"]["planned"]


# ------------------------------------------------- rebuild destinations
@pytest.mark.parametrize("scheme", ["cp-azure", "cp-uniform"])
@settings(max_examples=3, deadline=None)
@given(domain=st.sampled_from([0, 3, 7]))
def test_topology_destinations_preserve_invariants(domain, scheme):
    """Permanent loss of two nodes of one domain, on a fleet with spare
    copyset capacity (40 nodes / 8 domains / width 3): topology-aware
    destinations relocate every rebuilt block onto UP nodes, keep the
    spread policy's width bound, keep bytes intact, and leave the
    relocated blocks repairable again after a follow-up failure."""
    with tempfile.TemporaryDirectory() as tmp:
        store = _trace_store(Path(tmp) / "s", stripes=24, num_nodes=40,
                             domains=8, spread_width=3, scheme=scheme)
        topo = store.topology
        payload = np.asarray(store.get("blob")).tobytes()
        before = {sid: list(s.node_of_block)
                  for sid, s in store.stripes.items()}
        victims = topo.nodes_in(domain)[:2]
        for n in victims:
            store.fail_node(n)
        tele = store.repair_all(options=RepairOptions(
            destinations="topology"))
        assert tele["blocks_relocated"] > 0
        up = {n for n, s in store.nodes.items() if s.name == "UP"}
        moved_to = set()
        for sid, s in store.stripes.items():
            assert all(n in up for n in s.node_of_block), sid
            assert placement_ok("spread", topo, s.node_of_block), sid
            moved_to.update(n for n, o in zip(s.node_of_block, before[sid])
                            if n != o)
        assert moved_to and all(n in up for n in moved_to)
        assert np.asarray(store.get("blob")).tobytes() == payload
        # a relocated block's new home fails: the stripe repairs again
        # (single erasure -> local decode) and the bytes still round-trip
        follow = min(moved_to)
        store.fail_node(follow)
        tele2 = store.repair_all(options=RepairOptions(
            destinations="topology"))
        assert tele2["repairs_local"] > 0
        up2 = {n for n, s in store.nodes.items() if s.name == "UP"}
        for sid, s in store.stripes.items():
            assert all(n in up2 for n in s.node_of_block), sid
        assert np.asarray(store.get("blob")).tobytes() == payload


# ------------------------------------------------------------ rebalancer
def test_expand_validates_and_roundtrips(tmp_path):
    store = _trace_store(tmp_path / "s", stripes=24)
    topo2 = Topology(num_nodes=26, num_domains=13, spread_width=2, seed=7)
    # add-a-rack expansion: every existing node keeps its domain
    assert all(store.topology.domain_of(i) == topo2.domain_of(i)
               for i in range(24))
    added = store.expand(topo2)
    assert added == [24, 25]
    assert store.num_nodes == 26
    assert all(store.nodes[n].name == "UP" for n in added)
    with pytest.raises(ValueError):
        store.expand(Topology(num_nodes=24, num_domains=12))
    store.save_manifest()
    loaded = StripeStore.load(tmp_path / "s")
    assert loaded.num_nodes == 26
    assert loaded.topology == topo2


def test_plan_moves_deterministic_and_legal(tmp_path):
    # round_robin: dispersion (<= 1 block per domain here) is preserved by
    # moves into the added rack's fresh domain. A saturated spread copyset
    # on this fleet (2 blocks in each of 5 two-node racks) legally accepts
    # no expansion move at all — the planner must then emit an empty plan,
    # which test_rebalance_frozen_on_saturated_copysets pins.
    store = _trace_store(tmp_path / "s", stripes=48, policy="round_robin")
    store.expand(Topology(num_nodes=26, num_domains=13, spread_width=2,
                          seed=7))
    plan = plan_moves(store)
    assert plan and plan == plan_moves(store)   # pure + deterministic
    assert len({(m.sid, m.block) for m in plan}) == len(plan)  # move once
    placed = {sid: list(s.node_of_block) for sid, s in store.stripes.items()}
    for m in plan:
        assert m.src != m.dst
        assert store.nodes[m.dst].name == "UP"
        assert placed[m.sid][m.block] == m.src
        assert m.dst not in placed[m.sid]       # stays distinct
        placed[m.sid][m.block] = m.dst
    capped = plan_moves(store, max_moves=5)
    assert capped == plan[:5]


def test_rebalance_frozen_on_saturated_copysets(tmp_path):
    """When every legal move would widen a saturated spread copyset, the
    planner must refuse to trade durability for balance: empty plan."""
    store = _trace_store(tmp_path / "s", stripes=24)  # width-5 copysets
    store.expand(Topology(num_nodes=26, num_domains=13, spread_width=2,
                          seed=7))
    assert plan_moves(store) == []
    rep = rebalance(store)
    assert rep.planned == rep.moved == 0
    assert rep.imbalance_after == rep.imbalance_before


def test_rebalance_after_expansion_smooths_and_is_idempotent(tmp_path):
    store = _trace_store(tmp_path / "s", stripes=48, policy="round_robin")
    payload = np.asarray(store.get("blob")).tobytes()
    store.expand(Topology(num_nodes=26, num_domains=13, spread_width=2,
                          seed=7))
    hooks = []
    rep = rebalance(store, hook=lambda stage, i: hooks.append((stage, i)))
    assert rep.planned == rep.moved > 0
    assert rep.imbalance_after < rep.imbalance_before
    assert rep.windows == -(-rep.planned // store.cfg.pipeline_window)
    assert {s for s, _ in hooks} == {"prefetch", "commit"}
    assert sorted(i for s, i in hooks if s == "commit") \
        == list(range(rep.windows))
    loads = _loads(store)
    assert loads[24] > 0 and loads[25] > 0      # new rack received blocks
    # every replica is on disk where the manifest says, bytes unchanged
    assert all(store._block_path(sid, b).exists()
               for sid in store.stripes for b in range(store.scheme.n))
    assert np.asarray(store.get("blob")).tobytes() == payload
    assert rebalance(store).planned == 0        # idempotent


def test_rebalance_drains_down_nodes_after_in_place_repair(tmp_path):
    """The domain-loss migration case: an in-place repair of a permanent
    loss leaves rebuilt blocks addressed to the dead node; the rebalancer
    treats them as must-move and drains them onto UP nodes through the
    degraded-read path."""
    store = _trace_store(tmp_path / "s", stripes=24, num_nodes=40,
                         domains=8, spread_width=3)
    payload = np.asarray(store.get("blob")).tobytes()
    victim = store.stripes[min(store.stripes)].node_of_block[0]
    store.fail_node(victim)
    store.repair_all(options=RepairOptions(destinations="in_place"))
    held = [(sid, b) for sid, s in store.stripes.items()
            for b, n in enumerate(s.node_of_block) if n == victim]
    assert held                                 # still on the dead address
    rep = rebalance(store)
    assert rep.moved >= len(held)
    assert _loads(store)[victim] == 0
    assert all(n != victim for s in store.stripes.values()
               for n in s.node_of_block)
    assert np.asarray(store.get("blob")).tobytes() == payload
