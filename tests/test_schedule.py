"""Topology policies + locality-aware stripe scheduling: placement policy
geometry, the never-worse-than-contiguous scheduling property, bit-identity
of scheduled repair on 1- and 8-device meshes (sync and pipelined), the
telemetry that makes the uplift observable, and the docs/baseline CI
tooling that rides along.

The 1-device cases always run; the multi-device cases run in the
forced-8-device CI leg (``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.dist.placement import PlacementMap
from repro.dist.schedule import chunk_affinity, schedule_chunk
from repro.dist.sharding import with_rules
from repro.dist.topology import (POLICIES, Topology, place_stripe,
                                 placement_from_topology)
from repro.ftx import (RepairOptions, StoreConfig, StripeStore,
                       repair_failed_nodes)
from repro.launch.mesh import make_mesh

REPO = Path(__file__).resolve().parent.parent

multidevice = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _mesh(shape=(8, 1)):
    return make_mesh(shape, ("data", "model"))


def _build(root, *, stripes=320, block_size=512, num_nodes=40, domains=8,
           policy="spread", batch_stripes=8, **kw):
    topo = Topology(num_nodes=num_nodes, num_domains=domains,
                    spread_width=2, seed=7)
    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2,
                      block_size=block_size, batch_stripes=batch_stripes,
                      pipeline_window=batch_stripes, prefetch_threads=2,
                      placement_policy=policy, **kw)
    store = StripeStore(root, cfg, num_nodes=num_nodes, topology=topo)
    payload = np.random.default_rng(3).integers(
        0, 256, stripes * cfg.k * block_size, dtype=np.uint8)
    store.put("blob", payload.tobytes())
    store.seal()
    assert len(store.stripes) == stripes
    return store


def _all_blocks(store):
    return {(sid, b): store._block_path(sid, b).read_bytes()
            for sid in store.stripes for b in range(store.scheme.n)}


# --------------------------------------------------------------- topology
def test_topology_domains_are_contiguous_partition():
    topo = Topology(num_nodes=10, num_domains=3)
    doms = [topo.nodes_in(d) for d in range(3)]
    assert sorted(sum(doms, [])) == list(range(10))     # exact partition
    for d, nodes in enumerate(doms):
        assert nodes == sorted(nodes)
        assert all(topo.domain_of(n) == d for n in nodes)
    assert topo.shard_of_node() == tuple(topo.domain_of(i) for i in range(10))


def test_topology_validation():
    with pytest.raises(ValueError):
        Topology(num_nodes=0)
    with pytest.raises(ValueError):
        Topology(num_nodes=4, num_domains=5)
    with pytest.raises(ValueError):
        Topology(num_nodes=4, num_domains=2, spread_width=0)
    with pytest.raises(ValueError):
        place_stripe("contiguous", Topology(num_nodes=4), 0, 5)
    with pytest.raises(ValueError):
        place_stripe("bogus", Topology(num_nodes=16), 0, 4)


def test_contiguous_policy_matches_seed_arcs():
    """The default policy is exactly the seed store's stride-7 rotation."""
    topo = Topology(num_nodes=13)
    for sid in range(5):
        base = (sid * 7) % 13
        assert place_stripe("contiguous", topo, sid, 10) == \
            [(base + i) % 13 for i in range(10)]


@settings(max_examples=25, deadline=None)
@given(st.sampled_from(list(POLICIES)), st.integers(0, 99),
       st.integers(1, 8), st.integers(0, 5))
def test_place_stripe_distinct_in_range_deterministic(policy, sid, domains,
                                                      seed):
    topo = Topology(num_nodes=24, num_domains=domains, spread_width=2,
                    seed=seed)
    nodes = place_stripe(policy, topo, sid, 10)
    assert len(nodes) == 10
    assert len(set(nodes)) == 10                       # distinct nodes
    assert all(0 <= n < 24 for n in nodes)
    assert nodes == place_stripe(policy, topo, sid, 10)  # pure function


def test_round_robin_disperses_across_domains():
    topo = Topology(num_nodes=24, num_domains=8)
    for sid in range(4):
        nodes = place_stripe("round_robin", topo, sid, 8)
        # one block per domain when n == D
        assert sorted(topo.domain_of(n) for n in nodes) == list(range(8))


def test_spread_concentrates_in_few_domains():
    topo = Topology(num_nodes=40, num_domains=8, spread_width=2, seed=1)
    for sid in range(8):
        nodes = place_stripe("spread", topo, sid, 10)
        assert len({topo.domain_of(n) for n in nodes}) <= 2
    # widened automatically when the chosen domains can't hold n blocks
    narrow = Topology(num_nodes=40, num_domains=20, spread_width=2, seed=1)
    nodes = place_stripe("spread", narrow, 0, 10)
    assert len(set(nodes)) == 10
    assert len({narrow.domain_of(n) for n in nodes}) >= 5


def test_placement_from_topology_tracks_store(tmp_path):
    store = _build(tmp_path / "s", stripes=10)
    topo = store.topology
    pm = placement_from_topology(store, topo)
    assert pm.num_shards == topo.num_domains
    assert pm.remote_multiplier == store.cfg.remote_read_multiplier
    node, shard = pm.locate(0, 0)
    assert node == store.stripes[0].node_of_block[0]
    assert shard == topo.domain_of(node)
    with pytest.raises(ValueError):
        placement_from_topology(store, Topology(num_nodes=store.num_nodes + 1))


def test_store_rejects_unknown_policy_and_schedule(tmp_path):
    with pytest.raises(ValueError):
        StripeStore(tmp_path / "a", StoreConfig(placement_policy="bogus"))
    with pytest.raises(ValueError):
        StripeStore(tmp_path / "b", StoreConfig(stripe_schedule="bogus"))
    store = StripeStore(tmp_path / "c", StoreConfig(k=6, r=2, p=2))
    with pytest.raises(ValueError):
        store.repair_all(options=RepairOptions(schedule="bogus"))


def test_store_topology_mismatch_raises(tmp_path):
    with pytest.raises(ValueError):
        StripeStore(tmp_path / "s", StoreConfig(k=6, r=2, p=2),
                    num_nodes=20, topology=Topology(num_nodes=30))


def test_manifest_roundtrip_keeps_policy_and_topology(tmp_path):
    store = _build(tmp_path / "s", stripes=10)
    store.save_manifest()
    loaded = StripeStore.load(tmp_path / "s")
    assert loaded.cfg.placement_policy == "spread"
    assert loaded.cfg.stripe_schedule == "global"
    assert loaded.stripes[3].node_of_block == store.stripes[3].node_of_block
    # the explicit topology round-trips: same domains, same num_nodes, and
    # new stripes keep placing under the original copyset policy/seed
    assert loaded.topology == store.topology
    assert loaded.num_nodes == store.num_nodes
    assert loaded.placement is not None
    assert loaded.placement.shard_of_node == store.topology.shard_of_node()
    payload = np.random.default_rng(5).integers(
        0, 256, store.cfg.k * store.cfg.block_size, dtype=np.uint8).tobytes()
    for s in (store, loaded):
        s.put("extra", payload)
        s.seal()
    new_sid = max(loaded.stripes)
    assert loaded.stripes[new_sid].node_of_block == \
        store.stripes[new_sid].node_of_block
    # a store without an explicit topology keeps the seed manifest shape
    plain = StripeStore(tmp_path / "p", StoreConfig(k=6, r=2, p=2))
    plain.save_manifest()
    assert StripeStore.load(tmp_path / "p").topology == plain.topology


# -------------------------------------------------------------- scheduler
def _fake_placement(num_nodes, shards, reads, sids, seed):
    """A synthetic PlacementMap: seeded random node->shard and block->node."""
    rng = np.random.default_rng(seed)
    shard_of = tuple(int(s) for s in rng.integers(0, shards, num_nodes))
    table = {(sid, b): int(rng.integers(num_nodes))
             for sid in sids for b in reads}
    return PlacementMap(shard_of_node=shard_of,
                        node_of=lambda sid, b: table[(sid, b)])


def test_schedule_chunk_identity_without_mesh_or_resolver():
    sids = list(range(8))
    reads = (0, 1, 2)
    pm = _fake_placement(16, 4, reads, sids, 0)
    cs = schedule_chunk(sids, reads, pm, None)          # no mesh: span 1
    assert cs.is_identity and cs.span == 1
    assert cs.sids == tuple(sids)
    assert cs.scheduled_local == cs.contiguous_local
    assert cs.total_reads == len(sids) * len(reads)
    blind = PlacementMap(shard_of_node=pm.shard_of_node)  # no node_of
    cs = schedule_chunk(sids, reads, blind, None)
    assert cs.is_identity and cs.total_reads == 0
    assert cs.scheduled_local_fraction == 1.0           # no prediction


@multidevice
@settings(max_examples=25, deadline=None)
@given(st.integers(1, 4), st.integers(1, 6), st.integers(2, 9),
       st.integers(0, 999))
def test_scheduler_never_below_contiguous(windows, num_reads, shards, seed):
    """The core property: over random placements, the scheduled order's
    predicted local count never drops below the contiguous order's, and
    the output is a true permutation of the input chunk."""
    with with_rules(_mesh()) as mr:
        sids = [100 + i for i in range(8 * windows)]
        reads = tuple(range(num_reads))
        pm = _fake_placement(32, shards, reads, sids, seed)
        cs = schedule_chunk(sids, reads, pm, mr)
        assert cs.span == 8
        assert sorted(cs.sids) == sorted(sids)          # permutation
        assert tuple(sids[i] for i in cs.order) == cs.sids
        assert cs.scheduled_local >= cs.contiguous_local
        assert cs.scheduled_local_fraction >= cs.contiguous_local_fraction
        # the prediction matches a recount under the affinity matrix
        a = chunk_affinity(cs.sids, reads, pm, cs.span)
        cap = len(sids) // cs.span
        assert cs.scheduled_local == sum(
            int(a[i, i // cap]) for i in range(len(sids)))


@multidevice
def test_schedule_chunk_indivisible_degrades():
    with with_rules(_mesh()) as mr:
        sids = list(range(13))                          # 8 does not divide
        reads = (0, 1)
        pm = _fake_placement(16, 4, reads, sids, 3)
        cs = schedule_chunk(sids, reads, pm, mr)
        assert cs.is_identity and cs.span == 1
        # degraded gathers attribute every read to shard 0
        local = sum(1 for sid in sids for b in reads
                    if pm.shard_of_node[pm.node_of(sid, b)] == 0)
        assert cs.scheduled_local == cs.contiguous_local == local


# ------------------------------------------------- store integration
def test_scheduled_repair_bit_identical_one_device(tmp_path):
    """Without a mesh the scheduler is inert (span 1): scheduled and
    unscheduled repairs are byte- and telemetry-identical."""
    sa = _build(tmp_path / "a", stripes=40)
    sb = _build(tmp_path / "b", stripes=40)
    node = sa.stripes[0].node_of_block[0]
    rep = repair_failed_nodes(sa, [node], options=RepairOptions(schedule="locality"))
    rep_b = repair_failed_nodes(sb, [node], options=RepairOptions(schedule="none"))
    assert rep.schedule == "locality" and rep_b.schedule == "none"
    assert rep.blocks_read == rep_b.blocks_read
    assert rep.scheduled_local_read_fraction == \
        pytest.approx(rep_b.scheduled_local_read_fraction)
    assert rep.schedule_uplift == 1.0
    assert _all_blocks(sa) == _all_blocks(sb)


def test_schedule_defaults_from_config(tmp_path):
    store = _build(tmp_path / "s", stripes=10, stripe_schedule="none")
    node = store.stripes[0].node_of_block[0]
    rep = repair_failed_nodes(store, [node])
    assert rep.schedule == "none"
    rep = repair_failed_nodes(store, [node], options=RepairOptions(schedule="locality"))
    assert rep.schedule == "locality"


@multidevice
def test_scheduled_repair_bit_identical_and_uplifts_8dev(tmp_path):
    """The tentpole acceptance: on the skewed (spread/copyset) placement
    the scheduler's local-read fraction beats the contiguous assignment,
    with repair outputs bit-identical on both the sync and pipelined
    routes, and realized locality matching the scheduler's prediction."""
    sa = _build(tmp_path / "a")                      # scheduled, pipelined
    sb = _build(tmp_path / "b")                      # unscheduled, sync
    sc = _build(tmp_path / "c")                      # scheduled, sync
    node = sa.stripes[0].node_of_block[0]
    with with_rules(_mesh()):
        rep = repair_failed_nodes(
            sa, [node], options=RepairOptions(pipeline=True,
                                              schedule="locality"))
        rep_b = repair_failed_nodes(
            sb, [node], options=RepairOptions(pipeline=False,
                                              schedule="none"))
        rep_c = repair_failed_nodes(
            sc, [node], options=RepairOptions(pipeline=False,
                                              schedule="locality"))
    truth = _all_blocks(sb)
    assert _all_blocks(sa) == truth
    assert _all_blocks(sc) == truth
    assert rep.blocks_read == rep_b.blocks_read == rep_c.blocks_read
    # the scheduler moved reads onto owning shards — strictly better than
    # the contiguous assignment, on both routes, exactly as predicted
    for r in (rep, rep_c):
        assert r.local_read_fraction > rep_b.local_read_fraction
        assert r.schedule_uplift > 1.2
        assert r.local_read_fraction == \
            pytest.approx(r.scheduled_local_read_fraction)
        assert r.scheduled_local_read_fraction > \
            r.contiguous_local_read_fraction
    # the unscheduled run realizes its contiguous prediction
    assert rep_b.local_read_fraction == \
        pytest.approx(rep_b.scheduled_local_read_fraction)
    assert rep_b.schedule_uplift == 1.0


@multidevice
def test_degenerate_placement_keeps_contiguous_order(tmp_path):
    """When every stripe of a group lives on the same nodes (the seed
    store's arcs with num_nodes == n), affinity is flat and the scheduler
    must keep the identity assignment — uplift exactly 1.0."""
    def build(root):
        cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=512,
                          batch_stripes=8, pipeline_window=8,
                          prefetch_threads=2)
        store = StripeStore(root, cfg)
        payload = np.random.default_rng(3).integers(
            0, 256, 80 * cfg.k * 512, dtype=np.uint8)
        store.put("blob", payload.tobytes())
        store.seal()
        return store

    sa, sb = build(tmp_path / "a"), build(tmp_path / "b")
    node = sa.stripes[0].node_of_block[0]
    with with_rules(_mesh()):
        rep = repair_failed_nodes(sa, [node], options=RepairOptions(schedule="locality"))
        rep_b = repair_failed_nodes(sb, [node], options=RepairOptions(schedule="none"))
    assert rep.schedule_uplift == 1.0
    assert rep.local_read_fraction == rep_b.local_read_fraction
    assert _all_blocks(sa) == _all_blocks(sb)


@multidevice
@settings(max_examples=4, deadline=None)
@given(st.integers(0, 9), st.booleans())
def test_property_scheduled_repair_bit_identical(block_idx, pipelined):
    """Any failed node, any policy route: the scheduled permutation never
    changes bytes (write-back is keyed by sid)."""
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        sa = _build(Path(tmp) / "a", stripes=80)
        sb = _build(Path(tmp) / "b", stripes=80)
        node = sa.stripes[0].node_of_block[block_idx]
        with with_rules(_mesh()):
            repair_failed_nodes(
                sa, [node], options=RepairOptions(pipeline=pipelined,
                                                  schedule="locality"))
        repair_failed_nodes(sb, [node], options=RepairOptions(pipeline=False, schedule="none"))
        assert _all_blocks(sa) == _all_blocks(sb)


# ------------------------------------------------------- CI plumbing
def test_check_docs_passes_on_current_tree():
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-m", "benchmarks.check_docs"],
                          cwd=REPO, env=env, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert "docs consistent" in proc.stdout


def test_check_docs_table_parser():
    from benchmarks.check_docs import table_sections

    text = ("| section | paper |\n|---|---|\n"
            "| `alpha_one` | Fig 1 |\n| `beta_two`   | Fig 2 |\n"
            "not a | `row` |\n")
    assert table_sections(text) == ["alpha_one", "beta_two"]


def test_update_baseline_reports_merged_vs_reseeded(tmp_path, capsys):
    """--update-baseline must say which sections it re-seeded vs merged,
    so baseline bumps are auditable in CI logs."""
    from benchmarks.check_regression import main

    results = tmp_path / "results"
    results.mkdir()
    baseline = tmp_path / "baseline.json"
    (results / "stripe_schedule.json").write_text(json.dumps({
        "min_local_uplift": 2.0, "min_scheduled_local_fraction": 0.3}))
    (results / "sharded_gather.json").write_text(json.dumps({
        "gather_speedup_at_max_devices": 3.0, "min_shard_balance": 1.0}))
    common = ["--results", str(results), "--baseline", str(baseline)]
    assert main(["--update-baseline", *common,
                 "--sections", "stripe_schedule,sharded_gather"]) == 0
    out = capsys.readouterr().out
    assert "newly added: sharded_gather, stripe_schedule" in out
    assert "re-seeded from current results: -" in out
    # second pass re-seeds one section and must report the other as kept
    assert main(["--update-baseline", *common,
                 "--sections", "stripe_schedule"]) == 0
    out = capsys.readouterr().out
    assert "re-seeded from current results: stripe_schedule" in out
    assert "kept (merged from old baseline): sharded_gather" in out
    kept = json.loads(baseline.read_text())["sections"]
    assert set(kept) == {"stripe_schedule", "sharded_gather"}


def test_update_baseline_refuses_to_drop_gated_metric(tmp_path, capsys):
    """--update-baseline must exit non-zero when a re-seeded section no
    longer produces a metric its old baseline gated — a benchmark rename
    must not silently delete a CI floor."""
    from benchmarks.check_regression import main

    results = tmp_path / "results"
    results.mkdir()
    baseline = tmp_path / "baseline.json"
    (results / "stripe_schedule.json").write_text(json.dumps({
        "min_local_uplift": 2.0, "min_scheduled_local_fraction": 0.3}))
    baseline.write_text(json.dumps({"tolerance": 0.3, "sections": {
        "stripe_schedule": {"min_local_uplift": 2.0,
                            "min_scheduled_local_fraction": 0.3,
                            "retired_metric": 1.0}}}))
    before = baseline.read_text()
    assert main(["--update-baseline", "--results", str(results),
                 "--baseline", str(baseline),
                 "--sections", "stripe_schedule"]) == 1
    err = capsys.readouterr().err
    assert "stripe_schedule/retired_metric" in err
    assert baseline.read_text() == before       # baseline left untouched
