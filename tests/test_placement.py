"""Placement-aware sharded gather: the PlacementMap abstraction, the
per-shard gather geometry, locality accounting, and bit-identity of the
sharded read stack with the single-host path.

The 1-device cases always run; the multi-device cases run in the
forced-8-device CI leg (``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
import jax
import numpy as np
import pytest

from repro.dist.placement import PlacementMap, assemble_shards, shard_layout
from repro.dist.sharding import with_rules
from repro.dist.stripes import align_stripe_window, stripe_axis_span
from repro.ftx import (RepairOptions, StoreConfig, StripeStore,
                       repair_failed_nodes)
from repro.launch.mesh import make_mesh

multidevice = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _mesh(shape=(8, 1)):
    return make_mesh(shape, ("data", "model"))


def _build(root, *, stripes=80, block_size=512, batch_stripes=8, **kw):
    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2,
                      block_size=block_size, batch_stripes=batch_stripes,
                      pipeline_window=batch_stripes, prefetch_threads=2, **kw)
    store = StripeStore(root, cfg)
    payload = np.random.default_rng(3).integers(
        0, 256, stripes * cfg.k * block_size, dtype=np.uint8)
    store.put("blob", payload.tobytes())
    store.seal()
    assert len(store.stripes) == stripes
    return store


def _all_blocks(store):
    return {(sid, b): store._block_path(sid, b).read_bytes()
            for sid in store.stripes for b in range(store.scheme.n)}


# ------------------------------------------------------------ PlacementMap
def test_placement_map_locate_and_cost(tmp_path):
    store = _build(tmp_path / "s", stripes=10)
    pm = PlacementMap.from_store(store, num_shards=2, remote_multiplier=3.0)
    assert pm.num_shards == 2
    # contiguous node ranges: first half of the 10 nodes -> shard 0
    assert pm.shard_of(0) == 0 and pm.shard_of(store.num_nodes - 1) == 1
    node, shard = pm.locate(0, 0)
    assert node == store.stripes[0].node_of_block[0]
    assert shard == pm.shard_of(node)
    # locality cost model
    assert pm.is_local(node, shard) and pm.read_multiplier(node, shard) == 1.0
    other = 1 - shard
    assert not pm.is_local(node, other)
    assert pm.read_multiplier(node, other) == 3.0
    # unattributed reads are local by definition
    assert pm.is_local(node, None) and pm.read_multiplier(node, None) == 1.0


def test_placement_map_defaults_from_config(tmp_path):
    store = _build(tmp_path / "s", stripes=10, remote_read_multiplier=2.5)
    pm = PlacementMap.from_store(store, num_shards=4)
    assert pm.remote_multiplier == 2.5
    assert pm.num_shards == 4


def test_reader_shard_contiguous_mapping(tmp_path):
    store = _build(tmp_path / "s", stripes=10)
    pm = PlacementMap.from_store(store, num_shards=2)
    # device span 4 folded onto 2 hosts: first two device shards -> host 0
    assert [pm.reader_shard(d, 4) for d in range(4)] == [0, 0, 1, 1]
    # identity when span == hosts
    assert [pm.reader_shard(d, 2) for d in range(2)] == [0, 1]
    one = PlacementMap.from_store(store, num_shards=1)
    assert [one.reader_shard(d, 8) for d in range(8)] == [0] * 8


def test_shard_layout_degrades_without_mesh():
    assert shard_layout((32, 4, 512), None) is None
    with with_rules(_mesh((1, 1))) as mr:
        assert shard_layout((32, 4, 512), mr) is None


# ----------------------------------------------------- layout geometry
@multidevice
def test_shard_layout_partitions_in_stripe_order():
    with with_rules(_mesh()) as mr:
        layout = shard_layout((32, 4, 512), mr)
        assert layout is not None and len(layout) == 8
        # contiguous equal slices covering [0, S) in order — the same
        # stripe->device mapping align_stripe_window preserves
        assert [(sl.lo, sl.hi) for sl in layout] == \
            [(i * 4, (i + 1) * 4) for i in range(8)]
        assert all(sl.index == i for i, sl in enumerate(layout))
        assert all(len(sl.devices) == 1 for sl in layout)
        # an aligned window always yields a full-span layout
        win = align_stripe_window(20, mr)
        assert win == 16
        assert len(shard_layout((win, 4, 512), mr)) == stripe_axis_span(mr)
        # indivisible S degrades
        assert shard_layout((13, 4, 512), mr) is None


@multidevice
def test_shard_layout_replicated_axis_devices():
    """A 4x2 mesh shards stripes 4 ways and replicates over "model": each
    slice is owned by 2 devices and assembly still round-trips exactly."""
    with with_rules(_mesh((4, 2))) as mr:
        shape = (16, 3, 64)
        layout = shard_layout(shape, mr)
        assert len(layout) == 4
        assert all(len(sl.devices) == 2 for sl in layout)
        g = np.arange(np.prod(shape), dtype=np.uint8).reshape(shape)
        bufs = [g[sl.lo:sl.hi] for sl in layout]
        ga = assemble_shards(shape, mr, layout, bufs)
        assert (np.asarray(ga) == g).all()


@multidevice
def test_assemble_shards_zero_copy_launch():
    """An assembled global batch is consumed by the sharded launch with the
    same bytes as the host path (and the sharding it was built with)."""
    from repro.dist.stripes import stripe_sharding
    from repro.kernels.ops import gf_matmul_batch_op

    rng = np.random.default_rng(5)
    coef = rng.integers(0, 256, (3, 5), dtype=np.uint8)
    shape = (16, 5, 256)
    data = rng.integers(0, 256, shape, dtype=np.uint8)
    with with_rules(_mesh()) as mr:
        layout = shard_layout(shape, mr)
        ga = assemble_shards(shape, mr, layout,
                             [data[sl.lo:sl.hi] for sl in layout])
        assert ga.sharding.is_equivalent_to(stripe_sharding(shape, mr), 3)
        want = np.asarray(gf_matmul_batch_op(coef, data, backend="ref"))
        got = np.asarray(gf_matmul_batch_op(coef, ga, backend="ref",
                                            mesh_rules=mr))
        # non-uint8 host input is coerced identically on the sharded path
        wide = np.asarray(gf_matmul_batch_op(
            coef, data.astype(np.int64), backend="ref", mesh_rules=mr))
    assert (want == got).all()
    assert wide.dtype == np.uint8 and (want == wide).all()


# ------------------------------------------------- store integration
def test_unsharded_repair_counts_local(tmp_path):
    """Without a mesh the derived placement has one shard: every repair
    read is local and all gather bytes land on shard 0."""
    store = _build(tmp_path / "s", stripes=20)
    node = store.stripes[0].node_of_block[0]
    rep = repair_failed_nodes(store, [node])
    assert rep.remote_reads == 0
    assert rep.local_reads == rep.blocks_read > 0
    assert rep.local_read_fraction == 1.0
    assert set(rep.gather_bytes_per_shard) == {0}
    assert rep.gather_bytes_per_shard[0] == rep.bytes_read


def test_degraded_reads_not_attributed_to_gather(tmp_path):
    """Client/degraded-read paths stay out of the per-shard gather bytes
    (no shard attribution), and count as local."""
    store = _build(tmp_path / "s", stripes=10)
    before = store.telemetry.copy()
    store.fail_node(store.stripes[0].node_of_block[0])
    store.get("blob")                       # degraded read, no repair_all
    t = store.telemetry
    assert t.blocks_read > before.blocks_read
    assert t.remote_reads == 0
    assert t.gather_bytes_per_shard == before.gather_bytes_per_shard


def test_remote_multiplier_inflates_sim_time(tmp_path):
    """Two shards over the node set: cross-shard reads pay the multiplier
    in simulated time, but rebuilt bytes are identical."""
    sa = _build(tmp_path / "a", stripes=20)
    sb = _build(tmp_path / "b", stripes=20)
    node = sa.stripes[0].node_of_block[0]
    cheap = PlacementMap.from_store(sa, num_shards=1)
    costly = PlacementMap(
        shard_of_node=PlacementMap.from_store(sb, num_shards=2).shard_of_node,
        remote_multiplier=4.0,
        node_of=lambda sid, b: sb.stripes[sid].node_of_block[b])
    rep_a = repair_failed_nodes(sa, [node], options=RepairOptions(placement=cheap))
    # shard 0 gathers everything (span 1) but half the nodes are shard 1:
    # those reads are remote and 4x as expensive in simulated time
    rep_b = repair_failed_nodes(sb, [node], options=RepairOptions(placement=costly))
    assert rep_a.remote_reads == 0 and rep_b.remote_reads > 0
    assert rep_b.sim_seconds > rep_a.sim_seconds * 1.5
    assert rep_a.blocks_read == rep_b.blocks_read
    assert _all_blocks(sa) == _all_blocks(sb)


def test_store_level_placement_attribute(tmp_path):
    """A store-level PlacementMap is the repair default (no per-call arg)."""
    store = _build(tmp_path / "s", stripes=20)
    store.placement = PlacementMap.from_store(store, num_shards=2,
                                              remote_multiplier=2.0)
    node = store.stripes[0].node_of_block[0]
    rep = repair_failed_nodes(store, [node])
    assert rep.remote_reads > 0          # half the nodes live off-shard-0


@multidevice
def test_sharded_gather_repair_bit_identical(tmp_path):
    """The tentpole acceptance: per-shard gather + pre-sharded launch on 8
    devices produces bit-identical blocks to the single-host path, on both
    the synchronous and pipelined routes, with balanced per-shard bytes."""
    sa = _build(tmp_path / "a")                      # sharded, pipelined
    sb = _build(tmp_path / "b")                      # sharded, sync
    sc = _build(tmp_path / "c")                      # unsharded reference
    node = sa.stripes[0].node_of_block[0]
    with with_rules(_mesh()):
        rep_a = repair_failed_nodes(sa, [node], options=RepairOptions(pipeline=True))
        rep_b = repair_failed_nodes(sb, [node], options=RepairOptions(pipeline=False))
    rep_c = repair_failed_nodes(sc, [node], options=RepairOptions(pipeline=False))
    assert rep_a.devices == rep_b.devices == 8
    assert rep_c.devices == 1
    truth = _all_blocks(sc)
    assert _all_blocks(sa) == truth
    assert _all_blocks(sb) == truth
    # same disk traffic; gather bytes split evenly across the 8 shards
    assert rep_a.blocks_read == rep_b.blocks_read == rep_c.blocks_read
    for rep in (rep_a, rep_b):
        assert len(rep.gather_bytes_per_shard) == 8
        lo, hi = (min(rep.gather_bytes_per_shard.values()),
                  max(rep.gather_bytes_per_shard.values()))
        assert lo == hi                 # perfectly balanced pattern groups
        assert sum(rep.gather_bytes_per_shard.values()) == rep.bytes_read
        assert rep.local_reads + rep.remote_reads == rep.blocks_read
    # derived 8-shard placement over round-robin nodes: mostly remote
    assert rep_a.local_read_fraction < 0.5
    assert rep_c.local_read_fraction == 1.0


@multidevice
def test_sharded_gather_sim_time_unchanged_at_unity_multiplier(tmp_path):
    """With the default multiplier (1.0) sharding changes data movement,
    never the simulated link model: sim_seconds matches unsharded."""
    sa = _build(tmp_path / "a")
    sb = _build(tmp_path / "b")
    node = sa.stripes[0].node_of_block[0]
    with with_rules(_mesh()):
        rep = repair_failed_nodes(sa, [node], options=RepairOptions(pipeline=True))
    rep_b = repair_failed_nodes(sb, [node], options=RepairOptions(pipeline=False))
    assert rep.sim_seconds == pytest.approx(rep_b.sim_seconds)


@multidevice
def test_ragged_window_degrades_to_single_shard_gather(tmp_path):
    """Pattern groups the span does not divide fall back to the one-buffer
    gather (shard 0) and stay bit-identical."""
    sa = _build(tmp_path / "a", stripes=50, batch_stripes=5)
    sb = _build(tmp_path / "b", stripes=50, batch_stripes=5)
    node = sa.stripes[0].node_of_block[0]
    with with_rules(_mesh()):
        rep = repair_failed_nodes(sa, [node], options=RepairOptions(pipeline=True))
    assert rep.devices == 1              # every 5-stripe window degraded
    assert set(rep.gather_bytes_per_shard) == {0}
    rep_b = repair_failed_nodes(sb, [node], options=RepairOptions(pipeline=False))
    assert _all_blocks(sa) == _all_blocks(sb)
    assert rep.blocks_read == rep_b.blocks_read
