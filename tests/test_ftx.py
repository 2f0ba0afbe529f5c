"""Fault-tolerance layer: stripe store, EC checkpoints, failures, elastic."""
import json

import numpy as np
import pytest

from repro.ftx import CheckpointManager, StripeStore, StoreConfig
from repro.ftx.checkpoint import CheckpointConfig
from repro.ftx.failures import FailureInjector, restripe


@pytest.fixture
def store(tmp_path):
    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=2048)
    return StripeStore(tmp_path / "s", cfg)


def fill(store, rng, n=6):
    objs = {}
    for i in range(n):
        data = rng.integers(0, 256, int(rng.integers(64, 6000)), dtype=np.uint8)
        store.put(f"o{i}", data.tobytes())
        objs[f"o{i}"] = data
    store.seal()
    store.save_manifest()
    return objs


def test_put_get_roundtrip(store, rng):
    objs = fill(store, rng)
    for k, v in objs.items():
        assert (store.get(k) == v).all()


@pytest.mark.parametrize("block_size", [2048, 1024])
def test_degraded_read_single(tmp_path, rng, block_size):
    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=block_size)
    store = StripeStore(tmp_path / "s", cfg)
    objs = fill(store, rng)
    store.fail_node(store.stripes[0].node_of_block[0])
    for k, v in objs.items():
        assert (store.get(k) == v).all()
    assert store.telemetry.blocks_read > 0


def test_degraded_get_reads_only_the_byte_range(tmp_path, rng):
    """§V-C: a degraded ``get`` of an object inside a lost data block reads
    only the object's byte range ``[lo, hi)`` of each source of the serving
    plan, and rebuilds it bit-exactly."""
    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=1024)
    store = StripeStore(tmp_path / "s", cfg)
    store.put("head", bytes(100))
    payload = rng.integers(0, 256, 300, dtype=np.uint8)
    store.put("o", payload.tobytes())
    store.seal()
    meta = store.objects["o"]
    lo, hi = meta.offset, meta.offset + payload.size
    assert hi <= cfg.block_size and lo > 0
    store.fail_node(store.stripes[meta.sid].node_of_block[meta.block])
    down = store._down_blocks(meta.sid)
    plan = store.engine.planner.serving_plan(meta.block, down)
    before = store.telemetry.bytes_read
    assert (store.get("o") == payload).all()
    assert store.telemetry.bytes_read - before == len(plan.reads) * (hi - lo)


def test_two_node_repair_local_for_cp(store, rng):
    objs = fill(store, rng)
    st0 = store.stripes[0]
    store.fail_node(st0.node_of_block[0])
    store.fail_node(st0.node_of_block[store.scheme.k])  # a local parity
    tele = store.repair_all()
    assert tele["repairs_global"] == 0  # D+L is the paper's cascading case
    for n in list(store.nodes):
        store.revive_node(n)
    for k, v in objs.items():
        assert (store.get(k) == v).all()


def test_repair_bandwidth_cp_beats_azure(tmp_path, rng):
    """CP-Azure repairs a parity-node loss with fewer block reads."""
    reads = {}
    for scheme in ("azure", "cp-azure"):
        cfg = StoreConfig(scheme=scheme, k=8, r=2, p=2, block_size=1024)
        s = StripeStore(tmp_path / scheme, cfg)
        rng2 = np.random.default_rng(0)
        fill(s, rng2, n=4)
        # fail the node holding G_r of stripe 0
        gr = s.scheme.n - 1
        s.fail_node(s.stripes[0].node_of_block[gr])
        tele = s.repair_all()
        reads[scheme] = tele["blocks_read"]
    assert reads["cp-azure"] < reads["azure"]


def test_checkpoint_roundtrip_with_failures(tmp_path):
    cm = CheckpointManager(tmp_path / "ckpt", CheckpointConfig(
        store=StoreConfig(scheme="cp-uniform", k=6, r=2, p=2,
                          block_size=4096)))
    state = {"w": np.arange(3000, dtype=np.float32).reshape(60, 50),
             "m": np.full(123, 7, np.float64), "step": np.int64(42)}
    cm.save(10, state)
    cm.fail_hosts(10, [1, 2])
    restored, tele = cm.restore(10, state)
    import jax
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert tele["blocks_read"] > 0


def test_manifest_with_a_retired_config_field_loads_and_restores(tmp_path, rng):
    """Manifests written before the store's ``hedge`` knob was retired hold
    ``"hedge": 0`` in their ``cfg``; both a store and an EC checkpoint saved
    with one still load, and read back every byte with a node down."""
    store = StripeStore(tmp_path / "s", StoreConfig(
        scheme="cp-azure", k=6, r=2, p=2, block_size=1024))
    objs = fill(store, rng)
    path = tmp_path / "s" / "manifest.json"
    doc = json.loads(path.read_text())
    doc["cfg"]["hedge"] = 0
    path.write_text(json.dumps(doc))
    loaded = StripeStore.load(tmp_path / "s")
    assert loaded.cfg == store.cfg
    loaded.fail_node(loaded.stripes[0].node_of_block[0])
    for k, v in objs.items():
        assert (loaded.get(k) == v).all()

    ckpt = CheckpointConfig(store=StoreConfig(scheme="cp-uniform", k=6, r=2,
                                              p=2, block_size=4096))
    state = {"w": np.arange(3000, dtype=np.float32).reshape(60, 50),
             "step": np.int64(7)}
    CheckpointManager(tmp_path / "ckpt", ckpt).save(3, state)
    path = tmp_path / "ckpt" / "step3" / "manifest.json"
    doc = json.loads(path.read_text())
    doc["cfg"]["hedge"] = 0
    path.write_text(json.dumps(doc))
    cm = CheckpointManager(tmp_path / "ckpt", ckpt)
    cm.fail_hosts(3, [1])
    restored, tele = cm.restore(3, state)
    for name in state:
        assert (np.asarray(restored[name]) == np.asarray(state[name])).all()
    assert tele["blocks_read"] > 0


def test_checkpoint_retention(tmp_path):
    cm = CheckpointManager(tmp_path / "c", CheckpointConfig(
        store=StoreConfig(k=4, r=1, p=2, block_size=512), keep=2))
    state = {"x": np.zeros(100, np.float32)}
    for s in (1, 2, 3, 4):
        cm.save(s, state)
    assert cm.available() == [3, 4]


def test_failure_injector(store, rng):
    fill(store, rng)
    inj = FailureInjector(store, mttf_hours=10.0, seed=1)
    events = inj.run(hours=30.0)
    assert len(events) > 0
    # unified schema: every failure is paired with its repair-done record
    assert len(inj.failures()) == len(inj.repairs()) > 0
    assert all(r.blocks_read >= 0 for r in inj.repairs())
    assert all(r.t >= r.started_at for r in inj.repairs())


def test_restripe_elastic(tmp_path, rng):
    cfg = StoreConfig(scheme="cp-azure", k=4, r=2, p=2, block_size=1024)
    s = StripeStore(tmp_path / "a", cfg)
    objs = fill(s, rng, n=4)
    new_cfg = StoreConfig(scheme="cp-uniform", k=8, r=2, p=2, block_size=1024)
    s2, tele = restripe(s, new_cfg, tmp_path / "b")
    assert tele["bytes_moved"] > 0
    for k, v in objs.items():
        assert (s2.get(k) == v).all()

