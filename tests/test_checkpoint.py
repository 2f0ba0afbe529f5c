"""Asynchronous sharded checkpointing (DESIGN.md §13).

Save: snapshot isolation, async/sync and pipelined/serial bit-identity,
crash-mid-save atomicity (previous checkpoint always restorable, orphaned
staging dirs swept), retention under interleaved async saves. Restore:
parallel gather byte-parity with the serial path after host loss, across
schemes × kernel backends; the sharded-mesh case runs in the forced-
8-device CI leg (``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
import hashlib
import json

import jax
import numpy as np
import pytest

from repro.ftx import (CheckpointConfig, CheckpointManager, StoreConfig,
                       StripeStore)
from repro.ftx.pipeline import EncodePipeline
from repro.launch.mesh import make_mesh

multidevice = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _state(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": rng.standard_normal((96, 64)).astype(np.float32),
            "opt": {"m": rng.standard_normal(257).astype(np.float64),
                    "v": rng.integers(0, 255, 1000, np.uint8)},
            "step": np.int64(41)}


def _cfg(scheme="cp-azure", backend=None, **kw):
    over = {} if backend is None else {"backend": backend}
    return CheckpointConfig(
        store=StoreConfig(scheme=scheme, k=6, r=2, p=2, block_size=2048,
                          **over),
        encode_window=2, **kw)


def _assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.asarray(x).dtype == np.asarray(y).dtype
        assert np.array_equal(np.asarray(x), np.asarray(y))


def _disk_blocks(step_dir):
    return {p.relative_to(step_dir).as_posix():
            hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(step_dir.rglob("*.blk"))}


# ------------------------------------------------------------ save identity

def test_async_sync_and_serial_saves_bit_identical(tmp_path):
    state = _state()
    roots = {}
    for name, submit in (
            ("sync", lambda cm: cm.save(5, state)),
            ("async", lambda cm: cm.save_async(5, state).result()),
            ("serial", lambda cm: cm.save_async(5, state,
                                                pipelined=False).result())):
        cm = CheckpointManager(tmp_path / name, _cfg())
        info = submit(cm)
        assert info["step"] == 5 and info["stripes"] > 0
        roots[name] = tmp_path / name / "step5"
    ref = _disk_blocks(roots["sync"])
    assert ref and _disk_blocks(roots["async"]) == ref
    assert _disk_blocks(roots["serial"]) == ref
    ref_manifest = json.loads((roots["sync"] / "manifest.json").read_text())
    for name in ("async", "serial"):
        m = json.loads((roots[name] / "manifest.json").read_text())
        assert m["objects"] == ref_manifest["objects"]
        assert m["stripes"] == ref_manifest["stripes"]


def test_streamed_object_bit_identical_to_put(tmp_path):
    """The streaming put path registers exactly what put+seal would have."""
    payload = np.random.default_rng(5).integers(
        0, 256, 6 * 2048 * 3 + 777, dtype=np.uint8)
    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=2048)
    packed = StripeStore(tmp_path / "packed", cfg)
    packed.put("state", payload.tobytes())
    packed.seal()
    streamed = StripeStore(tmp_path / "streamed", cfg)
    stream = streamed.stream_writer("state", len(payload))
    EncodePipeline(streamed, window=2).run(stream, payload)
    stream.close()
    assert _disk_blocks(tmp_path / "streamed") == \
        _disk_blocks(tmp_path / "packed")
    assert streamed.objects.keys() == packed.objects.keys()
    for k in packed.objects:
        assert streamed.objects[k] == packed.objects[k]
    assert np.array_equal(streamed.get("state"), payload)


def test_stream_writer_contract(tmp_path):
    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=1024)
    store = StripeStore(tmp_path / "s", cfg)
    stream = store.stream_writer("obj", 3 * 6 * 1024)
    assert stream.num_stripes == 3
    with pytest.raises(ValueError):           # not (S, n, B)
        stream.write_window(0, np.zeros((1, store.n, 512), np.uint8))
    with pytest.raises(ValueError):           # out of range
        stream.write_window(3, np.zeros((1, store.n, 1024), np.uint8))
    with pytest.raises(RuntimeError):         # unwritten stripes
        stream.close()
    allocated = set(store.stripes)
    stream.abort()
    assert not (allocated & set(store.stripes))
    # an open (put-buffered) stripe blocks streaming
    store.put("x", b"abc")
    with pytest.raises(RuntimeError):
        store.stream_writer("y", 10)


def test_snapshot_isolation(tmp_path):
    state = _state()
    want = jax.tree.map(lambda x: np.copy(x), state)
    cm = CheckpointManager(tmp_path, _cfg())
    fut = cm.save_async(3, state)
    # Mutating the live state after save_async returns must not leak into
    # the checkpoint: the snapshot was taken before the call returned.
    state["w"][:] = -1.0
    state["opt"]["m"][:] = 0.0
    state["opt"]["v"][:] = 0
    fut.result()
    got, _ = cm.restore(3, state)
    _assert_tree_equal(got, want)


def test_snapshot_for_checkpoint_copies(tmp_path):
    from repro.train.train_step import snapshot_for_checkpoint

    state = _state()
    snap = snapshot_for_checkpoint(state)
    state["w"][:] = 0.0
    assert not np.array_equal(snap["w"], state["w"])
    cm = CheckpointManager(tmp_path, _cfg())
    cm.save(1, snap)
    got, _ = cm.restore(1, snap)
    _assert_tree_equal(got, snap)


# ------------------------------------------------- degraded restore parity

@pytest.mark.parametrize("scheme", ["cp-azure", "cp-uniform"])
@pytest.mark.parametrize("backend", ["gf", "crs"])
def test_restore_after_host_loss_parity(tmp_path, scheme, backend):
    state = _state(seed=3)
    cm = CheckpointManager(tmp_path, _cfg(scheme=scheme, backend=backend))
    cm.save(7, state)
    cm.fail_hosts(7, [1, 2])
    par, tele = cm.restore(7, state)
    ser, _ = cm.restore(7, state, parallel=False)
    _assert_tree_equal(par, ser)
    _assert_tree_equal(par, state)
    assert tele["parallel"] and tele["degraded_blocks"] > 0
    assert tele["restore_decode_launches"] > 0
    # live data sources come from the restore buffer: only the plans'
    # extra (parity) sources hit disk on top of the healthy gather
    assert tele["extra_source_reads"] < tele["blocks_read"]


def test_healthy_parallel_restore_reads_each_needed_block_once(tmp_path):
    state = _state(seed=4)
    cm = CheckpointManager(tmp_path, _cfg())
    info = cm.save(9, state)
    store = cm.store_for(9)
    before = store.telemetry.copy()
    got, tele = cm.restore(9, state)
    _assert_tree_equal(got, state)
    assert tele["degraded_blocks"] == 0
    assert store.telemetry.bytes_read - before.bytes_read == info["bytes"]
    k, B = cm.cfg.store.k, cm.cfg.store.block_size
    assert tele["blocks_read"] == -(-info["bytes"] // B) <= \
        info["stripes"] * k


@multidevice
def test_restore_after_host_loss_parity_sharded(tmp_path):
    from repro.dist.sharding import with_rules

    state = _state(seed=6)
    mesh = make_mesh((8, 1), ("data", "model"))
    cm = CheckpointManager(tmp_path, _cfg())
    with with_rules(mesh):
        info = cm.save(2, state)          # sharded encode launches
        assert info["encode"]["windows"] > 0
        cm.fail_hosts(2, [0, 3])
        par, tele = cm.restore(2, state)
        ser, _ = cm.restore(2, state, parallel=False)
    _assert_tree_equal(par, ser)
    _assert_tree_equal(par, state)
    assert tele["degraded_blocks"] > 0


# ------------------------------------------------------- crash consistency

def test_crash_mid_save_preserves_previous_checkpoint(tmp_path):
    state = _state()
    cm = CheckpointManager(tmp_path, _cfg())
    cm.save(1, state)

    def boom(stage, index):
        if stage == "drain" and index >= 1:
            raise RuntimeError("disk died mid-save")

    fut = cm.save_async(2, _state(seed=9), hook=boom)
    err = fut.exception()
    assert isinstance(err, RuntimeError)
    with pytest.raises(RuntimeError):
        fut.result()
    # the failed save left nothing: no step2, no staging dir
    assert cm.available() == [1]
    assert not (tmp_path / "step2.tmp").exists()
    assert not (tmp_path / "step2").exists()
    got, _ = cm.restore(1, state)
    _assert_tree_equal(got, state)
    # the manager recovers: the next save of the same step succeeds
    cm.save(2, state)
    assert cm.available() == [1, 2]


def test_init_sweeps_orphaned_save_debris(tmp_path):
    state = _state()
    cm = CheckpointManager(tmp_path, _cfg())
    cm.save(4, state)
    # simulate a hard crash: a staging dir and a meta-less step dir
    (tmp_path / "step9.tmp" / "node0").mkdir(parents=True)
    (tmp_path / "step9.tmp" / "node0" / "s0_b0.blk").write_bytes(b"junk")
    (tmp_path / "step7").mkdir()
    (tmp_path / "step7" / "manifest.json").write_text("{}")
    cm2 = CheckpointManager(tmp_path, _cfg())
    assert cm2.available() == [4]
    assert not (tmp_path / "step9.tmp").exists()
    assert not (tmp_path / "step7").exists()
    got, _ = cm2.restore(4, state)
    _assert_tree_equal(got, state)


def test_retention_under_interleaved_async_saves(tmp_path):
    state = _state()
    cm = CheckpointManager(tmp_path, _cfg(keep=2))
    futs = [cm.save_async(step, state) for step in (1, 2, 3, 4, 5)]
    infos = [f.result() for f in futs]
    assert [i["step"] for i in infos] == [1, 2, 3, 4, 5]
    assert cm.available() == [4, 5]
    assert sorted(p.name for p in tmp_path.glob("step*")) == \
        ["step4", "step5"]
    got, _ = cm.restore(5, state)
    _assert_tree_equal(got, state)


def test_available_ignores_junk_entries(tmp_path):
    cm = CheckpointManager(tmp_path, _cfg())
    cm.save(11, _state())
    (tmp_path / "stepXYZ").mkdir()          # junk that is not a checkpoint
    (tmp_path / "step12.tmp").mkdir()
    assert cm.available() == [11]


def test_encode_telemetry_shape(tmp_path):
    cm = CheckpointManager(tmp_path, _cfg())
    info = cm.save(1, _state())
    enc = info["encode"]
    assert enc["windows"] >= 2 and enc["launches"] == enc["windows"]
    assert 0.0 <= enc["overlap_fraction"] <= 1.0
    assert info["snapshot_seconds"] < info["encode_seconds"]
