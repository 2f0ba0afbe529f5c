"""Async pipelined repair: bit-identity with the synchronous path, overlap
telemetry, mid-pipeline failure injection, and the benchmark/CI plumbing
that gates it.

The 1-device cases always run; the sharded-pipeline case runs in the
forced-8-device CI leg (``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
import weakref
from pathlib import Path

import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.ftx import (FailureInjector, RepairOptions, RepairPipeline,
                       StoreConfig, StripeStore, repair_failed_nodes)
from repro.launch.mesh import make_mesh

REPO = Path(__file__).resolve().parent.parent

multidevice = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _build(root, *, stripes=40, block_size=512, batch_stripes=8, window=4,
           threads=4, **kw):
    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2,
                      block_size=block_size, batch_stripes=batch_stripes,
                      pipeline_window=window, prefetch_threads=threads, **kw)
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


# ------------------------------------------------------------ bit-identity
def test_pipelined_bit_identical_single_node(tmp_path):
    sa = _build(tmp_path / "a")
    sb = _build(tmp_path / "b")
    node = sa.stripes[0].node_of_block[0]
    rep = repair_failed_nodes(sa, [node], options=RepairOptions(pipeline=True))
    rep_b = repair_failed_nodes(sb, [node], options=RepairOptions(pipeline=False))
    assert rep.pipelined and not rep_b.pipelined
    assert rep.windows > 1 and rep_b.windows == 0
    assert rep.stripes_repaired == rep_b.stripes_repaired > 0
    # same disk traffic and identical simulated (bandwidth-model) time: the
    # pipeline changes wall-clock only
    assert rep.blocks_read == rep_b.blocks_read
    assert rep.sim_seconds == pytest.approx(rep_b.sim_seconds)
    assert rep.repairs_local == rep_b.repairs_local
    assert _all_blocks(sa) == _all_blocks(sb)


def test_pipelined_bit_identical_multi_node(tmp_path):
    sa = _build(tmp_path / "a")
    sb = _build(tmp_path / "b")
    n0 = sa.stripes[0].node_of_block[0]
    n1 = sa.stripes[0].node_of_block[sa.scheme.k]   # a local parity's node
    rep = repair_failed_nodes(sa, [n0, n1], options=RepairOptions(pipeline=True))
    rep_b = repair_failed_nodes(sb, [n0, n1], options=RepairOptions(pipeline=False))
    assert rep.stripes_repaired == rep_b.stripes_repaired > 0
    assert rep.blocks_read == rep_b.blocks_read
    assert _all_blocks(sa) == _all_blocks(sb)


def test_pipeline_ragged_windows_and_window_override(tmp_path):
    """A window size that doesn't divide the pattern groups leaves ragged
    tail windows; bytes must not care."""
    sa = _build(tmp_path / "a", stripes=30, window=3)
    sb = _build(tmp_path / "b", stripes=30)
    node = sa.stripes[0].node_of_block[2]
    sa.fail_node(node)
    tele = sa.repair_all(options=RepairOptions(window=3))
    sa.revive_node(node)
    assert tele["pipelined"] and tele["windows"] >= len(sa.stripes) // 3 - 1
    sb.fail_node(node)
    sb.repair_all(options=RepairOptions(pipeline=False))
    sb.revive_node(node)
    assert _all_blocks(sa) == _all_blocks(sb)


# ------------------------------------------------------------- telemetry
def test_pipeline_span_telemetry_observable(tmp_path):
    store = _build(tmp_path / "s", io_stall_scale=0.02)
    node = store.stripes[0].node_of_block[0]
    rep = repair_failed_nodes(store, [node], options=RepairOptions(pipeline=True))
    assert rep.pipelined
    assert rep.read_seconds > 0
    assert rep.compute_seconds > 0
    assert rep.write_seconds >= 0
    assert rep.overlap_seconds >= 0
    assert 0.0 <= rep.overlap_ratio <= 1.0
    # sync path accounts the same spans, serially (overlap telemetry ~0)
    rep_b = repair_failed_nodes(store, [node], options=RepairOptions(pipeline=False))
    assert rep_b.read_seconds > 0 and rep_b.compute_seconds > 0
    assert rep_b.windows == 0 and rep_b.replans == 0


def test_sync_fallback_config_knob(tmp_path):
    """pipeline_window=0 in the config disables pipelining by default;
    an explicit pipeline=True still opts in."""
    store = _build(tmp_path / "s", window=0)
    node = store.stripes[0].node_of_block[0]
    store.fail_node(node)
    tele = store.repair_all()
    assert not tele["pipelined"]
    tele = store.repair_all(options=RepairOptions(pipeline=True))
    assert tele["pipelined"]
    store.revive_node(node)


def test_pipelined_unrecoverable_raises_ioerror(tmp_path):
    store = _build(tmp_path / "s", stripes=10)
    for b in range(5):                      # beyond p+r: never decodable
        store.fail_node(store.stripes[0].node_of_block[b])
    with pytest.raises(IOError):
        store.repair_all(options=RepairOptions(pipeline=True))


def test_partial_repair_before_unrecoverable_pattern(tmp_path):
    """Mixed failures: pattern groups sorted before the first unrecoverable
    one still repair (on both paths, identically) before the IOError."""
    def build(root):
        cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=512,
                          batch_stripes=8, pipeline_window=4)
        store = StripeStore(root, cfg, num_nodes=20)
        payload = np.random.default_rng(3).integers(
            0, 256, 8 * cfg.k * cfg.block_size, dtype=np.uint8)
        store.put("blob", payload.tobytes())
        store.seal()
        return store

    sa, sb = build(tmp_path / "a"), build(tmp_path / "b")
    # Nodes 9-13 hold 5 blocks of stripe 1 (unrecoverable, n-k=4), but only
    # one block of stripe 0 — whose group sorts first and must repair.
    for store, pipe in ((sa, True), (sb, False)):
        assert len(store._down_blocks(1) | {0}) <= 1  # sanity: all up
        for node in range(9, 14):
            store.fail_node(node)
        assert len(store._down_blocks(1)) == 5
        assert len(store._down_blocks(0)) == 1
        with pytest.raises(IOError):
            store.repair_all(options=RepairOptions(pipeline=pipe))
        repaired = store.telemetry.repairs_local + store.telemetry.repairs_global
        assert repaired == 1, "the feasible group sorted first must repair"
    assert _all_blocks(sa) == _all_blocks(sb)


def test_failure_injector_pipeline_knob(tmp_path):
    store = _build(tmp_path / "s", stripes=10)
    inj = FailureInjector(store, mttf_hours=2.0, seed=1, pipeline=True)
    events = inj.run(hours=1.0)
    assert events                            # rate makes >=1 overwhelmingly likely
    blob = store.get("blob")
    assert blob.size == 10 * store.cfg.k * store.cfg.block_size


# ------------------------------------------------- mid-pipeline failures
@settings(max_examples=6, deadline=None)
@given(st.integers(0, 9), st.sampled_from(["prefetch", "launch"]),
       st.integers(1, 9), st.integers(1, 4))
def test_node_failure_between_prefetch_and_launch_bit_identical(
        fail_at, stage, offset, window):
    """A node dying after a window's prefetch was submitted (or right
    before its launch) must re-plan or fall back cleanly, and every block
    the repair touched must still be bit-identical to the pre-failure
    truth — which is exactly what the synchronous path would produce, since
    both decode the same exact GF system."""
    with tempfile.TemporaryDirectory() as tmp:
        store = _build(Path(tmp) / "s", stripes=20, window=window)
        truth = _all_blocks(store)
        node = store.stripes[0].node_of_block[0]
        second = (node + offset) % store.num_nodes
        if second == node:
            second = (node + 1) % store.num_nodes
        store.fail_node(node)
        fired = []

        def hook(hook_stage, index):
            if hook_stage == stage and index == fail_at and not fired:
                fired.append(index)
                store.fail_node(second)

        tele = store.repair_all(options=RepairOptions(pipeline=True, pipeline_hook=hook))
        assert tele["pipelined"]
        store.revive_node(node)
        store.revive_node(second)
        assert _all_blocks(store) == truth
        # The re-plan gave its slots back: the same repair again, its disk
        # really lost, reads every window into a kept buffer as it is.
        again = _repair(store, node)
        assert again["gather_buffer_allocs"] == 0
        assert again["gather_buffer_reuses"] == again["windows"] > 0
        assert _all_blocks(store) == truth


# ------------------------------------------------- kept gather buffers
def _lose(store, node):
    """Fail ``node`` and delete its block files (the lost disk)."""
    store.fail_node(node)
    for sid, st in store.stripes.items():
        for b, n in enumerate(st.node_of_block):
            if n == node:
                store._block_path(sid, b).unlink()


def _repair(store, node, **opts):
    _lose(store, node)
    tele = store.repair_all(options=RepairOptions(pipeline=True, **opts))
    store.revive_node(node)
    return tele


def _twin_arc_store(root):
    """24 stripes on 14 nodes: the stride-7 arcs put the even stripes on
    nodes 0-9 (block b on node b) and the odd ones on 7-13, 0-2. Nodes 3, 4
    and 5 hold one data block of each even stripe only, so losing any of
    them is one pattern of 12 stripes and three (4, 3, B) windows; node 1
    holds data block 1 of the even stripes and G1 (block 8, six reads) of
    the odd ones."""
    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=512,
                      batch_stripes=8, pipeline_window=4, prefetch_threads=4)
    store = StripeStore(root, cfg, num_nodes=14)
    payload = np.random.default_rng(5).integers(
        0, 256, 24 * cfg.k * cfg.block_size, dtype=np.uint8)
    store.put("blob", payload.tobytes())
    store.seal()
    return store


def test_repairs_reuse_two_kept_gather_buffers(tmp_path):
    """Rotating single-node repairs read every window into the store's two
    kept buffers: both are made by the first repair and reused after, a
    wider window (a G1 loss) grows each once, and every rebuilt block is
    the encoded reference."""
    store = _twin_arc_store(tmp_path / "s")
    truth = _all_blocks(store)
    k, bsz = store.cfg.k, store.cfg.block_size
    data = np.frombuffer(b"".join(truth[(2, b)] for b in range(k)),
                         np.uint8).reshape(k, bsz)
    assert np.array_equal(store.scheme.encode(data), np.stack(
        [np.frombuffer(truth[(2, b)], np.uint8) for b in range(store.n)]))
    tele = store.telemetry
    for i, node in enumerate((3, 4, 5, 3)):
        rep = _repair(store, node)
        assert rep["windows"] == 3 and rep["patterns"] == 1
        assert _all_blocks(store) == truth
        assert tele.gather_buffer_allocs == 2
        assert tele.gather_buffer_reuses == 3 * (i + 1) - 2
        assert (rep["gather_buffer_allocs"], rep["gather_buffer_reuses"]) \
            == ((2, 1) if i == 0 else (0, 3))
    rep = _repair(store, 1)                  # (4, 6, B) windows come first
    assert rep["windows"] == 6 and rep["patterns"] == 2
    assert (rep["gather_buffer_allocs"], rep["gather_buffer_reuses"]) == (2, 4)
    assert [s.nbytes for s in store._gather_slots] == [4 * 6 * bsz] * 2
    assert _all_blocks(store) == truth
    for node in (1, 4):
        rep = _repair(store, node)
        assert rep["gather_buffer_allocs"] == 0
        assert _all_blocks(store) == truth
    assert tele.gather_buffer_allocs == 4


def test_stale_slot_bytes_never_reach_a_repair(tmp_path):
    """Slots filled with a sentinel between repairs change no rebuilt byte;
    a short surviving block file fails the repair with a ValueError naming
    it (as the parent's failed broadcast did), and the slots come back."""
    store = _twin_arc_store(tmp_path / "s")
    truth = _all_blocks(store)
    for node in (3, 1, 5, 1):
        for slot in store._gather_slots:
            slot.fill(0xA5)
        _repair(store, node)
        assert _all_blocks(store) == truth
    path = store._block_path(4, 4)           # read to rebuild block 3
    path.write_bytes(truth[(4, 4)][:300])
    store.fail_node(3)
    with pytest.raises(ValueError, match="s4_b4 holds 300 bytes"):
        store.repair_all(options=RepairOptions(pipeline=True))
    store.revive_node(3)
    assert sorted(store._free_slots) == [0, 1]


# ------------------------------------------------------- deferred launch
def test_back_to_back_deferred_repairs_rebuild_every_byte(tmp_path,
                                                          monkeypatch):
    """Each window is put on the device by the coordinator and launched on
    the launch thread, while its slot goes back once the input is on the
    device: repairs run back to back over three or more windows each, every
    slot overwritten the moment it comes back (as if the next window's
    reads landed at once), rebuild every block as it was, and every
    pipelined launch is deferred. The slots are page-aligned, which the CPU
    backend wraps in place rather than copy: handed to the launch as numpy,
    or put without a copy, a slot is read by the launch after the
    overwrite."""
    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=1 << 16,
                      batch_stripes=8, pipeline_window=4, prefetch_threads=4)
    store = StripeStore(tmp_path / "s", cfg, num_nodes=14)
    store.put("blob", np.random.default_rng(7).integers(
        0, 256, 24 * cfg.k * cfg.block_size, dtype=np.uint8).tobytes())
    store.seal()
    truth = _all_blocks(store)
    widest = 4 * 6 * cfg.block_size          # a (4, 6, B) window of a G1 loss
    for i in range(2):
        raw = np.empty(widest + 4096, np.uint8)
        lo = -raw.ctypes.data % 4096
        store._gather_slots[i] = raw[lo:lo + widest]
    give = store._give_gather_slot

    def give_and_overwrite(slot):
        store._gather_slots[slot].fill(0x5A)
        give(slot)

    monkeypatch.setattr(store, "_give_gather_slot", give_and_overwrite)
    for node in (3, 4, 5, 1, 3, 1, 4):
        rep = _repair(store, node)
        assert rep["gather_buffer_allocs"] == 0
        assert rep["windows"] >= 3 and rep["replans"] == 0
        assert rep["deferred_launches"] == rep["launches"] == rep["windows"]
        assert _all_blocks(store) == truth
    assert sorted(store._free_slots) == [0, 1]


def test_failed_launch_surfaces_and_gives_both_slots_back(tmp_path,
                                                          monkeypatch):
    """A launch that fails on the launch thread surfaces from
    ``repair_all``, as the writer's errors do, after the other windows ran,
    and both kept slots come back; the next repair is whole."""
    store = _twin_arc_store(tmp_path / "s")
    truth = _all_blocks(store)
    execute = store.engine.execute
    calls = []

    def failing(plan, stacked, mesh_rules=None):
        calls.append(threading.current_thread().name)
        if len(calls) == 2:
            raise RuntimeError("device lost")
        return execute(plan, stacked, mesh_rules)

    monkeypatch.setattr(store.engine, "execute", failing)
    _lose(store, 3)
    with pytest.raises(RuntimeError, match="device lost"):
        store.repair_all(options=RepairOptions(pipeline=True))
    assert len(calls) == 3                   # every window was launched
    assert all(name.startswith("repair-launch") for name in calls)
    assert sorted(store._free_slots) == [0, 1]
    monkeypatch.setattr(store.engine, "execute", execute)
    rep = store.repair_all(options=RepairOptions(pipeline=True))
    store.revive_node(3)
    assert rep["gather_buffer_allocs"] == 0
    assert _all_blocks(store) == truth


def test_deferred_launch_report(tmp_path, monkeypatch):
    """Pipelined launches are deferred and the coordinator's put of their
    stacks is within their host time; synchronous and re-planned launches
    are not deferred and put no stack before their launch."""
    store = _build(tmp_path / "s", window=0)
    truth = _all_blocks(store)
    node = store.stripes[0].node_of_block[0]
    store.fail_node(node)
    sync = store.repair_all()
    assert not sync["pipelined"] and sync["launches"] > 0
    assert sync["deferred_launches"] == 0
    assert sync["stack_put_seconds"] == 0 < sync["compute_seconds"]
    rep = store.repair_all(options=RepairOptions(pipeline=True))
    assert rep["pipelined"] and rep["launches"] == rep["windows"] > 1
    assert rep["deferred_launches"] == rep["launches"]
    assert 0 < rep["stack_put_seconds"] <= rep["compute_seconds"]
    # One read of the first window fails: that window re-plans (the same
    # down set, one sub-window launched and collected on the coordinator).
    read_into = store._read_block_into
    failed = []

    def flaky(sid, b, out, **kw):
        if not failed:
            failed.append((sid, b))
            raise IOError("read lost")
        return read_into(sid, b, out, **kw)

    monkeypatch.setattr(store, "_read_block_into", flaky)
    rep = store.repair_all(options=RepairOptions(pipeline=True))
    assert failed and rep["replans"] == 1
    assert rep["launches"] == rep["windows"]
    assert rep["deferred_launches"] == rep["launches"] - 1
    assert 0 < rep["stack_put_seconds"] <= rep["compute_seconds"]
    store.revive_node(node)
    assert _all_blocks(store) == truth


def test_rebuilt_windows_are_freed_once_written_back(tmp_path,
                                                     monkeypatch):
    """The pipeline keeps only the launches it still waits on: when window
    *i* is about to be handed off, every window before *i-2* that was
    written back has had its rebuilt output freed, so a repair holds a few
    windows' outputs in host memory however many windows it has."""
    store = _build(tmp_path / "s", window=2)
    outs = []
    execute = store.engine.execute

    def recorded(plan, stacked, mesh_rules=None):
        out = execute(plan, stacked, mesh_rules)
        outs.append(weakref.ref(out))
        return out

    monkeypatch.setattr(store.engine, "execute", recorded)
    written = []
    writeback = RepairPipeline._writeback

    def counted(self, win, rebuilt, res):
        writeback(self, win, rebuilt, res)
        written.append(win.index)

    monkeypatch.setattr(RepairPipeline, "_writeback", counted)
    checked = []
    kept = []

    def hook(stage, index):
        if stage != "launch":
            return
        done = [k for k in list(written) if k < index - 2]
        deadline = time.monotonic() + 2.0
        alive = [k for k in done if outs[k]() is not None]
        while alive and time.monotonic() < deadline:
            time.sleep(1e-3)
            alive = [k for k in done if outs[k]() is not None]
        checked.extend(done)
        kept.extend(alive)

    rep = _repair(store, 0, pipeline_hook=hook)
    assert rep["launches"] == rep["windows"] == len(outs) >= 10
    assert rep["replans"] == 0
    assert len(set(checked)) >= rep["windows"] // 2
    assert kept == []


def test_launch_accounting_under_thread_switch_stress(tmp_path):
    """The coordinator, the launch thread and the writer bump one result;
    with the interpreter switching threads as often as it can and more
    readers than cores, no count is lost and every byte is rebuilt."""
    cores = len(os.sched_getaffinity(0))
    store = _build(tmp_path / "s", window=2, threads=min(2 * cores, 64))
    truth = _all_blocks(store)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for node in (0, 5, 9):
            rep = _repair(store, node)
            assert rep["launches"] == rep["windows"] >= 10
            assert rep["deferred_launches"] == rep["launches"]
            assert rep["device_launches"] == rep["launches"]
            assert _all_blocks(store) == truth
    finally:
        sys.setswitchinterval(interval)


def test_read_block_into_charges_as_read_block(tmp_path):
    """The in-place read and the array read of one block give the same bytes
    and charge the same counters, local and remote, per gather shard."""
    from repro.dist.placement import PlacementMap

    store = _build(tmp_path / "s", stripes=4)
    pm = PlacementMap.from_store(store, num_shards=2, remote_multiplier=3.0)
    keys = ("blocks_read", "bytes_read", "sim_seconds", "local_reads",
            "remote_reads", "gather_bytes_per_shard")
    for sid, b in ((0, 0), (1, 7), (3, 9)):
        for shard in (0, 1, None):
            charged = []
            out = np.zeros(store.cfg.block_size, np.uint8)
            for read in (
                    lambda: store._read_block(sid, b, shard=shard,
                                              placement=pm),
                    lambda: store._read_block_into(sid, b, out, shard=shard,
                                                   placement=pm) or out):
                before = store.telemetry.reset()
                data = read()
                charged.append({k: getattr(store.telemetry, k)
                                for k in keys})
                store.telemetry = before
            assert charged[0] == charged[1]
            assert np.array_equal(data, out) and charged[0]["blocks_read"]
    node = store.stripes[0].node_of_block[1]
    store._block_path(0, 1).unlink()
    with pytest.raises(IOError):
        store._read_block_into(0, 1, out)
    store.fail_node(node)
    with pytest.raises(IOError, match="down"):
        store._read_block_into(0, 1, out)


# ------------------------------------------------------------- sharding
def test_window_alignment_helpers():
    from repro.dist.stripes import align_stripe_window, stripe_axis_span

    assert stripe_axis_span(None) == 1
    assert align_stripe_window(13, None) == 13
    mesh = make_mesh((1, 1), ("data", "model"))
    from repro.dist.sharding import with_rules
    with with_rules(mesh) as mr:
        assert stripe_axis_span(mr) == 1
        assert align_stripe_window(13, mr) == 13


@multidevice
def test_window_alignment_rounds_to_device_span():
    from repro.dist.sharding import with_rules
    from repro.dist.stripes import align_stripe_window, stripe_axis_span

    with with_rules(make_mesh((8, 1), ("data", "model"))) as mr:
        assert stripe_axis_span(mr) == 8
        assert align_stripe_window(20, mr) == 16     # keeps 8-way launches
        assert align_stripe_window(8, mr) == 8
        assert align_stripe_window(5, mr) == 5       # sub-span: degrades


@multidevice
def test_pipelined_sharded_repair_bit_identical(tmp_path):
    """The pipeline's launches shard over the mesh (devices=8) and stay
    bit-identical to the unsharded synchronous path."""
    from repro.dist.sharding import with_rules

    sa = _build(tmp_path / "a", stripes=80, window=8)
    sb = _build(tmp_path / "b", stripes=80)
    node = sa.stripes[0].node_of_block[0]
    with with_rules(make_mesh((8, 1), ("data", "model"))):
        rep = repair_failed_nodes(sa, [node], options=RepairOptions(pipeline=True))
    assert rep.pipelined
    assert rep.devices == 8
    # round-robin placement makes every pattern group 8 stripes -> every
    # window is one full-span launch
    assert rep.device_launches == 8 * rep.launches
    rep_b = repair_failed_nodes(sb, [node], options=RepairOptions(pipeline=False))
    assert rep_b.devices == 1
    assert _all_blocks(sa) == _all_blocks(sb)
    # Each window's per-shard buffers were carved from a kept slot; a second
    # repair, slots full of stale bytes, carves them again and allocates
    # nothing.
    t = sa.telemetry
    assert t.gather_buffer_allocs + t.gather_buffer_reuses == rep.windows
    assert t.gather_buffer_reuses > 0
    for slot in sa._gather_slots:
        slot.fill(0xA5)
    with with_rules(make_mesh((8, 1), ("data", "model"))):
        again = _repair(sa, node)
    assert again["devices"] == 8
    assert again["gather_buffer_allocs"] == 0
    assert again["gather_buffer_reuses"] == again["windows"] == rep.windows
    assert _all_blocks(sa) == _all_blocks(sb)


# ------------------------------------------------------- CI plumbing
def _run_bench_cli(*args):
    env = dict(os.environ, PYTHONPATH="src")
    return subprocess.run([sys.executable, "-m", "benchmarks.run", *args],
                          cwd=REPO, env=env, capture_output=True, text=True)


def test_run_list_prints_sections_and_exits_zero():
    proc = _run_bench_cli("--list")
    assert proc.returncode == 0
    from benchmarks.run import SECTIONS

    assert proc.stdout.split() == list(SECTIONS)


def test_run_only_typo_exits_nonzero():
    proc = _run_bench_cli("--only", "definitely_not_a_benchmark")
    assert proc.returncode != 0
    assert "unknown benchmark section" in proc.stderr


def test_run_only_typo_in_list_exits_nonzero():
    proc = _run_bench_cli("--only", "repair_costs,bogus_name")
    assert proc.returncode != 0
    assert "bogus_name" in proc.stderr


def test_check_regression_gate(tmp_path):
    from benchmarks.check_regression import main

    results = tmp_path / "results"
    results.mkdir()
    baseline = tmp_path / "baseline.json"

    def write(speedup, us):
        (results / "batched_repair.json").write_text(json.dumps({
            "min_single_speedup_at_S32": speedup,
            "rows": [{"single_batched_us_per_stripe": us,
                      "multi_speedup": speedup}],
        }))
        (results / "pipelined_repair.json").write_text(json.dumps({
            "min_speedup_at_acceptance": speedup,
            "rows": [{"stripes_per_sec_pipe": 1e6 / us}],
        }))

    write(8.0, 100.0)
    common = ["--results", str(results), "--baseline", str(baseline),
              "--sections", "batched_repair,pipelined_repair"]
    assert main(["--update-baseline", *common]) == 0
    assert main(common) == 0                       # identical results pass
    write(8.0 * 0.8, 100.0 / 0.8)                  # -20%: inside tolerance
    assert main(common) == 0
    write(8.0 * 0.5, 100.0 / 0.5)                  # -50%: regression
    assert main(common) == 1
    write(8.0, 100.0)
    assert main(["--tolerance", "0.6", *common]) == 0   # looser gate passes
    # reseeding one section must merge, not drop the others' floors
    assert main(["--update-baseline", "--results", str(results),
                 "--baseline", str(baseline),
                 "--sections", "batched_repair"]) == 0
    kept = json.loads(baseline.read_text())["sections"]
    assert "pipelined_repair" in kept and "batched_repair" in kept
    (results / "pipelined_repair.json").unlink()        # missing section
    assert main(common) == 1
