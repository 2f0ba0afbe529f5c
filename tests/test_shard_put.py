"""The sharded launch's host-to-device copy: ``assemble_shards`` puts each
shard of a repair window on its device under the ``repro.launch.h2d`` span,
and the coordinator's time doing so is the report's ``put_seconds``.

The sharded cases run a P5 store (CP-Azure (24,2,2), 28 nodes in 4 failure
domains) cut to test size on four forced CPU devices, in a process of its
own, with each launch's stripe axis over a (4, 1) mesh, as the four-chip
benchmark cell runs it. The one-device case runs here.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.ftx import RepairOptions, StoreConfig, StripeStore

SRC = Path(__file__).resolve().parents[1] / "src"

SCRIPT = r"""
import glob, json, sys, tempfile
from pathlib import Path

import jax
import numpy as np
from jax.profiler import ProfileData

from repro.dist.sharding import with_rules
from repro.dist.topology import Topology
from repro.ftx import RepairOptions, StoreConfig, StripeStore
from repro.ftx.pipeline import RepairPipeline
from repro.launch.mesh import make_mesh

STRIPES, BS, NODE = 16, 512, 3
assert len(jax.devices()) == 4
tmp = Path(tempfile.mkdtemp())


def build(name):
    cfg = StoreConfig(scheme="cp-azure", k=24, r=2, p=2, block_size=BS)
    store = StripeStore(tmp / name, cfg, num_nodes=28,
                        topology=Topology(num_nodes=28, num_domains=4))
    payload = np.random.default_rng(7).integers(
        0, 256, STRIPES * cfg.k * BS, dtype=np.uint8)
    store.put("blob", payload.tobytes())
    store.seal()
    return store


def blocks(store):
    return {f"{sid},{b}": store._block_path(sid, b).read_bytes().hex()
            for sid in store.stripes for b in range(store.n)}


def repair(store, mesh, **options):
    store.fail_node(NODE)
    if mesh is None:
        rep = store.repair_all(options=RepairOptions(**options))
    else:
        with with_rules(mesh):
            rep = store.repair_all(options=RepairOptions(**options))
    store.revive_node(NODE)
    return rep


def h2d_spans(trace_dir):
    path = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    return [dict(ev.stats) for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for ev in line.events if ev.name == "repro.launch.h2d"]


mesh = make_mesh((4, 1), ("data", "model"))
one = build("one")
truth_rep = repair(one, None)
truth = blocks(one)

# every window's puts have landed, holding that window's bytes, before its
# kept gather slot goes back to the store for the next window's reads
landed = []
release = RepairPipeline._release


def checked_release(self, fetch):
    if fetch.batch is not None:
        landed.append(bool(fetch.batch.is_ready()) and all(
            np.array_equal(np.asarray(shard.data), buf)
            for shard, buf in zip(sorted(fetch.batch.addressable_shards,
                                         key=lambda s: s.index[0].start),
                                  fetch.bufs)))
    release(self, fetch)


RepairPipeline._release = checked_release
out = {"one": {"put_seconds": truth_rep["put_seconds"],
               "devices": truth_rep["devices"]}}
for mode, pipeline in (("pipelined", True), ("sync", False)):
    store = build(mode)
    reps = [repair(store, mesh, pipeline=pipeline) for _ in range(2)]
    trace = tmp / f"trace-{mode}"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace), profiler_options=options)
    try:
        traced = repair(store, mesh, pipeline=pipeline)
    finally:
        jax.profiler.stop_trace()
    out[mode] = {"reports": [{k: r[k] for k in (
        "put_seconds", "pipelined", "launches", "devices", "device_launches",
        "deferred_launches", "bytes_read", "gather_buffer_reuses",
        "gather_buffer_allocs")}
        for r in reps + [traced]],
        "h2d": h2d_spans(trace), "same_bytes": blocks(store) == truth}
out["landed"] = landed
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          cwd=tmp_path_factory.mktemp("four"),
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.parametrize("mode", ["pipelined", "sync"])
def test_sharded_repair_reports_its_put_and_matches_one_device(four_devices,
                                                               mode):
    out = four_devices[mode]
    assert out["same_bytes"]
    for rep in out["reports"]:
        assert rep["pipelined"] == (mode == "pipelined")
        assert rep["devices"] == 4
        assert rep["device_launches"] == 4 * rep["launches"]
        assert rep["deferred_launches"] \
            == (rep["launches"] if mode == "pipelined" else 0)
        assert rep["put_seconds"] > 0
    assert four_devices["one"] == {"put_seconds": 0.0, "devices": 1}


@pytest.mark.parametrize("mode", ["pipelined", "sync"])
def test_shard_put_span_carries_the_window_bytes(four_devices, mode):
    out = four_devices[mode]
    traced = out["reports"][-1]
    h2d = out["h2d"]
    assert len(h2d) == traced["launches"]
    assert all(s["shards"] == 4 and s["bytes"] > 0 for s in h2d)
    assert sum(s["bytes"] for s in h2d) == traced["bytes_read"]


def test_kept_slots_are_reused_and_put_before_given_back(four_devices):
    """The sharded pipeline reads into the store's kept gather slots, and
    every window's shard puts had landed with that window's bytes when its
    slot went back."""
    first, again, _ = four_devices["pipelined"]["reports"]
    assert first["gather_buffer_allocs"] >= 1
    assert again["gather_buffer_allocs"] == 0
    assert again["gather_buffer_reuses"] == again["launches"]
    assert len(four_devices["landed"]) == 3 * first["launches"]
    assert all(four_devices["landed"])


def test_one_device_repair_puts_nothing(tmp_path):
    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=256,
                      batch_stripes=4, pipeline_window=2)
    store = StripeStore(tmp_path, cfg)
    store.put("x", np.random.default_rng(1).integers(
        0, 256, 12 * cfg.k * cfg.block_size, dtype=np.uint8).tobytes())
    store.seal()
    for pipeline in (True, False):
        store.fail_node(2)
        rep = store.repair_all(options=RepairOptions(pipeline=pipeline))
        store.revive_node(2)
        assert rep["launches"] > 0 and rep["devices"] == 1
        assert rep["put_seconds"] == 0.0
