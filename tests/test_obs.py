"""Program spans (``repro.obs``): one shared no-op while no trace runs, and
under the profiler the spans of the repair, launch and serving paths, with
the args that tie them to their work, read back from the trace as
``benchmarks/chip/chipbench/trace_reduce.py`` reads it (``ProfileData``).

Shapes are test-size; the chip's readings are in PERF.md.
"""
import collections
import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.dist import stripes
from repro.ftx import RepairOptions, StoreConfig, StripeStore
from repro.kernels import ops

Span = collections.namedtuple("Span", "name start end thread args")


def _store(root, *, stripes=8, block_size=512, window=4, read_cache_blocks=8):
    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=block_size,
                      batch_stripes=8, pipeline_window=window,
                      prefetch_threads=4, read_cache_blocks=read_cache_blocks)
    store = StripeStore(root, cfg)
    payload = np.random.default_rng(5).integers(
        0, 256, stripes * cfg.k * block_size, dtype=np.uint8)
    store.put("blob", payload.tobytes())
    store.seal()
    return store


def _traced(trace_dir, work) -> tuple[list, object]:
    """Run ``work()`` under the profiler (host spans only, as the benchmark
    records them); the ``repro.*`` spans of the trace and what work gave."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        out = work()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for t, line in enumerate(plane.lines):
            spans += [Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                           (plane.name, t), dict(ev.stats))
                      for ev in line.events if ev.name.startswith("repro.")]
    return sorted(spans, key=lambda s: s.start), out


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _inside(outer, spans, name):
    """The ``name`` spans on ``outer``'s thread within its interval."""
    return [s for s in _named(spans, name) if s.thread == outer.thread
            and outer.start <= s.start and s.end <= outer.end]


def _fail_first_node(store):
    node = store.stripes[0].node_of_block[0]
    store.fail_node(node)
    return node


def test_span_without_a_trace_is_the_shared_noop():
    assert obs.span("repro.test.stage", bytes=1) is obs.OFF
    with obs.span("repro.test.stage", sid=3) as span:
        span.set_metadata(bytes=2)
    assert span is obs.OFF
    x = jnp.arange(3)
    assert obs.block_if_tracing(x) is x


def test_span_under_a_trace_records_its_args(tmp_path):
    def work():
        with obs.span("repro.test.stage", sid=3, block=1) as span:
            span.set_metadata(bytes=4096, degraded=True)

    spans, _ = _traced(tmp_path / "trace", work)
    (span,) = _named(spans, "repro.test.stage")
    assert span.args == {"sid": 3, "block": 1, "bytes": 4096, "degraded": 1}


@pytest.fixture(scope="module")
def pipelined(tmp_path_factory):
    store = _store(tmp_path_factory.mktemp("pipelined") / "s")
    _fail_first_node(store)
    spans, report = _traced(
        tmp_path_factory.mktemp("pipelined_trace"),
        lambda: store.repair_all(options=RepairOptions(pipeline=True)))
    return store, spans, report


def test_pipelined_repair_spans_and_bytes(pipelined):
    store, spans, rep = pipelined
    bs = store.cfg.block_size
    assert rep["pipelined"] and rep["windows"] > 1
    (plan,) = _named(spans, "repro.repair.plan")
    assert plan.args == {"stripes": rep["stripes_repaired"],
                         "patterns": rep["patterns"]}
    prefetch = _named(spans, "repro.repair.prefetch")
    assert len(prefetch) == rep["windows"]
    assert plan.end <= prefetch[0].start       # planning ends at the first
    waits = _named(spans, "repro.repair.gather_wait")
    assert sorted(s.args["window"] for s in waits) == list(range(rep["windows"]))
    assert sum(s.args["bytes"] for s in waits) == rep["bytes_read"]
    reads = _named(spans, "repro.store.read_block")
    assert len(reads) == rep["blocks_read"]
    assert sum(s.args["bytes"] for s in reads) == rep["bytes_read"]
    assert {s.thread for s in reads} != {plan.thread}   # on reader threads
    for stage in ("repro.launch.h2d", "repro.launch.device",
                  "repro.launch.d2h"):
        assert len(_named(spans, stage)) == rep["launches"], stage
    # two a launch: the coordinator's, then the launch thread's
    assert len(_named(spans, "repro.repair.launch")) == 2 * rep["launches"]
    assert sum(s.args["bytes"] for s in _named(spans, "repro.launch.h2d")) \
        == rep["bytes_read"]
    rebuilt = rep["stripes_repaired"] * bs         # one lost block a stripe
    assert sum(s.args["bytes"] for s in _named(spans, "repro.launch.d2h")) \
        == rebuilt
    writes = _named(spans, "repro.repair.writeback")
    assert len(writes) == rep["windows"]
    assert sum(s.args["bytes"] for s in writes) == rebuilt
    assert len(_named(spans, "repro.pipeline.drain_wait")) == 1
    (shutdown,) = _named(spans, "repro.pipeline.shutdown")
    assert shutdown.args == {"threads": store.cfg.prefetch_threads + 2}
    assert shutdown.start >= _named(spans, "repro.pipeline.drain_wait")[0].end
    released = _named(spans, "repro.pipeline.release")
    assert sorted(s.args["window"] for s in released) \
        == list(range(rep["windows"]))


def test_launch_triple_nests_in_the_pipeline_launch(pipelined):
    """A window's launch is two ``repro.repair.launch`` spans: the
    coordinator's holds the put, and the launch thread's, after it, holds
    the dispatch and wait for the result, then the copy back."""
    store, spans, rep = pipelined
    coordinator = _named(spans, "repro.repair.plan")[0].thread
    launches = _named(spans, "repro.repair.launch")
    puts = {s.args["window"]: s for s in launches if s.thread == coordinator}
    runs = {s.args["window"]: s for s in launches if s.thread != coordinator}
    assert len(puts) == len(runs) == rep["launches"] \
        == rep["deferred_launches"]
    assert len({s.thread for s in runs.values()}) == 1
    for window, put in puts.items():
        run = runs[window]
        assert run.args == put.args and run.start >= put.end
        (h2d,) = _inside(put, spans, "repro.launch.h2d")
        (device,) = _inside(run, spans, "repro.launch.device")
        (d2h,) = _inside(run, spans, "repro.launch.d2h")
        assert device.args["backend"] == store.cfg.backend
        assert device.args["stripes"] == put.args["stripes"]
        assert device.args["targets"] == 1
        assert h2d.args["bytes"] == put.args["stripes"] \
            * device.args["reads"] * store.cfg.block_size
        assert d2h.args["bytes"] == put.args["stripes"] * store.cfg.block_size
        assert device.end <= d2h.start


def test_sync_repair_spans(tmp_path):
    store = _store(tmp_path / "s")
    _fail_first_node(store)
    spans, rep = _traced(
        tmp_path / "trace",
        lambda: store.repair_all(options=RepairOptions(pipeline=False)))
    assert not rep["pipelined"]
    assert len(_named(spans, "repro.repair.plan")) == 1
    waits = _named(spans, "repro.repair.gather_wait")
    assert [s.args["window"] for s in waits] == list(range(rep["launches"]))
    # the coordinator reads every block itself, inside its gather spans
    assert sum(len(_inside(w, spans, "repro.store.read_block"))
               for w in waits) == rep["blocks_read"]
    assert sum(s.args["bytes"] for s in waits) == rep["bytes_read"]
    writes = _named(spans, "repro.repair.writeback")
    assert len(writes) == rep["launches"]
    assert sum(s.args["bytes"] for s in writes) \
        == rep["stripes_repaired"] * store.cfg.block_size
    for launch in _named(spans, "repro.repair.launch"):
        for stage in ("h2d", "device", "d2h"):
            assert len(_inside(launch, spans, f"repro.launch.{stage}")) == 1


def test_coalesced_degraded_read_spans(tmp_path):
    """Two readers of one lost block: the leader decodes, the other parks
    on it; both reads are degraded, and the park names the decode's block."""
    store = _store(tmp_path / "s", window=0, read_cache_blocks=0)
    _fail_first_node(store)
    sid = next(s for s in store.stripes if store._down_blocks(s))
    block = next(iter(store._down_blocks(sid)))
    gate = threading.Event()

    def hook(stage, s, b):
        if stage == "gather":
            gate.wait(timeout=30)        # hold the leader until one parks

    def work():
        store.read_hook = hook
        threads = [threading.Thread(target=store.read_range,
                                    args=(sid, block, 10, 200))
                   for _ in range(2)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            entry = store._inflight.get((sid, block))
            if entry is not None and entry.waiters == 1:
                break
            time.sleep(0.002)
        gate.set()
        for t in threads:
            t.join()
        store.read_hook = None

    spans, _ = _traced(tmp_path / "trace", work)
    reads = _named(spans, "repro.serve.read")
    assert len(reads) == 2
    assert all(r.args == {"sid": sid, "block": block, "bytes": 190,
                          "degraded": 1} for r in reads)
    (decode,) = _named(spans, "repro.serve.decode")
    (park,) = _named(spans, "repro.serve.park")
    assert park.args == {"sid": sid, "block": block}
    assert {decode.args["sid"], decode.args["block"]} == {sid, block}
    assert park.start < decode.end and decode.start < park.end
    assert park.thread != decode.thread
    plan = store.engine.planner.serving_plan(block, store._down_blocks(sid))
    assert decode.args["reads"] == len(plan.reads)
    assert len(_inside(decode, spans, "repro.store.read_block")) \
        == len(plan.reads)
    for stage in ("h2d", "device", "d2h"):
        assert len(_inside(decode, spans, f"repro.launch.{stage}")) == 1


@pytest.mark.parametrize("backend", ["ref", "gf", "crs", "mxu"])
def test_launch_programs_are_named_by_formulation(backend):
    body = (ops._bit_matmul_batch_kernel if backend in ops.BIT_BACKENDS
            else ops._gf_batch_kernel)
    launch = stripes._launcher(body, (("backend", backend),
                                      ("force_pallas", False),
                                      ("interpret", True)))
    coef = jnp.zeros((8, 16) if backend in ops.BIT_BACKENDS else (1, 2),
                     jnp.uint8)
    text = launch.lower(coef, jnp.zeros((1, 2, 64), jnp.uint8)).as_text()
    assert f"@jit_gf_launch_{backend}" in text
