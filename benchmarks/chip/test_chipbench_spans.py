"""The program's spans reduced from a trace (``chipbench/spans.py``) and the
nine readers of ``span_metrics.json``: gap naming over synthetic spans, the
summary of a trace recorded on the host's CPU, the committed v5e probe
reduced exactly as before, and each reader on a hand-made run."""
import glob
import json
import types
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from chipbench import generator, spans as sp, spec
from chipbench import trace_reduce as tr

PROBE = str(Path(__file__).parent / "testdata" / "probe.xplane.pb")
T = ("/host:CPU", 0)                         # the coordinator's thread
W = ("/host:CPU", 1)                         # another thread


def span(name, start, end, thread=T, **args):
    return sp.Span(name, start, end, thread, args)


# ------------------------------------------------------------ gap naming
def test_a_program_span_inside_a_bench_span_names_the_gap():
    spans = [span("bench.window", 0, 100), span("bench.repair_all", 10, 90),
             span("repro.repair.gather_wait", 20, 40),
             span("repro.store.read_block", 25, 35, W),
             span("repro.repair.writeback", 50, 80, W)]
    named = sp.name_gaps([(22, 38), (60, 70), (84, 88), (92, 99)], spans)
    assert [n for n, _ in named] == [
        "repro.store.read_block", "repro.repair.writeback",
        "bench.window", "bench.repair_all"]
    assert [s for _, s in named] == pytest.approx([16e-9, 10e-9, 7e-9, 4e-9])


def test_bench_spans_alone_name_gaps_as_trace_reduce_does():
    reduced = tr.reduce_trace(PROBE, chips=1)
    before = (reduced.window_s, list(reduced.busy_s),
              list(reduced.device_ops), list(reduced.idle_gaps))
    sp.attach(reduced, PROBE, chips=1)
    assert (reduced.window_s, reduced.busy_s, reduced.device_ops,
            reduced.idle_gaps) == before
    assert reduced.spans == {}
    assert reduced.kernel_s is None          # recorded before kernels had names
    assert reduced.span_checks["repair_all_covered"] == 0.0
    assert reduced.span_checks["repair_all_holes"][0][:2] == ["start", "end"]
    assert reduced.span_checks["launch_split_of_launch"] is None
    assert reduced.span_checks["unmatched_parks"] is None
    assert reduced.span_checks["modules"] == ["jit__unknown"]


def test_kernel_seconds_by_the_stable_name():
    ev = types.SimpleNamespace

    def plane(*ops):
        line = ev(name="XLA Ops", events=[
            ev(name=n, start_ns=s, duration_ns=d) for n, s, d in ops])
        return ev(name="/device:TPU:0", lines=[line])

    used = [plane(("%gf_kernel_mxu.1 = u8[16,8,131072] custom-call()", 10, 30),
                  ("%fusion = s32[16,12,8,131072] fusion()", 40, 50),
                  ("%gf_kernel_mxu.1 = u8[16,8,131072] custom-call()", 95, 20))]
    assert sp.kernel_seconds(used, 0, 100) == pytest.approx(35e-9)
    assert sp.kernel_seconds(used, 0, 5) is None


# ------------------------------------------------------- summary, checks
def test_summary_keeps_the_window_counts_bytes_and_degraded():
    spans = [span("repro.serve.read", 5, 9, bytes=100, degraded=1),
             span("repro.serve.read", 10, 14, bytes=50, degraded=0),
             span("repro.serve.read", 20, 30, bytes=7, degraded=1),
             span("bench.read", 10, 15)]
    summary = sp.summarize(spans, 8, 20)
    assert set(summary) == {"repro.serve.read"}
    row = summary["repro.serve.read"]
    assert (row.count, row.bytes, row.degraded) == (1, 50, 0)
    assert row.seconds == pytest.approx([4e-9])
    table = sp.table(sp.summarize(spans, 0, 100))["repro.serve.read"]
    assert table["count"] == 3 and table["degraded"] == 2
    assert table["p50_ms"] == pytest.approx(4e-6)
    assert table["max_ms"] == pytest.approx(10e-6)


def test_coverage_split_and_parks():
    spans = [span("bench.repair_all", 0, 100),
             span("repro.repair.plan", 0, 10),
             span("repro.repair.launch", 20, 60),
             span("repro.launch.h2d", 20, 30), span("repro.launch.device",
                                                    30, 50),
             span("repro.launch.d2h", 50, 58),
             span("repro.repair.writeback", 60, 100, W),
             span("repro.serve.decode", 200, 260, sid=1, block=2),
             span("repro.serve.park", 210, 250, W, sid=1, block=2),
             span("repro.serve.park", 270, 280, W, sid=1, block=2)]
    assert sp.coverage(spans) == pytest.approx(0.5)
    assert sp.holes(spans) == [["repro.repair.launch", "end", 40e-9, 1],
                               ["repro.repair.plan", "repro.repair.launch",
                                10e-9, 1]]
    summary = sp.summarize(spans, 0, 1000)
    assert sp.launch_split(summary) == pytest.approx(38 / 40)
    assert sp.gather_wall(spans) is None
    spans += [span("repro.repair.prefetch", 12, 14),
              span("repro.repair.gather_wait", 14, 18),
              span("repro.repair.prefetch", 60, 62),
              span("repro.repair.gather_wait", 62, 75)]
    assert sp.gather_wall(spans) == pytest.approx(63e-9)
    assert sp.unmatched_parks(spans) == 1
    assert sp.coverage([]) is None and sp.unmatched_parks([]) is None


def test_a_cpu_trace_of_a_repair_holds_the_program_spans(tmp_path):
    from repro.ftx import StoreConfig, StripeStore

    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=512,
                      batch_stripes=4, pipeline_window=2, prefetch_threads=2)
    store = StripeStore(tmp_path / "s", cfg)
    store.put("blob", np.arange(8 * 6 * 512, dtype=np.uint8) % 251)
    store.seal()
    store.fail_node(store.stripes[0].node_of_block[0])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.repair_all"):
                report = store.repair_all()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "trace/plugins/profile/*/*.xplane.pb"))[0]
    spans = sp.host_spans(ProfileData.from_file(path).planes)
    summary = sp.summarize(spans, *sp.window_of(spans))
    assert summary["repro.repair.plan"].count == 1
    assert summary["repro.repair.gather_wait"].bytes == report["bytes_read"]
    assert summary["repro.launch.h2d"].bytes == report["bytes_read"]
    assert summary["repro.launch.device"].count == report["launches"]
    assert summary["repro.repair.writeback"].bytes == 8 * 512
    assert 0 < sp.coverage(spans) <= 1
    assert 0 < sp.launch_split(summary) <= 1


# ---------------------------------------------------------------- readers
def read(metric, run):
    return spec.reader(metric)(run)


def summary_of(**rows):
    out = {}
    for name, (seconds, nbytes, degraded) in rows.items():
        out["repro." + name] = sp.SpanSummary(len(seconds), list(seconds),
                                              nbytes, degraded)
    return out


def rebuild_run(spans, kernel_s=None, launches=4, busy=2.0):
    run = generator.Run(parts=frozenset({"rebuild"}), chips=1,
                        block_size=1 << 20)
    run.reports = [{"launches": launches}]
    run.trace = types.SimpleNamespace(spans=spans, kernel_s=kernel_s,
                                      busy_mean_s=busy)
    return run


def test_rebuild_readers():
    spans = summary_of(**{
        "repair.plan": ([0.002, 0.004], 0, 0),
        "repair.gather_wait": ([0.1, 0.3], 0, 0),
        "repair.writeback": ([0.25, 0.25], 2**30, 0),
        "launch.h2d": ([0.5], 2**31, 0),
        "launch.device": ([0.01, 0.03], 0, 0)})
    run = rebuild_run(spans, kernel_s=0.5)
    assert read("plan_ms.rebuild", run) == pytest.approx(3.0)
    assert read("gather_wait_ms.rebuild", run) == pytest.approx(100.0)
    assert read("writeback_GiBps.rebuild", run) == pytest.approx(2.0)
    assert read("h2d_GiBps.rebuild", run) == pytest.approx(4.0)
    assert read("device_ms.rebuild", run) == pytest.approx(20.0)
    assert read("kernel_frac.rebuild", run) == pytest.approx(25.0)


def test_serving_readers():
    run = generator.Run(parts=frozenset({"reads"}), chips=1,
                        block_size=1 << 20)
    run.trace = types.SimpleNamespace(spans=summary_of(**{
        "serve.read": ([0.001, 0.002, 0.009, 0.004], 0, 2),
        "serve.park": ([0.003, 0.005], 0, 0),
        "serve.decode": ([0.02, 0.04], 0, 0)}), kernel_s=None)
    assert read("store_read_p50_ms.read", run) == pytest.approx(3.0)
    assert read("park_ms_per_degraded.read", run) == pytest.approx(4.0)
    assert read("decode_ms.read", run) == pytest.approx(30.0)
    del run.trace.spans["repro.serve.park"]     # nobody parked
    assert read("park_ms_per_degraded.read", run) == 0.0


REBUILD = ["plan_ms.rebuild", "gather_wait_ms.rebuild",
           "writeback_GiBps.rebuild", "h2d_GiBps.rebuild", "device_ms.rebuild",
           "kernel_frac.rebuild"]
SERVING = ["store_read_p50_ms.read", "park_ms_per_degraded.read",
           "decode_ms.read"]


@pytest.mark.parametrize("metric", REBUILD + SERVING)
def test_readers_read_nothing_without_spans_or_their_part(metric):
    part = "rebuild" if metric in REBUILD else "reads"
    run = generator.Run(parts=frozenset({part}), chips=1, block_size=1 << 20)
    run.reports = [{"launches": 4}]
    assert read(metric, run) is None                   # untraced
    run.trace = tr.ReducedTrace(window_s=30.0, busy_s=[1.0], device_ops=[],
                                idle_gaps=[])
    assert read(metric, run) is None                   # no program spans
    run.trace.spans, run.trace.kernel_s = {}, None
    assert read(metric, run) is None                   # none of its spans
    other = generator.Run(parts=frozenset({"writes"}), chips=1,
                          block_size=1 << 20)
    other.trace = types.SimpleNamespace(
        spans=summary_of(**{"repair.plan": ([1.0], 0, 0),
                            "serve.read": ([1.0], 0, 1)}),
        kernel_s=1.0, busy_mean_s=2.0)
    assert read(metric, other) is None                 # not its part


def test_span_metrics_are_benchmark_entries_with_readers():
    bench = spec.load_benchmark()
    metrics = sp.span_metrics()
    assert [m["name"] for m in metrics] == REBUILD[:5] + ["kernel_frac.rebuild"] \
        + SERVING
    layers = {m["layer"] for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    taken = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]}
    for m in metrics:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["name"] not in taken and m["layer"] in layers
        assert m["source"] == ("device_trace" if m["name"].startswith(
            "kernel_frac") else "program_span")
        for cell in m["workloads"]:
            assert cell in cells and cell in e2e[m["moves"]]["workloads"]
        assert callable(spec.reader(m["name"]))
    assert len(json.dumps(metrics)) < 4096
