"""The arithmetic of the metrics, the peaks, the seeded generators and the
refusal of any device that is not a TPU."""
import dataclasses
import time
import types

import numpy as np
import pytest

from chipbench import generator, harness, peaks, reference, spec, stats
from chipbench.trace_reduce import ReducedTrace


def read(metric, run):
    return spec.reader(metric)(run)


def reads_run(latency_s, lag_s, degraded, ok=None):
    latency_s = np.asarray(latency_s, float)
    ok = np.ones(latency_s.size, bool) if ok is None else np.asarray(ok)
    run = generator.Run(parts=frozenset({"reads"}), chips=1,
                        block_size=1 << 20)
    run.requests = {"latency_s": np.where(ok, latency_s, np.inf),
                    "lag_s": np.asarray(lag_s, float),
                    "degraded": np.asarray(degraded, bool), "ok": ok}
    return run


def test_percentiles_cover_every_sample():
    lat = np.arange(1, 101) / 1e3                    # 1..100 ms
    run = reads_run(lat, np.zeros(100), np.arange(100) >= 90)
    assert read("read_p50_ms", run) == pytest.approx(50.5)
    assert read("degraded_p99_ms.read", run) == pytest.approx(99.91)
    assert read("degraded_p50_ms", run) == pytest.approx(95.5)
    assert stats.percentile([], 99) is None


def test_a_failed_request_misses_every_limit():
    ok = np.ones(100, bool)
    ok[:2] = False
    run = reads_run(np.full(100, 1e-3), np.zeros(100), np.ones(100), ok)
    assert read("degraded_p99_ms.read", run) == np.inf
    assert read("read_p50_ms", run) == pytest.approx(1.0)


@dataclasses.dataclass
class Counters:
    degraded_reads: int = 0
    serve_decode_launches: int = 0


class SlowServer:
    """Serves every read in 50 ms, one at a time."""

    def read(self, *request):
        time.sleep(0.05)
        return np.zeros(1, np.uint8)


def test_latency_is_timed_from_due_time():
    """Two requests due 1 ms apart on one client that takes 50 ms each: the
    second waits for the first, and its latency counts that wait."""
    import jax.profiler  # noqa: F401  (loaded before the window, as in a run)

    gen = generator.Traffic.__new__(generator.Traffic)
    gen.traffic = {"reads": {"clients": 1}}
    stripe = types.SimpleNamespace(node_of_block=[0, 1])
    gen.store = types.SimpleNamespace(telemetry=Counters(), stripes={0: stripe})
    gen.down = {1}
    gen.server = SlowServer()
    gen.requests = [(0, 0, 0, 1), (0, 1, 0, 1)]
    gen.due = np.array([0.0, 0.001])
    gen.keep = np.array([False, False])
    gen.errors = []
    gen.run = generator.Run(parts=frozenset({"reads"}), chips=1, block_size=1)
    gen.window()
    assert list(gen.run.requests["degraded"]) == [False, True]
    lat = gen.run.requests["latency_s"]
    assert 0.045 < lat[0] < 0.09
    assert lat[1] > 0.095                       # 49 ms queued + 50 ms served
    assert np.all(gen.run.requests["lag_s"] < 0.03)
    assert gen.unfinished == 0


def test_generator_lag_reads_the_send_times():
    run = reads_run(np.ones(4), [0.0, 0.001, 0.002, 0.004], np.zeros(4))
    assert read("gen_lag_p99_ms.read", run) == pytest.approx(3.94)


def rebuild_run(trace=None):
    run = generator.Run(parts=frozenset({"rebuild"}), chips=2,
                        block_size=1 << 20)
    run.window_s = {"rebuild": 4.0}
    run.rebuilt_bytes = 400 << 20
    run.reports = [dict(bytes_read=3 << 30, read_seconds=2.0, launches=4,
                        compute_seconds=0.2, wall_seconds=3.0,
                        overlap_seconds=1.5)] * 2
    run.trace = trace
    run.peaks = peaks.peaks_for("TPU v5 lite")
    return run


def test_rebuild_rates_and_stage_ratios():
    run = rebuild_run()
    assert read("rebuild_MiBps", run) == pytest.approx(100.0)
    assert read("gather_GiBps.rebuild", run) == pytest.approx(1.5)
    assert read("launch_ms.rebuild", run) == pytest.approx(50.0)
    assert read("overlap_frac.rebuild", run) == pytest.approx(0.5)
    assert read("gf_roofline.rebuild", run) is None   # no trace, no share


def test_roofline_and_idle_from_the_trace():
    trace = ReducedTrace(window_s=4.0, busy_s=[0.5, 1.5], device_ops=[],
                         idle_gaps=[])
    run = rebuild_run(trace)
    moved = 2 * (3 << 30) + (400 << 20)
    least = moved / (2 * 819e9)
    assert read("gf_roofline.rebuild", run) == pytest.approx(100 * least / 1.0)
    assert read("device_idle_frac.rebuild", run) == pytest.approx(0.75)
    assert read("device_idle_frac.read", run) is None


def test_peaks_by_device_kind():
    assert peaks.peaks_for("TPU v5 lite").hbm_bytes_per_s == 819e9
    assert peaks.peaks_for("TPU v5 lite").bf16_flops_per_s == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def fake(platform):
    return types.SimpleNamespace(platform=platform, device_kind="x")


def test_a_device_that_is_not_a_tpu_is_refused():
    with pytest.raises(harness.NoChip):
        harness.require_chips([fake("cpu")], 1)
    with pytest.raises(harness.NoChip):
        harness.require_chips([fake("tpu")], 4)
    with pytest.raises(harness.NoChip):
        harness.require_chips([], 1)
    assert len(harness.require_chips([fake("tpu")] * 4, 1)) == 1


def test_run_on_this_host_prints_no_result(capsys):
    """The tests run on the CPU: the entry point refuses it, exits non-zero
    and prints nothing on standard output."""
    rc = harness.main(["--workload", "rebuild1-p5", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc == 1
    assert capsys.readouterr().out == ""


def test_seeded_generators_repeat():
    a = generator.zipf_ranks(np.random.default_rng(3), 1000, 0.99, 500)
    b = generator.zipf_ranks(np.random.default_rng(3), 1000, 0.99, 500)
    assert np.array_equal(a, b) and a.min() >= 0 and a.max() < 1000
    assert np.mean(a == 0) > np.mean(a == 999)
    ln = generator.log_uniform(np.random.default_rng(3), 5000, 1 << 20, 500)
    assert ln.min() >= 5000 and ln.max() <= 1 << 20


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**33 + 1])
def test_stripe_data_repeats_per_seed(seed):
    a = np.asarray(reference.stripe_data(seed, [0, 1, 2], 6, 64))
    b = np.asarray(reference.stripe_data(seed, [1], 6, 64))
    assert np.array_equal(a[1], b[0])
    assert not np.array_equal(a[0], a[1])
    c = np.asarray(reference.stripe_data(seed + 1, [1], 6, 64))
    assert not np.array_equal(b, c)


@pytest.mark.parametrize("geometry", [(6, 2, 2), (24, 2, 2), (20, 3, 5),
                                      (96, 5, 4)])
def test_reference_code_is_the_programs_code(geometry):
    from repro.core.schemes import make_scheme

    k, r, p = geometry
    cfg = dict(scheme="cp-azure", k=k, r=r, p=p, field_poly=0x11D,
               block_size=256)
    scheme = make_scheme("cp-azure", k, r, p)
    assert np.array_equal(reference.generator(cfg), scheme.gen)
    stripe = reference.reference_stripe(11, 2, cfg)
    assert np.array_equal(stripe, scheme.encode(stripe[:k]))


def test_field_arithmetic():
    assert reference.field_mul(0x80, 2, 0x11D) == 0x1D
    for a in (1, 2, 0x53, 0xFF):
        assert reference.field_mul(a, reference.field_inv(a, 0x11D),
                                   0x11D) == 1


def read_schedule(seed, rate=500.0):
    gen = generator.Traffic.__new__(generator.Traffic)
    gen.traffic = {"reads": {"template_seed": 1, "zipf_theta": 0.99,
                             "range_bytes": [5000, 1 << 20],
                             "check_live_share": 0.125, "warmup_s": 1}}
    gen.cfg = {"block_size": 1 << 20}
    gen.seconds = 2.0
    gen.rng = np.random.default_rng(seed)
    blocks = [(sid, b) for sid in range(16) for b in range(24)]
    gen.slot_block = [blocks[i] for i in gen.rng.permutation(len(blocks))]
    gen.n_lost = 16
    gen.schedule(rate, 2.0)
    return gen


def test_every_seed_gets_the_same_reads_in_another_order():
    a, b, a2 = read_schedule(3), read_schedule(2**31 + 9), read_schedule(3)
    assert a.requests == a2.requests and np.array_equal(a.due, a2.due)
    assert len(a.requests) == len(b.requests) == 1000
    assert a.requests != b.requests
    size = lambda g: sorted(hi - lo for _, _, lo, hi in g.requests)  # noqa: E731
    assert size(a) == size(b)
    assert a.on_lost.sum() == b.on_lost.sum() > 0
    gaps = lambda g: np.sort(np.diff(g.due))  # noqa: E731
    close = np.isclose(gaps(a)[:, None], gaps(b)[None, :], rtol=1e-9,
                       atol=1e-12)
    assert close.any(axis=1).mean() > 0.99          # all but the first gap
    assert all(5000 <= hi - lo <= 1 << 20 and 0 <= lo and hi <= 1 << 20
               for _, _, lo, hi in a.requests)
    assert a.keep[a.on_lost].all()


def test_ingest_rate():
    run = generator.Run(parts=frozenset({"writes"}), chips=1,
                        block_size=1 << 20)
    run.window_s = {"writes": 2.0}
    run.ingested_bytes = 300 << 20
    assert read("ingest_MiBps", run) == pytest.approx(150.0)
    assert read("rebuild_MiBps", run) is None


@pytest.mark.parametrize("mix,parts", [
    ({"failures": {"mode": "rotate"}}, {"rebuild"}),
    ({"failures": {"mode": "hold"}, "reads": {"rate_per_s": 1}}, {"reads"}),
    ({"failures": {"mode": "rotate", "offsets": [0, 26]},
      "reads": {"rate_per_s": 1}}, {"rebuild", "reads"}),
    ({"writes": {"object_stripes": [1, 4]}}, {"writes"}),
])
def test_a_mix_names_the_parts_it_runs(mix, parts):
    assert generator.parts_of(mix) == parts


@pytest.mark.parametrize("mix", [{}, {"failures": {"mode": "hold"}},
                                 {"failures": {"mode": "cascade"}}])
def test_a_mix_that_runs_nothing_is_refused(mix):
    with pytest.raises(ValueError):
        generator.parts_of(mix)
