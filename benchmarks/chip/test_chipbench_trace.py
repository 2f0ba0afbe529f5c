"""The trace reduction on a small trace recorded on a TPU v5e: three repair
launches of 4 stripes, each in a ``bench.repair_all`` span, with short
``bench.revive_node`` sleeps between them, inside ``bench.window``."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

PROBE = Path(__file__).parent / "testdata" / "probe.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(str(PROBE), chips=1)


def test_window_and_busy_time(reduced):
    assert reduced.window_s == pytest.approx(0.025664954)
    assert len(reduced.busy_s) == 1
    assert 0 < reduced.busy_mean_s < reduced.window_s
    assert reduced.busy_s[0] == pytest.approx(0.00060436)


def test_device_ops_are_named_and_ranked(reduced):
    names = [name for name, _ in reduced.device_ops]
    assert "%mod2_matmul_encode_batched.1 u8[4,8,8192]" in names
    seconds = [s for _, s in reduced.device_ops]
    assert seconds == sorted(seconds, reverse=True)
    assert len(reduced.device_ops) <= 10


def test_idle_gaps_are_named_by_host_span(reduced):
    names = {name for name, _ in reduced.idle_gaps}
    assert names <= {"bench.repair_all", "bench.revive_node", "bench.window"}
    assert "bench.revive_node" in names          # the sleeps between launches
    longest = reduced.idle_gaps[0][1]
    assert longest == max(s for _, s in reduced.idle_gaps)
    idle = reduced.window_s - reduced.busy_mean_s
    assert sum(s for _, s in reduced.idle_gaps) <= idle + 1e-9


def test_more_chips_than_the_trace_holds_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_trace(str(PROBE), chips=4)


def test_union_and_gaps():
    merged = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]]
    assert tr.gaps(merged, 0, 10) == [(3, 5), (8, 10)]
    assert tr.gaps([], 0, 1) == [(0, 1)]


def test_op_names_keep_the_name_and_shape():
    hlo = ("%copy.46 = s32[24,8,8,131072]{3,2,1,0:T(8,128)} copy("
           "s32[24,8,8,131072]{3,1,2,0:T(8,128)} %bitcast.12)")
    assert tr.op_name(hlo) == "%copy.46 s32[24,8,8,131072]"
    tup = "%fusion.4 = (u8[16,1,1,131072]{3,2,0,1}, u8[16,1]{1,0}) fusion()"
    assert tr.op_name(tup) == "%fusion.4 u8[16,1,1,131072]"
