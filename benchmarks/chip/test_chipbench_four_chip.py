"""The four-chip P5 rebuild cell, ``rebuild1-p5-x4``: found by its name with
its metrics, its ``shard_put_ms.rebuild`` reader, and a sound run of the
cell, cut small, on four CPU devices in a process of its own."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import generator, spec
from test_chipbench_correct import HERE, SMALL

CELL = "rebuild1-p5-x4"
PER_LAYER = {"gather_GiBps.rebuild", "overlap_frac.rebuild",
             "launch_ms.rebuild", "compiles_in_window.rebuild",
             "gf_roofline.rebuild", "device_idle_frac.rebuild",
             "gather_reuse_frac.rebuild", "shard_put_ms.rebuild"}


def rebuild_run(reports):
    run = generator.Run(parts=frozenset({"rebuild"}), chips=4,
                        block_size=1 << 20)
    run.reports = reports
    return run


def test_cell_loads_on_four_chips_with_its_metrics():
    cell = spec.load_cell(CELL)
    assert cell.chips == 4
    assert cell.config == spec.load_config("p5-cp-azure-1m-x4")
    assert cell.config["mesh"]["shape"] == [4, 1]
    assert cell.config["failure_domains"] == 4
    assert cell.traffic == spec.load_traffic("rebuild1")
    assert {m["name"] for m in cell.end_to_end} == {"rebuild_MiBps",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == PER_LAYER


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """One sound run of the cell, cut as ``SMALL`` cuts it, on four CPU
    devices: the result and the window's repair reports."""
    tmp = tmp_path_factory.mktemp("x4")
    script = f"""
import json, sys, time, types
sys.path[:0] = [{str(HERE)!r}, {str(HERE.parents[1] / 'src')!r}]
from pathlib import Path
from chipbench import generator, harness, spec
cell = spec.load_cell({CELL!r})
cell.config.update({SMALL[CELL]!r})
reports = []
release = generator.Traffic.release
def keep_reports(self):
    reports.extend(self.run.reports)
    release(self)
generator.Traffic.release = keep_reports
tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
r = harness.measure(cell, seed=2**32 + 11, seconds=1.0, trace=False,
                    devices=[tpu] * 4, t_start=time.perf_counter(),
                    root=Path({str(tmp)!r}))
print(json.dumps({{"result": r, "reports": reports}}, default=str))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.splitlines()[-1])


def test_sound_four_chip_run_is_correct(sound):
    result = sound["result"]
    assert result["correct"], result["checks"]
    assert set(result["checks"]) == {"errors", "bad_blocks", "missing_blocks",
                                     "empty_window", "unsharded_launches"}
    assert all(c["value"] == 0 for c in result["checks"].values())
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"rebuild_MiBps", "setup_s"}
    reports = sound["reports"]
    assert reports and all(r["devices"] == 4 for r in reports)


def test_shard_put_reader_on_recorded_reports(sound):
    reports = sound["reports"]
    read = spec.reader("shard_put_ms.rebuild")
    launches = sum(r["launches"] for r in reports)
    want = sum(r["put_seconds"] for r in reports) / launches * 1e3
    assert want > 0
    assert read(rebuild_run(reports)) == pytest.approx(want)
    bare = [{k: v for k, v in r.items() if k != "put_seconds"}
            for r in reports]
    assert read(rebuild_run(bare)) is None
    assert read(rebuild_run(reports[:1] + bare[1:])) is None
    assert read(rebuild_run([])) is None
    reads = generator.Run(parts=frozenset({"reads"}), chips=4,
                          block_size=1 << 20)
    reads.reports = reports
    assert read(reads) is None
