"""``correct`` at a size a test run holds: sound runs pass, and the control
and every fault a cell can have are caught.

These drive the harness past its look for a chip (``harness.measure``) on
the host's CPU, with the cells' own geometries and traffic cut to small
blocks and few stripes.
"""
import json
import os
import subprocess
import sys
import time
import types
from pathlib import Path

import pytest

from chipbench import control, generator, harness, spec

HERE = Path(__file__).resolve().parent
TPU = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
SMALL = {"rebuild1-p5": dict(block_size=4096, stripes=8),
         "rebuild1-p8": dict(block_size=4096, stripes=4),
         "degraded-read-p5": dict(block_size=8192, stripes=8),
         "rebuild1-p5-x4": dict(block_size=4096, stripes=16)}


def small_cell(name):
    cell = spec.load_cell(name)
    cell.config.update(SMALL[name])
    if cell.traffic.get("reads"):
        cell.traffic["reads"]["rate_per_s"] = 200
    return cell


def run(name, plant, tmp_path, seed=5, devices=None):
    cell = small_cell(name)
    with control.planted(plant):
        return harness.measure(cell, seed=seed, seconds=1.0, trace=False,
                               devices=devices or [TPU] * cell.chips,
                               t_start=time.perf_counter(), root=tmp_path)


@pytest.mark.parametrize("name", ["rebuild1-p5", "rebuild1-p8",
                                  "degraded-read-p5"])
def test_sound_run_is_correct(name, tmp_path):
    result = run(name, "none", tmp_path, seed=2**31 + 3)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert all(c["value"] == 0 for c in result["checks"].values())
    assert {"setup_s"} < set(result["metrics"])
    assert not list((tmp_path / harness.WORK_DIR).iterdir())


@pytest.mark.parametrize("name,plant", [
    ("rebuild1-p5", "control"), ("degraded-read-p5", "control"),
    *(("rebuild1-p5", f) for f in control.FAULTS["rebuild"]),
    *(("degraded-read-p5", f) for f in control.FAULTS["reads"])])
def test_control_and_faults_are_caught(name, plant, tmp_path):
    result = run(name, plant, tmp_path)
    assert not result["correct"], (plant, result["checks"])


# mixes with no cell yet, on the P5 configuration: each runs sound and
# fails under every fault its parts can have
LATER = {"rebuild2-p5": dict(block_size=4096, stripes=8),
         "ingest": dict(block_size=4096, stripes=4)}


def later_cases():
    for mix in LATER:
        parts = generator.parts_of(spec.load_traffic(mix))
        yield mix, "none"
        yield from ((mix, f) for p in sorted(parts) for f in control.FAULTS[p])


@pytest.mark.parametrize("mix,plant", list(later_cases()))
def test_later_mixes_run_and_are_checked(mix, plant, tmp_path):
    cell = spec.Cell(name=mix, chips=1,
                     config=spec.load_config("p5-cp-azure-1m"),
                     traffic=spec.load_traffic(mix), end_to_end=[],
                     per_layer=[])
    cell.config.update(LATER[mix])
    if "reads" in cell.traffic:
        cell.traffic["reads"].update(rate_per_s=200, warmup_s=0.5)
    if "writes" in cell.traffic:
        cell.traffic["writes"]["object_stripes"] = [1, 3]
    with control.planted(plant):
        result = harness.measure(cell, seed=2**32 + 7, seconds=1.0,
                                 trace=False, devices=[TPU],
                                 t_start=time.perf_counter(), root=tmp_path)
    assert result["correct"] == (plant == "none"), (plant, result["checks"])
    if plant == "none":
        assert result["attempted"] > 0 and result["failed"] == 0


def test_four_chip_cell_catches_a_lost_exchange(tmp_path):
    """The P5 rebuild on the four-domain configuration whose launches shard
    over a (4, 1) mesh, on four CPU devices in a process of its own: sound,
    then with each shard but the first left out, then the other faults."""
    script = f"""
import json, sys, time, types
sys.path[:0] = [{str(HERE)!r}, {str(HERE.parents[1] / 'src')!r}]
from pathlib import Path
from chipbench import control, generator, harness, spec
cell = spec.load_cell("rebuild1-p5")
cell.chips = 4
cell.config = json.loads((spec.CHIP_DIR / "configs" / "p5-cp-azure-1m-x4.json")
                         .read_text())
cell.config.update({SMALL['rebuild1-p5-x4']!r})
tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
for plant in ["none", "no_exchange", *control.FAULTS["rebuild"]]:
    with control.planted(plant):
        r = harness.measure(cell, seed=9, seconds=1.0, trace=False,
                            devices=[tpu] * 4, t_start=time.perf_counter(),
                            root=Path({str(tmp_path)!r}))
    print(json.dumps({{"plant": plant, "correct": r["correct"],
                      "checks": r["checks"]}}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()]
    verdicts = {x["plant"]: x["correct"] for x in lines}
    assert verdicts.pop("none") is True, lines
    assert not any(verdicts.values()), lines
    sound = lines[0]["checks"]
    assert sound["unsharded_launches"]["value"] == 0
