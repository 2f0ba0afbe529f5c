"""Find a cell's configuration, traffic mix and metrics by name.

``BENCHMARK.json`` at the root of the checkout names every cell. A cell's
configuration is the file its ``configs`` entry names; its traffic mix is
``traffic/<traffic>.json`` beside this package; each metric is read by
``metrics/<metric name>.py``, which defines ``read(run) -> float | None``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parents[1]
ROOT = CHIP_DIR.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list                  # metric entries this cell reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    config = load_config(w["config"], root)
    traffic = load_traffic(w["traffic"])
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _applies(m, name) and m["moves"] in reported]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def load_config(name: str, root: Path = ROOT) -> dict:
    """The configuration file that ``BENCHMARK.json``'s ``configs`` names."""
    configs = {c["name"]: c for c in load_benchmark(root)["configs"]}
    return json.loads((root / configs[name]["file"]).read_text())


def load_traffic(name: str) -> dict:
    """The traffic mix ``traffic/<name>.json``."""
    return json.loads((CHIP_DIR / "traffic" / f"{name}.json").read_text())


def reader(metric: str):
    """The ``read`` function of ``metrics/<metric>.py``."""
    path = CHIP_DIR / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
