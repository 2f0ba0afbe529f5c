"""The stack_put_ms.rebuild reader on the reports of real repairs."""
import numpy as np
import pytest

from chipbench import generator, spec


def read(metric, run):
    return spec.reader(metric)(run)


def rebuild_run(reports, parts=("rebuild",)):
    run = generator.Run(parts=frozenset(parts), chips=1, block_size=1 << 20)
    run.reports = reports
    return run


@pytest.fixture(scope="module")
def reports(tmp_path_factory):
    """A pipelined and a synchronous repair of one node."""
    from repro.ftx import RepairOptions, StoreConfig, StripeStore

    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=256,
                      batch_stripes=4, pipeline_window=2)
    store = StripeStore(tmp_path_factory.mktemp("stack_put"), cfg)
    store.put("x", np.random.default_rng(1).integers(
        0, 256, 20 * cfg.k * cfg.block_size, dtype=np.uint8).tobytes())
    store.seal()
    store.fail_node(3)
    pipelined = store.repair_all()
    sync = store.repair_all(options=RepairOptions(pipeline=False))
    store.revive_node(3)
    assert pipelined["pipelined"] and not sync["pipelined"]
    return pipelined, sync


def test_stack_put_ms_of_recorded_repairs(reports):
    pipelined, sync = reports
    ms = read("stack_put_ms.rebuild", rebuild_run([pipelined]))
    assert ms == pytest.approx(
        pipelined["stack_put_seconds"] / pipelined["launches"] * 1e3)
    assert read("stack_put_ms.rebuild", rebuild_run([sync])) == 0.0
    both = read("stack_put_ms.rebuild", rebuild_run([pipelined, sync]))
    assert both == pytest.approx(
        pipelined["stack_put_seconds"]
        / (pipelined["launches"] + sync["launches"]) * 1e3)


def test_stack_put_ms_is_part_of_launch_ms(reports):
    pipelined, _ = reports
    run = rebuild_run([pipelined])
    assert 0 < read("stack_put_ms.rebuild", run) \
        < read("launch_ms.rebuild", run)


@pytest.mark.parametrize("which", ["bare", "mixed", "empty", "reads"])
def test_reports_without_the_field_read_nothing(reports, which):
    pipelined, _ = reports
    bare = {k: v for k, v in pipelined.items() if k != "stack_put_seconds"}
    run = {"bare": lambda: rebuild_run([bare]),
           "mixed": lambda: rebuild_run([pipelined, bare]),
           "empty": lambda: rebuild_run([]),
           "reads": lambda: rebuild_run([pipelined], parts=("reads",))}[which]()
    assert read("stack_put_ms.rebuild", run) is None
