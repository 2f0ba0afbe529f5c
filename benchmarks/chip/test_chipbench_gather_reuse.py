"""The gather_reuse_frac.rebuild reader on the reports of real repairs."""
import numpy as np
import pytest

from chipbench import generator, spec


def read(metric, run):
    return spec.reader(metric)(run)


def rebuild_run(reports):
    run = generator.Run(parts=frozenset({"rebuild"}), chips=1,
                        block_size=1 << 20)
    run.reports = reports
    return run


def test_gather_reuse_share_of_recorded_repairs(tmp_path):
    """The share read from the reports of two real repairs of one node, the
    first of which makes the store's two kept buffers; a repeat reuses them
    all, and reports without the counters read nothing."""
    from repro.ftx import StoreConfig, StripeStore

    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=256,
                      batch_stripes=4, pipeline_window=2)
    store = StripeStore(tmp_path, cfg)
    store.put("x", np.random.default_rng(1).integers(
        0, 256, 20 * cfg.k * cfg.block_size, dtype=np.uint8).tobytes())
    store.seal()
    reports = []
    for _ in range(2):
        store.fail_node(3)
        reports.append(store.repair_all())
        store.revive_node(3)
    first, again = reports
    assert first["gather_buffer_allocs"] >= 2
    assert (again["gather_buffer_allocs"], again["gather_buffer_reuses"]) \
        == (0, again["windows"])
    reuses = first["gather_buffer_reuses"] + again["windows"]
    assert read("gather_reuse_frac.rebuild", rebuild_run(reports)) \
        == pytest.approx(reuses / (first["windows"] + again["windows"]))
    assert read("gather_reuse_frac.rebuild", rebuild_run([again])) == 1.0
    bare = {k: v for k, v in first.items()
            if not k.startswith("gather_buffer")}
    assert read("gather_reuse_frac.rebuild", rebuild_run([bare])) is None
    assert read("gather_reuse_frac.rebuild", rebuild_run([])) is None
    reads = generator.Run(parts=frozenset({"reads"}), chips=1,
                          block_size=1 << 20)
    reads.reports = reports
    assert read("gather_reuse_frac.rebuild", reads) is None
