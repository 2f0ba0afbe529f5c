"""Stripe-axis device sharding: bit-identity with the single-device path.

The 1-device cases always run (degradation must be a clean no-op); the
multi-device cases run in the forced-8-device CI leg
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.engine import BatchedCodecEngine
from repro.core.schemes import make_scheme
from repro.dist.sharding import with_rules
from repro.dist.stripes import stripe_span, stripe_spec
from repro.launch.mesh import make_mesh

multidevice = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _mesh():
    return make_mesh((8, 1), ("data", "model"))


def _stripes(scheme, S, B, seed=0):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (S, scheme.k, B), dtype=np.uint8)
    engine = BatchedCodecEngine(scheme, backend="ref")
    return data, np.asarray(engine.encode(data)), engine


# ------------------------------------------------------------- resolution
def test_stripe_spec_degrades_on_trivial_mesh():
    mesh = make_mesh((1, 1), ("data", "model"))
    with with_rules(mesh) as mr:
        assert stripe_spec((32, 8, 1024), mr) == P("data", None, None)
        assert stripe_span((32, 8, 1024), mr) == 1
    assert stripe_span((32, 8, 1024), None) == 1


def test_engine_without_rules_unchanged():
    scheme = make_scheme("cp-azure", 6, 2, 2)
    data, stripes, engine = _stripes(scheme, 4, 256)
    assert engine.last_span == 1
    out, _ = engine.repair_single(0, {i: stripes[:, i, :]
                                      for i in range(1, scheme.n)})
    assert engine.last_span == 1
    assert (np.asarray(out) == stripes[:, 0, :]).all()


@multidevice
def test_stripe_spec_resolves_to_data_axis():
    with with_rules(_mesh()) as mr:
        assert stripe_spec((32, 8, 1024), mr) == P("data", None, None)
        assert stripe_span((32, 8, 1024), mr) == 8
        # indivisible S degrades to a single-device launch
        assert stripe_spec((13, 8, 1024), mr) == P(None, None, None)
        assert stripe_span((13, 8, 1024), mr) == 1


# ------------------------------------------------------------ bit-identity
@multidevice
@pytest.mark.parametrize("backend", ["ref", "gf", "crs"])
def test_sharded_repair_bit_identical(backend):
    """Sharded encode/repair/decode == single-device, bit for bit."""
    scheme = make_scheme("cp-azure", 8, 2, 2)
    data, stripes, plain = _stripes(scheme, 32, 1024)
    with with_rules(_mesh()) as mr:
        sharded = BatchedCodecEngine(scheme, backend=backend, mesh_rules=mr)
        assert (np.asarray(sharded.encode(data)) == stripes).all()
        assert sharded.last_span == 8

        avail = {i: stripes[:, i, :] for i in range(scheme.n)
                 if i not in (0, scheme.k)}
        want, _ = plain.repair_multi({0, scheme.k}, avail)
        got, _ = sharded.repair_multi({0, scheme.k}, avail)
        assert sharded.last_span == 8
        for b in (0, scheme.k):
            assert (np.asarray(want[b]) == np.asarray(got[b])).all()

        # drop data block 0; its local parity (block k) stands in
        ids = list(range(1, scheme.k)) + [scheme.k]
        dec = sharded.decode({i: stripes[:, i, :] for i in ids})
        assert (np.asarray(dec) == data).all()


@multidevice
def test_sharded_pallas_kernel_lockstep():
    """Each batched-grid Pallas kernel itself runs under shard_map — the
    path real TPUs take (no CPU table fallback) — in lockstep with the
    table oracle, on a ragged block length."""
    from repro.kernels.ops import gf_matmul_batch_op

    rng = np.random.default_rng(1)
    coef = rng.integers(0, 256, (3, 5), dtype=np.uint8)
    data = rng.integers(0, 256, (16, 5, 264), dtype=np.uint8)
    with with_rules(_mesh()) as mr:
        want = np.asarray(gf_matmul_batch_op(coef, data, backend="ref"))
        for backend in ("gf", "crs", "mxu"):
            got = np.asarray(gf_matmul_batch_op(
                coef, data, backend=backend, force_pallas=True,
                mesh_rules=mr))
            assert (want == got).all(), backend


@multidevice
def test_sharded_repair_ragged_batch_degrades_bit_identical():
    """S=13 (indivisible by 8) silently runs single-device, same bits."""
    scheme = make_scheme("cp-azure", 6, 2, 2)
    data, stripes, plain = _stripes(scheme, 13, 512)
    with with_rules(_mesh()) as mr:
        sharded = BatchedCodecEngine(scheme, backend="ref", mesh_rules=mr)
        out, _ = sharded.repair_single(
            0, {i: stripes[:, i, :] for i in range(1, scheme.n)})
        assert sharded.last_span == 1
        assert (np.asarray(out) == stripes[:, 0, :]).all()


def _filled_store(root, *, stripes=80, block_size=1024, batch_stripes=8):
    """A store with exactly ``stripes`` sealed stripes (one spanning object).

    Round-robin placement cycles every ``n`` stripes, so one failed node
    yields ``n`` distinct failure patterns with ``stripes/n`` members each —
    sized here so every pattern group is divisible across 8 devices.
    """
    from repro.ftx import StoreConfig, StripeStore

    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2,
                      block_size=block_size, batch_stripes=batch_stripes)
    store = StripeStore(root, cfg)
    extent = cfg.k * cfg.block_size
    payload = np.random.default_rng(3).integers(
        0, 256, stripes * extent, dtype=np.uint8)
    store.put("blob", payload.tobytes())
    store.seal()
    assert len(store.stripes) == stripes
    return store


@multidevice
def test_store_sharded_repair_bit_identical_with_telemetry(tmp_path):
    """Fleet repair with mesh context: same disk bytes as unsharded, and
    telemetry reports per-device launch counts."""
    from repro.ftx import RepairOptions, repair_failed_nodes

    sa = _filled_store(tmp_path / "a")
    sb = _filled_store(tmp_path / "b")
    node = sa.stripes[0].node_of_block[0]

    with with_rules(_mesh()) as mr:
        rep = repair_failed_nodes(sa, [node], options=RepairOptions(mesh_rules=mr))
    assert rep.stripes_repaired > 0
    assert rep.devices == 8
    # every pattern group is an 8-stripe chunk -> every launch spans 8 devices
    assert rep.device_launches == 8 * rep.launches

    rep_b = repair_failed_nodes(sb, [node])
    assert rep_b.devices == 1
    assert rep_b.device_launches == rep_b.launches

    for sid in sa.stripes:
        for b in range(sa.scheme.n):
            assert sa._block_path(sid, b).read_bytes() == \
                sb._block_path(sid, b).read_bytes(), (sid, b)


@multidevice
def test_store_ambient_rules_picked_up(tmp_path):
    """repair_all with no explicit mesh_rules uses the ambient context."""
    store = _filled_store(tmp_path / "s", block_size=512)
    store.fail_node(store.stripes[0].node_of_block[0])
    with with_rules(_mesh()):
        tele = store.repair_all()
    assert tele["devices"] == 8
    assert tele["device_launches"] == 8 * tele["launches"]
