"""Backend bit-parity: encode/repair/decode through every registered kernel
backend must produce identical bytes — batched engine, sharded launches, and
the store's sync / pipelined / degraded-serving paths.

Since PR 7 the bit-plane backends (crs/mxu) are first-class through the
whole stack: there is no silent ``matmul_backend`` downgrade left, so these
tests drive the *actual* crs/mxu formulations (their jnp references on the
CPU interpret path — same math, fused) and assert bit-identity against the
table oracle. The 1-device cases always run; the 8-device cases run in the
forced-8-device CI leg. ``effective_backend`` telemetry is pinned here too:
gf batches report "ref" on interpreter hosts, everything else reports
itself.
"""
import jax
import numpy as np
import pytest

from repro.core.engine import BatchedCodecEngine
from repro.core.schemes import make_scheme
from repro.dist.sharding import with_rules
from repro.kernels.ops import BACKENDS, effective_backend
from repro.launch.mesh import make_mesh

multidevice = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

SCHEMES = ("cp-azure", "cp-uniform")
# Single failure (one data block) and double failure (data block + its
# local parity — the cascading case).
PATTERNS = ("single", "double")


def _mesh():
    return make_mesh((8, 1), ("data", "model"))


def _pattern(scheme, kind):
    return frozenset({0} if kind == "single" else {0, scheme.k})


@pytest.fixture(scope="module", params=SCHEMES)
def reference(request):
    """Per-scheme golden bytes from the table oracle: encoded stripes plus
    repaired blocks for the single and double failure patterns."""
    scheme = make_scheme(request.param, 8, 2, 2)
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (8, scheme.k, 512), dtype=np.uint8)
    ref = BatchedCodecEngine(scheme, backend="ref")
    stripes = np.asarray(ref.encode(data))
    want = {}
    for kind in PATTERNS:
        pattern = _pattern(scheme, kind)
        avail = {i: stripes[:, i, :] for i in range(scheme.n)
                 if i not in pattern}
        out, _ = ref.repair_multi(pattern, avail)
        want[kind] = {b: np.asarray(v) for b, v in out.items()}
    return scheme, data, stripes, want


def _check_engine(engine, scheme, data, stripes, want, *, span=1):
    enc = np.asarray(engine.encode(data))
    assert (enc == stripes).all(), f"{engine.backend}: encode bytes differ"
    assert engine.last_span == span
    assert engine.effective_backend == effective_backend(engine.backend)
    for kind in PATTERNS:
        pattern = _pattern(scheme, kind)
        avail = {i: stripes[:, i, :] for i in range(scheme.n)
                 if i not in pattern}
        got, _ = engine.repair_multi(pattern, avail)
        for b in sorted(pattern):
            assert (np.asarray(got[b]) == want[kind][b]).all(), \
                f"{engine.backend}/{kind}: repaired block {b} differs"
    # decode the data blocks with block 0 replaced by its local parity
    ids = list(range(1, scheme.k)) + [scheme.k]
    dec = np.asarray(engine.decode({i: stripes[:, i, :] for i in ids}))
    assert (dec == data).all(), f"{engine.backend}: decode bytes differ"


@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_parity_single_device(backend, reference):
    scheme, data, stripes, want = reference
    eng = BatchedCodecEngine(scheme, backend=backend)
    _check_engine(eng, scheme, data, stripes, want)


@multidevice
@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_parity_sharded_8dev(backend, reference):
    """Same golden bytes through the jit(shard_map) launch on 8 devices."""
    scheme, data, stripes, want = reference
    with with_rules(_mesh()) as mr:
        eng = BatchedCodecEngine(scheme, backend=backend, mesh_rules=mr)
        _check_engine(eng, scheme, data, stripes, want, span=8)


def test_effective_backend_reporting():
    """gf substitutes the fused table path on interpreter hosts (and says
    so); the bit-plane backends and ref always report themselves."""
    on_cpu = jax.default_backend() == "cpu"
    assert effective_backend("gf") == ("ref" if on_cpu else "gf")
    assert effective_backend("gf", force_pallas=True) == "gf"
    assert effective_backend("gf", interpret=False) == "gf"
    for b in ("crs", "mxu", "ref"):
        assert effective_backend(b) == b
    with pytest.raises(ValueError, match="unknown kernel backend"):
        effective_backend("nope")


def test_unknown_backend_rejected():
    scheme = make_scheme("cp-azure", 6, 2, 2)
    with pytest.raises(ValueError, match="unknown kernel backend"):
        BatchedCodecEngine(scheme, backend="nope")


# ----------------------------------------------------------- store parity
def _build_store(root, backend, *, stripes=12, pipeline_window=0):
    from repro.ftx import StoreConfig, StripeStore

    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=512,
                      backend=backend, batch_stripes=4,
                      pipeline_window=pipeline_window, prefetch_threads=2)
    store = StripeStore(root, cfg, num_nodes=cfg.k + cfg.r + cfg.p)
    rng = np.random.default_rng(3)
    payload = rng.integers(0, 256, stripes * cfg.k * cfg.block_size,
                           dtype=np.uint8).tobytes()
    store.put("obj", payload)
    store.seal()
    return store, payload


@pytest.mark.parametrize("backend", ("crs", "mxu"))
@pytest.mark.parametrize("pipelined", (False, True))
def test_store_repair_parity_bit_plane_backends(tmp_path, backend, pipelined):
    """Fleet repair (sync and pipelined) through crs/mxu rebuilds the same
    bytes as the ref store, and the report names the backend that ran."""
    from repro.ftx import repair_failed_nodes

    window = 4 if pipelined else 0
    ref_store, payload = _build_store(tmp_path / "ref", "ref",
                                      pipeline_window=window)
    bit_store, _ = _build_store(tmp_path / backend, backend,
                                pipeline_window=window)
    repair_failed_nodes(ref_store, [0, 6])
    report = repair_failed_nodes(bit_store, [0, 6])
    assert report.effective_backend == backend
    assert report.pipelined == pipelined
    assert bit_store.get("obj").tobytes() == payload
    for sid, stripe in ref_store.stripes.items():
        for b in range(ref_store.scheme.n):
            assert (bit_store._read_block(sid, b)
                    == ref_store._read_block(sid, b)).all(), (sid, b)


@pytest.mark.parametrize("backend", ("crs", "mxu"))
def test_store_degraded_serving_parity(tmp_path, backend):
    """Degraded reads (the serving path) through crs/mxu return the same
    bytes as healthy reads, and the engine records the formulation."""
    store, _ = _build_store(tmp_path / backend, backend)
    sid = min(store.stripes)
    healthy = {b: store.read(sid, b).tobytes()
               for b in range(store.scheme.n)}
    down = store.stripes[sid].node_of_block[0]
    store.fail_node(down)
    served = {b: store.read(sid, b).tobytes()
              for b in range(store.scheme.n)}
    assert served == healthy
    assert store.telemetry.degraded_reads > 0
    assert store.engine.effective_backend == backend
