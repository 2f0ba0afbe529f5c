"""Batched engine + planner: bit-exact vs the numpy reference and vs
one-stripe (S=1) launches, ragged batches, plan-cache behavior, batched
kernel lockstep."""
import numpy as np
import pytest

from repro.core.engine import BatchedCodecEngine
from repro.core.gf import gf_rank
from repro.core.planner import RepairPlanner
from repro.core.schemes import SCHEMES as ALL_SCHEMES, make_scheme
from repro.kernels.ops import default_backend

SCHEMES = ("cp-azure", "cp-uniform", "azure")


def _stack(stripes, ids):
    return {b: stripes[:, b, :] for b in ids}


@pytest.fixture(params=SCHEMES)
def pair(request):
    # default_backend() honors REPRO_BACKEND, so the CI backend-matrix legs
    # (REPRO_BACKEND=crs / =mxu) drive this whole module through the
    # bit-plane backends.
    s = make_scheme(request.param, 8, 2, 2)
    return s, BatchedCodecEngine(s, backend=default_backend())


# ------------------------------------------------------------------ encode
@pytest.mark.parametrize("S", [1, 3, 5])  # ragged/odd batch sizes included
def test_batched_encode_matches_per_stripe(pair, rng, S):
    s, engine = pair
    data = rng.integers(0, 256, (S, s.k, 96), dtype=np.uint8)
    batch = engine.encode(data)
    for i in range(S):
        assert (batch[i] == s.encode(data[i])).all(), i


@pytest.mark.parametrize("geom,S,B", [(("cp-azure", 12, 3, 3), 1, 80),
                                      (("cp-uniform", 12, 3, 3), 1, 80),
                                      (("cp-azure", 6, 2, 2), 4, 72)])
@pytest.mark.parametrize("backend", ["gf", "crs", "mxu", "ref"])
def test_batched_encode_all_backends(geom, S, B, backend, rng):
    """The engine's stripes, and the backend's Pallas kernel (interpreted)
    on the same parity matrix, match the numpy ground truth."""
    from repro.kernels.ops import gf_matmul_batch_op

    s = make_scheme(*geom)
    engine = BatchedCodecEngine(s, backend=backend)
    data = rng.integers(0, 256, (S, s.k, B), dtype=np.uint8)
    batch = engine.encode(data)
    parity = np.asarray(gf_matmul_batch_op(s.parity_matrix(), data,
                                           backend=backend,
                                           force_pallas=backend != "ref"))
    for i in range(S):
        want = s.encode(data[i])  # numpy planning-tier ground truth
        assert (batch[i] == want).all(), backend
        assert (parity[i] == want[s.k:]).all(), backend


# ------------------------------------------------------------------ repair
@pytest.mark.parametrize("name,k,S", [(n, 6, 1) for n in sorted(ALL_SCHEMES)]
                         + [(n, 8, 4) for n in SCHEMES])
def test_batched_single_repair_every_block(name, k, S, rng):
    """Every block of every scheme rebuilds bit-exactly, and a batch of S
    stripes equals S one-stripe launches."""
    s = make_scheme(name, k, 2, 2)
    engine = BatchedCodecEngine(s, backend=default_backend())
    data = rng.integers(0, 256, (S, s.k, 64), dtype=np.uint8)
    stripes = engine.encode(data)
    for failed in range(s.n):
        avail = _stack(stripes, [i for i in range(s.n) if i != failed])
        out, plan = engine.repair_single(failed, avail)
        assert (out == stripes[:, failed, :]).all(), (failed, plan.meta.method)
        loop = np.concatenate([
            engine.repair_single(failed, {b: v[j:j + 1]
                                          for b, v in avail.items()})[0]
            for j in range(S)])
        assert (out == loop).all(), failed


def test_batched_multi_repair_cascade(pair, rng):
    s, engine = pair
    S = 3
    data = rng.integers(0, 256, (S, s.k, 48), dtype=np.uint8)
    stripes = np.asarray(engine.encode(data))
    pattern = frozenset({0, s.k})  # data + local parity: the cascading case
    avail = _stack(stripes, [i for i in range(s.n) if i not in pattern])
    rebuilt, plan = engine.repair_multi(pattern, avail)
    assert set(rebuilt) == set(pattern)
    for b in pattern:
        assert (np.asarray(rebuilt[b]) == stripes[:, b, :]).all(), b
    # one flattened launch: coeff matrix covers every target at once
    compiled = engine.planner.multi_plan(pattern)
    assert compiled.coeffs.shape == (len(pattern), len(compiled.reads))


def test_batched_repair_accepts_dense_availability(pair, rng):
    s, engine = pair
    data = rng.integers(0, 256, (2, s.k, 32), dtype=np.uint8)
    stripes = np.asarray(engine.encode(data))
    out, _ = engine.repair_single(1, stripes)  # (S, n, B) array form
    assert (np.asarray(out) == stripes[:, 1, :]).all()


def test_batched_repair_missing_read_raises(pair, rng):
    s, engine = pair
    data = rng.integers(0, 256, (2, s.k, 32), dtype=np.uint8)
    stripes = np.asarray(engine.encode(data))
    plan = engine.planner.single_plan(0)
    some_read = plan.reads[0]
    avail = _stack(stripes, [i for i in range(1, s.n) if i != some_read])
    with pytest.raises(KeyError):
        engine.repair_single(0, avail)


# ------------------------------------------------------------------ decode
@pytest.mark.parametrize("name,k,S", [("cp-uniform", 6, 1)]
                         + [(n, 8, 3) for n in SCHEMES])
def test_batched_decode_any_rank_k_subset(name, k, S, rng):
    s = make_scheme(name, k, 2, 2)
    engine = BatchedCodecEngine(s, backend=default_backend())
    data = rng.integers(0, 256, (S, s.k, 40), dtype=np.uint8)
    stripes = engine.encode(data)
    hits = 0
    for _ in range(10):
        ids = sorted(rng.choice(s.n, s.k, replace=False).tolist())
        if gf_rank(s.gen[ids]) < s.k:
            continue
        hits += 1
        dec = engine.decode(_stack(stripes, ids))
        assert (dec == data).all()
        loop = np.concatenate([engine.decode(
            {i: stripes[j:j + 1, i, :] for i in ids}) for j in range(S)])
        assert (dec == loop).all()
    assert hits > 0


# ------------------------------------------------------------- plan cache
def test_plan_cache_hit_miss_counters():
    s = make_scheme("cp-azure", 6, 2, 2)
    planner = RepairPlanner(s)
    assert planner.stats.lookups == 0
    p1 = planner.multi_plan({0, s.k})
    assert planner.stats.misses == 1 and planner.stats.hits == 0
    p2 = planner.multi_plan({s.k, 0})  # order-insensitive key
    assert planner.stats.hits == 1 and planner.stats.misses == 1
    assert p1 is p2
    planner.single_plan(0)
    planner.single_plan(0)
    planner.single_plan(0, policy="min")  # distinct key per policy
    assert planner.stats.misses == 3 and planner.stats.hits == 2


def test_plan_cache_lru_eviction():
    s = make_scheme("cp-azure", 6, 2, 2)
    planner = RepairPlanner(s, maxsize=2)
    planner.single_plan(0)
    planner.single_plan(1)
    planner.single_plan(2)  # evicts block 0's plan
    assert planner.stats.evictions == 1
    planner.single_plan(0)
    assert planner.stats.misses == 4  # recompiled after eviction


def test_one_planner_serves_get_and_read(tmp_path, rng):
    """The object read ``get`` and the serving read ``read`` plan a lost
    block through the same ``RepairPlanner.serving_plan``: a degraded
    ``get`` compiles the plan, and ``read`` of the same block (no hot
    cache) finds it cached: one miss between them."""
    from repro.ftx import StoreConfig, StripeStore

    cfg = StoreConfig(scheme="cp-uniform", k=6, r=2, p=2, block_size=512,
                      read_cache_blocks=0)
    store = StripeStore(tmp_path / "s", cfg)
    payload = rng.integers(0, 256, 300, dtype=np.uint8)
    store.put("o", payload.tobytes())
    store.seal()
    meta = store.objects["o"]
    store.fail_node(store.stripes[meta.sid].node_of_block[meta.block])
    before = store.engine.planner.stats.snapshot()
    assert (store.get("o") == payload).all()
    got = store.read(meta.sid, meta.block)
    after = store.engine.planner.stats.snapshot()
    assert (got[meta.offset:meta.offset + payload.size] == payload).all()
    assert after["misses"] - before["misses"] == 1
    assert after["hits"] - before["hits"] == 1


def test_infeasible_pattern_raises():
    s = make_scheme("azure", 6, 2, 2)
    planner = RepairPlanner(s)
    # k+1 failures can never be decodable (rank < k survives)
    with pytest.raises(RuntimeError):
        planner.multi_plan(set(range(s.k + 1)))


# ------------------------------------------------- batched kernel lockstep
def test_batched_pallas_kernel_lockstep(rng):
    """The batched-grid Pallas kernel (interpreted) matches the table oracle
    exactly — uneven shapes exercise the padding path."""
    from repro.kernels.ops import gf_matmul_batch_op

    for (S, t, R, B) in [(1, 1, 5, 100), (3, 2, 9, 257), (5, 8, 12, 128)]:
        coef = rng.integers(0, 256, (t, R), dtype=np.uint8)
        data = rng.integers(0, 256, (S, R, B), dtype=np.uint8)
        want = np.asarray(gf_matmul_batch_op(coef, data, backend="ref"))
        got = np.asarray(gf_matmul_batch_op(coef, data, backend="gf",
                                            interpret=True, force_pallas=True))
        assert (got == want).all(), (S, t, R, B)
        fast = np.asarray(gf_matmul_batch_op(coef, data, backend="gf"))
        assert (fast == want).all(), (S, t, R, B)


def test_batch_op_rejects_unknown_backend(rng):
    from repro.kernels.ops import gf_matmul_batch_op

    data = rng.integers(0, 256, (2, 3, 16), dtype=np.uint8)
    coef = rng.integers(0, 256, (1, 3), dtype=np.uint8)
    with pytest.raises(ValueError):
        gf_matmul_batch_op(coef, data, backend="nope")


def test_batch_op_all_backends_bit_identical(rng):
    """Every registered backend — including the bit-plane pair, which used
    to be silently downgraded — runs the batched matmul bit-identically."""
    from repro.kernels.ops import BACKENDS, gf_matmul_batch_op

    coef = rng.integers(0, 256, (3, 5), dtype=np.uint8)
    data = rng.integers(0, 256, (4, 5, 200), dtype=np.uint8)
    want = np.asarray(gf_matmul_batch_op(coef, data, backend="ref"))
    for backend in BACKENDS:
        got = np.asarray(gf_matmul_batch_op(coef, data, backend=backend))
        assert (got == want).all(), backend


def test_batch_op_rejects_wrong_bitmatrix_shape(rng):
    from repro.kernels.ops import gf_matmul_batch_op

    coef = rng.integers(0, 256, (2, 3), dtype=np.uint8)
    data = rng.integers(0, 256, (2, 3, 16), dtype=np.uint8)
    bad = np.zeros((16, 16), dtype=np.uint8)   # want (16, 24)
    with pytest.raises(ValueError):
        gf_matmul_batch_op(coef, data, backend="crs", bitmatrix=bad)


def test_bit_plane_batched_kernels_lockstep(rng):
    """The stripe-grid crs/mxu Pallas kernels (interpreted, force_pallas)
    match the table oracle exactly, including the B-padding path."""
    from repro.kernels.ops import gf_matmul_batch_op

    for (S, t, R, B) in [(1, 1, 5, 104), (3, 2, 9, 264), (4, 4, 7, 128)]:
        coef = rng.integers(0, 256, (t, R), dtype=np.uint8)
        data = rng.integers(0, 256, (S, R, B), dtype=np.uint8)
        want = np.asarray(gf_matmul_batch_op(coef, data, backend="ref"))
        for backend in ("crs", "mxu"):
            got = np.asarray(gf_matmul_batch_op(
                coef, data, backend=backend, interpret=True,
                force_pallas=True))
            assert (got == want).all(), (backend, S, t, R, B)


# ------------------------------------------------------------- put
@pytest.mark.parametrize("backend", ["gf", "crs", "mxu"])
def test_execute_on_a_put_stack_matches_the_host_stack(backend, rng,
                                                       monkeypatch):
    """``execute`` gives the same bytes on a host stack and on one ``put``
    first; untraced, it hands a host stack to the jit as it is, with no
    explicit ``jax.device_put``, and ``put``'s device value is a copy that
    outlives the host stack's bytes."""
    import jax

    s = make_scheme("cp-azure", 6, 2, 2)
    engine = BatchedCodecEngine(s, backend=backend)
    plan = engine.planner.multi_plan({0, s.k})
    stack = rng.integers(0, 256, (3, len(plan.reads), 128), dtype=np.uint8)
    puts = []
    device_put = jax.device_put

    def counted(*args, **kw):
        puts.append(args[0])
        return device_put(*args, **kw)

    monkeypatch.setattr(jax, "device_put", counted)
    want = engine.execute(plan, stack)
    assert puts == []
    assert isinstance(want, np.ndarray)
    assert want.shape == (3, len(plan.targets), 128)
    on_device = engine.put(stack)
    assert isinstance(on_device, jax.Array) and len(puts) == 1
    stack.fill(0)                        # the device holds a copy of its own
    assert np.array_equal(engine.execute(plan, on_device), want)
    assert len(puts) == 1
    assert engine.effective_backend and engine.last_span == 1


# -------------------------------------------------------- store integration
def test_store_batched_repair_bit_identical_and_ragged(tmp_path, rng):
    """Fleet repair through the store rewrites every lost block with the
    bytes it held before the failure; batch_stripes=2 forces ragged last
    chunks."""
    from repro.ftx import StoreConfig, StripeStore, repair_failed_nodes

    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=1024,
                      batch_stripes=2)
    store = StripeStore(tmp_path / "a", cfg)
    r = np.random.default_rng(7)
    for i in range(5):
        store.put(f"o{i}", r.integers(0, 256, 5000, dtype=np.uint8).tobytes())
    store.seal()
    truth = {(sid, b): store._block_path(sid, b).read_bytes()
             for sid in store.stripes for b in range(store.scheme.n)}
    node = store.stripes[0].node_of_block[0]
    lost = [k for k in truth if store.stripes[k[0]].node_of_block[k[1]] == node]
    for sid, b in lost:
        store._block_path(sid, b).unlink()

    rep = repair_failed_nodes(store, [node])
    assert rep.stripes_repaired == len(lost) > 0
    assert rep.plan_cache["misses"] >= 1
    assert {k: store._block_path(*k).read_bytes() for k in truth} == truth


def test_store_unrecoverable_raises_ioerror_both_paths(tmp_path):
    """Pipelined and synchronous repair_all share the IOError contract on
    an unrecoverable stripe (neither leaks planner RuntimeErrors)."""
    from repro.ftx import RepairOptions, StoreConfig, StripeStore

    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2, block_size=512)
    store = StripeStore(tmp_path / "s", cfg)
    store.put("o", bytes(2000))
    store.seal()
    # Down 5 blocks of stripe 0: beyond p+r, never decodable.
    for b in range(5):
        store.fail_node(store.stripes[0].node_of_block[b])
    for pipeline in (True, False):
        with pytest.raises(IOError):
            store.repair_all(options=RepairOptions(pipeline=pipeline))
