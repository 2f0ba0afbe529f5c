"""Stripe codec: encode / repair / decode roundtrips, property-based."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.codec import StripeCodec
from repro.core.schemes import SCHEMES, make_scheme

ALL = sorted(SCHEMES)


@pytest.mark.parametrize("name", ALL)
def test_single_repair_every_block(name, rng):
    s = make_scheme(name, 6, 2, 2)
    codec = StripeCodec(s)
    data = rng.integers(0, 256, (6, 64), dtype=np.uint8)
    stripe = np.asarray(codec.encode(data))
    for b in range(s.n):
        avail = {i: stripe[i] for i in range(s.n) if i != b}
        blk, plan = codec.repair_single(b, avail)
        assert (np.asarray(blk) == stripe[b]).all(), (name, b, plan.method)


@given(st.sampled_from(ALL), st.integers(0, 10_000), st.integers(1, 2))
@settings(max_examples=40, deadline=None)
def test_property_within_tolerance_always_repairs(name, seed, nfail):
    """Any failure pattern of size <= tolerance repairs bit-exactly."""
    rng = np.random.default_rng(seed)
    s = make_scheme(name, 8, 2, 2)
    nfail = min(nfail, s.tolerance)
    codec = StripeCodec(s)
    data = rng.integers(0, 256, (8, 40), dtype=np.uint8)
    stripe = np.asarray(codec.encode(data))
    failed = frozenset(rng.choice(s.n, nfail, replace=False).tolist())
    avail = {i: stripe[i] for i in range(s.n) if i not in failed}
    rebuilt, plan = codec.repair_multi(failed, avail)
    assert plan.feasible
    for b in failed:
        assert (np.asarray(rebuilt[b]) == stripe[b]).all()


@given(st.sampled_from(ALL), st.integers(0, 10_000), st.integers(3, 4))
@settings(max_examples=30, deadline=None)
def test_property_decodable_iff_rank(name, seed, nfail):
    """Beyond the guarantee: repair succeeds exactly when rank says so."""
    rng = np.random.default_rng(seed)
    s = make_scheme(name, 8, 2, 2)
    codec = StripeCodec(s)
    data = rng.integers(0, 256, (8, 24), dtype=np.uint8)
    stripe = np.asarray(codec.encode(data))
    failed = frozenset(rng.choice(s.n, nfail, replace=False).tolist())
    avail = {i: stripe[i] for i in range(s.n) if i not in failed}
    if s.decodable(failed):
        rebuilt, _ = codec.repair_multi(failed, avail)
        for b in failed:
            assert (np.asarray(rebuilt[b]) == stripe[b]).all()
    else:
        with pytest.raises(RuntimeError):
            codec.repair_multi(failed, avail)


@pytest.mark.parametrize("name", ["cp-azure", "cp-uniform"])
@pytest.mark.parametrize("backend", ["gf", "crs", "mxu", "ref"])
def test_encode_backends_match(name, backend, rng):
    """The codec's stripes, and the backend's Pallas kernel (interpreted)
    on the same parity matrix, match the numpy ground truth."""
    from repro.kernels.ops import gf_matmul_op

    s = make_scheme(name, 12, 3, 3)
    codec = StripeCodec(s, backend=backend)
    data = rng.integers(0, 256, (12, 80), dtype=np.uint8)
    stripe = np.asarray(codec.encode(data))
    want = s.encode(data)  # numpy planning-tier ground truth
    assert (stripe == want).all(), backend
    parity = gf_matmul_op(s.parity_matrix(), data, backend=backend,
                          force_pallas=backend != "ref")
    assert (np.asarray(parity) == want[s.k:]).all(), backend


def test_decode_all_any_rank_k_subset(rng):
    s = make_scheme("cp-uniform", 6, 2, 2)
    codec = StripeCodec(s)
    data = rng.integers(0, 256, (6, 48), dtype=np.uint8)
    stripe = np.asarray(codec.encode(data))
    for _ in range(10):
        ids = sorted(rng.choice(s.n, s.k, replace=False).tolist())
        from repro.core.gf import gf_rank

        if gf_rank(s.gen[ids]) < s.k:
            continue
        dec = np.asarray(codec.decode_all({i: stripe[i] for i in ids}))
        assert (dec == data).all()
