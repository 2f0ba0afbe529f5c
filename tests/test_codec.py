"""One-stripe codec properties: repair through ``BatchedCodecEngine`` at
S=1, property-based."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.engine import BatchedCodecEngine
from repro.core.schemes import SCHEMES, make_scheme

ALL = sorted(SCHEMES)


@given(st.sampled_from(ALL), st.integers(0, 10_000), st.integers(1, 2))
@settings(max_examples=40, deadline=None)
def test_property_within_tolerance_always_repairs(name, seed, nfail):
    """Any failure pattern of size <= tolerance repairs bit-exactly."""
    rng = np.random.default_rng(seed)
    s = make_scheme(name, 8, 2, 2)
    nfail = min(nfail, s.tolerance)
    engine = BatchedCodecEngine(s)
    data = rng.integers(0, 256, (1, 8, 40), dtype=np.uint8)
    stripe = engine.encode(data)
    failed = frozenset(rng.choice(s.n, nfail, replace=False).tolist())
    avail = {i: stripe[:, i] for i in range(s.n) if i not in failed}
    rebuilt, plan = engine.repair_multi(failed, avail)
    assert plan.meta.feasible
    for b in failed:
        assert (rebuilt[b] == stripe[:, b]).all()


@given(st.sampled_from(ALL), st.integers(0, 10_000), st.integers(3, 4))
@settings(max_examples=30, deadline=None)
def test_property_decodable_iff_rank(name, seed, nfail):
    """Beyond the guarantee: repair succeeds exactly when rank says so."""
    rng = np.random.default_rng(seed)
    s = make_scheme(name, 8, 2, 2)
    engine = BatchedCodecEngine(s)
    data = rng.integers(0, 256, (1, 8, 24), dtype=np.uint8)
    stripe = engine.encode(data)
    failed = frozenset(rng.choice(s.n, nfail, replace=False).tolist())
    avail = {i: stripe[:, i] for i in range(s.n) if i not in failed}
    if s.decodable(failed):
        rebuilt, _ = engine.repair_multi(failed, avail)
        for b in failed:
            assert (rebuilt[b] == stripe[:, b]).all()
    else:
        with pytest.raises(RuntimeError):
            engine.repair_multi(failed, avail)
