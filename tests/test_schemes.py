"""Scheme construction invariants: cascade identity, distance, coverage."""
import itertools

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import gf
from repro.core.schemes import PAPER_PARAMS, SCHEMES, make_scheme

ALL = sorted(SCHEMES)
SMALL = [(6, 2, 2), (12, 2, 2), (16, 3, 2), (20, 3, 5), (9, 3, 3), (10, 2, 3)]


@pytest.mark.parametrize("name", ALL)
@pytest.mark.parametrize("krp", SMALL)
def test_construction_invariants(name, krp):
    k, r, p = krp
    if name == "azure+1" and p < 2:
        pytest.skip("azure+1 needs p>=2")
    s = make_scheme(name, k, r, p)
    assert s.n == k + r + p
    # data rows are identity
    assert (s.gen[:k] == np.eye(k, dtype=np.uint8)).all()
    # every local parity row equals its group composition
    for g in s.groups:
        row = np.zeros(k, np.uint8)
        for b, c in zip(g.items, g.coeffs):
            row ^= gf.gf_mul(np.uint8(c), s.gen[b])
        assert (row == s.gen[g.parity]).all(), (name, g.gid)
    # cascade: XOR of local parities == G_r
    if s.cascade is not None:
        acc = np.zeros(k, np.uint8)
        for b in s.cascade.members[:-1]:
            acc ^= s.gen[b]
        assert (acc == s.gen[s.cascade.members[-1]]).all()
    # every data block is covered by exactly one group for non-optimal
    covered = [0] * k
    for g in s.groups:
        for b in g.items:
            if b < k:
                covered[b] += 1
    assert all(c >= 1 for c in covered)


@pytest.mark.parametrize("name", ALL)
@pytest.mark.parametrize("krp", [(6, 2, 2), (9, 3, 3), (12, 2, 2)])
def test_guaranteed_tolerance_exhaustive(name, krp):
    """Every pattern of size <= scheme.tolerance is decodable (exhaustive)."""
    k, r, p = krp
    s = make_scheme(name, k, r, p)
    t = s.tolerance
    untouched = make_scheme(name, k, r, p)
    for f in range(1, t + 1):
        for pat in itertools.combinations(range(s.n), f):
            alive = [b for b in range(s.n) if b not in pat]
            assert gf.gf_rank(untouched.gen[alive]) == k, (name, pat)


@pytest.mark.parametrize("name", ["cp-azure", "cp-uniform"])
def test_cp_distance_is_exactly_r_plus_1(name):
    """CP-LRCs tolerate any r failures but not all r+1 (paper §IV)."""
    s = make_scheme(name, 6, 2, 2)
    bad = 0
    for pat in itertools.combinations(range(s.n), s.r + 1):
        alive = [b for b in range(s.n) if b not in pat]
        if gf.gf_rank(s.gen[alive]) < s.k:
            bad += 1
    assert bad > 0  # minimum distance exactly r+1


def test_cp_spread_failures_decodable():
    """r+i failures decodable when i failures land in i distinct groups."""
    s = make_scheme("cp-azure", 12, 2, 3)
    # 2 globals + one data failure per distinct group
    g0 = s.groups[0].items[0]
    g1 = s.groups[1].items[0]
    pat = frozenset([g0, g1] + list(s.global_ids)[:2])
    assert s.decodable(pat)


@given(st.sampled_from(ALL), st.integers(0, 3))
@settings(max_examples=24, deadline=None)
def test_paper_params_construct(name, idx):
    lbl = list(PAPER_PARAMS)[idx]
    k, r, p = PAPER_PARAMS[lbl]
    s = make_scheme(name, k, r, p)
    assert s.n == k + r + p
    assert len(s.groups) == p


@pytest.mark.parametrize("krp", [(7, 2, 2), (11, 3, 2), (13, 2, 3)])
def test_non_divisible_parameters(krp):
    """k % p != 0 and (k+r-1) % p != 0 still construct and hold identities."""
    k, r, p = krp
    for name in ("azure", "optimal", "uniform", "cp-azure", "cp-uniform"):
        s = make_scheme(name, k, r, p)
        sizes = [len(g.items) for g in s.groups]
        assert max(sizes) - min(sizes) <= 1 or name in ("uniform",)
