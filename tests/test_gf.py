"""GF(2^8) field properties + the paper's Appendix Theorem 1."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import gf
from repro.core.cauchy import (
    cauchy_matrix,
    theorem1_coefficients,
    uniform_combination_coefficients,
    vandermonde_matrix,
    verify_mds,
)

bytes_ = st.integers(0, 255)
nz_bytes = st.integers(1, 255)


@given(bytes_, bytes_, bytes_)
@settings(max_examples=200, deadline=None)
def test_field_axioms(a, b, c):
    mul, add = gf.gf_mul, lambda x, y: int(x) ^ int(y)
    assert mul(a, b) == mul(b, a)
    assert mul(a, mul(b, c)) == mul(mul(a, b), c)
    # distributivity
    assert int(mul(a, add(b, c))) == add(mul(a, b), mul(a, c))
    assert mul(a, 1) == a and mul(a, 0) == 0


@given(nz_bytes)
@settings(max_examples=100, deadline=None)
def test_inverse(a):
    assert gf.gf_mul(a, gf.gf_inv(a)) == 1


@given(st.integers(2, 20), st.integers(1, 8), st.integers(0, 1000))
@settings(max_examples=30, deadline=None)
def test_matmul_matches_naive(m, k, seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 256, (m, k), dtype=np.uint8)
    b = rng.integers(0, 256, (k, 7), dtype=np.uint8)
    got = gf.gf_matmul(a, b)
    want = np.zeros((m, 7), np.uint8)
    for i in range(m):
        for j in range(7):
            acc = 0
            for t in range(k):
                acc ^= int(gf.gf_mul(a[i, t], b[t, j]))
            want[i, j] = acc
    assert (got == want).all()


@given(st.integers(2, 24), st.integers(0, 500))
@settings(max_examples=30, deadline=None)
def test_matrix_inverse(n, seed):
    rng = np.random.default_rng(seed)
    for _ in range(10):
        m = rng.integers(0, 256, (n, n), dtype=np.uint8)
        if gf.gf_rank(m) == n:
            break
    else:
        pytest.skip("no invertible sample")
    inv = gf.gf_mat_inv(m)
    assert (gf.gf_matmul(m, inv) == np.eye(n, dtype=np.uint8)).all()


@given(st.integers(0, 255))
@settings(max_examples=60, deadline=None)
def test_bitmatrix_representation(c):
    """M_c applied to bits of v == bits of c*v, for all v (vectorized)."""
    m = gf.coeff_bitmatrix(c)
    v = np.arange(256, dtype=np.uint8)
    bits = (v[None, :] >> np.arange(8)[:, None]) & 1      # (8, 256)
    out_bits = (m @ bits) % 2
    got = np.zeros(256, np.uint8)
    for i in range(8):
        got |= (out_bits[i] << i).astype(np.uint8)
    assert (got == gf.gf_mul(c, v)).all()


def test_gf_solve_any_consistency(rng):
    for _ in range(20):
        a = rng.integers(0, 256, (6, 9), dtype=np.uint8)
        x0 = rng.integers(0, 256, 9, dtype=np.uint8)
        y = gf.gf_matvec(a, x0)
        x = gf.gf_solve_any(a, y)
        assert x is not None
        assert (gf.gf_matvec(a, x) == y).all()


@pytest.mark.parametrize("k,r", [(6, 2), (12, 2), (16, 3), (24, 2), (48, 4),
                                 (96, 5), (128, 4)])
def test_cauchy_mds(k, r):
    m = cauchy_matrix(k, r)
    assert (m != 0).all()
    assert verify_mds(m, trials=40)


@pytest.mark.parametrize("k,r", [(6, 2), (16, 3), (24, 2)])
def test_vandermonde_mds(k, r):
    assert verify_mds(vandermonde_matrix(k, r), trials=40)


@pytest.mark.parametrize("k,r", [(6, 2), (12, 2), (16, 3), (20, 3), (48, 4),
                                 (96, 5)])
def test_theorem1_identity(k, r):
    """gamma_bar_i + sum_j eta_bar_j alpha_ij == 0 (Appendix, Theorem 1)."""
    alpha = cauchy_matrix(k, r)
    gamma, eta = theorem1_coefficients(k, r)
    assert (gamma != 0).all() and (eta != 0).all()
    for i in range(k):
        acc = int(gamma[i])
        for j in range(r):
            acc ^= int(gf.gf_mul(eta[j], alpha[j, i]))
        assert acc == 0


@pytest.mark.parametrize("k,r", [(6, 2), (16, 3), (96, 5)])
def test_eq10_identity(k, r):
    """G_r == sum gamma_i D_i + sum eta_j G_j on random data (Eq. 10)."""
    rng = np.random.default_rng(1)
    alpha = cauchy_matrix(k, r)
    gamma, eta = uniform_combination_coefficients(k, r)
    data = rng.integers(0, 256, (k, 33), dtype=np.uint8)
    g = gf.gf_matmul(alpha, data)
    rhs = gf.gf_matmul(gamma.reshape(1, -1), data)[0]
    for j in range(r - 1):
        rhs ^= gf.gf_mul(eta[j], g[j])
    assert (rhs == g[r - 1]).all()
