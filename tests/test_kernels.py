"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, bit-exact."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.gf import gf_matmul, matrix_to_bitmatrix
from repro.kernels import ref as R
from repro.kernels.bitmatrix_encode import (bitmatrix_encode_batched,
                                           mod2_matmul_encode_batched)
from repro.kernels.gf256_matmul import gf256_matmul_batched
from repro.kernels.ops import gf_matmul_batch_op

SHAPES = [(2, 4, 128), (4, 6, 256), (8, 24, 512), (9, 96, 128), (3, 17, 384)]


@pytest.mark.parametrize("m,k,b", SHAPES)
def test_gf256_matmul_kernel(m, k, b, rng):
    coef = rng.integers(0, 256, (m, k), dtype=np.uint8)
    data = rng.integers(0, 256, (k, b), dtype=np.uint8)
    want = gf_matmul(coef, data)
    got = np.asarray(gf_matmul_batch_op(coef, data[None], backend="gf",
                                        force_pallas=True)[0])
    assert (got == want).all()


@pytest.mark.parametrize("m,k,b", SHAPES)
def test_refs_agree(m, k, b, rng):
    coef = rng.integers(0, 256, (m, k), dtype=np.uint8)
    data = rng.integers(0, 256, (k, b), dtype=np.uint8)
    want = gf_matmul(coef, data)
    r1 = np.asarray(R.gf256_matmul_ref(jnp.asarray(coef), jnp.asarray(data)))
    r2 = np.asarray(R.gf256_matmul_shift_ref(jnp.asarray(coef),
                                             jnp.asarray(data)))
    assert (r1 == want).all() and (r2 == want).all()


@pytest.mark.parametrize("m,k,b", SHAPES)
@pytest.mark.parametrize("backend", ["crs", "mxu"])
def test_bitmatrix_kernels(m, k, b, backend, rng):
    coef = rng.integers(0, 256, (m, k), dtype=np.uint8)
    data = rng.integers(0, 256, (k, b), dtype=np.uint8)
    want = gf_matmul(coef, data)
    got = np.asarray(gf_matmul_batch_op(coef, data[None], backend=backend,
                                        force_pallas=True)[0])
    assert (got == want).all(), backend


@given(st.integers(1, 6), st.integers(2, 12), st.integers(1, 40),
       st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_property_all_backends_agree(m, k, nwords, seed):
    """Any (m, k, B): every backend computes the same parity bytes, the
    Pallas ones through their kernels under the interpreter."""
    rng = np.random.default_rng(seed)
    b = nwords * 8
    coef = rng.integers(1, 256, (m, k), dtype=np.uint8)
    data = rng.integers(0, 256, (k, b), dtype=np.uint8)
    want = gf_matmul(coef, data)
    for backend in ("gf", "crs", "mxu", "ref"):
        got = np.asarray(gf_matmul_batch_op(
            coef, data[None], backend=backend,
            force_pallas=backend != "ref")[0])
        assert (got == want).all(), backend


@given(st.integers(1, 8), st.integers(1, 64), st.integers(0, 10_000))
@settings(max_examples=30, deadline=None)
def test_packetize_roundtrip(k, words, seed):
    rng = np.random.default_rng(seed)
    blocks = rng.integers(0, 256, (k, words * 8), dtype=np.uint8)
    pk = R.packetize(jnp.asarray(blocks))
    assert pk.shape == (k * 8, words)
    back = np.asarray(R.unpacketize(pk))
    assert (back == blocks).all()


def test_kernel_tile_sweep(rng):
    """The batched kernels called directly, across their tilings: blocks of
    fewer than ROWS lane rows (the whole axis in one block, 5 rows at
    b = 640) and several grid steps (b = 8192: two ROWS-row steps of the
    gf kernel, two TILE_P tiles of the mxu kernel)."""
    coef = rng.integers(0, 256, (8, 16), dtype=np.uint8)
    bm = jnp.asarray(matrix_to_bitmatrix(coef))
    for s, b in [(1, 128), (2, 640), (1, 8192)]:
        data = rng.integers(0, 256, (s, 16, b), dtype=np.uint8)
        want = np.stack([gf_matmul(coef, d) for d in data])
        got = gf256_matmul_batched(jnp.asarray(coef), jnp.asarray(data),
                                   interpret=True)
        assert (np.asarray(got) == want).all(), (s, b)
        pad = max(1024, b) - b
        padded = jnp.pad(jnp.asarray(data), ((0, 0), (0, 0), (0, pad)))
        pk = R.packetize_batched(padded)
        want_pk = R.bitmatrix_encode_batched_ref(bm, pk)
        for kernel in (bitmatrix_encode_batched, mod2_matmul_encode_batched):
            got = kernel(bm, pk, interpret=True)
            assert (np.asarray(got) == np.asarray(want_pk)).all(), (kernel, b)


@pytest.mark.parametrize("n_rows", [64, 17])
def test_block_rows_fit_vmem(n_rows, monkeypatch, rng):
    """Grid steps take ROWS lane rows unless the step would overflow the
    VMEM budget, as the P8 bit-plane decode (768 x 768 bits) would at 1 MiB
    blocks. A halved step computes the ref oracle's bytes, also on a short
    axis that it does not divide (17 lane rows under 8-row steps), in the
    gf kernel and in the crs bit-plane launch of the same kernel."""
    from repro.kernels import gf256_matmul as G
    from repro.kernels.ops import gf_matmul_batch_op

    assert G.block_rows(24, 4, 1024) == G.ROWS        # P5 GF encode
    assert G.block_rows(6, 5, 3) == 3                 # short axis, whole
    assert G.block_rows(768, 768, 1024) == 16         # P8 bit-plane decode
    assert G.block_rows(768, 768, 17) == 16           # ... at 17 KiB blocks
    coef = rng.integers(0, 256, (5, 6), dtype=np.uint8)
    monkeypatch.setattr(G, "_VMEM_BUDGET", 8 * G.LANES * (2 * 6 + 6 * 5))
    assert G.block_rows(6, 5, n_rows) == 8
    # gf: n_rows lane rows of bytes; crs: n_rows lane rows of packets.
    for b, run in [
        (n_rows * G.LANES,
         lambda d: G.gf256_matmul_batched(jnp.asarray(coef), jnp.asarray(d),
                                          interpret=True)),
        (8 * n_rows * G.LANES,
         lambda d: gf_matmul_batch_op(coef, d, backend="crs",
                                      interpret=True, force_pallas=True)),
    ]:
        data = rng.integers(0, 256, (2, 6, b), dtype=np.uint8)
        want = R.gf256_matmul_batched_ref(jnp.asarray(coef), jnp.asarray(data))
        assert (np.asarray(run(data)) == np.asarray(want)).all(), b
