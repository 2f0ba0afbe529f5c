"""Pallas TPU kernels: XOR-accumulating matmuls over a stripe batch.

``out[s, i, :] = XOR_j coef[i, j] * data[s, j, :]``

Two products share one kernel body (see DESIGN.md §3):

* GF(2^8) (``gf256_matmul_batched``): ``*`` is the field multiply.
  Jerasure's table-driven SIMD lookups do not map to the TPU VPU (no fast
  byte gather across lanes), so each data row is expanded into its eight
  ``xtime`` powers once and every output row XORs in the powers selected
  by the bits of its scalar coefficient — pure int32 lane ops.
* GF(2) (``bitmatrix_encode.bitmatrix_encode_batched``): coefficients are
  bits, so only the zeroth power exists — select-and-XOR on bit-plane
  packets.

Layout for Mosaic. The byte axis is viewed as ``(rows, 128)`` lanes and a
grid step takes ``ROWS`` of those rows per data row, so every load of data
row ``j`` is a ref index on a leading axis (``data_ref[0, j]``) that widens
to whole (8, 128) int32 vregs. Coefficients ride in SMEM (scalar prefetch)
and are read one scalar per ``(i, j)``: there is no dynamic slice of a
loaded vector value, which Mosaic does not lower.

VMEM per grid step (ROWS = 32, so 4 KiB of every block):
  data block   k x 4 KiB (uint8), double-buffered
  out block    m x 4 KiB (uint8), double-buffered
  accumulator  m x 16 KiB (int32)
At P8 (k = 96) a full GF decode (m = 96) takes 768 + 768 KiB + 1.5 MiB;
the bit-plane decode (k = m = 768) would take 24 MiB, so the block halves
its rows until the step fits a 12 MiB budget under v5e's 16 MiB scoped
VMEM. SMEM holds the coefficients as int32 words (36 KiB at m = k = 96)
of its 1 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.gf import PRIM_POLY

_XT = PRIM_POLY & 0xFF  # 0x1D: xtime reduction constant
LANES = 128
ROWS = 32  # block rows per grid step: one uint8 (32, 128) tile
# Working-set cap per grid step, under v5e's 16 MiB scoped VMEM.
_VMEM_BUDGET = 12 << 20
# Every Pallas kernel of the store is named this prefix plus its
# formulation ("gf", "crs", "mxu"), so a device trace finds the kernels by
# name whatever the surrounding jit is called.
KERNEL_PREFIX = "gf_kernel_"


def padded_length(n: int) -> int:
    """Smallest length >= ``n`` that :func:`xor_matmul` tiles: a whole
    number of lane rows, and of ``ROWS``-row blocks once it exceeds one."""
    rows = -(-n // LANES)
    if rows > ROWS:
        rows = -(-rows // ROWS) * ROWS
    return rows * LANES


def block_rows(k: int, m: int, n_rows: int) -> int:
    """Lane rows per grid step for ``k`` data rows and ``m`` output rows of
    ``n_rows`` lane rows each: ``ROWS``, halved while the step's
    double-buffered uint8 blocks and int32 accumulator exceed the VMEM
    budget, or all ``n_rows`` when fewer. A short axis that a halved step
    does not divide is padded up to whole steps by :func:`xor_matmul`."""
    rows = ROWS
    while rows > 8 and rows * LANES * (2 * k + 6 * m) > _VMEM_BUDGET:
        rows //= 2
    return min(rows, n_rows)


def _xtime(x):
    """Multiply int32 byte values by x in GF(2^8)."""
    return ((x << 1) & 0xFF) ^ ((x >> 7) * _XT)


def _xor_matmul_kernel(coef_ref, data_ref, out_ref, acc_ref, *, m: int,
                       k: int, coef_at, bits: int):
    """One stripe's (m, ROWS, 128) output block.

    Outer loop over data rows j: load row j once and expand its ``bits``
    xtime powers. Inner loop over output rows i: XOR the powers that the
    bits of scalar ``coef_at(coef_ref, i, j)`` select into row i of the
    int32 accumulator.
    """
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def in_row(j, carry):
        powers = [data_ref[0, j].astype(jnp.int32)]        # (ROWS, 128)
        for _ in range(bits - 1):
            powers.append(_xtime(powers[-1]))

        def out_row(i, carry):
            c = coef_at(coef_ref, i, j)
            prod = powers[0] & -(c & 1)
            for b in range(1, bits):
                prod = prod ^ (powers[b] & -((c >> b) & 1))
            acc_ref[i] = acc_ref[i] ^ prod
            return carry

        return jax.lax.fori_loop(0, m, out_row, carry)

    jax.lax.fori_loop(0, k, in_row, 0)
    out_ref[0] = acc_ref[...].astype(jnp.uint8)


def xor_matmul(coef_words: jax.Array, data: jax.Array, *, m: int, coef_at,
               bits: int, interpret: bool) -> jax.Array:
    """``coef (m, k) x data (S, k, n) -> (S, m, n)`` uint8, one launch.

    ``coef_words`` is the int32 SMEM image of the coefficients and
    ``coef_at(ref, i, j)`` reads coefficient ``(i, j)`` from it; ``bits``
    is 8 for GF(2^8) and 1 for GF(2). ``n`` must be a
    :func:`padded_length`. Grid ``(S, steps)`` of :func:`block_rows` lane
    rows each.
    """
    s, k, n = data.shape
    if n != padded_length(n):
        raise ValueError(f"length {n} is not tiled; pad to padded_length()")
    n_rows = n // LANES
    rows = block_rows(k, m, n_rows)
    steps = -(-n_rows // rows)
    x = data.reshape(s, k, n_rows, LANES)
    if steps * rows != n_rows:  # a short axis under a halved step
        x = jnp.pad(x, ((0, 0), (0, 0), (0, steps * rows - n_rows), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_xor_matmul_kernel, m=m, k=k, coef_at=coef_at,
                          bits=bits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, steps),
            in_specs=[pl.BlockSpec((1, k, rows, LANES),
                                   lambda si, t, c: (si, 0, t, 0))],
            out_specs=pl.BlockSpec((1, m, rows, LANES),
                                   lambda si, t, c: (si, 0, t, 0)),
            scratch_shapes=[pltpu.VMEM((m, rows, LANES), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((s, m, steps * rows, LANES),
                                       jnp.uint8),
        interpret=interpret,
        name=KERNEL_PREFIX + ("gf" if bits == 8 else "crs"),
    )(coef_words, x)
    return out[:, :, :n_rows].reshape(s, m, n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gf256_matmul_batched(coef: jax.Array, data: jax.Array, *,
                         interpret: bool = False) -> jax.Array:
    """Batched GF(2^8) product ``coef (m,k) @ data (S,k,B) -> (S,m,B)``.

    One Pallas launch covers every stripe in the batch; the (tiny)
    coefficient matrix is shared by all of them. This is the executor's
    workhorse — a fleet repair becomes a single launch per failure pattern
    instead of S dispatches (DESIGN.md §4). ``B`` must be a
    :func:`padded_length`; ``interpret=True`` runs the body in the Pallas
    interpreter (CPU correctness path).
    """
    m, k = coef.shape
    if data.shape[1] != k:
        raise ValueError(f"shape mismatch: coef {coef.shape} vs data {data.shape}")
    words = coef.astype(jnp.int32).reshape(m * k)
    return xor_matmul(words, data, m=m, bits=8, interpret=interpret,
                      coef_at=lambda ref, i, j: ref[i * k + j])
