"""Pure-jnp oracles for the Pallas erasure-coding kernels.

Every kernel in this package has a reference implementation here; the test
suite sweeps shapes/dtypes and asserts bit-exact equality (erasure coding is
integer math — there is no tolerance, results must match exactly).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gf import GF_MUL_TABLE, PRIM_POLY

_BITS = 8


# --------------------------------------------------------------------------
# GF(2^8) matmul (table path — ground truth)
# --------------------------------------------------------------------------
def gf256_matmul_ref(coef: jax.Array, data: jax.Array) -> jax.Array:
    """(m, k) x (k, B) over GF(2^8) via the 64 KB multiplication table."""
    coef = coef.astype(jnp.int32)
    data = data.astype(jnp.int32)
    table = jnp.asarray(GF_MUL_TABLE.reshape(-1))
    idx = coef[:, :, None] * 256 + data[None, :, :]
    prods = jnp.take(table, idx, axis=0).astype(jnp.uint8)  # (m, k, B)
    return jax.lax.reduce(prods, np.uint8(0),
                          lambda a, b: jax.lax.bitwise_xor(a, b), (1,))


def gf256_matmul_batched_ref(coef: jax.Array, data: jax.Array) -> jax.Array:
    """Batched oracle: ``coef (m,k) @ data (S,k,B) -> (S,m,B)``, table path.

    vmap of :func:`gf256_matmul_ref` over the stripe axis — bit-exact lockstep
    for the batched Pallas kernel.
    """
    return jax.vmap(gf256_matmul_ref, in_axes=(None, 0))(coef, data)


def gf256_matmul_shift_ref(coef: jax.Array, data: jax.Array) -> jax.Array:
    """Same product via the table-free shift-and-XOR algorithm the TPU kernel
    uses (oracle for the algorithm itself, not just the result)."""
    coef = coef.astype(jnp.int32)[:, :, None]  # (m, k, 1)
    cur = data.astype(jnp.int32)[None, :, :]   # (1, k, B)
    m, k, _ = coef.shape
    acc = jnp.zeros((m, k, data.shape[1]), jnp.int32)
    cur = jnp.broadcast_to(cur, acc.shape)
    cf = jnp.broadcast_to(coef, acc.shape)
    for _ in range(_BITS):
        acc = acc ^ jnp.where((cf & 1) != 0, cur, 0)
        cur = ((cur << 1) & 0xFF) ^ jnp.where((cur & 0x80) != 0, PRIM_POLY & 0xFF, 0)
        cf = cf >> 1
    return jax.lax.reduce(acc.astype(jnp.uint8), np.uint8(0),
                          lambda a, b: jax.lax.bitwise_xor(a, b), (1,))


# --------------------------------------------------------------------------
# CRS bit-plane layout helpers
# --------------------------------------------------------------------------
def packetize(blocks: jax.Array) -> jax.Array:
    """(k, B) byte blocks -> (k*8, B//8) packed bit-plane packets.

    Each block is viewed as 8 segments of P = B/8 bytes. Packet (j*8 + i)
    is bit-plane i of block j: bit t of its byte p is bit i of byte p of
    segment t. Both directions reduce over a leading axis of 8 and keep
    the long byte axis minor, which the TPU compiler lays out in lanes.
    """
    k, B = blocks.shape
    if B % _BITS:
        raise ValueError(f"block bytes {B} must be divisible by 8")
    x = blocks.reshape(k, 1, _BITS, B // _BITS).astype(jnp.int32)
    bit = jnp.arange(_BITS)
    planes = (x >> bit[None, :, None, None]) & 1      # (k, plane, seg, P)
    packed = jnp.sum(planes << bit[None, None, :, None], axis=2)
    return packed.reshape(k * _BITS, B // _BITS).astype(jnp.uint8)


def unpacketize(packets: jax.Array) -> jax.Array:
    """Inverse of :func:`packetize`: (k*8, B//8) -> (k, B)."""
    k8, P = packets.shape
    k = k8 // _BITS
    x = packets.reshape(k, _BITS, 1, P).astype(jnp.int32)
    bit = jnp.arange(_BITS)
    segs = (x >> bit[None, None, :, None]) & 1        # (k, plane, seg, P)
    segs = jnp.sum(segs << bit[None, :, None, None], axis=1).astype(jnp.uint8)
    # For a TPU, concatenating the segments compiles about 8x faster than
    # the equivalent (k, 8, P) -> (k, B) reshape of bytes.
    return jnp.concatenate([segs[:, t] for t in range(_BITS)], axis=-1)


def packetize_batched(blocks: jax.Array) -> jax.Array:
    """Batched :func:`packetize`: (S, k, B) -> (S, k*8, B//8)."""
    return jax.vmap(packetize)(blocks)


def unpacketize_batched(packets: jax.Array) -> jax.Array:
    """Batched :func:`unpacketize`: (S, k*8, B//8) -> (S, k, B)."""
    return jax.vmap(unpacketize)(packets)


def bitmatrix_encode_ref(bitmatrix: jax.Array, packets: jax.Array) -> jax.Array:
    """CRS encode on packed bit-plane packets: out[i] = XOR_{j: bm[i,j]=1} packets[j].

    bitmatrix: (R8, K8) of {0,1}; packets: (K8, P) packed bytes -> (R8, P).
    """
    bm = bitmatrix.astype(jnp.int32)
    pk = packets.astype(jnp.int32)
    sel = bm[:, :, None] * pk[None, :, :]  # 0/packet per (i, j)
    return jax.lax.reduce(sel.astype(jnp.uint8), np.uint8(0),
                          lambda a, b: jax.lax.bitwise_xor(a, b), (1,))


def bitmatrix_encode_batched_ref(bitmatrix: jax.Array,
                                 packets: jax.Array) -> jax.Array:
    """Batched oracle for the stripe-grid CRS kernel: ``bitmatrix (R8, K8) x
    packets (S, K8, P) -> (S, R8, P)`` — vmap over the stripe axis, bit-exact
    lockstep for :func:`repro.kernels.bitmatrix_encode.bitmatrix_encode_batched`."""
    return jax.vmap(bitmatrix_encode_ref, in_axes=(None, 0))(bitmatrix, packets)


def mod2_matmul_encode_batched_ref(bitmatrix: jax.Array,
                                   packets: jax.Array) -> jax.Array:
    """Batched MXU-formulation oracle: vmap of :func:`mod2_matmul_encode_ref`
    over the stripe axis. Must equal :func:`bitmatrix_encode_batched_ref`."""
    return jax.vmap(mod2_matmul_encode_ref, in_axes=(None, 0))(bitmatrix, packets)


def mod2_matmul_encode_ref(bitmatrix: jax.Array, packets: jax.Array) -> jax.Array:
    """The MXU formulation oracle: unpack packets to bits, real matmul,
    reduce mod 2, repack. Must equal :func:`bitmatrix_encode_ref` exactly."""
    k8, P = packets.shape
    x = packets.astype(jnp.int32)
    bits = ((x[:, :, None] >> jnp.arange(_BITS)[None, None, :]) & 1)  # (K8, P, 8)
    bits = bits.reshape(k8, P * _BITS).astype(jnp.float32)
    counts = jnp.dot(bitmatrix.astype(jnp.float32), bits,
                     precision=jax.lax.Precision.HIGHEST)
    outbits = counts.astype(jnp.int32) & 1  # (R8, P*8)
    outbits = outbits.reshape(-1, P, _BITS)
    weights = (1 << jnp.arange(_BITS)).astype(jnp.int32)
    out = jnp.sum(outbits * weights[None, None, :], axis=-1)
    return out.astype(jnp.uint8)
