"""Pallas TPU kernels: Cauchy-RS bitmatrix (CRS) products on packed bit-planes.

Two TPU-native formulations of the same GF(2) product
``out[s, i] = XOR_{j : bm[i,j]=1} packets[s, j]`` (see DESIGN.md §3):

* ``bitmatrix_encode_batched`` — VPU path: select-and-XOR accumulation over
  packet rows. Zero multiplies; it is the shared XOR-matmul kernel of
  ``gf256_matmul`` with 1-bit coefficients, packed 32 to an SMEM word.
* ``mod2_matmul_encode_batched`` — MXU path: XOR-sums over GF(2) are
  ordinary sums mod 2, so each bit position of the packed bytes is a 0/1
  plane and one *real* bf16 matmul per plane on the systolic array
  (counts <= 8k << 2^24 are exact in f32 accumulation), reduced mod 2 and
  shifted back into place, gives the product. The planes never leave VMEM.

Inputs use the packed bit-plane layout of ``repro.kernels.ref.packetize``:
packets (S, k*8, P) where P = block_bytes / 8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .gf256_matmul import KERNEL_PREFIX, xor_matmul

_BITS = 8
_WORD = 32


# --------------------------------------------------------------------------
# VPU select-and-XOR path
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("interpret",))
def bitmatrix_encode_batched(bitmatrix: jax.Array, packets: jax.Array, *,
                             interpret: bool = False) -> jax.Array:
    """Batched CRS apply: ``bitmatrix (R8, K8) x packets (S, K8, P) ->
    (S, R8, P)``, one launch.

    The bitmatrix is one compiled plan's bit expansion, shared by every
    stripe; its rows are packed 32 bits to an int32 word so that the widest
    P8 decode (768 x 768 bits, 72 KiB) fits SMEM. ``P`` must be a
    ``gf256_matmul.padded_length``.
    """
    r8, k8 = bitmatrix.shape
    if packets.shape[1] != k8:
        raise ValueError(f"shape mismatch {bitmatrix.shape} vs {packets.shape}")
    w = -(-k8 // _WORD)
    bm = jnp.pad(bitmatrix.astype(jnp.uint32), ((0, 0), (0, w * _WORD - k8)))
    shifts = jnp.arange(_WORD, dtype=jnp.uint32)
    words = jnp.sum(bm.reshape(r8, w, _WORD) << shifts, axis=-1,
                    dtype=jnp.uint32)
    words = jax.lax.bitcast_convert_type(words, jnp.int32).reshape(r8 * w)
    return xor_matmul(words, packets, m=r8, bits=1, interpret=interpret,
                      coef_at=lambda ref, i, j: ref[i * w + (j >> 5)]
                      >> (j & 31))


# --------------------------------------------------------------------------
# MXU mod-2 matmul path
# --------------------------------------------------------------------------
TILE_P = 512  # packet lanes per grid step


def mod2_padded_length(p: int) -> int:
    """Smallest packet length >= ``p`` that :func:`mod2_matmul_encode_batched`
    tiles: whole 128-lane rows, and whole ``TILE_P`` steps beyond one."""
    p = -(-p // 128) * 128
    return p if p <= TILE_P else -(-p // TILE_P) * TILE_P


def _mod2_kernel(bm_ref, pk_ref, out_ref):
    """One stripe's (R8, TP) output slab: per bit plane b, one bf16 dot of
    the (R8, K8) bitmatrix with the plane's 0/1 values, reduced mod 2 and
    shifted back to bit b."""
    bm = bm_ref[...]                       # (R8, K8) bf16 of 0/1
    pk = pk_ref[0].astype(jnp.int32)       # (K8, TP) packed bytes
    acc = jnp.zeros(out_ref.shape[1:], jnp.int32)
    for b in range(_BITS):
        plane = ((pk >> b) & 1).astype(jnp.float32).astype(jnp.bfloat16)
        counts = jax.lax.dot_general(
            bm, plane, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc = acc | ((counts.astype(jnp.int32) & 1) << b)
    out_ref[0] = acc.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mod2_matmul_encode_batched(bitmatrix: jax.Array, packets: jax.Array, *,
                               interpret: bool = False) -> jax.Array:
    """Batched MXU-path apply: ``bitmatrix (R8, K8) x packets (S, K8, P) ->
    (S, R8, P)`` with a ``(S, P/TP)`` grid — one systolic launch per batch.

    VMEM per step: the (R8, K8) bf16 bitmatrix, the (K8, TP) packet block,
    one bf16 plane of it, and the (R8, TP) f32 counts and int32 result —
    about 8 MiB with double buffering at the widest, a P8 decode
    (R8 = K8 = 768, TP = 512). ``P`` must be a :func:`mod2_padded_length`.
    """
    r8, k8 = bitmatrix.shape
    s, k8b, p = packets.shape
    if k8 != k8b:
        raise ValueError(f"shape mismatch {bitmatrix.shape} vs {packets.shape}")
    if p != mod2_padded_length(p):
        raise ValueError(f"P={p} is not tiled; pad to mod2_padded_length()")
    tp = min(TILE_P, p)
    return pl.pallas_call(
        _mod2_kernel,
        grid=(s, p // tp),
        in_specs=[
            pl.BlockSpec((r8, k8), lambda si, j: (0, 0)),
            pl.BlockSpec((1, k8, tp), lambda si, j: (si, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, r8, tp), lambda si, j: (si, 0, j)),
        out_shape=jax.ShapeDtypeStruct((s, r8, p), jnp.uint8),
        interpret=interpret,
        name=KERNEL_PREFIX + "mxu",
    )(bitmatrix.astype(jnp.bfloat16), packets)
