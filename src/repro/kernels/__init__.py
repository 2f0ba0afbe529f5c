"""Pallas TPU kernels for the erasure-coding hot path.

Kernels (each with a pure-jnp oracle in ``ref.py``), all over a stripe
batch; a single stripe is S=1:
  gf256_matmul_batched       — bit-serial GF(2^8) matmul (VPU)
  bitmatrix_encode_batched   — CRS select-and-XOR on packed bit-planes (VPU)
  mod2_matmul_encode_batched — per-bit-plane matmul mod 2 (MXU)

``ops.py`` is the dispatch layer used by the batched engine
(``repro.core.engine``), the one codec of the stripe store.
"""
from .gf256_matmul import gf256_matmul_batched  # noqa: F401
from .bitmatrix_encode import (bitmatrix_encode_batched,  # noqa: F401
                               mod2_matmul_encode_batched)
from . import ref  # noqa: F401
