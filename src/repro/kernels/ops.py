"""Public ops for the erasure-coding kernels.

Dispatch layer: picks the Pallas kernel, compiled by Mosaic on a TPU and
run by the Pallas interpreter on CPU hosts only (``jax.default_backend()
== "cpu"``), with shape padding so callers never worry about tile
divisibility. ``backend``:

  "gf"    — gf256_matmul_batched (bit-serial VPU multiply)
  "crs"   — bitmatrix_encode_batched (select-and-XOR on bit-planes)
  "mxu"   — mod2_matmul_encode_batched (per-bit-plane MXU matmul mod 2)
  "ref"   — pure-jnp table oracle (no Pallas)

Every backend supports every op — encode, repair/decode combines — on a
stripe batch; a single stripe is a batch of S=1. The bit-plane
backends ("crs"/"mxu") run general GF matmuls through the packed
bit-matrix expansion of the byte coefficient matrix
(``repro.core.gf.matrix_to_bitmatrix``): callers that hold a compiled plan
pass its cached expansion via ``bitmatrix=`` so the 8x blow-up is amortized
over every chunk of a failure pattern (DESIGN.md §11). There is no silent
backend downgrade anywhere in this module: unknown names raise, and the
one documented substitution (an interpreted "gf" batch runs the fused
table path, bit-identically, because the Pallas interpreter replays every
grid cell) is reported by :func:`effective_backend` and recorded in engine
and fleet telemetry.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gf import matrix_to_bitmatrix
from repro.dist.stripes import sharded_launch

from . import ref as ref_lib
from .bitmatrix_encode import (bitmatrix_encode_batched,
                               mod2_matmul_encode_batched, mod2_padded_length)
from .gf256_matmul import gf256_matmul_batched, padded_length

BACKENDS = ("gf", "crs", "mxu", "ref")
# Backends whose general matmul runs on packed bit-planes (GF(2) algebra).
BIT_BACKENDS = ("crs", "mxu")


def require_backend(backend: str) -> str:
    """Validate a backend name, raising a clear error for unknown ones."""
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {backend!r}; expected one of {BACKENDS}")
    return backend


def effective_backend(backend: str, *, interpret: bool | None = None,
                      force_pallas: bool = False) -> str:
    """The formulation a batched GF matmul with ``backend`` actually runs.

    Identical to ``backend`` everywhere except the one documented
    substitution: on interpreter hosts a "gf" batch executes the fused
    table path ("ref") instead of replaying the bit-serial kernel cell by
    cell — bit-identical, ~60x faster (see :func:`gf_matmul_batch_op`).
    The bit-plane backends keep their own formulation on every host (the
    interpreted path runs the same select-and-XOR / mod-2-matmul math as
    one fused XLA call), so they report as themselves. Engine and fleet
    telemetry record this value per launch; nothing downgrades silently.
    """
    require_backend(backend)
    if interpret is None:
        interpret = _on_cpu()
    if backend == "gf" and interpret and not force_pallas:
        return "ref"
    return backend


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x: jax.Array, axis: int, length: int) -> tuple[jax.Array, int]:
    """Zero-pad ``axis`` of ``x`` up to ``length``; returns the original size."""
    size = x.shape[axis]
    if size == length:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, length - size)
    return jnp.pad(x, widths), size


def _as_bitmatrix(coef, bitmatrix) -> jax.Array:
    """The GF(2) expansion of byte coeffs ``coef`` (m, t): the caller's
    precomputed ``bitmatrix`` (a compiled plan's cached expansion) when
    given — shape-checked against ``coef`` — else expanded here."""
    if bitmatrix is None:
        return jnp.asarray(matrix_to_bitmatrix(np.asarray(coef, np.uint8)))
    bm = jnp.asarray(bitmatrix, jnp.uint8)
    want = (coef.shape[0] * 8, coef.shape[1] * 8)
    if bm.shape != want:
        raise ValueError(f"bitmatrix shape {bm.shape} does not match the "
                         f"{coef.shape} coefficient matrix (want {want})")
    return bm


def _gf_batch_kernel(coef, data, *, backend: str, interpret: bool,
                     force_pallas: bool) -> jax.Array:
    """Single-device body of the batched GF matmul (shard_map-able)."""
    if backend == "ref" or (interpret and not force_pallas):
        return ref_lib.gf256_matmul_batched_ref(coef, data)
    padded, b = _pad_to(data, 2, padded_length(data.shape[2]))
    return gf256_matmul_batched(coef, padded, interpret=interpret)[:, :, :b]


def _bit_matmul_batch_kernel(bm, data, *, backend: str, interpret: bool,
                             force_pallas: bool) -> jax.Array:
    """Single-device body of the batched bit-plane matmul (shard_map-able).

    ``bm`` is the packed (8m, 8t) GF(2) expansion of a byte coefficient
    matrix, ``data`` the (S, t, B) read stack. Pads B so the packet length
    P = B/8 tiles the kernel, packetizes per stripe, runs the stripe-grid
    kernel, unpacks. On CPU hosts the interpreter replays every grid cell,
    so an interpreted batch runs the *same formulation* as one fused XLA
    call (the vmapped jnp oracles) — still select-and-XOR for crs and
    mod-2 matmul for mxu, so the backend identity is preserved;
    ``force_pallas=True`` runs the batched-grid kernel under the
    interpreter anyway (lockstep tests).
    """
    p = -(-data.shape[2] // 8)
    if interpret and not force_pallas:
        kernel = (ref_lib.bitmatrix_encode_batched_ref if backend == "crs"
                  else ref_lib.mod2_matmul_encode_batched_ref)
    elif backend == "crs":
        kernel, p = bitmatrix_encode_batched, padded_length(p)
        kernel = functools.partial(kernel, interpret=interpret)
    else:
        kernel, p = mod2_matmul_encode_batched, mod2_padded_length(p)
        kernel = functools.partial(kernel, interpret=interpret)
    padded, b = _pad_to(data, 2, 8 * p)
    with jax.named_scope("pack"):
        packets = ref_lib.packetize_batched(padded)
    par = kernel(bm, packets)
    with jax.named_scope("unpack"):
        return ref_lib.unpacketize_batched(par)[:, :, :b]


def gf_matmul_batch_op(coef, data, *, backend: str = "gf",
                       interpret: bool | None = None,
                       force_pallas: bool = False,
                       mesh_rules=None, bitmatrix=None) -> jax.Array:
    """Batched GF(2^8) ``coef (m,k) @ data (S,k,B) -> (S,m,B)``.

    One launch for the whole stripe batch; pads B to the kernel's tiling
    and slices the result back. All four backends:
    gf/ref run the byte-table/bit-serial grid, crs/mxu run the stripe-grid
    bit-plane kernels on the coefficient matrix's packed GF(2) expansion
    (``bitmatrix=`` passes a precomputed one — the batched engine hands in
    its compiled plan's cached expansion so a whole pattern chunk pays for
    exactly one 8x blow-up).

    On CPU hosts the Pallas interpreter is a correctness tool, not a
    throughput path (it replays every grid cell), so an interpreted "gf"
    batch executes as one fused table-path XLA call instead — bit-identical,
    ~60x faster than S interpreted launches — and the bit-plane backends
    run their own formulation as fused XLA calls. :func:`effective_backend`
    names what actually ran. ``force_pallas=True`` runs the batched-grid
    kernels under the interpreter anyway (lockstep tests).

    ``mesh_rules`` shards the stripe axis over the mesh's data axes and runs
    one launch per device via ``shard_map`` (repro.dist.stripes); an
    indivisible S degrades to the single-device launch. Stripes are
    independent, so the result is bit-identical either way.

    ``data`` is handed to :func:`~repro.dist.stripes.sharded_launch`
    *unconverted*: a host numpy stack scatters straight onto the stripe
    sharding and a pre-sharded global array passes through with zero
    re-transfer, so the batch never materializes on one device first.
    """
    require_backend(backend)
    if interpret is None:
        interpret = _on_cpu()
    coef = jnp.asarray(coef, jnp.uint8)
    if isinstance(data, np.ndarray):
        if data.dtype != np.uint8:
            data = np.ascontiguousarray(data, np.uint8)
    elif not isinstance(data, jax.Array) or data.dtype != jnp.uint8:
        data = jnp.asarray(data, jnp.uint8)
    if data.ndim != 3:
        raise ValueError(f"expected (S, k, B) data, got {data.shape}")
    if backend in BIT_BACKENDS:
        bm = _as_bitmatrix(coef, bitmatrix)
        return sharded_launch(_bit_matmul_batch_kernel, bm, data, mesh_rules,
                              backend=backend, interpret=interpret,
                              force_pallas=force_pallas)
    return sharded_launch(_gf_batch_kernel, coef, data, mesh_rules,
                          backend=backend, interpret=interpret,
                          force_pallas=force_pallas)


def encode_batch_op(coding: np.ndarray, blocks, *, backend: str = "gf",
                    interpret: bool | None = None,
                    mesh_rules=None, bitmatrix=None) -> jax.Array:
    """Batched stripe-parity: ``blocks (S, k, B) -> parity (S, m, B)``.

    Parity is a matmul of the generator's parity rows, so every backend
    routes through :func:`gf_matmul_batch_op`: gf/ref run the batched table
    /bit-serial grid, crs/mxu the stripe-grid bit-plane kernels (the coding
    matrix's packed expansion, passed via ``bitmatrix=`` when the caller
    caches it). ``mesh_rules`` shards the stripe axis over the mesh's data
    axes, one launch per device.
    """
    require_backend(backend)
    blocks = jnp.asarray(blocks, jnp.uint8)
    if blocks.ndim != 3:
        raise ValueError(f"expected (S, k, B) blocks, got {blocks.shape}")
    return gf_matmul_batch_op(np.asarray(coding, np.uint8), blocks,
                              backend=backend, interpret=interpret,
                              mesh_rules=mesh_rules, bitmatrix=bitmatrix)


def default_backend(cpu: str = "gf") -> str:
    """``REPRO_BACKEND`` when set (CI backend-matrix legs), else the MXU
    Pallas kernel on a TPU, else ``cpu`` (the store passes "ref", the jnp
    table oracle). No TPU default is a non-Pallas path. Uncached so a test
    can monkeypatch the env var; constructors resolve it once via
    ``dataclasses.field(default_factory=...)``."""
    env = os.environ.get("REPRO_BACKEND")
    if env:
        return require_backend(env)
    return "mxu" if jax.default_backend() == "tpu" else require_backend(cpu)
