"""Batched multi-stripe codec engine.

The planner/executor split (DESIGN.md §4): :class:`~repro.core.planner.
RepairPlanner` compiles and caches the host-side GF algebra; this module's
:class:`BatchedCodecEngine` executes a compiled plan over a whole *batch* of
stripes at once — ``(S, k, B)`` in, ``(S, n, B)`` out — as a single Pallas
launch with a stripe grid axis, instead of the seed codec's one solve + one
launch per stripe per block.

Batches are homogeneous in the failure pattern, not in S: callers group
stripes by pattern (``ftx.stripestore`` does this per fleet repair) and may
pass ragged last batches of any size, including S=1.

Availability can be given either as a dense ``(S, n, B)`` array or as a
mapping ``block-id -> (S, B)`` holding only surviving blocks; both gather to
the plan's read order before the launch.

Passing :class:`~repro.dist.sharding.MeshRules` (at construction or per
call) shards the stripe axis over the mesh's data axes — one device-parallel
launch per call via ``repro.dist.stripes`` — with bit-identical results;
``last_span`` reports how many devices the most recent launch spread over.

Every launch runs in three steps, each a program span (``repro.obs``):
``repro.launch.h2d`` puts a host input on the device and waits for the copy
(``execute`` splits this step out only under a trace, and ``put`` runs it
alone, for a caller that must have the device hold its own copy before it
reuses the host buffer; a pre-sharded input was put, and spanned, by
``repro.dist.placement.assemble_shards``), ``repro.launch.device``
dispatches the program and waits for it, and ``repro.launch.d2h`` copies
the result back. ``execute`` and ``encode`` return host arrays.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Mapping, Optional, Union

import jax
import numpy as np

from repro import obs
from repro.dist.sharding import MeshRules
from repro.dist.stripes import put_copy, stripe_sharding, stripe_span
from repro.kernels.ops import (default_backend, effective_backend,
                               encode_batch_op, gf_matmul_batch_op,
                               require_backend)

from .planner import CompiledPlan, RepairPlanner
from .schemes import LRCScheme

Blocks = Union[jax.Array, np.ndarray, Mapping[int, "jax.Array | np.ndarray"]]


@dataclasses.dataclass
class BatchedCodecEngine:
    scheme: LRCScheme
    # REPRO_BACKEND > mxu-on-TPU > gf (kernels.ops.default_backend),
    # resolved once at construction.
    backend: str = dataclasses.field(default_factory=default_backend)
    planner: RepairPlanner | None = None
    mesh_rules: MeshRules | None = None
    last_span: int = dataclasses.field(default=1, init=False)
    # Formulation the most recent launch actually ran (kernels.ops.
    # effective_backend): equals ``backend`` except for the one documented
    # substitution — an interpreted "gf" batch executes the fused table
    # path and reports "ref". Nothing downgrades silently; this field is
    # the telemetry record of what ran, per launch.
    effective_backend: str = dataclasses.field(default="", init=False)

    def __post_init__(self):
        require_backend(self.backend)
        if self.planner is None:
            self.planner = RepairPlanner(self.scheme)
        elif self.planner.scheme is not self.scheme:
            raise ValueError("planner is bound to a different scheme")

    def _rules(self, mesh_rules: Optional[MeshRules]) -> Optional[MeshRules]:
        return self.mesh_rules if mesh_rules is None else mesh_rules

    # --------------------------------------------------------------- helpers
    def _gather(self, available: Blocks, reads: tuple[int, ...]) -> jax.Array:
        """Stack the read blocks into (S, |reads|, B) in plan column order."""
        import jax.numpy as jnp

        if isinstance(available, Mapping):
            cols = []
            for b in reads:
                try:
                    cols.append(jnp.asarray(available[b], jnp.uint8))
                except KeyError:
                    raise KeyError(f"plan reads block {b} but it was not "
                                   f"provided") from None
            return jnp.stack(cols, axis=1)
        arr = jnp.asarray(available, jnp.uint8)
        if arr.ndim != 3:
            raise ValueError(f"expected (S, n, B) availability, got {arr.shape}")
        return arr[:, list(reads), :]

    @staticmethod
    def _to_device(batch, mr: Optional[MeshRules]) -> jax.Array:
        """The ``repro.launch.h2d`` step: a host batch goes onto the stripe
        sharding (or the default device); a device array moves nothing and
        has no span here (one pre-sharded by
        ``repro.dist.placement.assemble_shards`` had its copy spanned
        there). The copy is waited for only under a trace: a sync between
        the copy and the dispatch costs about 1.2 ms a launch."""
        if not isinstance(batch, np.ndarray):
            return batch
        with obs.span("repro.launch.h2d", bytes=batch.nbytes):
            sharding = (stripe_sharding(batch.shape, mr)
                        if stripe_span(batch.shape, mr) > 1 else None)
            return obs.block_if_tracing(put_copy(batch, sharding))

    @staticmethod
    def _to_host(out: jax.Array) -> np.ndarray:
        """The ``repro.launch.d2h`` step: the result, copied to the host."""
        with obs.span("repro.launch.d2h", bytes=out.nbytes):
            return np.asarray(out)

    def put(self, batch, mesh_rules: Optional[MeshRules] = None
            ) -> jax.Array:
        """Put a host ``(S, |reads|, B)`` stack on the device now, on the
        stripe sharding a launch under ``mesh_rules`` reads it with (the
        ``repro.launch.h2d`` step). The device value is a copy: the host
        buffer may be refilled once the returned array is ready."""
        return self._to_device(np.ascontiguousarray(batch, np.uint8),
                               self._rules(mesh_rules))

    def execute(self, plan: CompiledPlan, stacked: jax.Array | np.ndarray,
                mesh_rules: Optional[MeshRules] = None) -> np.ndarray:
        """Run a compiled plan on an already-gathered (S, |reads|, B) stack
        and return the (S, |targets|, B) result on the host.

        The zero-copy entry point for callers that materialize the read
        stack themselves — skips the per-block gather/stack. ``stacked``
        may be a host numpy array (the stripe store's single-shard gather;
        put straight onto the stripe sharding), a device array from
        :meth:`put`, or a pre-sharded global ``jax.Array`` built per device
        shard (``repro.dist.placement.assemble_shards``), which is consumed
        with zero re-transfer — never bounced through one device.
        """
        import jax.numpy as jnp

        if isinstance(stacked, np.ndarray):
            stacked = np.ascontiguousarray(stacked, np.uint8)
        else:
            stacked = jnp.asarray(stacked, jnp.uint8)
        if stacked.ndim != 3 or stacked.shape[1] != len(plan.reads):
            raise ValueError(f"expected (S, {len(plan.reads)}, B) stack for "
                             f"plan reads {plan.reads}, got {stacked.shape}")
        mr = self._rules(mesh_rules)
        self.last_span = stripe_span(stacked.shape, mr)
        self.effective_backend = effective_backend(self.backend)
        bitmatrix = (plan.bit_coeffs()
                     if self.backend in ("crs", "mxu") else None)
        if obs.tracing():
            # Taken apart only under a trace: with none running, the jit
            # copies a host stack on its own fast path, which an explicit
            # device_put would cost 0.3 ms or more a launch.
            stacked = self._to_device(stacked, mr)
        with obs.span("repro.launch.device", stripes=stacked.shape[0],
                      reads=len(plan.reads), targets=len(plan.targets),
                      backend=self.backend):
            out = jax.block_until_ready(gf_matmul_batch_op(
                plan.coeffs, stacked, backend=self.backend,
                bitmatrix=bitmatrix, mesh_rules=mr))
        return self._to_host(out)

    def _execute(self, plan: CompiledPlan, available: Blocks,
                 mesh_rules: Optional[MeshRules] = None) -> np.ndarray:
        return self.execute(plan, self._gather(available, plan.reads),
                            mesh_rules)

    # ------------------------------------------------------------- encoding
    def encode(self, data: jax.Array | np.ndarray,
               mesh_rules: Optional[MeshRules] = None) -> np.ndarray:
        """(S, k, B) data -> (S, n, B) systematic stripes on the host, one
        launch."""
        import jax.numpy as jnp

        if isinstance(data, jax.Array):
            data = jnp.asarray(data, jnp.uint8)
        else:
            data = np.ascontiguousarray(data, np.uint8)
        if data.ndim != 3 or data.shape[1] != self.scheme.k:
            raise ValueError(
                f"expected (S, {self.scheme.k}, B) data, got {data.shape}")
        mr = self._rules(mesh_rules)
        self.last_span = stripe_span(data.shape, mr)
        self.effective_backend = effective_backend(self.backend)
        plan = self.planner.encode_plan()
        bitmatrix = (plan.bit_coeffs()
                     if self.backend in ("crs", "mxu") else None)
        data = self._to_device(data, mr)
        with obs.span("repro.launch.device", stripes=data.shape[0],
                      reads=data.shape[1], targets=self.scheme.n,
                      backend=self.backend):
            parity = encode_batch_op(plan.coeffs, data, backend=self.backend,
                                     mesh_rules=mr, bitmatrix=bitmatrix)
            out = jax.block_until_ready(
                jnp.concatenate([data, parity], axis=1))
        return self._to_host(out)

    # ------------------------------------------------------------- repair
    def repair_single(self, failed: int, available: Blocks,
                      policy: str = "paper",
                      mesh_rules: Optional[MeshRules] = None
                      ) -> tuple[np.ndarray, CompiledPlan]:
        """Rebuild one block across S stripes: (S, B) plus the cached plan."""
        plan = self.planner.single_plan(failed, policy)
        return self._execute(plan, available, mesh_rules)[:, 0, :], plan

    def repair_multi(self, failed: Iterable[int], available: Blocks,
                     mesh_rules: Optional[MeshRules] = None
                     ) -> tuple[dict[int, np.ndarray], CompiledPlan]:
        """Rebuild a failure pattern across S stripes in one launch.

        Returns ``{block -> (S, B)}``; the cascade is pre-flattened by the
        planner so there is exactly one kernel launch regardless of how many
        blocks the pattern repairs — one per device when sharded.
        """
        plan = self.planner.multi_plan(failed)
        out = self._execute(plan, available, mesh_rules)
        return {b: out[:, i, :] for i, b in enumerate(plan.targets)}, plan

    # ------------------------------------------------------------- decode
    def decode(self, available: Blocks, ids: Iterable[int] | None = None,
               mesh_rules: Optional[MeshRules] = None) -> np.ndarray:
        """(S, k, B) data blocks from any rank-k subset of surviving blocks.

        ``ids`` names the surviving blocks; it may be omitted for a Mapping
        availability (its keys are used).
        """
        if ids is None:
            if not isinstance(available, Mapping):
                raise ValueError("ids is required for dense availability")
            ids = available.keys()
        plan = self.planner.decode_plan(ids)
        return self._execute(plan, available, mesh_rules)
