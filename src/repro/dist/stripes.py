"""Stripe-axis sharding: scale the batched codec engine across devices.

The batched codec engine executes ``coeffs (m, t) @ batch (S, t, B)`` with a
stripe grid axis. Stripes are independent — no cross-stripe terms exist in
any codec operation — so the stripe axis ``S`` is embarrassingly parallel:
this module resolves it onto the mesh's data-parallel axes (the "stripes"
logical axis, ``("data", "pod")`` by default) and wraps the kernel in a
``shard_map`` so each device runs one launch over its local ``S/D`` shard.

Degradation mirrors ``repro.dist.sharding._resolve``: an ``S`` that the data
axis does not divide falls back to a single-device launch (bit-identical
either way — GF(2^8) arithmetic is exact, so partitioning never changes
results, only wall-clock).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .sharding import MeshRules, _resolve


def put_copy(buf: np.ndarray, dest=None) -> jax.Array:
    """``jax.device_put(buf, dest)`` whose device value never shares
    ``buf``'s memory, so ``buf`` may be refilled once the returned array is
    ready. The CPU backend wraps a 64-byte-aligned host buffer in place
    instead of copying it, so there ``buf`` is copied on the host first;
    an accelerator's put is always a transfer."""
    if jax.default_backend() == "cpu":
        buf = np.array(buf)
    return jax.device_put(buf, dest)


def stripe_spec(shape, mr: MeshRules) -> P:
    """PartitionSpec sharding axis 0 (stripes) of an ``(S, ...)`` batch.

    Args:
        shape: the batch shape; only ``shape[0]`` (the stripe count S)
            participates in resolution, trailing dims always replicate.
        mr: active mesh + rules; the "stripes" logical axis resolves onto
            its data-parallel axes with divisibility degradation.

    Returns:
        A spec like ``P(("data",), None, ...)``, or ``P(None, ...)`` when
        the stripe axis degrades (indivisible S / no candidate axes).
    """
    names = ("stripes",) + (None,) * (len(shape) - 1)
    return _resolve(shape, names, mr)


def stripe_sharding(shape, mr: MeshRules) -> NamedSharding:
    """:func:`stripe_spec` bound to ``mr``'s mesh as a ``NamedSharding`` —
    the layout both the sharded launch and the per-shard gather geometry
    (``repro.dist.placement.shard_layout``) derive from."""
    return NamedSharding(mr.mesh, stripe_spec(shape, mr))


def stripe_axis_span(mr: Optional[MeshRules]) -> int:
    """Device count the "stripes" logical axis *can* claim on ``mr``'s mesh
    (the product of its candidate axes present in the mesh), independent of
    any particular batch size. 1 with no rules or no candidate axes."""
    if mr is None:
        return 1
    sizes = dict(mr.mesh.shape)
    span = 1
    for ax in dict.fromkeys(mr.axes_for("stripes")):
        span *= sizes.get(ax, 1)
    return span


def align_stripe_window(window: int, mr: Optional[MeshRules]) -> int:
    """Largest window' <= ``window`` divisible by the stripe-axis device
    span, so windowed launches keep their full device parallelism instead of
    degrading to one device on an indivisible S. Windows smaller than the
    span are returned unchanged (they degrade, matching ragged-tail
    semantics elsewhere)."""
    span = stripe_axis_span(mr)
    if span <= 1 or window < span:
        return window
    return (window // span) * span


def stripe_span(shape, mr: Optional[MeshRules]) -> int:
    """How many devices an ``(S, ...)`` batch spreads over (1 = degraded).

    Unlike :func:`stripe_axis_span` this accounts for the *batch*: an S the
    stripe axis does not divide resolves to ``None`` and returns 1. The
    scheduler (``repro.dist.schedule``) and the gather layout both key off
    this value, so "will this launch shard?" has one answer everywhere.
    """
    if mr is None:
        return 1
    entry = stripe_spec(shape, mr)[0] if len(shape) else None
    if entry is None:
        return 1
    axes = (entry,) if isinstance(entry, str) else tuple(entry)
    sizes = dict(mr.mesh.shape)
    span = 1
    for ax in axes:
        span *= sizes[ax]
    return span


@functools.lru_cache(maxsize=128)
def _launcher(fn: Callable, kwargs_items: tuple, mesh=None,
              spec: Optional[P] = None, coef_ndim: int = 0) -> Callable:
    """The jitted launch of ``fn``, cached on (fn, static kwargs, mesh,
    spec): the whole single-device launch (padding, packing, kernel,
    slicing) as one program without a mesh, else its ``shard_map``,
    named ``gf_launch_<backend>``.

    ``fn`` must be a module-level function (stable identity) taking
    ``(coeffs, batch, **kwargs)``. Under the mesh, coeffs replicate, the
    batch shards on axis 0, and the output inherits the batch's spec.
    ``check_vma=False``: ``pallas_call`` carries no varying-manual-axes
    annotation, and the stripe launch needs none (coeffs replicate,
    everything else shards on S).
    """
    kwargs = dict(kwargs_items)

    def body(coeffs, batch):
        return fn(coeffs, batch, **kwargs)

    # jit names the program after the function: one stable name per
    # formulation (jit_gf_launch_mxu, ...) for a trace's module line.
    body.__name__ = body.__qualname__ = f"gf_launch_{kwargs['backend']}"
    if mesh is None:
        return jax.jit(body)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(*([None] * coef_ndim)), spec),
        out_specs=spec, check_vma=False))


def _matches(batch, sharding: NamedSharding) -> bool:
    """Is ``batch`` already laid out shard-for-shard as ``sharding``?"""
    return (isinstance(batch, jax.Array)
            and batch.sharding.is_equivalent_to(sharding, batch.ndim))


def sharded_launch(fn: Callable, coeffs, batch, mr: Optional[MeshRules],
                   **kwargs):
    """Run ``fn(coeffs, batch, **kwargs)`` as one device-parallel launch.

    With no rules, or when the stripe axis degrades (indivisible ``S`` or a
    trivial mesh), falls through to a plain single-device call. ``kwargs``
    must be hashable (they key the jit cache).

    ``batch`` may arrive three ways, cheapest first:

    * a global ``jax.Array`` already sharded as the stripe spec resolves
      (e.g. assembled per shard by ``repro.dist.placement.assemble_shards``)
      — consumed with **zero re-transfer**;
    * a host ``numpy`` array — scattered shard-by-shard with one
      ``device_put`` onto the target sharding (no device-0 bounce);
    * anything else (including a single-device ``jax.Array``) — resharded
      by ``device_put`` onto the stripe sharding.
    """
    kwargs_items = tuple(sorted(kwargs.items()))
    if stripe_span(batch.shape, mr) <= 1:
        return _launcher(fn, kwargs_items)(coeffs, batch)
    spec = stripe_spec(batch.shape, mr)
    sharding = NamedSharding(mr.mesh, spec)
    if not _matches(batch, sharding):
        batch = jax.device_put(batch, sharding)
    return _launcher(fn, kwargs_items, mr.mesh, spec,
                     coeffs.ndim)(coeffs, batch)
