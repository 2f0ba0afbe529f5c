"""Meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run driver must set XLA_FLAGS
before any jax initialization.

Every mesh in the repo is built by :func:`make_mesh`, with ``Auto`` axis
types: ``jax.make_mesh`` defaults to ``Explicit`` axes, under which
``with_sharding_constraint`` and ``device_put`` refuse the plain
``NamedSharding`` specs that ``repro.dist.sharding`` resolves.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh(shape, axes)`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (256 chips/pod) single-pod, or 2x16x16 = 512 chips multi-pod.

    Axes: ("data", "model") single-pod; ("pod", "data", "model") multi-pod —
    "pod" carries data parallelism across the pod-interconnect (DCN), "data"
    batch parallelism within a pod, "model" tensor/expert parallelism.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist on this host, as a (data, model) mesh — used by
    the CPU examples and smoke tests (typically 1x1)."""
    return make_mesh((len(jax.devices()), 1), ("data", "model"))
