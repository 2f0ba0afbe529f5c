"""The store's kernels compile for a described TPU v5e, with no chip attached.

Each case lowers the single-device launch body a TPU runs
(``kernels.ops._gf_batch_kernel`` / ``_bit_matmul_batch_kernel``: padding,
bit-plane packing, the Pallas kernel, slicing) for one real plan of the
paper's P5 (24,2,2) and P8 (96,5,4) geometries at 1 MiB blocks, compiles it
with the TPU compiler for one chip of a ``v5e:2x2`` topology, and checks
that a Mosaic kernel is in the program, under its stable name. Nothing
runs: these compiles say nothing about results or times, only that Mosaic
accepts the kernels' tiles and that each grid step fits the chip's scoped
VMEM and SMEM.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and the test workers all
import this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.planner import RepairPlanner
from repro.core.schemes import PAPER_PARAMS, make_scheme
from repro.kernels import ops
from repro.kernels.gf256_matmul import KERNEL_PREFIX

BLOCK = 1 << 20   # the StoreConfig default block size
STRIPES = 4       # the stripe grid axis adds grid cells, not kernel code


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _plan(planner: RepairPlanner, which: str):
    """The seal plan, a cascade repair, and the widest plan: the k data
    blocks decoded from every block that survives that cascade's loss."""
    if which == "encode":
        return planner.encode_plan()
    if which == "cascade":          # two data blocks of one local group
        return planner.multi_plan({0, 1})
    return planner.decode_plan(set(range(planner.scheme.n)) - {0, 1})


@pytest.mark.parametrize("plan", ["encode", "cascade", "decode"])
@pytest.mark.parametrize("backend", ["gf", "crs", "mxu"])
@pytest.mark.parametrize("geometry", ["P5", "P8"])
def test_kernel_compiles_for_v5e(geometry, backend, plan, one_chip):
    k, r, p = PAPER_PARAMS[geometry]
    planner = RepairPlanner(make_scheme("cp-azure", k, r, p))
    compiled_plan = _plan(planner, plan)
    if backend == "gf":
        body, coef = ops._gf_batch_kernel, compiled_plan.coeffs
    else:
        body, coef = ops._bit_matmul_batch_kernel, compiled_plan.bit_coeffs()
    fn = functools.partial(body, backend=backend, interpret=False,
                           force_pallas=False)
    coef_s = jax.ShapeDtypeStruct(coef.shape, jnp.uint8, sharding=one_chip)
    data_s = jax.ShapeDtypeStruct((STRIPES, len(compiled_plan.reads), BLOCK),
                                  jnp.uint8, sharding=one_chip)
    compiled = jax.jit(fn).lower(coef_s, data_s).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the kernel's stable name, which a device trace finds it by
    assert f"%{KERNEL_PREFIX}{backend}." in compiled.as_text()
    out = jax.eval_shape(fn, coef_s, data_s)
    assert out.shape == (STRIPES, len(compiled_plan.targets), BLOCK)
