"""Logical sharding resolution + smoke-mesh lowering of every family."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import _resolve, opt_state_sharding, with_rules
from repro.launch.mesh import make_mesh


@pytest.fixture
def mesh():
    # 1x1 host mesh with the production axis names
    return make_mesh((1, 1), ("data", "model"))


def test_resolve_divisible(mesh):
    with with_rules(mesh) as mr:
        spec = _resolve((32, 64), ("batch", "ff"), mr)
        assert spec == P("data", "model")


@pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
def test_resolve_indivisible_degrades():
    """Real degradation cases on a 2x4 mesh (the multi-device CI leg)."""
    mesh = make_mesh((2, 4), ("data", "model"))
    with with_rules(mesh) as mr:
        # divisible everywhere: both axes assigned
        assert _resolve((8, 8), ("batch", "ff"), mr) == P("data", "model")
        # 4-way model axis does not divide 3 heads -> replicate (arctic case)
        assert _resolve((6, 3), ("batch", "heads"), mr) == P("data", None)
        # 2-way data axis does not divide batch 3 -> replicate
        assert _resolve((3, 8), ("batch", "ff"), mr) == P(None, "model")
        # grok case: indivisible experts degrade, freeing "model" for the
        # expert FFN dim (tensor-parallel expert FFNs)
        assert _resolve((3, 16, 32), ("experts", None, "expert_ff"), mr) \
            == P(None, None, "model")
        # divisible experts claim "model" first; expert_ff then degrades
        assert _resolve((4, 16, 32), ("experts", None, "expert_ff"), mr) \
            == P("model", None, None)
        # opt_state_sharding degradation: the largest replicated dim (7) is
        # indivisible by "data"(2), so it is skipped and the next-largest
        # divisible dim (4) takes the axis instead
        ns = opt_state_sharding(P(), (7, 4), mr)
        assert ns.spec == P(None, "data")
        # nothing divisible -> fully replicated
        ns = opt_state_sharding(P(), (7, 5), mr)
        assert all(e is None for e in ns.spec)


@pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
def test_rule_overrides_and_freed_axes():
    """Overrides reroute logical axes; degradation frees axes for later dims
    (the batch=1 long-context kv_seq context-parallel trick)."""
    mesh = make_mesh((2, 4), ("data", "model"))
    with with_rules(mesh, {"kv_seq": ("data",)}) as mr:
        # batch=1 cannot take "data" (1 % 2 != 0); kv_seq picks it up
        spec = _resolve((1, 1024, 4, 64), ("batch", "kv_seq", "kv_heads", None), mr)
        assert spec == P(None, "data", "model", None)
        # with a shardable batch, batch wins "data" and kv_seq degrades
        spec = _resolve((4, 1024, 4, 64), ("batch", "kv_seq", "kv_heads", None), mr)
        assert spec == P("data", None, "model", None)


def test_axis_used_once(mesh):
    with with_rules(mesh) as mr:
        spec = _resolve((4, 4), ("heads", "ff"), mr)  # both want "model"
        assert spec[0] == "model" and spec[1] is None


def test_opt_state_extends(mesh):
    with with_rules(mesh) as mr:
        ns = opt_state_sharding(P(None, "model"), (8, 4), mr)
        assert ns.spec[0] == "data"


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "seamless-m4t-medium",
                                  "jamba-v0.1-52b", "mamba2-2.7b",
                                  "arctic-480b"])
@pytest.mark.parametrize("shape", ["train_4k", "decode_32k"])
def test_smoke_lowering_compiles(arch, shape):
    """lower().compile() of reduced configs on the host mesh — the same
    driver the 512-device dry-run uses."""
    from repro.launch.dryrun import lower_cell

    mesh = make_mesh((1, 1), ("data", "model"))
    record, lowered, compiled = lower_cell(arch, shape, mesh, smoke=True)
    assert record["cost"].get("flops", 0) > 0
    assert "error" not in record["memory"]
