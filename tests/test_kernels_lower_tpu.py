"""Kernel pre-flight: every registered Pallas kernel must lower for the
TPU, checked from the CPU sandbox in seconds.

Two strengths of the same question, at the chip_smoke.py shapes (the
flagship at full width) and at a toy shape each kernel's `qualify`
accepts:

  cross-lowering   jax.jit(f).trace(...).lower(lowering_platforms=("tpu",))
                   runs the Pallas -> Mosaic lowering. This is where the
                   (1, bs, 1, Dh) paged BlockSpecs of PR 17 were refused
                   ("last two dimensions of your block shape ...").
  topology compile the installed libtpu compiles for a `v5e:2x2`
                   topology description with no chip attached: the real
                   TPU compiler, Mosaic included. This is where the
                   tree window's [1, 1] -> [C, bs] broadcast was refused.

Whether a kernel compiles is settled here. Numerics, memory and time are
not: those are chip_smoke.py's `kernels` leg, on the chip.

The kernels decide compiled-vs-interpreted in one place
(core.device.pallas_interpret); `device.compiling_for` tells that place
the trace is for a TPU this process does not hold.
"""

import os

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from paddle_tpu.core import device
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops.kernel_registry import registered_kernels

V5E = device.DeviceIdentity("tpu", "TPU v5 lite", 1)
SIZES = {"smoke": chip_smoke.FULL, "toy": chip_smoke.TOY}
KERNELS = sorted(registered_kernels())


def _case(name, sizes):
    specs, kwargs, qualify, _fill = chip_smoke.kernel_cases(SIZES[sizes])[name]
    spec = registered_kernels()[name]
    ok, why = spec.qualify(**qualify) if qualify else (True, None)
    assert ok, "%s disqualifies its own %s shape: %s" % (name, sizes, why)
    # a fresh function per lowering: the interpret decision is baked in
    # at trace time and must not be served from a CPU trace's cache
    return (lambda *a: spec.pallas(*a, **kwargs)), specs


def test_cases_cover_every_registered_kernel():
    assert sorted(chip_smoke.kernel_cases(chip_smoke.FULL)) == KERNELS


@pytest.mark.parametrize("sizes", sorted(SIZES))
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_cross_lowers_for_tpu(name, sizes):
    fn, specs = _case(name, sizes)
    with device.compiling_for(V5E):
        lowered = jax.jit(fn).trace(
            *[jax.ShapeDtypeStruct(s, d) for s, d in specs]).lower(
                lowering_platforms=("tpu",))
    # compiled, not interpreted: the kernel is a Mosaic custom call
    assert "tpu_custom_call" in lowered.as_text()


@pytest.fixture(scope="module")
def v5e_chips():
    """The four devices of a v5e:2x2 topology description: compile
    targets, not chips. Compile-only use takes no device, so several
    processes may load libtpu at once."""
    from jax.experimental import topologies

    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # libtpu could not start in this sandbox
        pytest.skip("no TPU compiler here: %r" % (e,))
    return topo.devices


@pytest.fixture
def v5e_chip(v5e_chips):
    return v5e_chips[0]


def _compile(fn, specs, chip):
    sharding = jax.sharding.SingleDeviceSharding(chip)
    with device.compiling_for(chip):
        return jax.jit(fn).lower(
            *[jax.ShapeDtypeStruct(s, d, sharding=sharding)
              for s, d in specs]).compile()


@pytest.mark.parametrize("sizes", sorted(SIZES))
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, sizes, v5e_chip):
    assert v5e_chip.device_kind == V5E.kind
    _compile(*_case(name, sizes), v5e_chip)


def test_flash_backward_compiles_for_v5e(v5e_chip):
    """The trainer differentiates through the library flash kernel."""
    specs = chip_smoke.kernel_cases(chip_smoke.FULL)["flash_attention"][0]

    def loss(q, k, v):
        return pk.flash_attention(q, k, v, causal=True) \
            .astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), specs, v5e_chip)


@pytest.mark.parametrize("shape,axes", [((4,), ("dp",)),
                                        ((2, 2), ("dp", "tp"))])
def test_flash_in_a_gspmd_step_compiles_for_four_chips(shape, axes,
                                                       v5e_chips):
    """GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"): the data-parallel trainer's flash call
    must go through the shard_map in compat_ops._flash_on_mesh. Compiled
    for all four devices of the topology, forward and backward."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from paddle_tpu.ops import compat_ops

    mesh = Mesh(np.array(v5e_chips).reshape(shape), axes)
    batch_sharded = NamedSharding(mesh, PartitionSpec("dp"))
    qkv = [jax.ShapeDtypeStruct(s, d, sharding=batch_sharded) for s, d in
           chip_smoke.kernel_cases(chip_smoke.FULL)["flash_attention"][0]]

    def loss(q, k, v):
        return compat_ops._flash_on_mesh(q, k, v, True, None, mesh) \
            .astype(jnp.float32).sum()

    with device.compiling_for(v5e_chips[0], count=4):
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*qkv).compile()


def test_shape_mosaic_refuses_is_disqualified_with_a_reason(v5e_chip):
    """A one-row page is the one paged geometry found that Mosaic
    refuses; `qualify` must say so, so the dispatch takes the lax
    fallback with a warning and never reaches the compiler."""
    B, H, Dh, bs, Mb, C = 4, 8, 64, 1, 16, 5
    spec = registered_kernels()["spec_window_tree"]
    ok, why = spec.qualify(head_dim=Dh, block_size=bs, window=C)
    assert not ok and "block_size" in why
    f32, i32 = jnp.float32, jnp.int32
    NB = B * Mb + 1
    specs = [((NB, bs, H, Dh), f32), ((NB, bs, H, Dh), f32),
             ((B, C, H, Dh), f32), ((B, Mb), i32), ((B, C), i32),
             ((C, C), f32)]
    with pytest.raises(Exception, match="Mosaic failed to compile"):
        _compile(lambda *a: spec.pallas(*a), specs, v5e_chip)
