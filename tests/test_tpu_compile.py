"""Main-path kernels compile for a TPU v5e chip at 24³×48.

The TPU compiler compiles for a chip that is described, not attached, so
these tests need no accelerator: they lower the Pallas kernels with the
Mosaic lowering for one chip of a described ``v5e:2x2`` topology and let
the chip's compiler refuse what the chip would refuse (VMEM overflow,
unaligned blocks, zero-width vectors).  The topology is described inside a
fixture, never at import: only one process at a time may load the TPU
library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels.cg_fused.kernel as cg_kernel
import repro.kernels.cg_fused.ops as cg_ops
import repro.kernels.wilson_dslash.kernel as dslash_kernel
from repro.kernels.wilson_dslash.ops import schur_normal_op

T, Z, Y, X = 48, 24, 24, 24   # ETMC A40.24-sized lattice
XH = X // 2
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.fail("no v5e:2x2 topology can be described: these compile "
                    f"checks need the TPU library (libtpu) installed: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Lower every kernel with Mosaic and keep the compile cache off: a
    compile for a described chip can be written to the cache but not read
    back without one."""
    monkeypatch.setattr(dslash_kernel, "resolve_lowering",
                        lambda interpret: "mosaic")
    monkeypatch.setattr(cg_ops, "resolve_lowering",
                        lambda interpret: "mosaic")
    monkeypatch.setattr(cg_kernel, "resolve_interpret",
                        lambda interpret: False)
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _half_fields(one_chip):
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    return spec((4, T, Z, Y, 18, XH)), spec((T, Z, Y, 24, XH))


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    return compiled


@pytest.mark.parametrize("kernel", ["eo", "oe"])
def test_parity_kernel_default_tile_compiles(mosaic, one_chip, kernel):
    u, p = _half_fields(one_chip)
    fn = {"eo": dslash_kernel.dslash_eo_pallas,
          "oe": dslash_kernel.dslash_oe_pallas}[kernel]
    _compile(fn, u, u, p)


def test_parity_kernel_fused_twisted_epilogue_compiles(mosaic, one_chip):
    """The Schur launch with the accumulator operand and both twists."""
    u, p = _half_fields(one_chip)
    _compile(lambda a, b, c: dslash_kernel.dslash_eo_pallas(
        a, b, c, psi_acc=c, acc_coeff=4.1, acc_twist=0.25,
        hop_coeff=-1.0, hop_twist=0.1), u, u, p)


def test_unit_blocks_compile(mosaic, one_chip):
    """bz=1 and by=1: the z- and y-shifts of a one-plane block are the
    boundary plane itself (no zero-width slice)."""
    u, p = _half_fields(one_chip)
    _compile(lambda a, b, c: dslash_kernel.dslash_eo_pallas(
        a, b, c, bz=1, by=1), u, u, p)


@pytest.mark.parametrize("twist", [0.0, 0.25])
def test_schur_normal_op_compiles(mosaic, one_chip, twist):
    u, p = _half_fields(one_chip)
    compiled = _compile(lambda a, b, c: schur_normal_op(
        a, b, c, 0.1, twist=twist), u, u, p)
    assert compiled.as_text().count("tpu_custom_call") >= 4


def test_fused_cg_vector_kernels_compile(mosaic, one_chip):
    _, p = _half_fields(one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    _compile(lambda a, x, r, q, ap: cg_ops.cg_update(a, x, r, q, ap),
             scalar, p, p, p, p)
    _compile(lambda b, r, q: cg_ops.cg_xpay(b, r, q), scalar, p, p)
