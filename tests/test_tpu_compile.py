"""Main-path kernels compile for a TPU v5e chip at 24³×48 (and 16³×32).

The TPU compiler compiles for a chip that is described, not attached, so
these tests need no accelerator: they lower the Pallas kernels with the
Mosaic lowering for one chip of a described ``v5e:2x2`` topology and let
the chip's compiler refuse what the chip would refuse (VMEM overflow,
unaligned blocks, zero-width vectors).  The topology is described inside a
fixture, never at import: only one process at a time may load the TPU
library, and every test worker imports this file.
"""

import contextlib
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels.cg_fused.kernel as cg_kernel
import repro.kernels.cg_fused.ops as cg_ops
import repro.kernels.wilson_dslash.kernel as dslash_kernel
from repro.core import phases
from repro.core import plan as plan_mod
from repro.kernels.wilson_dslash.ops import schur_normal_op

T, Z, Y, X = 48, 24, 24, 24   # ETMC A40.24-sized lattice
XH = X // 2
SERVED = (32, 16, 16, 16, 8)  # tm-16c32 at the served ladder's top rung
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


@contextlib.contextmanager
def _mosaic_lowering():
    """Lower every kernel with Mosaic and keep the compile cache off: a
    compile for a described chip can be written to the cache but not read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dslash_kernel, "resolve_lowering",
                   lambda interpret: "mosaic")
        mp.setattr(cg_ops, "resolve_lowering", lambda interpret: "mosaic")
        mp.setattr(cg_kernel, "resolve_interpret", lambda interpret: False)
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield mp
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture
def mosaic():
    with _mosaic_lowering():
        yield


def _half_fields(one_chip, dims=(T, Z, Y, X), nrhs=None, dtype=jnp.float32):
    """Packed per-parity links (4, T, 18, Z, Y*Xh) and a spinor half field
    (T, 24, Z, Y*Xh), with a leading RHS batch when ``nrhs`` is given."""
    t, z, y, x = dims
    spec = lambda shape: jax.ShapeDtypeStruct(shape, dtype,
                                              sharding=one_chip)
    batch = (nrhs,) if nrhs else ()
    return (spec((4, t, 18, z, y * x // 2)),
            spec(batch + (t, 24, z, y * x // 2)))


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
    _compile(functools.partial(fn, xh=XH), u, u, p)


@pytest.mark.parametrize("kernel", ["eo", "oe"])
def test_parity_kernel_served_batch_compiles(mosaic, one_chip, kernel):
    """16³×32 with N = 8 right-hand sides (a full lane row, Y*Xh = 128),
    the batch on the grid."""
    *dims, nrhs = SERVED
    u, p = _half_fields(one_chip, dims, nrhs)
    fn = {"eo": dslash_kernel.dslash_eo_pallas,
          "oe": dslash_kernel.dslash_oe_pallas}[kernel]
    _compile(functools.partial(fn, xh=dims[3] // 2), u, u, p)


# (dims, nrhs, dtype, bz, the chunk the launch must take)
CHUNK_CASES = {
    "bf16-24c48": ((T, Z, Y, X), None, jnp.bfloat16, None, Z),
    "bf16-16c32-n8": (SERVED[:4], SERVED[4], jnp.bfloat16, None, 16),
    "f32-z20": ((8, 20, 20, 20), None, jnp.float32, None, 20),
    "f32-z12": ((8, 12, 12, 12), None, jnp.float32, None, 12),
    "f32-24c48-whole-z": ((T, Z, Y, X), None, jnp.float32, Z, Z),
}


@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_parity_kernel_chunks_compile(mosaic, one_chip, case):
    """Every kind of chunk the parity kernels may take compiles: bf16
    storage (the mixed-precision solve's inner operator, whole Z), Z
    extents with no whole sublane tile (Z = 20, and Z = 12 with 72 lanes),
    the whole Z of an f32 field.  One row is `test_unit_blocks_compile`."""
    dims, nrhs, dtype, bz, want = CHUNK_CASES[case]
    u, p = _half_fields(one_chip, dims, nrhs, dtype)
    xh = dims[3] // 2
    assert dslash_kernel._resolve_parity_tile(
        bz, (dims[0], dims[1], dims[2], xh), dtype, gauge_dtype=dtype,
        has_acc=True) == want
    _compile(lambda a, b, c: dslash_kernel.dslash_eo_pallas(
        a, b, c, xh=xh, bz=bz, psi_acc=c, acc_coeff=4.1, hop_coeff=-1.0),
        u, u, p)


def test_parity_kernel_fused_twisted_epilogue_compiles(mosaic, one_chip):
    """The Schur launch with the accumulator operand and both twists."""
    u, p = _half_fields(one_chip)
    _compile(lambda a, b, c: dslash_kernel.dslash_eo_pallas(
        a, b, c, xh=XH, psi_acc=c, acc_coeff=4.1, acc_twist=0.25,
        hop_coeff=-1.0, hop_twist=0.1), u, u, p)


def test_unit_blocks_compile(mosaic, one_chip):
    """bz=1: the z-shift of a one-row chunk is the edge row itself (no
    zero-width slice)."""
    u, p = _half_fields(one_chip)
    _compile(lambda a, b, c: dslash_kernel.dslash_eo_pallas(
        a, b, c, xh=XH, bz=1), u, u, p)


@pytest.mark.parametrize("twist", [0.0, 0.25])
def test_schur_normal_op_compiles(mosaic, one_chip, twist):
    u, p = _half_fields(one_chip)
    compiled = _compile(lambda a, b, c: schur_normal_op(
        a, b, c, 0.1, xh=XH, twist=twist), u, u, p)
    assert compiled.as_text().count("tpu_custom_call") >= 4


def test_mixed_precision_schur_normal_op_compiles(mosaic, one_chip):
    """The inner operator of the mixed-precision solve: bf16 half fields
    at 24³×48."""
    u, p = _half_fields(one_chip, dtype=jnp.bfloat16)
    compiled = _compile(lambda a, b, c: schur_normal_op(
        a, b, c, 0.1, xh=XH), u, u, p)
    assert compiled.as_text().count("tpu_custom_call") >= 4


def test_served_schur_normal_op_compiles(mosaic, one_chip):
    """The served twisted operator at 16³×32 on an N = 8 batch."""
    *dims, nrhs = SERVED
    u, p = _half_fields(one_chip, dims, nrhs)
    compiled = _compile(lambda a, b, c: schur_normal_op(
        a, b, c, 0.1, xh=dims[3] // 2, twist=0.25), u, u, p)
    assert compiled.as_text().count("tpu_custom_call") >= 4


def test_fused_cg_vector_kernels_compile(mosaic, one_chip):
    _, p = _half_fields(one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    _compile(lambda a, x, r, q, ap: cg_ops.cg_update(a, x, r, q, ap),
             scalar, p, p, p, p)
    _compile(lambda b, r, q: cg_ops.cg_xpay(b, r, q), scalar, p, p)


# ---------------------------------------------------------------------------
# The solve's phase scopes (repro.core.phases) in the compiled program
# ---------------------------------------------------------------------------

SMALL = (8, 4, 4, 8)    # T, Z, Y, X
# the custom calls of the eo-schur Pallas CGNR solve, as the device trace
# names them: the benchmark's kernel metrics match these names
KERNELS = {None: {"schur_op", "dslash_eo", "dslash_oe", "cg_update",
                  "cg_xpay"},
           2: {"schur_op", "dslash_eo", "dslash_oe", "cg_update_batched",
               "cg_xpay_batched"}}
# instructions that carry no op of the program: the compiler's own
EXEMPT = {"parameter", "constant", "tuple", "get-tuple-element"}


@contextlib.contextmanager
def _no_scope(name):
    yield


@pytest.fixture(scope="module")
def solve_text(one_chip):
    """(nrhs, scoped) -> ``as_text()`` of the eo-schur Pallas CGNR solve
    compiled for the described chip at a small lattice; ``scoped=False``
    compiles it with every phase scope a no-op."""
    texts = {}

    def get(nrhs, scoped=True):
        if (nrhs, scoped) not in texts:
            spec = lambda shape: jax.ShapeDtypeStruct(
                shape, jnp.complex64, sharding=one_chip)
            u = spec((4,) + SMALL + (3, 3))
            b = spec(((nrhs,) if nrhs else ()) + SMALL + (4, 3))
            plan = plan_mod.SolverPlan(operator="eo-schur", backend="pallas",
                                       solver="cgnr", nrhs=nrhs)
            fn = jax.jit(lambda u, b: plan_mod.solve(plan, u, b, 0.1,
                                                     tol=1e-6, maxiter=100))
            with _mosaic_lowering() as mp:
                if not scoped:
                    mp.setattr(phases, "scope", _no_scope)
                texts[nrhs, scoped] = fn.lower(u, b).compile().as_text()
        return texts[nrhs, scoped]

    return get


def _device_ops(text):
    """(name, opcode, op_name or None) of each instruction the device runs
    as an op: those of the computations no fusion or reduction calls."""
    inner = set(re.findall(r"(?:calls|to_apply)=%([\w.-]+)", text))
    comp = None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"\s+(?:ROOT )?%([\w.-]+) = (?:\(.*?\)|\S+) "
                     r"([\w-]+)\(", line)
        if m and comp not in inner:
            op = re.search(r'op_name="([^"]*)"', line)
            yield m.group(1), m.group(2), op.group(1) if op else None


def _strip(text):
    """The module without its debug tables and metadata."""
    lines = text.splitlines()
    start = next(i for i, line in enumerate(lines)
                 if line.startswith(("%", "ENTRY")))
    return re.sub(r",? metadata=\{[^{}]*\}", "", "\n".join(lines[start:]))


@pytest.mark.parametrize("nrhs", [None, 2], ids=["single", "batched"])
def test_every_op_of_the_solve_is_in_one_phase(solve_text, nrhs):
    """Each op the program traced (its op_name is a path) names exactly one
    ``solve.*`` phase.  The compiler's own copies, bitcasts and splits of
    a complex parameter carry no such path and are exempt."""
    seen, stray = set(), []
    for name, opcode, op_name in _device_ops(solve_text(nrhs)):
        if opcode in EXEMPT or op_name is None or "/" not in op_name:
            continue
        found = {m for part in op_name.split(";")
                 for m in re.findall(r"(?:^|/)(solve\.[a-z_]+)(?=/|$)", part)}
        seen |= found
        if len(found) != 1:
            stray.append((name, op_name))
    assert stray == []
    assert seen == set(phases.SOLVE_PHASES)


@pytest.mark.parametrize("nrhs", [None, 2], ids=["single", "batched"])
def test_kernel_launches_keep_their_names(solve_text, nrhs):
    names = re.findall(r'^\s+(?:ROOT )?%([\w.-]+) = .*custom_call_target='
                       r'"tpu_custom_call"', solve_text(nrhs), re.M)
    assert {re.sub(r"\.\d+$", "", n) for n in names} == KERNELS[nrhs]


@pytest.mark.parametrize("nrhs", [None, 2], ids=["single", "batched"])
def test_scopes_change_only_metadata(solve_text, nrhs):
    assert _strip(solve_text(nrhs)) == _strip(solve_text(nrhs, False))
