"""Each phase of a solve, and each kernel launch, carries its named scope.

``repro.core.phases`` names the phases of ``plan.solve`` and the kernel
launches inside them with ``jax.named_scope``; the scopes reach the
lowered program as the ``op_name`` of every operation traced under them.
These tests lower solves on the CPU and read the scope names out of the
lowered text.  (That the scopes change nothing else of the compiled TPU
program is checked in ``tests/test_tpu_compile.py``.)
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import phases
from repro.core.lattice import LatticeShape, random_gauge, random_spinor
from repro.core.plan import SolverPlan, solve

LAT = LatticeShape(4, 4, 4, 4)
MASS = 0.1


@pytest.fixture(scope="module")
def fields():
    ku, kb = jax.random.split(jax.random.PRNGKey(3))
    return random_gauge(ku, LAT), random_spinor(kb, LAT)


def _scopes(plan, u, b) -> set[str]:
    """The scope names in the op names of the lowered solve."""
    text = jax.jit(lambda u, b: solve(plan, u, b, MASS, tol=1e-6,
                                      maxiter=20)).lower(u, b).as_text(
        debug_info=True)
    return set(re.findall(r"(?<![\w.])((?:solve|wilson|cg|mesh)\.[a-z_]+)",
                          text))


@pytest.mark.parametrize("kw, nrhs", [
    (dict(solver="cgnr"), None),
    (dict(solver="cgnr"), 2),
    (dict(solver="pipecg"), None),
    (dict(solver="blockcg"), 2),
    (dict(solver="cgnr", precision="mixed"), None),
], ids=["cgnr", "cgnr-batched", "pipecg", "blockcg", "mixed"])
def test_every_eo_phase_is_named(fields, kw, nrhs):
    u, b = fields
    if nrhs:
        b = jnp.stack([b] * nrhs)
    found = _scopes(SolverPlan(operator="eo-schur", nrhs=nrhs, **kw), u, b)
    assert set(phases.SOLVE_PHASES) <= found


def test_full_operator_names_its_loop_and_verification(fields):
    u, b = fields
    found = _scopes(SolverPlan(operator="full"), u, b)
    assert {phases.KRYLOV, phases.VERIFY} <= found


def test_pallas_launches_are_named_inside_the_loop(fields):
    u, b = fields
    plan = SolverPlan(operator="eo-schur", backend="pallas", interpret=True)
    found = _scopes(plan, u, b)
    assert set(phases.SOLVE_PHASES) <= found
    assert {phases.SCHUR, phases.HOP_EO, phases.HOP_OE, phases.CG_UPDATE,
            phases.CG_XPAY} <= found


def test_sharded_reductions_are_named(fields):
    u, b = fields
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    found = _scopes(SolverPlan(operator="eo-schur", mesh=mesh), u, b)
    assert {phases.KRYLOV, phases.PSUM, phases.VERIFY} <= found


def test_scoped_decorator_follows_a_replaced_scope(monkeypatch):
    """``scoped`` looks ``scope`` up at each call, so replacing it (as the
    compile tests do) reaches every decorated function."""
    seen = []

    @contextlib.contextmanager
    def record(name):
        seen.append(name)
        yield

    monkeypatch.setattr(phases, "scope", record)
    assert phases.scoped(phases.VERIFY)(lambda x: x + 1)(1) == 2
    assert seen == [phases.VERIFY]
