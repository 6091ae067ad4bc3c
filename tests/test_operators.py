"""The operator registry: site-local physics decoupled from hop transport.

Covers the registry surface (round-trip, did-you-mean validation), the
SiteTerm algebra, and the acceptance contract for the second operator
family: twisted-mass EO-Schur solves (single, batched, sharded) match
their reference-backend counterparts to <= 1e-5 per RHS, mu -> 0 reduces
BITWISE to Wilson on both backends, and ``schur_normal_op`` stays exactly
4 kernel launches with zero standalone full-field passes for BOTH
families."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LatticeShape, SolverPlan, pack_half_gauge,
                        pack_half_spinor, pack_spinor, random_gauge,
                        random_spinor, solve_plan, split_eo, split_eo_gauge)
from repro.core.lattice import field_dot
from repro.core.operators import (LatticeOperator, SiteTerm,
                                  apply_igamma5_packed, dslash_dagger_g,
                                  dslash_g, get_operator, operator_names,
                                  register_operator, schur_dagger_g,
                                  schur_op_g)
from repro.testing import full_field_passes, pallas_call_eqns

LAT = LatticeShape(2, 4, 4, 4)  # small: interpret-mode trace cost
XH = LAT.x // 2
MASS = 0.1
MU = 0.3
TOL = 1e-6


@pytest.fixture(scope="module")
def problem():
    key = jax.random.PRNGKey(7)
    ku, kb = jax.random.split(key)
    return random_gauge(ku, LAT), random_spinor(kb, LAT)


@pytest.fixture(scope="module")
def eo_packed(problem):
    u, b = problem
    u_e, u_o = split_eo_gauge(u)
    p_e, _ = split_eo(b)
    return pack_half_gauge(u_e), pack_half_gauge(u_o), pack_half_spinor(p_e)


def _rel_res_tm(u, x, b):
    r = dslash_g(u, x, MASS, twist=MU) - b
    return float(jnp.linalg.norm(r.ravel()) / jnp.linalg.norm(b.ravel()))


def _tm_plan(**kw):
    kw.setdefault("mu", MU)
    return SolverPlan(operator="eo-schur", operator_family="twisted-mass",
                      **kw)


# ---------------------------------------------------------------------------
# Registry surface
# ---------------------------------------------------------------------------


def test_registry_round_trip():
    names = operator_names()
    assert {"wilson", "twisted-mass"} <= set(names)
    for name in names:
        spec = get_operator(name)
        assert spec.name == name
        assert get_operator(spec.name) is spec
    assert get_operator("wilson").params == ()
    assert get_operator("twisted-mass").params == ("mu",)


def test_registry_rejects_duplicate_names():
    with pytest.raises(ValueError, match="already registered"):
        register_operator(LatticeOperator(
            name="wilson", description="dup", params=(),
            make_site_term=lambda mass, r: SiteTerm(mass + 4.0 * r)))


def test_unknown_operator_family_suggests_registered_names():
    with pytest.raises(ValueError) as e:
        get_operator("twisted_mass")
    msg = str(e.value)
    assert "did you mean 'twisted-mass'" in msg
    for name in operator_names():  # the full registered list is shown
        assert repr(name) in msg
    # the same validation fires from the plan surface
    with pytest.raises(ValueError, match="twisted-mass"):
        SolverPlan(operator_family="twisted_mass")


def test_unknown_backend_suggests_allowed_names():
    with pytest.raises(ValueError) as e:
        SolverPlan(backend="palas")
    msg = str(e.value)
    assert "did you mean 'pallas'" in msg and "'reference'" in msg


def test_mu_requires_a_family_that_declares_it():
    with pytest.raises(ValueError, match="twisted-mass"):
        SolverPlan(mu=0.3)  # wilson has no 'mu' site parameter
    # declared family: fine, and the twist is exposed to the transport
    assert _tm_plan().twist == MU
    assert SolverPlan().twist == 0.0
    assert _tm_plan(mu=0.0).twist == 0.0


def test_plan_site_term_comes_from_registry():
    site = _tm_plan().site_term(MASS)
    assert site.scale == pytest.approx(MASS + 4.0) and site.twist == MU
    w = SolverPlan().site_term(MASS)
    assert w.scale == pytest.approx(MASS + 4.0) and w.twist == 0.0


def test_family_with_nonstandard_scale_fails_loudly(problem):
    """The transport kernels fold the site scale mass+4r at trace time,
    so a registered family declaring any OTHER scale must be rejected at
    resolve time — loudly, never silently solved with the Wilson scale."""
    name = "test-bad-scale"
    try:
        get_operator(name)
    except ValueError:
        register_operator(LatticeOperator(
            name=name, description="scale contract probe", params=(),
            make_site_term=lambda mass, r: SiteTerm(mass + 5.0 * r, 0.0)))
    u, b = problem
    with pytest.raises(NotImplementedError, match="scale"):
        solve_plan(SolverPlan(operator="eo-schur", operator_family=name),
                   u, b, MASS, tol=TOL, maxiter=10)


# ---------------------------------------------------------------------------
# SiteTerm algebra
# ---------------------------------------------------------------------------


def test_site_term_apply_solve_round_trip(problem):
    _, b = problem
    site = SiteTerm(MASS + 4.0, MU)
    # natural complex layout
    v = split_eo(b)[0]
    np.testing.assert_allclose(np.asarray(site.solve(site.apply(v))),
                               np.asarray(v), atol=1e-6)
    # packed half-field layout (dispatch on dtype) round-trips too
    p = pack_half_spinor(v)
    np.testing.assert_allclose(np.asarray(site.solve(site.apply(p))),
                               np.asarray(p), atol=1e-6)
    # packed apply agrees with the natural-layout definition
    nat = site.apply(v)
    np.testing.assert_allclose(np.asarray(site.apply(p)),
                               np.asarray(pack_half_spinor(nat)), atol=1e-6)
    # dagger flips the twist; inverse is analytic
    assert site.dag.twist == -MU and site.inv.twist == pytest.approx(
        -MU / ((MASS + 4.0) ** 2 + MU ** 2))


def test_wilson_site_term_solve_is_bitwise_division(problem):
    _, b = problem
    site = SiteTerm(MASS + 4.0, 0.0)
    v = split_eo(b)[0]
    np.testing.assert_array_equal(np.asarray(site.solve(v)),
                                  np.asarray(v / (MASS + 4.0)))


def test_igamma5_packed_matches_natural(problem):
    _, b = problem
    p = pack_spinor(b)
    np.testing.assert_allclose(np.asarray(apply_igamma5_packed(p)),
                               np.asarray(pack_spinor(
                                   1j * b * jnp.asarray(
                                       [1, 1, -1, -1],
                                       b.dtype)[:, None])), atol=1e-6)


# ---------------------------------------------------------------------------
# Twisted-mass operator identities (natural-layout oracles)
# ---------------------------------------------------------------------------


def test_twisted_dagger_is_the_adjoint(problem):
    """<q, D p> == <D^dag q, p> for the twisted full AND Schur operators
    (D is NOT gamma5-hermitian for mu != 0 — the dagger flips mu)."""
    u, b = problem
    q = random_spinor(jax.random.PRNGKey(3), LAT)
    lhs = complex(field_dot(q, dslash_g(u, b, MASS, twist=MU)))
    rhs = complex(field_dot(dslash_dagger_g(u, q, MASS, twist=MU), b))
    assert abs(lhs - rhs) < 1e-3 * abs(lhs)
    u_e, u_o = split_eo_gauge(u)
    b_e, q_e = split_eo(b)[0], split_eo(q)[0]
    lhs = complex(field_dot(q_e, schur_op_g(u_e, u_o, b_e, MASS, twist=MU)))
    rhs = complex(field_dot(schur_dagger_g(u_e, u_o, q_e, MASS, twist=MU),
                            b_e))
    assert abs(lhs - rhs) < 1e-3 * abs(lhs)


# ---------------------------------------------------------------------------
# Twisted-mass Pallas kernels vs the reference backend
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dagger", [False, True], ids=["plain", "dagger"])
def test_twisted_schur_kernel_matches_reference(eo_packed, dagger):
    from repro.kernels.wilson_dslash import ops as wops
    from repro.kernels.wilson_dslash.ref import schur_op_ref
    upe, upo, ppe = eo_packed
    out = wops.schur_op(upe, upo, ppe, MASS, xh=XH, twist=MU, dagger=dagger,
                        interpret=True)
    ref = schur_op_ref(upe, upo, ppe, MASS, xh=XH, twist=MU, dagger=dagger)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_twisted_batched_schur_matches_looped(eo_packed):
    """The gauge-amortized batched kernels extend to the second family
    UNCHANGED: each batched slice equals its single-RHS launch.  (Unlike
    Wilson — whose batched-equals-looped contract IS bitwise and stays
    so, see test_kernels.py — the twisted epilogue's longer multiply-add
    chain lets XLA pick fma contractions differently between the batched
    and unbatched compilations, so this family's contract is ulp-level.)"""
    from repro.kernels.wilson_dslash import ops as wops
    upe, upo, ppe = eo_packed
    key = jax.random.PRNGKey(11)
    batch = jnp.stack([ppe * (i + 1.0) for i in range(3)]) \
        + jax.random.normal(key, (3,) + ppe.shape, jnp.float32)
    out = wops.schur_op(upe, upo, batch, MASS, xh=XH, twist=MU,
                        interpret=True)
    looped = jnp.stack([wops.schur_op(upe, upo, batch[i], MASS, xh=XH,
                                      twist=MU, interpret=True)
                        for i in range(3)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(looped),
                               atol=2e-6)


@pytest.mark.parametrize("twist", [0.0, MU], ids=["wilson", "twisted"])
@pytest.mark.parametrize("n_rhs", [None, 2], ids=["single", "batched"])
def test_schur_normal_op_is_4_launches_for_both_families(eo_packed, twist,
                                                         n_rhs):
    """Acceptance: A_hat is EXACTLY 4 kernel launches with zero standalone
    full-field passes for BOTH operator families — the site term (and its
    twist) rides the kernel epilogues, never a separate pass."""
    from repro.kernels.wilson_dslash import ops as wops
    upe, upo, ppe = eo_packed
    v = ppe if n_rhs is None else jnp.stack([ppe] * n_rhs)
    jx = jax.make_jaxpr(
        lambda a, b, w: wops.schur_normal_op(a, b, w, MASS, xh=XH,
                                             twist=twist,
                                             interpret=True))(upe, upo, v)
    assert len(pallas_call_eqns(jx)) == 4
    assert full_field_passes(jx, v.size) == []
    if n_rhs is not None:  # per-RHS halves are never materialized either
        assert full_field_passes(jx, v.size // n_rhs) == []


# ---------------------------------------------------------------------------
# End-to-end: twisted-mass EO-Schur solves on every path
# ---------------------------------------------------------------------------


def test_mu_zero_reduces_bitwise_to_wilson(problem):
    """operator_family='twisted-mass' with mu=0 IS Wilson, bitwise, on
    both backends: every twist gate is a trace-time float compare, so the
    emitted program is identical."""
    u, b = problem
    for backend in ("reference", "pallas"):
        pw = SolverPlan(operator="eo-schur", backend=backend,
                        interpret=True)
        pt = _tm_plan(mu=0.0, backend=backend, interpret=True)
        xw, sw = solve_plan(pw, u, b, MASS, tol=TOL, maxiter=1000)
        xt, st = solve_plan(pt, u, b, MASS, tol=TOL, maxiter=1000)
        np.testing.assert_array_equal(np.asarray(xw), np.asarray(xt))
        assert int(sw.iterations) == int(st.iterations)


def test_twisted_eo_solve_pallas_matches_reference(problem):
    """Single-RHS twisted EO-Schur: the Pallas fast path reproduces the
    reference backend to <= 1e-5 and solves the twisted system."""
    u, b = problem
    x_ref, st_ref = solve_plan(_tm_plan(), u, b, MASS, tol=TOL,
                               maxiter=1000)
    x_pal, st_pal = solve_plan(_tm_plan(backend="pallas", interpret=True),
                               u, b, MASS, tol=TOL, maxiter=1000)
    assert bool(st_ref.converged) and bool(st_pal.converged)
    assert _rel_res_tm(u, x_ref, b) < 1e-5
    assert _rel_res_tm(u, x_pal, b) < 1e-5
    assert abs(int(st_pal.iterations) - int(st_ref.iterations)) <= 1
    assert float(jnp.max(jnp.abs(x_pal - x_ref))) <= 1e-5


def test_twisted_batched_solve_matches_reference_singles(problem):
    """Batched (N=4) twisted EO-Schur on the Pallas path: every RHS
    matches its independent reference-backend solve to <= 1e-5 (the
    acceptance bound), and its own single-RHS Pallas solve to ulp-level
    (same fma-contraction caveat as the kernel test above — the WILSON
    batched-equals-looped contract remains bitwise in test_eo.py)."""
    u, _ = problem
    n = 4
    kb = jax.random.PRNGKey(17)
    b = jnp.stack([random_spinor(jax.random.fold_in(kb, i), LAT)
                   for i in range(n)])
    xb, stb = solve_plan(_tm_plan(backend="pallas", nrhs=n, interpret=True),
                         u, b, MASS, tol=TOL, maxiter=1000)
    assert stb.converged.shape == (n,) and bool(jnp.all(stb.converged))
    for i in range(n):
        xi, _ = solve_plan(_tm_plan(), u, b[i], MASS, tol=TOL, maxiter=1000)
        assert float(jnp.max(jnp.abs(xb[i] - xi))) <= 1e-5
        assert _rel_res_tm(u, xb[i], b[i]) < 1e-5
    x0, st0 = solve_plan(_tm_plan(backend="pallas", interpret=True),
                         u, b[0], MASS, tol=TOL, maxiter=1000)
    np.testing.assert_allclose(np.asarray(xb[0]), np.asarray(x0),
                               atol=1e-5)
    assert int(st0.iterations) <= int(stb.iterations)


def test_twisted_mixed_precision_composes(problem):
    """The reliable-update mixed-precision Schur solve is operator-
    agnostic: bf16 inner iterations on the twisted operator still reach
    the f32 tolerance."""
    u, b = problem
    x, st = solve_plan(_tm_plan(precision="mixed"), u, b, MASS, tol=TOL,
                       maxiter=1000)
    assert bool(st.converged)
    assert _rel_res_tm(u, x, b) < 1e-5
    assert int(st.iterations) >= 2 * int(st.outer_iterations)


# ---------------------------------------------------------------------------
# Sharded: the 8-device mesh runs the second family unchanged
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.core import (LatticeShape, SolverPlan, random_gauge,
                        random_spinor, solve_plan)
from repro.core.operators import dslash_g

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
lat = LatticeShape(4, 4, 4, 8)
m, mu, tol, N = 0.1, 0.3, 1e-6, 2
ku, kb = jax.random.split(jax.random.PRNGKey(7))
u = random_gauge(ku, lat)
b = jnp.stack([random_spinor(jax.random.fold_in(kb, i), lat)
               for i in range(N)])
psh = SolverPlan(operator="eo-schur", operator_family="twisted-mass",
                 mu=mu, solver="pipecg", nrhs=N, mesh=mesh)
xsh, stsh = solve_plan(psh, u, b, m, tol=tol, maxiter=500)
# gather to the host: the checks below apply the global-view operator
xsh = jax.device_get(xsh)
p1 = SolverPlan(operator="eo-schur", operator_family="twisted-mass",
                mu=mu, nrhs=N)
x1, _ = solve_plan(p1, u, b, m, tol=tol, maxiter=500)
res = jax.vmap(lambda xx, bv: dslash_g(u, xx, m, twist=mu) - bv)(xsh, b)
rels = (jnp.linalg.norm(res.reshape(N, -1), axis=1)
        / jnp.linalg.norm(b.reshape(N, -1), axis=1))
out = {"all_converged": bool(jnp.all(stsh.converged)),
       "iters": int(stsh.iterations),
       "rhs_iters": [int(v) for v in stsh.rhs_iterations],
       "max_rel_res": float(jnp.max(rels)),
       "max_dev_vs_single_device": float(jnp.max(jnp.abs(xsh - x1)))}
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def test_sharded_twisted_solve_matches_single_device(sharded_results):
    """Acceptance: the sharded (2x2x2 mesh, one-psum pipelined) twisted
    batched Schur solve converges per RHS and matches the single-device
    reference solve to <= 1e-5 — the halo transport never looked at the
    operator family."""
    r = sharded_results
    assert r["all_converged"], r
    assert r["max_rel_res"] < 1e-4, r
    assert r["max_dev_vs_single_device"] <= 1e-5, r
    assert max(r["rhs_iters"]) == r["iters"]
