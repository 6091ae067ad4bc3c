"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LatticeShape, complex_to_real_pair, pack_gauge,
                        pack_half_gauge, pack_half_spinor, pack_spinor,
                        random_gauge, random_spinor, real_pair_to_complex,
                        split_eo, split_eo_gauge)
from repro.kernels.cg_fused import (cg_pallas, cg_update, cg_update_ref,
                                    cg_xpay, cg_xpay_ref)
from repro.kernels.wilson_dslash import dslash as dslash_k
from repro.kernels.wilson_dslash import (dslash_eo_ref, dslash_oe_ref,
                                         dslash_ref, schur_normal_op_ref,
                                         schur_op_ref)
from repro.kernels.wilson_dslash.ops import dslash_eo as eo_k
from repro.kernels.wilson_dslash.ops import dslash_oe as oe_k
from repro.kernels.wilson_dslash.ops import normal_op as normal_k
from repro.kernels.wilson_dslash.ops import schur_normal_op as schur_nk
from repro.kernels.wilson_dslash.ops import schur_op as schur_k
from repro.core.wilson import dslash_dagger_packed
from repro.testing import full_field_passes, maybe_hypothesis, pallas_call_eqns

given, settings, st = maybe_hypothesis()

SHAPES = [LatticeShape(2, 2, 4, 8), LatticeShape(4, 4, 4, 8),
          LatticeShape(3, 6, 8, 16), LatticeShape(2, 8, 8, 8)]

# the acceptance lattices for the parity kernels: 4^4 and 8*4^3
EO_SHAPES = [LatticeShape(4, 4, 4, 4), LatticeShape(8, 4, 4, 4)]
EO_MASS = 0.1


@pytest.fixture(scope="module")
def fields():
    key = jax.random.PRNGKey(11)
    out = {}
    for lat in SHAPES:
        ku, kp = jax.random.split(jax.random.fold_in(key, lat.volume))
        out[lat.dims] = (pack_gauge(random_gauge(ku, lat)),
                         pack_spinor(random_spinor(kp, lat)))
    return out


@pytest.mark.parametrize("lat", SHAPES, ids=str)
@pytest.mark.parametrize("mass", [0.0, 0.25])
def test_dslash_kernel_shape_sweep(fields, lat, mass):
    up, pp = fields[lat.dims]
    ref = dslash_ref(up, pp, mass)
    out = dslash_k(up, pp, mass)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("bz", [1, 2, 4])
def test_dslash_kernel_block_sizes(fields, bz):
    lat = SHAPES[1]
    up, pp = fields[lat.dims]
    ref = dslash_ref(up, pp, 0.1)
    out = dslash_k(up, pp, 0.1, bz=bz)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dslash_kernel_dtype_sweep(fields, dtype):
    lat = SHAPES[0]
    up, pp = fields[lat.dims]
    upd, ppd = up.astype(dtype), pp.astype(dtype)
    ref32 = dslash_ref(up, pp, 0.1)
    out = dslash_k(upd, ppd, 0.1).astype(jnp.float32)
    tol = 2e-5 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref32),
                               atol=tol, rtol=tol)


def test_dslash_kernel_dagger_hermiticity(fields):
    lat = SHAPES[1]
    up, pp = fields[lat.dims]
    key = jax.random.PRNGKey(3)
    qq = pack_spinor(random_spinor(key, lat))
    from repro.kernels.wilson_dslash.ops import dslash_dagger as dag_k
    lhs = float(jnp.sum(qq * dslash_k(up, pp, 0.1)))
    rhs = float(jnp.sum(dag_k(up, qq, 0.1) * pp))
    assert np.isclose(lhs, rhs, rtol=1e-4)


# ---------------------------------------------------------------------------
# Parity (even-odd) kernels vs the core/wilson.py references
# ---------------------------------------------------------------------------


def _eo_packed(lat, key):
    """Packed per-parity links + packed halves of a random spinor."""
    ku, kp = jax.random.split(jax.random.fold_in(key, lat.volume))
    u_e, u_o = split_eo_gauge(random_gauge(ku, lat))
    p_e, p_o = split_eo(random_spinor(kp, lat))
    return (pack_half_gauge(u_e), pack_half_gauge(u_o),
            pack_half_spinor(p_e), pack_half_spinor(p_o))


@pytest.fixture(scope="module")
def eo_fields():
    """Packed per-parity fields + packed halves of a random spinor."""
    key = jax.random.PRNGKey(23)
    return {lat.dims: _eo_packed(lat, key) for lat in EO_SHAPES}


@pytest.mark.parametrize("lat", EO_SHAPES, ids=str)
def test_parity_kernels_match_core(eo_fields, lat):
    """D_eo / D_oe Pallas kernels match the core oracles to <= 1e-5."""
    upe, upo, ppe, ppo = eo_fields[lat.dims]
    xh = lat.x // 2
    np.testing.assert_allclose(np.asarray(eo_k(upe, upo, ppo, xh=xh)),
                               np.asarray(dslash_eo_ref(upe, upo, ppo,
                                                        xh=xh)),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(oe_k(upe, upo, ppe, xh=xh)),
                               np.asarray(dslash_oe_ref(upe, upo, ppe,
                                                        xh=xh)),
                               atol=1e-5)


@pytest.mark.parametrize("lat", EO_SHAPES, ids=str)
@pytest.mark.parametrize("dagger", [False, True], ids=["plain", "dagger"])
def test_schur_kernel_matches_core(eo_fields, lat, dagger):
    """The 2-launch Schur kernel (γ5 + axpy folded) matches the oracle,
    including the γ5-folded dagger path."""
    upe, upo, ppe, _ = eo_fields[lat.dims]
    xh = lat.x // 2
    out = schur_k(upe, upo, ppe, EO_MASS, xh=xh, dagger=dagger)
    ref = schur_op_ref(upe, upo, ppe, EO_MASS, xh=xh, dagger=dagger)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_schur_normal_op_matches_core(eo_fields):
    lat = EO_SHAPES[0]
    upe, upo, ppe, _ = eo_fields[lat.dims]
    out = schur_nk(upe, upo, ppe, EO_MASS, xh=lat.x // 2)
    ref = schur_normal_op_ref(upe, upo, ppe, EO_MASS, xh=lat.x // 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_parity_gamma5_flags_match_ref(eo_fields):
    """The folded gamma5_in/gamma5_out flags equal explicit γ5 wrapping."""
    lat = EO_SHAPES[0]
    upe, upo, ppe, _ = eo_fields[lat.dims]
    xh = lat.x // 2
    out = oe_k(upe, upo, ppe, xh=xh, gamma5_in=True, gamma5_out=True)
    ref = dslash_oe_ref(upe, upo, ppe, xh=xh, gamma5_in=True,
                        gamma5_out=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_gamma5_folding_zero_extra_passes(fields, eo_fields):
    """γ5 folding means the normal operators are PURE kernel launches: no
    non-pallas equation in the jaxpr materializes a full field — i.e. zero
    standalone apply_gamma5_packed (or axpy) HBM passes."""
    lat = SHAPES[0]
    up, pp = fields[lat.dims]
    jx = jax.make_jaxpr(
        lambda u, p: normal_k(u, p, 0.1, interpret=True))(up, pp)
    assert len(pallas_call_eqns(jx)) == 2
    assert full_field_passes(jx, pp.size) == []

    upe, upo, ppe, _ = eo_fields[EO_SHAPES[0].dims]
    xh = EO_SHAPES[0].x // 2
    jx = jax.make_jaxpr(
        lambda a, b, v: schur_nk(a, b, v, EO_MASS, xh=xh, interpret=True))(
            upe, upo, ppe)
    assert len(pallas_call_eqns(jx)) == 4
    assert full_field_passes(jx, ppe.size) == []


@pytest.mark.parametrize("shape", [(128, 128), (3, 5, 7, 24, 8), (1000,),
                                   (256, 24, 8)])
def test_cg_update_shapes(shape):
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 4)
    x, r, p, ap = (jax.random.normal(k, shape, jnp.float32) for k in ks)
    alpha = jnp.float32(0.37)
    xo, ro, rs = cg_update(alpha, x, r, p, ap)
    xr, rr, rsr = cg_update_ref(alpha, x, r, p, ap)
    np.testing.assert_allclose(np.asarray(xo), np.asarray(xr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(ro), np.asarray(rr), atol=1e-6)
    assert np.isclose(float(rs), float(rsr), rtol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.floats(-2, 2), st.floats(-2, 2))
def test_cg_fused_property(seed, alpha, beta):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    shape = (37, 11)  # deliberately not lane-aligned: exercises padding
    x, r, p, ap = (jax.random.normal(k, shape, jnp.float32) for k in ks)
    xo, ro, rs = cg_update(jnp.float32(alpha), x, r, p, ap)
    assert np.allclose(np.asarray(xo), np.asarray(x + alpha * p), atol=1e-5)
    assert np.allclose(np.asarray(ro), np.asarray(r - alpha * ap), atol=1e-5)
    assert np.isclose(float(rs), float(jnp.sum(ro * ro)), rtol=1e-4)
    po = cg_xpay(jnp.float32(beta), r, p)
    assert np.allclose(np.asarray(po), np.asarray(r + beta * p), atol=1e-5)


def test_cg_update_complex_via_real_pair_view():
    """complex64 CG state runs through the fused kernels as f32 real pairs;
    the result equals the complex arithmetic and the reduction is the
    complex ||r||^2."""
    key = jax.random.PRNGKey(17)
    shape = (5, 7, 3)
    ks = jax.random.split(key, 8)
    mk = lambda kr, ki: (jax.random.normal(kr, shape)
                         + 1j * jax.random.normal(ki, shape)
                         ).astype(jnp.complex64)
    x, r, p, ap = (mk(ks[2 * i], ks[2 * i + 1]) for i in range(4))
    alpha = jnp.float32(0.61)
    pairs = [complex_to_real_pair(v) for v in (x, r, p, ap)]
    xo, ro, rs = cg_update(alpha, *pairs)
    np.testing.assert_allclose(np.asarray(real_pair_to_complex(xo)),
                               np.asarray(x + alpha * p), atol=1e-6)
    np.testing.assert_allclose(np.asarray(real_pair_to_complex(ro)),
                               np.asarray(r - alpha * ap), atol=1e-6)
    r_new = r - alpha * ap
    assert np.isclose(float(rs),
                      float(jnp.sum(jnp.abs(r_new) ** 2)), rtol=1e-5)
    po = cg_xpay(jnp.float32(0.3), pairs[1], pairs[2])
    np.testing.assert_allclose(np.asarray(real_pair_to_complex(po)),
                               np.asarray(r + 0.3 * p), atol=1e-6)


def test_cg_update_bf16_storage():
    """bf16 storage dtype round-trips (narrow storage, f32 accumulate)."""
    key = jax.random.PRNGKey(29)
    ks = jax.random.split(key, 4)
    shape = (64, 24, 8)
    x, r, p, ap = (jax.random.normal(k, shape, jnp.float32).astype(
        jnp.bfloat16) for k in ks)
    alpha = jnp.float32(0.37)
    xo, ro, rs = cg_update(alpha, x, r, p, ap)
    assert xo.dtype == ro.dtype == jnp.bfloat16
    assert rs.dtype == jnp.float32
    xr, rr, rsr = cg_update_ref(alpha, x, r, p, ap)
    np.testing.assert_allclose(np.asarray(xo, np.float32),
                               np.asarray(xr, np.float32), atol=1e-6)
    np.testing.assert_allclose(np.asarray(ro, np.float32),
                               np.asarray(rr, np.float32), atol=1e-6)
    assert np.isclose(float(rs), float(rsr), rtol=1e-5)
    po = cg_xpay(jnp.float32(0.25), r, p)
    assert po.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(po, np.float32),
        np.asarray(cg_xpay_ref(jnp.float32(0.25), r, p), np.float32),
        atol=1e-6)


# ---------------------------------------------------------------------------
# Multi-RHS (batched) kernels: gauge-amortized stencils + batched vector engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def batched_eo_fields():
    """Packed per-parity gauge + an N=3 stack of packed spinor halves."""
    lat = EO_SHAPES[0]
    key = jax.random.PRNGKey(41)
    ku, kp = jax.random.split(key)
    u = random_gauge(ku, lat)
    u_e, u_o = split_eo_gauge(u)
    halves = [split_eo(random_spinor(jax.random.fold_in(kp, i), lat))
              for i in range(3)]
    ppe = jnp.stack([pack_half_spinor(h[0]) for h in halves])
    ppo = jnp.stack([pack_half_spinor(h[1]) for h in halves])
    return pack_half_gauge(u_e), pack_half_gauge(u_o), ppe, ppo


def test_batched_parity_kernels_bitwise_match_looped(batched_eo_fields):
    """The batched parity kernels (one launch, N spinor planes per gauge
    fetch) produce bitwise the same halves as N single-RHS launches."""
    upe, upo, ppe, ppo = batched_eo_fields
    n = ppe.shape[0]
    xh = EO_SHAPES[0].x // 2
    out = eo_k(upe, upo, ppo, xh=xh)
    ref = jnp.stack([eo_k(upe, upo, ppo[i], xh=xh) for i in range(n)])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    out = oe_k(upe, upo, ppe, xh=xh, gamma5_in=True, gamma5_out=True)
    ref = jnp.stack([oe_k(upe, upo, ppe[i], xh=xh, gamma5_in=True,
                          gamma5_out=True) for i in range(n)])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("dagger", [False, True], ids=["plain", "dagger"])
def test_batched_schur_matches_looped_and_ref(batched_eo_fields, dagger):
    upe, upo, ppe, _ = batched_eo_fields
    n = ppe.shape[0]
    xh = EO_SHAPES[0].x // 2
    out = schur_k(upe, upo, ppe, EO_MASS, xh=xh, dagger=dagger)
    looped = jnp.stack([schur_k(upe, upo, ppe[i], EO_MASS, xh=xh,
                                dagger=dagger) for i in range(n)])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(looped))
    ref = schur_op_ref(upe, upo, ppe, EO_MASS, xh=xh, dagger=dagger)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_batched_full_dslash_matches_looped(fields):
    lat = SHAPES[0]
    up, pp = fields[lat.dims]
    key = jax.random.PRNGKey(31)
    pps = jnp.stack([pack_spinor(random_spinor(jax.random.fold_in(key, i),
                                               lat)) for i in range(2)])
    out = dslash_k(up, pps, 0.1)
    looped = jnp.stack([dslash_k(up, pps[i], 0.1) for i in range(2)])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(looped))
    # reference fallback takes the same batched rank
    ref = dslash_k(up, pps, 0.1, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("n_rhs", [2, 5])
def test_batched_schur_normal_op_launch_count_independent_of_n(
        batched_eo_fields, n_rhs):
    """Acceptance: the batched A_hat is STILL exactly 4 kernel launches with
    zero standalone full-field γ5/axpy/cast passes, whatever N is."""
    upe, upo, ppe, _ = batched_eo_fields
    batch = jnp.concatenate([ppe] * 2)[:n_rhs]
    xh = EO_SHAPES[0].x // 2
    jx = jax.make_jaxpr(
        lambda a, b, v: schur_nk(a, b, v, EO_MASS, xh=xh, interpret=True))(
            upe, upo, batch)
    assert len(pallas_call_eqns(jx)) == 4
    assert full_field_passes(jx, batch.size) == []       # batched fields
    assert full_field_passes(jx, batch.size // n_rhs) == []  # per-RHS halves


def test_batched_cg_update_matches_looped_and_ref():
    from repro.kernels.cg_fused import (cg_update_batched,
                                        cg_update_batched_ref,
                                        cg_xpay_batched, cg_xpay_batched_ref)
    key = jax.random.PRNGKey(43)
    n, shape = 3, (37, 11)  # not lane-aligned: exercises per-RHS padding
    ks = jax.random.split(key, 4)
    x, r, p, ap = (jax.random.normal(k, (n,) + shape, jnp.float32)
                   for k in ks)
    alpha = jnp.asarray([0.5, 0.0, -1.2], jnp.float32)
    xo, ro, rs = cg_update_batched(alpha, x, r, p, ap)
    assert rs.shape == (n,)
    # bitwise vs the unbatched fused kernel per RHS (the solver equivalence
    # contract), close vs the jnp oracle (FMA fusion differs by ulps)
    for i in range(n):
        xi, ri, rsi = cg_update(alpha[i], x[i], r[i], p[i], ap[i])
        np.testing.assert_array_equal(np.asarray(xo[i]), np.asarray(xi))
        np.testing.assert_array_equal(np.asarray(ro[i]), np.asarray(ri))
        assert float(rs[i]) == float(rsi)
    xr, rr, rsr = cg_update_batched_ref(alpha, x, r, p, ap)
    np.testing.assert_allclose(np.asarray(xo), np.asarray(xr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(ro), np.asarray(rr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(rs), np.asarray(rsr), rtol=1e-5)
    # alpha = 0 slice is bitwise frozen
    np.testing.assert_array_equal(np.asarray(xo[1]), np.asarray(x[1]))
    np.testing.assert_array_equal(np.asarray(ro[1]), np.asarray(r[1]))

    beta = jnp.asarray([0.3, 7.7, -0.7], jnp.float32)
    gate = jnp.asarray([True, False, True])
    po = cg_xpay_batched(beta, r, p, gate)
    np.testing.assert_allclose(
        np.asarray(po), np.asarray(cg_xpay_batched_ref(beta, r, p, gate)),
        atol=1e-6)
    # gated-off slice is bitwise frozen; gated-on matches the unbatched kernel
    np.testing.assert_array_equal(np.asarray(po[1]), np.asarray(p[1]))
    np.testing.assert_array_equal(np.asarray(po[0]),
                                  np.asarray(cg_xpay(beta[0], r[0], p[0])))


@pytest.mark.parametrize("n", [130, 407, 1000])
def test_cg_update_pad_region_contributes_exactly_zero(n):
    """Sizes that are not multiples of 128*block_rows: the streaming pad
    must contribute EXACTLY 0 to the ||r||^2 partial sums."""
    x = jnp.zeros((n,), jnp.float32)
    r = jnp.ones((n,), jnp.float32)
    p = jnp.full((n,), 2.0, jnp.float32)
    ap = jnp.full((n,), 3.0, jnp.float32)
    # alpha = 0: r is untouched, so any nonzero pad contribution is visible
    xo, ro, rs = cg_update(jnp.float32(0.0), x, r, p, ap)
    assert float(rs) == float(n)
    assert xo.shape == ro.shape == (n,)
    # alpha != 0: pad lanes are 0 - alpha*0 = 0 and must stay invisible
    _, ro2, rs2 = cg_update(jnp.float32(0.5), x, r, p, ap)
    assert float(rs2) == float(jnp.sum(ro2 * ro2))
    np.testing.assert_allclose(np.asarray(ro2), np.full((n,), -0.5),
                               atol=1e-7)


def test_cg_pallas_end_to_end(fields):
    """Full CG through both Pallas kernels solves the Wilson system."""
    lat = SHAPES[1]
    up, pp = fields[lat.dims]
    m = 0.4
    b = dslash_dagger_packed(up, pp, m)
    x, (k, rs) = cg_pallas(lambda v: normal_k(up, v, m), b, tol=1e-6,
                           maxiter=300)
    res = dslash_k(up, x, m) - pp
    rel = float(jnp.linalg.norm(res.ravel()) / jnp.linalg.norm(pp.ravel()))
    assert rel < 1e-5
