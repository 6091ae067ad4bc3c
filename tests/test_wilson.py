"""Dirac-Wilson operator: gamma algebra, Hermiticity structure, layouts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LatticeShape, dslash, dslash_dagger, field_dot,
                        merge_eo, pack_gauge, pack_spinor, random_gauge,
                        random_spinor, split_eo, split_eo_gauge, unit_gauge,
                        unpack_spinor)
from repro.core.wilson import (DSLASH_FLOPS_PER_SITE, GAMMAS, GAMMA5,
                               dslash_eo, dslash_oe, dslash_packed,
                               dslash_dagger_packed, hop_term_packed,
                               normal_op, normal_op_packed, schur_dagger,
                               schur_normal_op, schur_op)

LAT = LatticeShape(4, 4, 4, 8)
MASS = 0.3


def test_gamma_algebra():
    for mu in range(4):
        assert np.allclose(GAMMAS[mu] @ GAMMAS[mu], np.eye(4), atol=1e-7)
        assert np.allclose(GAMMAS[mu].conj().T, GAMMAS[mu], atol=1e-7)
        for nu in range(mu + 1, 4):
            anti = GAMMAS[mu] @ GAMMAS[nu] + GAMMAS[nu] @ GAMMAS[mu]
            assert np.allclose(anti, 0, atol=1e-7)
    g5 = GAMMAS[0] @ GAMMAS[3] @ GAMMAS[2] @ GAMMAS[1]
    # gamma5 is diagonal ±1 in this basis (overall sign conventional)
    assert np.allclose(np.abs(np.diag(g5)), np.ones(4), atol=1e-7)
    assert np.allclose(GAMMA5 @ GAMMA5, np.eye(4), atol=1e-7)


def test_free_field_constant_mode(rng):
    """With unit links, a constant spinor is an eigenvector: D psi = m psi."""
    u = unit_gauge(LAT)
    psi = jnp.ones(LAT.dims + (4, 3), dtype=jnp.complex64)
    out = dslash(u, psi, MASS)
    assert jnp.max(jnp.abs(out - MASS * psi)) < 1e-5


def test_dslash_linearity(rng):
    k1, k2, ku = jax.random.split(rng, 3)
    u = random_gauge(ku, LAT)
    a, b = random_spinor(k1, LAT), random_spinor(k2, LAT)
    lhs = dslash(u, 2.0 * a + 1j * b, MASS)
    rhs = 2.0 * dslash(u, a, MASS) + 1j * dslash(u, b, MASS)
    assert jnp.max(jnp.abs(lhs - rhs)) < 1e-4


def test_gamma5_hermiticity(rng):
    """<phi, D psi> == <D^dag phi, psi> with D^dag = g5 D g5."""
    k1, k2, ku = jax.random.split(rng, 3)
    u = random_gauge(ku, LAT)
    phi, psi = random_spinor(k1, LAT), random_spinor(k2, LAT)
    lhs = complex(field_dot(phi, dslash(u, psi, MASS)))
    rhs = complex(field_dot(dslash_dagger(u, phi, MASS), psi))
    assert np.isclose(lhs, rhs, rtol=1e-4)


def test_normal_op_hpd(rng):
    k1, ku = jax.random.split(rng)
    u = random_gauge(ku, LAT)
    psi = random_spinor(k1, LAT)
    quad = complex(field_dot(psi, normal_op(u, psi, MASS)))
    assert abs(quad.imag) < 1e-3 * abs(quad.real)
    assert quad.real > 0


def test_packed_matches_natural(rng):
    k1, ku = jax.random.split(rng)
    u = random_gauge(ku, LAT)
    psi = random_spinor(k1, LAT)
    ref = dslash(u, psi, MASS)
    out = unpack_spinor(dslash_packed(pack_gauge(u), pack_spinor(psi), MASS))
    assert jnp.max(jnp.abs(out - ref)) < 1e-5


def test_packed_dagger_and_normal(rng):
    k1, ku = jax.random.split(rng)
    u = random_gauge(ku, LAT)
    psi = random_spinor(k1, LAT)
    up, pp = pack_gauge(u), pack_spinor(psi)
    ref = dslash_dagger(u, psi, MASS)
    out = unpack_spinor(dslash_dagger_packed(up, pp, MASS))
    assert jnp.max(jnp.abs(out - ref)) < 1e-5
    refn = normal_op(u, psi, MASS)
    outn = unpack_spinor(normal_op_packed(up, pp, MASS))
    assert jnp.max(jnp.abs(outn - refn)) < 2e-4


def test_hop_term_consistency(rng):
    """Sum of mass term + 8 aligned hop terms == dslash_packed."""
    k1, ku = jax.random.split(rng)
    u = random_gauge(ku, LAT)
    psi = random_spinor(k1, LAT)
    up, pp = pack_gauge(u), pack_spinor(psi)
    acc = (MASS + 4.0) * pp
    ax = {0: 0, 1: 1, 2: 2, 3: 4}
    for mu in range(4):
        fwd = jnp.roll(pp, -1, axis=ax[mu])
        acc = acc + hop_term_packed(up[mu], fwd, mu, forward=True)
        bwd = jnp.roll(pp, 1, axis=ax[mu])
        ub = jnp.roll(up[mu], 1, axis=ax[mu] if mu < 3 else 4)
        acc = acc + hop_term_packed(ub, bwd, mu, forward=False)
    ref = dslash_packed(up, pp, MASS)
    assert jnp.max(jnp.abs(acc - ref)) < 1e-5


def test_eo_blocks_reassemble_dslash(rng):
    """D reassembled from {M, dslash_eo, dslash_oe} matches dslash exactly:
    merge(M psi_e + D_eo psi_o, M psi_o + D_oe psi_e) == D psi."""
    k1, ku = jax.random.split(rng)
    u = random_gauge(ku, LAT)
    psi = random_spinor(k1, LAT)
    ue, uo = split_eo_gauge(u)
    pe, po = split_eo(psi)
    m = MASS + 4.0
    even = m * pe + dslash_eo(ue, uo, po)
    odd = m * po + dslash_oe(ue, uo, pe)
    ref = dslash(u, psi, MASS)
    assert jnp.max(jnp.abs(merge_eo(even, odd) - ref)) < 1e-5


def test_eo_hop_free_field(rng):
    """Unit links, constant spinor: D psi = m psi implies the even-output
    hop block contributes exactly -4r psi_e."""
    u = unit_gauge(LAT)
    ue, uo = split_eo_gauge(u)
    psi = jnp.ones(LAT.dims + (4, 3), dtype=jnp.complex64)
    pe, po = split_eo(psi)
    # free-field D psi = m psi  =>  hop block contribution is -4r psi_e
    hop = dslash_eo(ue, uo, po)
    assert jnp.max(jnp.abs(hop + 4.0 * pe)) < 1e-5


def test_schur_gamma5_hermiticity(rng):
    """<phi_e, D_hat psi_e> == <D_hat^dag phi_e, psi_e> with
    D_hat^dag = g5 D_hat g5 — CGNR applies to the reduced operator."""
    k1, k2, ku = jax.random.split(rng, 3)
    u = random_gauge(ku, LAT)
    ue, uo = split_eo_gauge(u)
    phi = split_eo(random_spinor(k1, LAT))[0]
    psi = split_eo(random_spinor(k2, LAT))[0]
    lhs = complex(field_dot(phi, schur_op(ue, uo, psi, MASS)))
    rhs = complex(field_dot(schur_dagger(ue, uo, phi, MASS), psi))
    assert np.isclose(lhs, rhs, rtol=1e-4)


def test_schur_normal_op_hpd(rng):
    k1, ku = jax.random.split(rng)
    u = random_gauge(ku, LAT)
    ue, uo = split_eo_gauge(u)
    psi = split_eo(random_spinor(k1, LAT))[0]
    quad = complex(field_dot(psi, schur_normal_op(ue, uo, psi, MASS)))
    assert abs(quad.imag) < 1e-3 * abs(quad.real)
    assert quad.real > 0


def test_flops_constant():
    assert DSLASH_FLOPS_PER_SITE == 1320  # the standard Wilson count


def _contractions(fn, *args):
    """Precision attributes of every dot_general in ``fn``'s lowering."""
    import re
    text = jax.jit(fn).lower(*args).as_text()
    return re.findall(r"stablehlo\.dot_general.*", text)


@pytest.mark.parametrize("op", ["natural", "packed", "eo", "xla"])
def test_reference_contractions_ask_full_precision(op):
    """A TPU contracts f32 in one bf16 pass by default (~1e-3 relative
    error, far above a 1e-6 solve tolerance); every contraction of the
    jnp operators — the verification oracle among them — asks HIGHEST."""
    from repro.kernels.wilson_dslash.xla import dslash_xla
    u = jnp.zeros((4,) + LAT.dims + (3, 3), jnp.complex64)
    psi = jnp.zeros(LAT.dims + (4, 3), jnp.complex64)
    up, pp = pack_gauge(u), pack_spinor(psi)
    ue, uo = split_eo_gauge(u)
    fn, args = {
        "natural": (lambda u, p: dslash(u, p, MASS), (u, psi)),
        "packed": (lambda u, p: dslash_packed(u, p, MASS), (up, pp)),
        "eo": (lambda a, b, p: dslash_eo(a, b, p), (ue, uo, split_eo(psi)[1])),
        "xla": (lambda u, p: dslash_xla(u, p, MASS), (up, pp)),
    }[op]
    dots = _contractions(fn, *args)
    assert dots
    assert all("precision = [HIGHEST, HIGHEST]" in d for d in dots), dots
