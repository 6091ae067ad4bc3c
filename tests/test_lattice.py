"""Lattice geometry, SU(3) fields, packing bijections."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LatticeShape, field_dot, field_norm2, merge_eo,
                        merge_eo_gauge, pack_gauge, pack_spinor, parity_masks,
                        random_gauge, random_spinor, split_eo, split_eo_gauge,
                        unit_gauge, unpack_gauge, unpack_spinor)
from repro.core.lattice import _project_su3
from repro.testing import maybe_hypothesis

given, settings, st = maybe_hypothesis()

LAT = LatticeShape(4, 4, 4, 8)


def test_su3_unitarity_and_det(rng):
    u = random_gauge(rng, LAT)
    uu = jnp.einsum("dtzyxab,dtzyxcb->dtzyxac", u, jnp.conj(u))
    eye = jnp.eye(3, dtype=u.dtype)
    assert jnp.max(jnp.abs(uu - eye)) < 5e-6
    det = jnp.linalg.det(u)
    assert jnp.max(jnp.abs(det - 1.0)) < 5e-6


def test_random_gauge_is_drawn_on_the_host(rng):
    """The field is drawn on the host's CPU backend and placed, uncommitted,
    on the default device: the same field as a plain draw on the CPU."""
    u = random_gauge(rng, LAT)
    assert not u.committed
    kr, ki = jax.random.split(rng)
    shape = (4,) + LAT.dims + (3, 3)
    z = (jax.random.normal(kr, shape, dtype=jnp.float32)
         + 1j * jax.random.normal(ki, shape, dtype=jnp.float32))
    assert np.array_equal(np.asarray(u),
                          np.asarray(_project_su3(z.astype(jnp.complex64))))


def test_unit_gauge_is_identity():
    u = unit_gauge(LAT)
    assert u.shape == (4, 4, 4, 4, 8, 3, 3)
    assert jnp.allclose(u[0, 0, 0, 0, 0], jnp.eye(3, dtype=u.dtype))


def test_pack_unpack_spinor_roundtrip(rng):
    psi = random_spinor(rng, LAT)
    assert jnp.allclose(unpack_spinor(pack_spinor(psi)), psi, atol=1e-6)


def test_pack_unpack_gauge_roundtrip(rng):
    u = random_gauge(rng, LAT)
    assert jnp.allclose(unpack_gauge(pack_gauge(u)), u, atol=1e-6)


def test_packed_layout_axes(rng):
    psi = random_spinor(rng, LAT)
    p = pack_spinor(psi)
    assert p.shape == (4, 4, 4, 24, 8)  # (T, Z, Y, S, X) — X innermost
    # component (spin=1, color=2, im) of site (t,z,y,x)
    s_idx = (1 * 3 + 2) * 2 + 1
    assert np.isclose(float(p[2, 1, 3, s_idx, 5]),
                      float(jnp.imag(psi[2, 1, 3, 5, 1, 2])), atol=1e-6)


def test_split_merge_eo_roundtrip(rng):
    psi = random_spinor(rng, LAT)
    e, o = split_eo(psi)
    assert e.shape == (4, 4, 4, 4, 4, 3) and o.shape == e.shape
    assert jnp.array_equal(merge_eo(e, o), psi)  # exact bijection


def test_split_eo_gauge_roundtrip(rng):
    u = random_gauge(rng, LAT)
    ue, uo = split_eo_gauge(u)
    assert ue.shape == (4, 4, 4, 4, 4, 3, 3)
    assert jnp.array_equal(merge_eo_gauge(ue, uo), u)


def test_split_eo_site_addressing(rng):
    """Even field index (t,z,y,j) addresses site x = 2j + (t+z+y)%2."""
    psi = random_spinor(rng, LAT)
    e, o = split_eo(psi)
    full = np.asarray(psi)
    for (t, z, y, j) in [(0, 0, 0, 1), (1, 0, 0, 2), (2, 3, 1, 0),
                         (3, 3, 3, 3)]:
        s = (t + z + y) % 2
        assert np.array_equal(np.asarray(e)[t, z, y, j], full[t, z, y, 2 * j + s])
        assert np.array_equal(np.asarray(o)[t, z, y, j],
                              full[t, z, y, 2 * j + 1 - s])


def test_parity_masks_partition():
    even, odd = parity_masks(LAT)
    assert even.shape == LAT.dims
    assert int(even.sum()) == LAT.volume // 2
    assert not np.any(even & odd) and np.all(even | odd)
    # parity really is (t+z+y+x) % 2
    assert bool(even[0, 0, 0, 0]) and not bool(even[0, 0, 0, 1])
    assert not bool(even[1, 0, 0, 0]) and bool(even[1, 1, 0, 0])


def test_split_eo_requires_even_x(rng):
    psi = random_spinor(rng, LatticeShape(2, 2, 2, 3))
    with pytest.raises(AssertionError):
        split_eo(psi)


def test_dot_matches_norm(rng):
    psi = random_spinor(rng, LAT)
    assert np.isclose(float(jnp.real(field_dot(psi, psi))),
                      float(field_norm2(psi)), rtol=1e-5)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.floats(-3, 3), st.floats(-3, 3))
def test_field_dot_sesquilinear(seed, a_re, a_im):
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    lat = LatticeShape(2, 2, 2, 4)
    x = random_spinor(k1, lat)
    y = random_spinor(k2, lat)
    alpha = jnp.complex64(a_re + 1j * a_im)
    lhs = field_dot(x, alpha * y)
    rhs = alpha * field_dot(x, y)
    assert np.isclose(complex(lhs), complex(rhs), rtol=2e-4, atol=1e-3)
    # conjugate symmetry
    assert np.isclose(complex(field_dot(x, y)),
                      np.conj(complex(field_dot(y, x))), rtol=2e-4,
                      atol=1e-3)
