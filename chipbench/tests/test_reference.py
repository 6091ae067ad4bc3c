"""The benchmark's data and reference operator, against the program's own
operator at a small size (the reference itself imports nothing of it)."""

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import data, harness

DIMS = (8, 4, 6, 4)


@pytest.fixture(scope="module")
def ref():
    return harness.load_module("references", "wilson_tm.py")


def test_links_are_special_unitary():
    u = data.to_natural_gauge(data.gauge_planes(data.seed_key(3, 0), DIMS),
                              DIMS)
    m = np.asarray(u).reshape(-1, 3, 3)
    eye = np.eye(3)
    assert np.abs(m @ m.conj().transpose(0, 2, 1) - eye).max() < 5e-6
    assert np.abs(np.linalg.det(m) - 1).max() < 5e-6


@pytest.mark.parametrize("seed", [0, 2**31 - 1, 2**31 + 5, 2**40 + 3, -7])
def test_any_whole_seed_draws_the_same_field_twice(seed):
    a = data.spinor_planes(data.seed_key(seed, 1, 4), DIMS)
    b = data.spinor_planes(data.seed_key(seed, 1, 4), DIMS)
    c = data.spinor_planes(data.seed_key(seed + 1, 1, 4), DIMS)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_layouts_round_trip():
    psi = data.spinor_planes(data.seed_key(1, 1), DIMS)
    nat = data.to_natural_spinor(psi, DIMS)
    assert nat.shape == DIMS + (4, 3)
    assert np.array_equal(np.asarray(data.from_natural_spinor(nat)),
                          np.asarray(psi))


@pytest.mark.parametrize("mass,mu", [(0.1, 0.0), (-1.4, 0.0), (0.1, 0.25)])
def test_reference_matches_the_program_operator(ref, mass, mu):
    from repro.core.operators import dslash_g

    u = data.gauge_planes(data.seed_key(9, 0), DIMS)
    psi = data.spinor_planes(data.seed_key(9, 1), DIMS)
    want = dslash_g(data.to_natural_gauge(u, DIMS),
                    data.to_natural_spinor(psi, DIMS), mass, twist=mu)
    got = data.to_natural_spinor(ref.dslash(u, psi, mass, mu, yx=DIMS[2:]),
                                 DIMS)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))


def test_relative_residual_of_the_exact_image_is_zero(ref):
    u = data.gauge_planes(data.seed_key(4, 0), DIMS)
    x = data.spinor_planes(data.seed_key(4, 1), DIMS)
    b = ref.dslash(u, x, 0.1, 0.25, yx=DIMS[2:])
    assert float(ref.relative_residual(u, x, b, 0.1, 0.25, yx=DIMS[2:])) \
        < 1e-6
    assert float(ref.relative_residual(u, 0 * x, b, 0.1, 0.25,
                                       yx=DIMS[2:])) == pytest.approx(1.0)
