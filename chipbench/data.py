"""Gauge fields and right-hand sides drawn from the seed, on the device.

Fields are made in the *plane* layout, components first and sites last,
so every step is elementwise work with the sites on the lane axis:

* spinor ``(4 spin, 3 colour, T, Z, Y*X)`` complex64;
* gauge  ``(4 dir, 3 row, 3 col, T, Z, Y*X)`` complex64.

Each link is the Gram-Schmidt of two complex Gaussian 3-vectors, and its
third row is the conjugate cross product of the first two, which makes it
special unitary and Haar-distributed.  ``to_natural_*`` converts to the
program's natural layout, ``(T, Z, Y, X, 4, 3)`` and
``(4, T, Z, Y, X, 3, 3)``; ``from_natural_spinor`` converts back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, *stream: int) -> jax.Array:
    """A PRNG key from any whole seed, 64 bits and negative ones included."""
    s = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(s & 0xFFFFFFFF))
    key = jax.random.fold_in(key, np.uint32(s >> 32))
    for k in stream:
        key = jax.random.fold_in(key, np.uint32(k))
    return key


def _cnormal(key, shape):
    re, im = jax.random.normal(key, (2,) + shape, jnp.float32)
    return (re + 1j * im).astype(jnp.complex64)


def _cdot(a, b):
    """sum_i conj(a_i) b_i over the leading (colour) axis."""
    return jnp.sum(jnp.conj(a) * b, axis=0)


def _su3_rows(key, sites):
    """(3 row, 3 col, *sites) special unitary matrices."""
    a, b = _cnormal(key, (2, 3) + sites)
    r1 = a / jnp.sqrt(jnp.real(_cdot(a, a)))
    b = b - _cdot(r1, b) * r1
    r2 = b / jnp.sqrt(jnp.real(_cdot(b, b)))
    r3 = jnp.conj(jnp.stack([r1[1] * r2[2] - r1[2] * r2[1],
                             r1[2] * r2[0] - r1[0] * r2[2],
                             r1[0] * r2[1] - r1[1] * r2[0]]))
    return jnp.stack([r1, r2, r3])


def gauge_planes(key, dims):
    """A random SU(3) gauge field in the plane layout."""
    t, z, y, x = dims
    keys = jax.random.split(key, 4)
    return jnp.stack([_su3_rows(k, (t, z, y * x)) for k in keys])


def spinor_planes(key, dims):
    """A Gaussian spinor field in the plane layout."""
    t, z, y, x = dims
    return _cnormal(key, (4, 3, t, z, y * x))


def to_natural_gauge(u, dims):
    t, z, y, x = dims
    return jnp.transpose(u.reshape(4, 3, 3, t, z, y, x),
                         (0, 3, 4, 5, 6, 1, 2))


def to_natural_spinor(psi, dims):
    t, z, y, x = dims
    return jnp.transpose(psi.reshape(4, 3, t, z, y, x), (2, 3, 4, 5, 0, 1))


def from_natural_spinor(psi):
    t, z, y, x = psi.shape[:4]
    return jnp.transpose(psi, (4, 5, 0, 1, 2, 3)).reshape(4, 3, t, z, y * x)
