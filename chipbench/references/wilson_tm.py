"""Plain reference of the Wilson-Dirac operator family, in the plane layout.

Independent of the program under test: written from the operator's
definition, with elementwise complex arithmetic only (no matrix unit, so
float32 throughout on every backend).  Fields are in the plane layout of
:mod:`chipbench.data`, spinor ``(4, 3, T, Z, Y*X)`` and gauge
``(4, 3, 3, T, Z, Y*X)``.  Directions are (t, z, y, x).

    D psi(x) = (m + 4) psi(x) + i mu gamma5 psi(x)
             - 1/2 sum_mu [ (1 - gamma_mu) U_mu(x) psi(x + mu)
                          + (1 + gamma_mu) U_mu(x - mu)^dag psi(x - mu) ]

Gamma matrices in the DeGrand-Rossi basis, gamma5 = diag(1, 1, -1, -1);
mu = 0 is the Wilson operator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_I = 1j
GAMMAS = np.array([
    [[0, 0, 1, 0], [0, 0, 0, 1], [1, 0, 0, 0], [0, 1, 0, 0]],            # t
    [[0, 0, _I, 0], [0, 0, 0, -_I], [-_I, 0, 0, 0], [0, _I, 0, 0]],      # z
    [[0, 0, 0, -1], [0, 0, 1, 0], [0, 1, 0, 0], [-1, 0, 0, 0]],          # y
    [[0, 0, 0, _I], [0, 0, _I, 0], [0, -_I, 0, 0], [-_I, 0, 0, 0]],      # x
], dtype=np.complex64)
GAMMA5 = np.array([1, 1, -1, -1], dtype=np.float32)


def _shift(f, mu, step, yx):
    """f(site + step * mu) with periodic wrap; site axes (T, Z, Y*X)."""
    y, x = yx
    if mu == 0:
        return jnp.roll(f, -step, axis=-3)
    if mu == 1:
        return jnp.roll(f, -step, axis=-2)
    if mu == 2:
        return jnp.roll(f, -step * x, axis=-1)
    xi = jnp.arange(y * x) % x
    if step == 1:
        return jnp.where(xi == x - 1, jnp.roll(f, x - 1, axis=-1),
                         jnp.roll(f, -1, axis=-1))
    return jnp.where(xi == 0, jnp.roll(f, 1 - x, axis=-1),
                     jnp.roll(f, 1, axis=-1))


def _spin_mix(coef, psi):
    """(coef psi)_s = sum_p coef[s, p] psi_p for a constant 4x4 ``coef``."""
    rows = []
    for s in range(4):
        acc = None
        for p in range(4):
            c = complex(coef[s, p])
            if c == 0:
                continue
            term = psi[p] if c == 1 else c * psi[p]
            acc = term if acc is None else acc + term
        rows.append(acc)
    return jnp.stack(rows)


def _colour(u, psi, dagger):
    """U psi or U^dag psi on every spin; u (3, 3, *sites), psi (4, 3, *sites)."""
    if dagger:
        return jnp.sum(jnp.conj(u)[None, :, :] * psi[:, :, None], axis=1)
    return jnp.sum(u[None] * psi[:, None, :], axis=2)


@functools.partial(jax.jit, static_argnames=("yx",))
def dslash(u, psi, mass, mu, *, yx):
    """D psi; ``yx`` = (Y, X), the extents folded into the last axis."""
    eye = np.eye(4, dtype=np.complex64)
    out = (mass + 4.0) * psi
    out = out + (1j * mu) * (GAMMA5[:, None, None, None, None] * psi)
    for d in range(4):
        fwd = _colour(u[d], _shift(psi, d, 1, yx), dagger=False)
        bwd = _shift(_colour(u[d], psi, dagger=True), d, -1, yx)
        hop = _spin_mix(eye - GAMMAS[d], fwd) + _spin_mix(eye + GAMMAS[d], bwd)
        out = out - 0.5 * hop
    return out


@functools.partial(jax.jit, static_argnames=("yx",))
def relative_residual(u, x, b, mass, mu, *, yx):
    """||D x - b|| / ||b||, accumulated in float32."""
    r = dslash(u, x, mass, mu, yx=yx) - b
    return jnp.sqrt(jnp.sum(jnp.abs(r) ** 2) / jnp.sum(jnp.abs(b) ** 2))
