"""Pure-jnp oracles for the wilson_dslash Pallas kernels.

The full-lattice reference is the packed-layout operator from the core
library, which is itself validated against the natural-layout complex
operator (and the latter against gamma-matrix algebra identities) in
tests/test_wilson.py.

The parity (even-odd) references round-trip through the natural-layout
complex half-field operators in :mod:`repro.core.wilson` — slow but
maximally independent of the kernel code they validate, "compiled and
executed exclusively on CPU for debugging and reference benchmarking"
in the paper's words.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.lattice import (pack_half_spinor, unpack_half_gauge,
                                unpack_half_spinor)
from repro.core.operators import (schur_dagger_g, schur_normal_op_g,
                                  schur_op_g)
from repro.core.wilson import apply_gamma5
from repro.core.wilson import dslash_eo as _core_dslash_eo
from repro.core.wilson import dslash_oe as _core_dslash_oe
from repro.core.wilson import dslash_packed as dslash_ref  # noqa: F401
from repro.core.wilson import (dslash_dagger_packed as dslash_dagger_ref,  # noqa: F401
                               normal_op_packed as normal_op_ref)  # noqa: F401


def _via_natural(fn, u_e_p: jax.Array, u_o_p: jax.Array, pp: jax.Array,
                 gamma5_in: bool, gamma5_out: bool, xh: int) -> jax.Array:
    """Unpack packed half fields, apply a natural-layout op, repack.

    A rank-5 ``pp`` is an (N, T, 24, Z, Y*Xh) RHS batch: the natural-layout
    operator is vmapped over the leading axis (gauge held fixed), so each
    slice reproduces the single-RHS oracle exactly.
    """
    u_e = unpack_half_gauge(u_e_p.astype(jnp.float32), xh)
    u_o = unpack_half_gauge(u_o_p.astype(jnp.float32), xh)
    v = unpack_half_spinor(pp.astype(jnp.float32), xh)
    if gamma5_in:
        v = apply_gamma5(v)
    op = lambda w: fn(u_e, u_o, w)
    out = jax.vmap(op)(v) if pp.ndim == 5 else op(v)
    if gamma5_out:
        out = apply_gamma5(out)
    return pack_half_spinor(out, dtype=pp.dtype)


def dslash_eo_ref(u_e_p, u_o_p, pp_o, *, xh, gamma5_in=False,
                  gamma5_out=False):
    """D_eo on packed half fields (odd in, even out), via the core oracle."""
    return _via_natural(_core_dslash_eo, u_e_p, u_o_p, pp_o,
                        gamma5_in, gamma5_out, xh)


def dslash_oe_ref(u_e_p, u_o_p, pp_e, *, xh, gamma5_in=False,
                  gamma5_out=False):
    """D_oe on packed half fields (even in, odd out), via the core oracle."""
    return _via_natural(_core_dslash_oe, u_e_p, u_o_p, pp_e,
                        gamma5_in, gamma5_out, xh)


def schur_op_ref(u_e_p, u_o_p, pp_e, mass, *, xh, twist=0.0, dagger=False):
    """Schur complement D_hat (or D_hat^dag) on packed even half fields.

    ``twist`` is the operator registry's site-term twist: the dagger of a
    twisted operator flips it alongside the γ5 wraps
    (``schur_dagger_g`` handles the sign internally).
    """
    fn = schur_dagger_g if dagger else schur_op_g
    return _via_natural(lambda ue, uo, v: fn(ue, uo, v, mass, twist=twist),
                        u_e_p, u_o_p, pp_e, False, False, xh)


def schur_normal_op_ref(u_e_p, u_o_p, pp_e, mass, *, xh, twist=0.0):
    """A_hat = D_hat^dag D_hat on packed even half fields."""
    return _via_natural(
        lambda ue, uo, v: schur_normal_op_g(ue, uo, v, mass, twist=twist),
        u_e_p, u_o_p, pp_e, False, False, xh)
