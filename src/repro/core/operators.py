"""The operator registry: site-local physics decoupled from hop transport.

The paper's central architectural claim is that its framework "allows for
a simple implementation of other linear operators, while keeping the data
transport mechanisms unaltered".  This module is that seam made explicit:

* the **transport layer** — the eight-direction hopping stencil
  (``wilson_dslash`` plane-streaming kernels and their jnp references),
  the parity halo exchange in :mod:`repro.core.distributed`, RHS batching
  and precision packing — is operator-AGNOSTIC and lives where it always
  did;
* an **operator** contributes only its site-local diagonal block, captured
  by :class:`SiteTerm`::

      S = scale * 1 + twist * (i gamma5)

  with an analytic inverse (``S^-1 = (scale - i twist gamma5) /
  (scale^2 + twist^2)`` because gamma5^2 = 1) and an adjoint
  (``S^dag = S(-twist)``).  Both are what the even-odd Schur reduction
  needs: the odd-odd block is inverted in closed form, and the kernels
  fold the site term into their hop epilogues so the Schur normal
  operator stays exactly four kernel launches for EVERY registered
  operator.

Registered operators:

* ``wilson``       — S = (m + 4r) * 1 (twist = 0).  Every twist gate in
  the stack compares the trace-time float against 0.0, so the Wilson path
  emits bitwise the same program it did before the registry existed.
* ``twisted-mass`` — S = (m + 4r) + i mu gamma5 (one Wilson-clover-free
  flavor of the twisted-mass discretization).  Not gamma5-hermitian:
  ``D(mu)^dag = gamma5 D(-mu) gamma5``, so every dagger in the stack
  flips the twist sign alongside the folded gamma5 flags; CGNR on
  ``D^dag D`` is unaffected.

A new operator registers a :class:`LatticeOperator` naming its site term;
it inherits, untouched: both backends (reference jnp and Pallas kernels),
multi-RHS batching, mixed precision, and the sharded one-psum pipelined
path.  See DESIGN.md §8 for the full contract.
"""

from __future__ import annotations

import dataclasses
import difflib
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.lattice import HALF_S_AXIS, NCOL, NSPIN
from repro.core.wilson import (apply_gamma5, dslash, dslash_eo, dslash_oe,
                               schur_dagger, schur_op)

Array = jax.Array


def unknown_name(kind: str, value, allowed) -> str:
    """Error text for an unknown registry/enum name, with a did-you-mean.

    Shared by the registry lookup and ``SolverPlan`` field validation so
    every unknown-name failure in the stack lists what IS registered and
    suggests the closest match.
    """
    allowed = tuple(allowed)
    msg = (f"unknown {kind} {value!r}; registered names: "
           f"{', '.join(repr(a) for a in allowed)}")
    hits = difflib.get_close_matches(str(value), [str(a) for a in allowed],
                                     n=1, cutoff=0.4)
    if hits:
        msg += f" — did you mean {hits[0]!r}?"
    return msg


# ---------------------------------------------------------------------------
# The site-local term
# ---------------------------------------------------------------------------


def apply_igamma5_packed(p: Array, axis: int = -2) -> Array:
    """(i gamma5) on a packed field's S ``axis``; the other axes pass
    through (-2: the full-lattice packed layout, ``HALF_S_AXIS``: a packed
    half field).

    In the packed real layout the S axis interleaves (spin, color, re/im),
    so multiplying by i swaps the re/im planes (re' = -im, im' = re) and
    gamma5 = diag(+,+,-,-) signs the spin blocks.
    """
    q = jnp.moveaxis(p, axis, -1)
    assert q.shape[-1] == NSPIN * NCOL * 2
    q = q.reshape(q.shape[:-1] + (NSPIN, NCOL, 2))
    re, im = q[..., 0], q[..., 1]  # each (..., NSPIN, NCOL)
    sign = jnp.asarray([1.0, 1.0, -1.0, -1.0], p.dtype).reshape((NSPIN, 1))
    out = jnp.stack([-sign * im, sign * re], axis=-1)
    return jnp.moveaxis(out.reshape(out.shape[:-3] + (-1,)), -1, axis)


@dataclasses.dataclass(frozen=True)
class SiteTerm:
    """The site-local diagonal block ``S = scale*1 + twist*(i gamma5)``.

    ``twist`` MUST be a trace-time Python float: every consumer gates on
    ``twist == 0.0`` to keep the Wilson path bitwise identical to the
    pre-registry code (``scale`` may be a float or a traced scalar).
    ``apply``/``solve`` dispatch on the field layout — complex arrays are
    natural layout (gamma5 on spin axis -2), real arrays are packed half
    fields (..., T, 24, Z, Y*Xh) — so the same SiteTerm serves the
    reference operators and the packed even-odd fast path.
    """

    scale: object
    twist: float = 0.0

    @property
    def dag(self) -> "SiteTerm":
        """S^dag: gamma5 and scale are Hermitian, (i mu gamma5)^dag flips."""
        return SiteTerm(self.scale, -self.twist)

    @property
    def inv(self) -> "SiteTerm":
        """S^-1 = (scale - twist*(i gamma5)) / (scale^2 + twist^2).

        Only for a CONCRETE (Python float) scale: the derived twist must
        itself stay trace-time static.  ``solve`` applies the inverse
        without materializing it and handles traced scales.
        """
        den = self.scale * self.scale + self.twist * self.twist
        return SiteTerm(self.scale / den, -self.twist / den)

    def apply(self, v: Array) -> Array:
        """S v on a natural (complex) or packed (real) field."""
        if self.twist == 0.0:
            return self.scale * v
        if jnp.iscomplexobj(v):
            return self.scale * v + (1j * self.twist) * apply_gamma5(v)
        return self.scale * v + self.twist * apply_igamma5_packed(
            v, HALF_S_AXIS)

    def solve(self, v: Array) -> Array:
        """S^-1 v (``v / scale`` bitwise when twist == 0 — the historical
        Wilson ``m_inv``).  Gates on THIS term's trace-time twist only,
        so a traced ``scale`` is fine."""
        if self.twist == 0.0:
            return v / self.scale
        den = self.scale * self.scale + self.twist * self.twist
        if jnp.iscomplexobj(v):
            return (self.scale * v
                    - (1j * self.twist) * apply_gamma5(v)) / den
        return (self.scale * v
                - self.twist * apply_igamma5_packed(v, HALF_S_AXIS)) / den


# ---------------------------------------------------------------------------
# The registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LatticeOperator:
    """What a lattice operator must DECLARE to ride the transport stack.

    Fields:
      name:        registry key (also ``SolverPlan.operator_family``).
      description: one line for ``--operator`` help and error messages.
      params:      names of the extra site-local parameters the operator
        consumes beyond ``(mass, r)`` — each must exist as a field on
        :class:`repro.core.plan.SolverPlan` (currently: ``mu``).
      make_site_term: ``(mass, r, **params) -> SiteTerm`` — the ENTIRE
        operator-specific contribution.  The hop term, its kernels, the
        halo exchange, batching and precision packing are inherited.
    """

    name: str
    description: str
    params: tuple[str, ...]
    make_site_term: Callable[..., SiteTerm]

    def site_term(self, mass, r: float = 1.0, **params) -> SiteTerm:
        return self.make_site_term(mass, r, **params)


_REGISTRY: dict[str, LatticeOperator] = {}


def register_operator(spec: LatticeOperator) -> LatticeOperator:
    """Add ``spec`` to the registry (name collisions are an error)."""
    if spec.name in _REGISTRY:
        raise ValueError(f"operator family {spec.name!r} is already "
                         "registered")
    _REGISTRY[spec.name] = spec
    return spec


def operator_names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_operator(name: str) -> LatticeOperator:
    """Look up a registered operator; unknown names get a did-you-mean."""
    spec = _REGISTRY.get(name)
    if spec is None:
        raise ValueError(unknown_name("operator family", name,
                                      operator_names()))
    return spec


WILSON = register_operator(LatticeOperator(
    name="wilson",
    description="Dirac-Wilson: site term (m + 4r)*1",
    params=(),
    make_site_term=lambda mass, r: SiteTerm(mass + 4.0 * r, 0.0)))

TWISTED_MASS = register_operator(LatticeOperator(
    name="twisted-mass",
    description="twisted-mass Wilson: site term (m + 4r) + i*mu*gamma5",
    params=("mu",),
    make_site_term=lambda mass, r, mu: SiteTerm(mass + 4.0 * r, float(mu))))


# ---------------------------------------------------------------------------
# Generic natural-layout operators (reference backend / correctness oracles)
#
# Each function reduces BITWISE to its repro.core.wilson counterpart when
# twist == 0 — the gates below select the historical expression, not a
# generic one multiplied by zero.
# ---------------------------------------------------------------------------


def dslash_g(u: Array, psi: Array, mass, r: float = 1.0,
             twist: float = 0.0) -> Array:
    """D psi for the (mass, r, twist) operator family, natural layout."""
    out = dslash(u, psi, mass, r=r)
    if twist != 0.0:
        out = out + (1j * twist) * apply_gamma5(psi)
    return out


def dslash_dagger_g(u: Array, psi: Array, mass, r: float = 1.0,
                    twist: float = 0.0) -> Array:
    """D^dag = gamma5 D(-twist) gamma5 (for twist = 0: plain gamma5 D
    gamma5 — the Wilson dagger)."""
    return apply_gamma5(dslash_g(u, apply_gamma5(psi), mass, r=r,
                                 twist=-twist))


def normal_op_g(u: Array, psi: Array, mass, r: float = 1.0,
                twist: float = 0.0) -> Array:
    """A = D^dag D — HPD for every family; the CGNR operator."""
    return dslash_dagger_g(u, dslash_g(u, psi, mass, r=r, twist=twist),
                           mass, r=r, twist=twist)


def schur_launch_coeffs(scale: float, twist: float, dagger: bool
                        ) -> tuple[float, float, float, float]:
    """Epilogue coefficients of the TWO-launch twisted Schur split.

    D_hat(tw) = S(tw) - D_eo S(tw)^-1 D_oe and D_hat(tw)^dag =
    gamma5 D_hat(-tw) gamma5, so with tw = -twist if dagger else twist
    and den = scale^2 + tw^2:

      launch 1 (D_oe, gamma5_in=dagger) folds S(tw)^-1 into its hop
        epilogue: (hop1_coeff, hop1_twist) = (scale, -tw) / den;
      launch 2 (D_eo, gamma5_out=dagger) accumulates S(tw) psi with
        hop_coeff = -1: (acc_coeff, acc_twist) = (scale, tw).

    The ONE home of this sign algebra — the single-device kernels
    (``kernels/wilson_dslash/ops.schur_op``) and the sharded halo path
    (``distributed.schur_op_halo``) both consume it.  Returns
    (hop1_coeff, hop1_twist, acc_coeff, acc_twist).
    """
    tw = -twist if dagger else twist
    den = scale * scale + tw * tw
    return scale / den, -tw / den, scale, tw


def schur_op_g(u_e: Array, u_o: Array, psi_e: Array, mass, r: float = 1.0,
               twist: float = 0.0) -> Array:
    """Schur complement D_hat = S - D_eo S^-1 D_oe on even half fields.

    For twist = 0 the scalar S^-1 commutes with the hops and the
    historical Wilson expression (divide the even output) is emitted
    bitwise; a twisted S^-1 is gamma5-valued and must stay between the
    hops.
    """
    if twist == 0.0:
        return schur_op(u_e, u_o, psi_e, mass, r=r)
    site = SiteTerm(mass + 4.0 * r, twist)
    tmp_o = site.solve(dslash_oe(u_e, u_o, psi_e, r=r))
    return site.apply(psi_e) - dslash_eo(u_e, u_o, tmp_o, r=r)


def schur_dagger_g(u_e: Array, u_o: Array, psi_e: Array, mass,
                   r: float = 1.0, twist: float = 0.0) -> Array:
    """D_hat(twist)^dag = gamma5 D_hat(-twist) gamma5."""
    if twist == 0.0:
        return schur_dagger(u_e, u_o, psi_e, mass, r=r)
    return apply_gamma5(schur_op_g(u_e, u_o, apply_gamma5(psi_e), mass,
                                   r=r, twist=-twist))


def schur_normal_op_g(u_e: Array, u_o: Array, psi_e: Array, mass,
                      r: float = 1.0, twist: float = 0.0) -> Array:
    """A_hat = D_hat^dag D_hat — HPD on the even sublattice."""
    return schur_dagger_g(u_e, u_o,
                          schur_op_g(u_e, u_o, psi_e, mass, r=r,
                                     twist=twist),
                          mass, r=r, twist=twist)
