"""Jitted public entry points for the wilson_dslash Pallas kernels.

Full lattice:
``dslash(up, pp, mass)`` — D psi
``dslash_dagger(...)``   — D^dag psi  (gamma5 D gamma5, γ5 FOLDED into the
                           kernel tables — zero extra full-field passes)
``normal_op(...)``       — D^dag D psi (the CGNR operator; two kernel
                           launches, no standalone gamma5 application)

Even-odd half lattice (packed half fields (T, 24, Z, Y*Xh), see
repro.core.lattice; every entry point takes the compressed extent ``xh``):
``dslash_eo``/``dslash_oe`` — the parity-changing hopping blocks
``schur_op``                — D_hat = m psi - D_eo D_oe psi / m, with the
                              axpy folded into the second kernel's epilogue
``schur_dagger``            — D_hat^dag via the folded γ5 flags
``schur_normal_op``         — D_hat^dag D_hat (four kernel launches total)

Every entry point is **multi-RHS batched**: pass a spinor with a leading
RHS-batch axis and the same operator applies to all
N right-hand sides in the SAME kernel launches — the gauge field is read
once per grid step and amortized across the batch, so ``schur_normal_op``
stays exactly 4 launches (and ``normal_op`` exactly 2) independent of N.

``use_pallas=False`` falls back to the pure-jnp reference — the same
escape hatch the paper's package offers ("compiled and executed exclusively
on CPU for debugging and reference benchmarking").  ``interpret=None``
(default) interprets the kernels only on CPU; GPU/TPU runs compile.
"""

from __future__ import annotations

import functools

import jax

from repro.core import phases
from repro.core.lattice import HALF_S_AXIS
from repro.core.operators import apply_igamma5_packed, schur_launch_coeffs
from repro.core.wilson import apply_gamma5_packed, dslash_packed
from repro.kernels.wilson_dslash.kernel import (dslash_eo_pallas,
                                                dslash_oe_pallas,
                                                dslash_pallas)
from repro.kernels.wilson_dslash.ref import (dslash_eo_ref, dslash_oe_ref,
                                             schur_normal_op_ref,
                                             schur_op_ref)

_STATIC = ("mass", "twist", "bz", "interpret", "use_pallas")
_STATIC_G5 = _STATIC + ("gamma5_in", "gamma5_out")
_STATIC_XH = _STATIC + ("xh",)


@phases.scoped(phases.DSLASH)
@functools.partial(jax.jit, static_argnames=_STATIC_G5)
def dslash(up: jax.Array, pp: jax.Array, mass: float, *,
           twist: float = 0.0, bz: int | None = None,
           interpret: bool | None = None, use_pallas: bool = True,
           gamma5_in: bool = False, gamma5_out: bool = False) -> jax.Array:
    """D psi on packed fields; ``pp`` may carry a leading RHS-batch axis.

    ``twist`` is the operator registry's site-term twist: the applied
    operator is ``D_wilson + i·twist·γ5`` (0 = Wilson, bitwise the
    historical path).
    """
    if not use_pallas:
        out = apply_gamma5_packed(pp) if gamma5_in else pp
        if twist == 0.0:
            ref = lambda q: dslash_packed(up, q, mass)
        else:
            ref = lambda q: (dslash_packed(up, q, mass)
                             + twist * apply_igamma5_packed(q)
                             ).astype(q.dtype)
        out = jax.vmap(ref)(out) if pp.ndim == 6 else ref(out)
        return apply_gamma5_packed(out) if gamma5_out else out
    return dslash_pallas(up, pp, mass, bz=bz, interpret=interpret,
                         twist=twist, gamma5_in=gamma5_in,
                         gamma5_out=gamma5_out)


@functools.partial(jax.jit, static_argnames=_STATIC)
def dslash_dagger(up: jax.Array, pp: jax.Array, mass: float, *,
                  twist: float = 0.0, bz: int | None = None,
                  interpret: bool | None = None,
                  use_pallas: bool = True) -> jax.Array:
    """D^dag = gamma5 D(-twist) gamma5, folded into the kernel tables."""
    return dslash(up, pp, mass, twist=-twist, bz=bz, interpret=interpret,
                  use_pallas=use_pallas, gamma5_in=True, gamma5_out=True)


@functools.partial(jax.jit, static_argnames=_STATIC)
def normal_op(up: jax.Array, pp: jax.Array, mass: float, *,
              twist: float = 0.0, bz: int | None = None,
              interpret: bool | None = None,
              use_pallas: bool = True) -> jax.Array:
    """A = D^dag D in exactly two kernel launches: D, then γ5 D(-twist) γ5
    with both γ5 factors folded — no standalone ``apply_gamma5_packed``
    pass for any operator family."""
    dv = dslash(up, pp, mass, twist=twist, bz=bz, interpret=interpret,
                use_pallas=use_pallas)
    return dslash(up, dv, mass, twist=-twist, bz=bz, interpret=interpret,
                  use_pallas=use_pallas, gamma5_in=True, gamma5_out=True)


# ---------------------------------------------------------------------------
# Parity (even-odd) blocks and the Schur complement
# ---------------------------------------------------------------------------

_STATIC_EO = ("xh", "bz", "interpret", "use_pallas", "gamma5_in",
              "gamma5_out")


@phases.scoped(phases.HOP_EO)
@functools.partial(jax.jit, static_argnames=_STATIC_EO)
def dslash_eo(u_e: jax.Array, u_o: jax.Array, pp_o: jax.Array, *, xh: int,
              bz: int | None = None, interpret: bool | None = None,
              use_pallas: bool = True, gamma5_in: bool = False,
              gamma5_out: bool = False) -> jax.Array:
    """D_eo: ODD half field in, EVEN half field out (hopping term only).

    ``u_e``/``u_o`` are packed per-parity link fields (4, T, 18, Z, Y*Xh);
    ``pp_o`` is a packed (T, 24, Z, Y*Xh) odd-parity spinor half field or
    an (N, T, 24, Z, Y*Xh) RHS batch (gauge amortized across the batch);
    ``xh`` is the compressed X extent X/2.
    """
    if not use_pallas:
        return dslash_eo_ref(u_e, u_o, pp_o, xh=xh, gamma5_in=gamma5_in,
                             gamma5_out=gamma5_out)
    return dslash_eo_pallas(u_e, u_o, pp_o, xh=xh, bz=bz,
                            interpret=interpret, gamma5_in=gamma5_in,
                            gamma5_out=gamma5_out)


@phases.scoped(phases.HOP_OE)
@functools.partial(jax.jit, static_argnames=_STATIC_EO)
def dslash_oe(u_e: jax.Array, u_o: jax.Array, pp_e: jax.Array, *, xh: int,
              bz: int | None = None, interpret: bool | None = None,
              use_pallas: bool = True, gamma5_in: bool = False,
              gamma5_out: bool = False) -> jax.Array:
    """D_oe: EVEN half field in, ODD half field out (hopping term only)."""
    if not use_pallas:
        return dslash_oe_ref(u_e, u_o, pp_e, xh=xh, gamma5_in=gamma5_in,
                             gamma5_out=gamma5_out)
    return dslash_oe_pallas(u_e, u_o, pp_e, xh=xh, bz=bz,
                            interpret=interpret, gamma5_in=gamma5_in,
                            gamma5_out=gamma5_out)


_STATIC_HOP = ("which", "xh", "bz", "interpret", "use_pallas", "gamma5_in",
               "gamma5_out", "acc_coeff", "hop_coeff", "acc_twist",
               "hop_twist")


@functools.partial(jax.jit, static_argnames=_STATIC_HOP)
def hop_block(u_e: jax.Array, u_o: jax.Array, pp: jax.Array, *,
              which: str, xh: int, gamma5_in: bool = False,
              gamma5_out: bool = False,
              psi_acc: jax.Array | None = None, acc_coeff: float = 0.0,
              hop_coeff: float = 1.0, acc_twist: float = 0.0,
              hop_twist: float = 0.0, bz: int | None = None,
              interpret: bool | None = None,
              use_pallas: bool = True) -> jax.Array:
    """One parity hop block with the full fused-epilogue surface exposed:

        out = (acc_coeff + acc_twist·iγ5) psi_acc
            + (hop_coeff + hop_twist·iγ5) γ5out Hop_which(γ5in ψ)

    This is the shard_map-compatible LOCAL building block of the
    distributed even-odd fast path (:mod:`repro.core.distributed`): called
    on a per-device shard it evaluates the bulk stencil with local periodic
    wrap, and the halo layer corrects only the boundary planes.  ``which``
    is ``"eo"`` (odd in, even out) or ``"oe"`` (even in, odd out); ``pp``
    may carry a leading RHS-batch axis.  The twist terms are the operator
    registry's site-term hook (twisted-mass Schur blocks; 0 for Wilson).
    The ``use_pallas=False`` reference composes the same epilogue out of
    the round-trip oracle blocks.
    """
    if which not in ("eo", "oe"):  # must survive `python -O`
        raise ValueError(f"hop_block: which must be 'eo' or 'oe', "
                         f"got {which!r}")
    if not use_pallas:
        ref = dslash_eo_ref if which == "eo" else dslash_oe_ref
        hop = ref(u_e, u_o, pp, xh=xh, gamma5_in=gamma5_in,
                  gamma5_out=gamma5_out)
        ig5 = lambda v: apply_igamma5_packed(v, axis=HALF_S_AXIS)
        out = hop if hop_coeff == 1.0 else hop_coeff * hop
        if hop_twist != 0.0:
            out = out + hop_twist * ig5(hop)
        if psi_acc is not None:
            acc = acc_coeff * psi_acc
            if acc_twist != 0.0:
                acc = acc + acc_twist * ig5(psi_acc)
            out = acc + out
        return out.astype(pp.dtype)
    kern = dslash_eo_pallas if which == "eo" else dslash_oe_pallas
    return kern(u_e, u_o, pp, xh=xh, bz=bz, interpret=interpret,
                gamma5_in=gamma5_in, gamma5_out=gamma5_out,
                psi_acc=psi_acc, acc_coeff=acc_coeff, hop_coeff=hop_coeff,
                acc_twist=acc_twist, hop_twist=hop_twist)


_STATIC_SCHUR = ("mass", "twist", "xh", "bz", "interpret", "use_pallas",
                 "dagger")


@phases.scoped(phases.SCHUR)
@functools.partial(jax.jit, static_argnames=_STATIC_SCHUR)
def schur_op(u_e: jax.Array, u_o: jax.Array, pp_e: jax.Array, mass: float, *,
             xh: int, twist: float = 0.0, bz: int | None = None,
             interpret: bool | None = None, use_pallas: bool = True,
             dagger: bool = False) -> jax.Array:
    """Schur complement D_hat psi = S psi - D_eo S^-1 D_oe psi, where S is
    the registry site term ``(mass+4) + i·twist·γ5`` (Wilson: twist = 0).

    Two kernel launches for EVERY operator family: D_oe streams the even
    field to a temporary odd field with ``S^-1`` folded into its epilogue
    (for Wilson the scalar commutes and rides the second launch's
    ``hop_coeff`` — bitwise the historical path), then D_eo's fused
    epilogue computes ``S psi - hop`` in one pass via
    ``acc_coeff``/``acc_twist`` — no separate scale/add/γ5 HBM traffic.
    ``dagger=True`` gives D_hat(twist)^dag = γ5 D_hat(-twist) γ5 by
    folding γ5 into the first kernel's prologue and the second kernel's
    hop epilogue and flipping the twist signs (S commutes with γ5).
    """
    if not use_pallas:
        return schur_op_ref(u_e, u_o, pp_e, mass, xh=xh, twist=twist,
                            dagger=dagger)
    m = float(mass) + 4.0
    kw = dict(xh=xh, bz=bz, interpret=interpret)
    if twist == 0.0:
        tmp_o = dslash_oe_pallas(u_e, u_o, pp_e, gamma5_in=dagger, **kw)
        return dslash_eo_pallas(u_e, u_o, tmp_o, gamma5_out=dagger,
                                psi_acc=pp_e, acc_coeff=m, hop_coeff=-1.0 / m,
                                **kw)
    # twisted site term: the two-launch split's sign algebra lives in
    # repro.core.operators.schur_launch_coeffs (shared with the sharded
    # halo path) — S(∓tw)^-1 folded into launch 1's hop epilogue,
    # S(±tw) into launch 2's accumulator.
    h1c, h1t, acc, acct = schur_launch_coeffs(m, twist, dagger)
    tmp_o = dslash_oe_pallas(u_e, u_o, pp_e, gamma5_in=dagger, hop_coeff=h1c,
                             hop_twist=h1t, **kw)
    return dslash_eo_pallas(u_e, u_o, tmp_o, gamma5_out=dagger, psi_acc=pp_e,
                            acc_coeff=acc, acc_twist=acct, hop_coeff=-1.0,
                            **kw)


@functools.partial(jax.jit, static_argnames=_STATIC_XH)
def schur_dagger(u_e: jax.Array, u_o: jax.Array, pp_e: jax.Array,
                 mass: float, *, xh: int, twist: float = 0.0,
                 bz: int | None = None, interpret: bool | None = None,
                 use_pallas: bool = True) -> jax.Array:
    """D_hat^dag = gamma5 D_hat(-twist) gamma5, folded into the kernels."""
    return schur_op(u_e, u_o, pp_e, mass, xh=xh, twist=twist, bz=bz,
                    interpret=interpret, use_pallas=use_pallas, dagger=True)


@functools.partial(jax.jit, static_argnames=_STATIC_XH)
def schur_normal_op(u_e: jax.Array, u_o: jax.Array, pp_e: jax.Array,
                    mass: float, *, xh: int, twist: float = 0.0,
                    bz: int | None = None, interpret: bool | None = None,
                    use_pallas: bool = True) -> jax.Array:
    """A_hat = D_hat^dag D_hat — the even-sublattice CGNR operator.

    Four parity-kernel launches total for EVERY registered operator
    family; every γ5, every site-term axpy and every twist is folded into
    a kernel prologue/epilogue, so the whole HPD matvec touches HBM
    exactly as often as its four hopping stencils demand.
    """
    if not use_pallas:
        return schur_normal_op_ref(u_e, u_o, pp_e, mass, xh=xh, twist=twist)
    w = schur_op(u_e, u_o, pp_e, mass, xh=xh, twist=twist, bz=bz,
                 interpret=interpret)
    return schur_op(u_e, u_o, w, mass, xh=xh, twist=twist, bz=bz,
                    interpret=interpret, dagger=True)
