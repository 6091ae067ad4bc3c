"""Even-odd (red-black) Schur-preconditioned Wilson solves.

This module owns the Schur-decomposition PLUMBING shared by every
even-odd solve path:

* :class:`EOOperators`/:func:`eo_operators`/:func:`eo_operators_packed` —
  the parity blocks of D bound to a gauge field, natural-layout reference
  or packed Pallas fast path;
* :func:`eo_context` — the one-stop resolver: operator blocks + RHS/
  solution layout converters + the fused vector engine, derived ONCE for
  a given (backend, batch shape).  This is what the
  :mod:`repro.core.plan` resolver builds every single-device even-odd
  solve from — the three historical ``solve_wilson_eo*`` variants used
  to re-derive the parity gauge/packing independently.

``solve_wilson_eo`` / ``solve_wilson_eo_batched`` / ``solve_wilson_eo_mp``
remain the stable public entry points but are now thin forwarders to the
:class:`repro.core.plan.SolverPlan` machinery: each one names its path as
a plan (operator family, backend, batch shape, precision policy) and the
plan resolver executes it.  Their contracts — including the bitwise
batched-equals-looped-singles guarantee and the packed-path r=1
restriction — are unchanged and tested in tests/test_eo.py.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import phases, solvers
from repro.core.lattice import (field_dot, field_norm2, merge_eo,
                                pack_half_gauge, pack_half_spinor, split_eo,
                                split_eo_gauge, unpack_half_spinor)
from repro.core.operators import (SiteTerm, schur_dagger_g, schur_op_g)
from repro.core.wilson import dslash_eo, dslash_oe

Array = jax.Array


class EOOperators(NamedTuple):
    """The parity blocks of D, bound to a gauge field, as callables."""

    dhat: solvers.Op       # Schur operator on even half fields
    dhat_dag: solvers.Op   # its gamma5-adjoint
    d_eo: solvers.Op       # odd -> even hopping block
    d_oe: solvers.Op       # even -> odd hopping block
    m_inv: solvers.Op      # M_oo^{-1} (site-term inverse; 1/(m+4r) Wilson)
    u_e: Array             # per-parity link fields (for callers reusing them)
    u_o: Array


def eo_operators(u: Array, mass, r: float = 1.0,
                 twist: float = 0.0) -> EOOperators:
    """Split the gauge field by parity and bind the Schur-system blocks.

    ``twist`` is the operator registry's site-term twist (the site block
    is ``(m + 4r) + i·twist·γ5``); 0 is Wilson, bitwise the historical
    blocks.  The hop blocks ``d_eo``/``d_oe`` are operator-agnostic
    transport and never see the twist.
    """
    with phases.scope(phases.GAUGE_LAYOUT):
        u_e, u_o = split_eo_gauge(u)
    site = SiteTerm(mass + 4.0 * r, twist)
    return EOOperators(
        dhat=lambda v: schur_op_g(u_e, u_o, v, mass, r=r, twist=twist),
        dhat_dag=lambda v: schur_dagger_g(u_e, u_o, v, mass, r=r,
                                          twist=twist),
        d_eo=lambda v: dslash_eo(u_e, u_o, v, r=r),
        d_oe=lambda v: dslash_oe(u_e, u_o, v, r=r),
        m_inv=site.solve,
        u_e=u_e, u_o=u_o)


def eo_operators_packed(u: Array, mass, r: float = 1.0, *,
                        twist: float = 0.0, bz: int | None = None,
                        interpret: bool | None = None,
                        use_pallas: bool = True) -> EOOperators:
    """The Schur-system blocks on PACKED half fields, Pallas fast path.

    The returned callables act on packed (T, 24, Z, Y*Xh) real half
    fields (:func:`repro.core.lattice.pack_half_spinor`) — or
    (N, T, 24, Z, Y*Xh) RHS batches: every block is rank-polymorphic, and
    the Pallas kernels amortize each gauge-plane fetch across the whole
    batch (see DESIGN.md §6).

    Supported-parameter matrix (packed path, ``use_pallas`` either way —
    the packed references round-trip through the same spin-projection
    contract):

    ==========  =======================  ==============================
    parameter   supported                notes
    ==========  =======================  ==============================
    r           1.0 only                 rank-2 (1 ∓ γ_mu) projectors
                                         are baked into the trace-time
                                         half-spinor tables; any other r
                                         raises ``NotImplementedError``
    mass        any float                trace-time constant
    twist       any float                site-term twist (operator
                                         registry): folded into the
                                         kernel epilogues, still 2
                                         launches per Schur block
    dtype       f32 / bf16 storage       kernels accumulate in f32
    batch       none or leading N axis   gauge read once per grid step
    ==========  =======================  ==============================

    For r != 1 use the natural-layout blocks (:func:`eo_operators`), which
    build the full rank-4 projectors.
    """
    if r != 1.0:  # a real exception, not assert: must survive `python -O`
        raise NotImplementedError(
            "the packed/Pallas parity kernels hard-code r=1 (their "
            "trace-time spin-projection tables need the rank-2 projectors "
            f"(1 -+ gamma_mu)); got r={r}. Use the natural-layout path "
            "(eo_operators / solve_wilson_eo(use_pallas=False)) for r != 1.")
    # local import: repro.core is imported by the kernels package, so a
    # module-level import here would be circular.
    from repro.kernels.wilson_dslash import ops as wops

    with phases.scope(phases.GAUGE_LAYOUT):
        u_e, u_o = split_eo_gauge(u)
        upe, upo = pack_half_gauge(u_e), pack_half_gauge(u_o)
    site = SiteTerm(mass + 4.0 * r, twist)
    kw = dict(xh=u.shape[4] // 2, bz=bz, interpret=interpret,
              use_pallas=use_pallas)
    return EOOperators(
        dhat=lambda v: wops.schur_op(upe, upo, v, mass, twist=twist, **kw),
        dhat_dag=lambda v: wops.schur_op(upe, upo, v, mass, twist=twist,
                                         dagger=True, **kw),
        d_eo=lambda v: wops.dslash_eo(upe, upo, v, **kw),
        d_oe=lambda v: wops.dslash_oe(upe, upo, v, **kw),
        m_inv=site.solve,
        u_e=upe, u_o=upo)


@phases.scoped(phases.SCHUR_RHS)
def schur_rhs(ops: EOOperators, b_e: Array, b_o: Array) -> Array:
    """The Schur normal-equation RHS ``D̂†(b_e − D_eo M_oo⁻¹ b_o)``.

    Every even-odd Krylov path iterates against this vector — plain CGNR,
    pipecg, block CG, and the deflation projection all derive it
    identically, so it is built here once.  Prologue work: NOT a counted
    matvec (see ``SolveStats.matvecs``).
    """
    return ops.dhat_dag(b_e - ops.d_eo(ops.m_inv(b_o)))


@phases.scoped(phases.BACK_SUBSTITUTE)
def back_substitute_odd(ops: EOOperators, b_o: Array, x_e: Array) -> Array:
    """Recover the odd half field: ``x_o = M_oo⁻¹ (b_o − D_oe x_e)``."""
    return ops.m_inv(b_o - ops.d_oe(x_e))


class EOContext(NamedTuple):
    """A resolved even-odd solve: blocks + layout converters + engine.

    ``prepare`` maps the natural-layout RHS ``b`` to the pair of
    working-layout half fields the solver iterates on; ``finish`` inverts
    it for the solution.  ``engine`` is the (update, xpay) fused vector
    engine when the working layout is packed (Pallas streaming triads),
    else None (the solver's default jnp algebra).  The blocks in ``ops``
    already accept the declared batch shape — vmapped natural-layout
    references or rank-polymorphic packed kernels.
    """

    ops: EOOperators
    prepare: Callable[[Array], tuple[Array, Array]]
    finish: Callable[[Array, Array], Array]
    engine: tuple[Callable, Callable] | None
    packed: bool
    batched: bool


def eo_context(u: Array, mass, *, r: float = 1.0, twist: float = 0.0,
               use_pallas: bool = False,
               batched: bool = False, bz: int | None = None,
               interpret: bool | None = None,
               out_dtype=jnp.complex64) -> EOContext:
    """Resolve the even-odd solve pieces for one (backend, batch) shape.

    This is the single place the parity gauge split, the field packing,
    the batch vmapping and the fused-engine choice are derived —
    everything downstream (the plan resolver, and through it the
    ``solve_wilson_eo*`` forwarders) composes these callables.  ``twist``
    selects the operator family's site term (0 = Wilson); the layout
    converters, batching and the fused engine are operator-agnostic and
    identical for every family.
    """
    if use_pallas:
        ops = eo_operators_packed(u, mass, r=r, twist=twist, bz=bz,
                                  interpret=interpret)

        xh = u.shape[4] // 2

        @phases.scoped(phases.RHS_LAYOUT)
        def prepare(b: Array) -> tuple[Array, Array]:
            b_e, b_o = (jax.vmap(split_eo)(b) if batched else split_eo(b))
            return pack_half_spinor(b_e), pack_half_spinor(b_o)

        @phases.scoped(phases.MERGE)
        def finish(x_e: Array, x_o: Array) -> Array:
            xe = unpack_half_spinor(x_e, xh, dtype=out_dtype)
            xo = unpack_half_spinor(x_o, xh, dtype=out_dtype)
            return (jax.vmap(merge_eo)(xe, xo) if batched
                    else merge_eo(xe, xo))

        # local import: see eo_operators_packed
        from repro.kernels.cg_fused import fused_engine, fused_engine_batched
        engine = (fused_engine_batched(interpret=interpret) if batched
                  else fused_engine(interpret=interpret))
        return EOContext(ops=ops, prepare=prepare, finish=finish,
                         engine=engine, packed=True, batched=batched)

    ops = eo_operators(u, mass, r=r, twist=twist)
    prepare, finish = split_eo, merge_eo
    if batched:
        # natural-layout blocks are single-RHS; vmap them (m_inv is
        # elementwise and batch-transparent already)
        ops = ops._replace(dhat=jax.vmap(ops.dhat),
                           dhat_dag=jax.vmap(ops.dhat_dag),
                           d_eo=jax.vmap(ops.d_eo),
                           d_oe=jax.vmap(ops.d_oe))
        prepare, finish = jax.vmap(split_eo), jax.vmap(merge_eo)
    return EOContext(ops=ops,
                     prepare=phases.scoped(phases.RHS_LAYOUT)(prepare),
                     finish=phases.scoped(phases.MERGE)(finish),
                     engine=None, packed=False, batched=batched)


# ---------------------------------------------------------------------------
# Legacy entry points — thin forwarders to the SolverPlan machinery
# ---------------------------------------------------------------------------


def solve_wilson_eo(u: Array, b: Array, mass, *, r: float = 1.0,
                    tol: float = 1e-8, maxiter: int = 1000,
                    dot=field_dot, norm2=field_norm2,
                    use_pallas: bool = False,
                    interpret: bool | None = None, bz: int | None = None,
                    ) -> tuple[Array, solvers.SolveStats]:
    """Solve D x = b by CGNR on the even-sublattice Schur complement.

    Same contract as a plain ``cgnr`` solve: natural-layout inputs, the
    merged full-lattice solution out, but the CG runs on half-size
    vectors against the better-conditioned reduced operator.

    Forwards to ``plan.solve`` with the equivalent
    ``SolverPlan(operator="eo-schur", backend=...)``; ``use_pallas=True``
    is the ``backend="pallas"`` fast path (packed real half fields,
    parity-hop stencil kernels, fused streaming vector algebra).
    ``interpret``/``bz`` tune the kernels (None = backend defaults).
    """
    from repro.core import plan as plan_mod  # forwarder; avoid import cycle
    p = plan_mod.SolverPlan(
        operator="eo-schur",
        backend="pallas" if use_pallas else "reference",
        r=r, bz=bz, interpret=interpret)
    return plan_mod.solve(p, u, b, mass, tol=tol, maxiter=maxiter,
                          dot=dot, norm2=norm2)


def solve_wilson_eo_batched(u: Array, b: Array, mass, *, r: float = 1.0,
                            tol: float = 1e-8, maxiter: int = 1000,
                            use_pallas: bool = True,
                            interpret: bool | None = None,
                            bz: int | None = None,
                            ) -> tuple[Array, solvers.SolveStats]:
    """Solve D x_n = b_n for a BATCH of right-hand sides in one CG loop.

    Args:
      u: (4, T, Z, Y, X, 3, 3) gauge field, shared by the whole batch —
        this sharing is the point: the matvec reads each gauge plane once
        per grid step and streams all N spinor planes through it, so the
        dslash arithmetic intensity grows with N (DESIGN.md §6).
      b: (N, T, Z, Y, X, 4, 3) batched RHS.
    Returns:
      (x, stats): x is (N, T, Z, Y, X, 4, 3); ``stats.iterations`` is the
      masked loop's trip count (= the slowest system's iterations) while
      ``stats.residual_norm2``/``stats.converged``/``stats.rhs_iterations``
      are per-RHS (N,).

    Per-RHS convergence masking freezes each system the iteration it
    meets ITS OWN ``tol``: the returned x_n is bitwise the iterate an
    independent single-RHS solve of b_n would have returned.  Forwards to
    ``plan.solve`` with ``SolverPlan(operator="eo-schur", nrhs=N)``;
    ``use_pallas`` selects the backend exactly as in
    :func:`solve_wilson_eo`.
    """
    if b.ndim != 7:  # a real exception, not assert: must survive `python -O`
        raise ValueError(
            f"batched RHS must be (N, T, Z, Y, X, 4, 3); got {b.shape}. "
            "For a single RHS use solve_wilson_eo (or add a leading axis).")
    from repro.core import plan as plan_mod  # forwarder; avoid import cycle
    p = plan_mod.SolverPlan(
        operator="eo-schur",
        backend="pallas" if use_pallas else "reference",
        nrhs=b.shape[0], r=r, bz=bz, interpret=interpret)
    return plan_mod.solve(p, u, b, mass, tol=tol, maxiter=maxiter)


def solve_wilson_eo_mp(u: Array, b: Array, mass, *, r: float = 1.0,
                       tol: float = 1e-6, inner_tol: float = 5e-2,
                       inner_maxiter: int = 200, max_outer: int = 50,
                       low_dtype=jnp.bfloat16, dot=field_dot,
                       norm2=field_norm2, use_pallas: bool = False,
                       interpret: bool | None = None, bz: int | None = None,
                       ) -> tuple[Array, solvers.SolveStats]:
    """Even-odd + mixed-precision: bf16 half-size inner CG, f32 updates.

    The low-precision representation is the bf16 real-pair view of the
    complex even half field (complex bf16 does not exist); links are
    rounded to bf16 once up front.  The inner CG's vector updates and
    stored iterates are bf16 while every contraction inside the operator
    still accumulates wide — narrow datapath, wide accumulator, as on
    the paper's FPGA.

    ``use_pallas=True`` keeps the WHOLE mixed-precision solve on the
    packed-field fast path: the low representation is simply the bf16
    packed real half field (kernels read bf16 storage and accumulate in
    f32 registers), so ``to_low``/``to_high`` are plain storage casts at
    the reliable-update boundary — once per outer cycle, on half fields —
    rather than standalone complex<->real-pair conversion passes, and the
    inner CG streams through the parity kernels + fused vector engine.
    Requires r = 1 (raises ``NotImplementedError`` otherwise; see
    :func:`eo_operators_packed` for the supported-parameter matrix).

    Forwards to ``plan.solve`` with ``SolverPlan(operator="eo-schur",
    precision="mixed", low=low_dtype)``.
    """
    from repro.core import plan as plan_mod  # forwarder; avoid import cycle
    p = plan_mod.SolverPlan(
        operator="eo-schur",
        backend="pallas" if use_pallas else "reference",
        precision="mixed", low=low_dtype, r=r, bz=bz, interpret=interpret)
    return plan_mod.solve(p, u, b, mass, tol=tol, inner_tol=inner_tol,
                          inner_maxiter=inner_maxiter, max_outer=max_outer,
                          dot=dot, norm2=norm2)
