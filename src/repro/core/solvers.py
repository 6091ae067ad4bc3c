"""Krylov solvers: CG, CGNR, mixed-precision reliable-update CG, pipelined CG,
BiCGStab.

Design notes
------------

* Every solver takes the operator as a *callable* ``op(x) -> Ax`` and the
  inner product as injectable callables ``dot``/``norm2``.  This is what
  makes the same solver run (a) single-device, (b) inside ``shard_map``
  where vectors are local shards and the injected ``dot`` performs the
  ``psum`` — the paper's "global communications ... for total error
  estimates" become a single fused collective per iteration.

* The per-iteration VECTOR algebra is injectable too: ``cg``/``cg_trace``
  accept ``update(alpha, x, r, p, ap) -> (x', r', ||r'||²)`` and
  ``xpay(beta, r, p) -> p'`` callables (see DESIGN.md, "fused-engine
  contract").  The defaults are the plain jnp expressions; passing
  :func:`repro.kernels.cg_fused.fused_engine`'s pair swaps in the Pallas
  streaming kernels, and the iteration's vector traffic drops from seven
  reads + three writes of HBM to one 4-read/2-write triad kernel plus one
  2-read/1-write direction kernel — the TPU analogue of the FPGA paper
  hiding all vector updates inside the streaming pipeline.

* ``mpcg`` is the paper's central algorithmic feature (its Ref. [10],
  Strzodka–Göddeke): run bulk CG iterations in a *low*-precision type and
  periodically recompute the true residual / accumulate the solution in a
  *high*-precision type ("reliable update" / defect correction).

* ``pipecg`` (Ghysels–Vanroose) restructures CG so each iteration has ONE
  fused reduction, issued alongside the matvec — the cluster-scale
  analogue of the paper's transfer/compute overlap (T4 in DESIGN.md).

* ``cg``/``cg_trace`` (and the cgnr/cgnr_eo/mpcg/mpcg_eo forwarders) are
  **multi-RHS batched** behind ``batched=True``: operands carry a leading
  RHS-batch axis, ``dot``/``norm2`` return per-RHS (N,) scalars, and every
  iteration applies per-RHS ``alpha``/``beta`` under a convergence MASK —
  a converged system's ``alpha`` is forced to 0 (its x/r stay bitwise
  frozen) and its direction update is gated off, so one slow system never
  perturbs the already-converged ones.  The loop runs until every RHS
  meets its own relative tolerance (see DESIGN.md §6).

* All solvers are ``lax.while_loop`` based and fully jittable.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import phases
from repro.core.lattice import (exact_einsum, exact_matmul, field_dot,
                                field_dot_batched, field_norm2,
                                field_norm2_batched)

Array = jax.Array
Op = Callable[[Array], Array]


class SolveStats(NamedTuple):
    iterations: Array          # total (inner) iterations executed
    outer_iterations: Array    # outer/reliable-update cycles (1 for plain CG)
    residual_norm2: Array      # final TRUE residual squared (high precision)
    converged: Array           # bool; per-RHS (N,) for batched solves
    # per-RHS (N,) iteration counts for batched solves: the step at which
    # each system's convergence mask froze it (``iterations`` is the
    # slowest system's count = the masked loop's trip count).  None for
    # unbatched solves, so the pytree structure of legacy stats (and the
    # shard_map out_specs built from them) is unchanged.
    rhs_iterations: Array | None = None
    # failure-taxonomy verdict code (int32; per-RHS (N,) for batched
    # solves): an index into ``VERDICTS``.  Computed from loop-exit state
    # only — no host syncs and no extra device work inside the iteration
    # body.  ``converged`` stays the raw ``rs <= limit`` bool; ``verdict``
    # is the classified WHY when it is False.
    verdict: Array | None = None
    # filled by plan.solve's post-solve verification matvec (None straight
    # out of a raw solver): the squared TRUE residual ``‖b - A x‖²``
    # recomputed through the operator registry, and whether it meets the
    # verification gate.  See plan._attach_verification.
    true_residual_norm2: Array | None = None
    verified: Array | None = None
    # exact count of ITERATION-OPERATOR applications (int32; per-RHS (N,)
    # for batched solves): one "matvec" is one application of the Krylov
    # operator the solver iterates with — for CGNR paths the normal
    # operator D†D / D̂†D̂ counts as ONE matvec (the paper's per-iteration
    # cost unit).  Counts the loop body's applications plus any x0-seeded
    # initial residual and pipecg's init/replacement applications; RHS
    # preparation (D†b) and the post-solve verification D-application are
    # epilogue/prologue work in a different unit and are NOT counted.  In
    # a batched solve every lane rides every block matvec, so per-RHS
    # matvecs equal the loop trip count (a frozen lane still streams
    # through the operator — this is physical work, which is exactly what
    # block CG and deflation reduce).  Derived from loop-exit counters
    # only: the hot body and its carry are untouched.
    matvecs: Array | None = None


# ---------------------------------------------------------------------------
# Failure taxonomy
# ---------------------------------------------------------------------------
#
# Every solver classifies its exit into one of these verdicts, carried on
# ``SolveStats.verdict`` as an int32 code (per-RHS for batched solves).
# Classification reads only loop-exit carries — breakdown/stagnation flags
# are accumulated as cheap scalar lanes inside the while-loop carry, so the
# hot iteration body gains zero host syncs and zero extra field passes.

CONVERGED, MAXITER_EXHAUSTED, BREAKDOWN, STAGNATION, NONFINITE = range(5)
VERDICTS = ("converged", "maxiter_exhausted", "breakdown", "stagnation",
            "nonfinite")

# a solve is "stagnant" when ‖r‖² fails to shrink by STAGNATION_FACTOR over
# the last STAGNATION_WINDOW iterations (healthy CG on these operators
# contracts far faster; see DESIGN.md §10)
STAGNATION_WINDOW = 25
STAGNATION_FACTOR = 0.5


def verdict_name(code) -> str:
    """Host-side: map a verdict code (python int / 0-d array) to its name."""
    return VERDICTS[int(code)]


# ---------------------------------------------------------------------------
# Loop decomposition — the segmented-solving contract (DESIGN.md §11)
# ---------------------------------------------------------------------------


class LoopParts(NamedTuple):
    """A solver's ``lax.while_loop`` decomposed into reusable pieces.

    ``cg``/``pipecg``/``mpcg`` are each exactly
    ``finish(lax.while_loop(cond, body, init))`` over their parts — and a
    SEGMENTED runner may instead iterate
    ``while_loop(lambda c: cond(c) & (counter(c) < stop), body, carry)``
    in bounded chunks, snapshotting the carry between chunks.  Because
    both spellings close over the SAME ``body`` function with the same
    carry avals, the while-loop body jaxpr is bitwise identical — the
    durability layer (plan.CheckpointPolicy) never touches the hot loop,
    only the stopping condition.  Asserted in tests/test_checkpoint_resume.
    """

    init: tuple                 # initial carry (concrete arrays)
    cond: Callable              # carry -> bool   (the solver's own test)
    body: Callable              # carry -> carry  (the hot loop, untouched)
    finish: Callable            # carry -> (x, SolveStats)
    counter: Callable           # carry -> int32 iteration count


def segment_cond(parts: LoopParts) -> Callable:
    """The segmented stopping rule: the solver's own ``cond`` AND an
    iteration bound ``counter(carry) < stop`` (``stop`` traced, so one
    compiled segment program serves every segment)."""

    def cond(carry, stop):
        return jnp.logical_and(parts.cond(carry),
                               parts.counter(carry) < stop)

    return cond


def classify(rs: Array, limit: Array, broken=False, stalled=False) -> Array:
    """Classify a solver exit from its final ``‖r‖²`` and failure flags.

    Precedence (most → least specific): converged, breakdown, nonfinite,
    stagnation, maxiter_exhausted.  NaN comparisons are False, so a
    non-finite residual never classifies as converged.
    """
    rs = jnp.asarray(rs)
    v = jnp.where(jnp.asarray(stalled), STAGNATION, MAXITER_EXHAUSTED)
    v = jnp.where(~jnp.isfinite(rs), NONFINITE, v)
    v = jnp.where(jnp.asarray(broken), BREAKDOWN, v)
    v = jnp.where(rs <= limit, CONVERGED, v)
    return jnp.broadcast_to(v, rs.shape).astype(jnp.int32)


def _real(x):
    return jnp.real(x) if jnp.iscomplexobj(x) else x


def _bcast(s: Array, field: Array) -> Array:
    """Broadcast per-RHS (N,) scalars over a batched field's site axes."""
    return s.reshape(s.shape + (1,) * (field.ndim - 1))


def _batched_defaults(dot, norm2):
    """Swap the unbatched default reductions for their per-RHS versions."""
    if dot is field_dot:
        dot = field_dot_batched
    if norm2 is field_norm2:
        norm2 = field_norm2_batched
    return dot, norm2


# the engine's in-stream norm can be trusted when norm2 is a known default
_DEFAULT_NORM2 = (field_norm2, field_norm2_batched)


def _stop_limit(tol, bs: Array, batched: bool) -> Array:
    """The stopping limit ``tol² · ‖b‖²`` (per-RHS when batched).

    ``tol`` may be a scalar or — for batched solves — a per-RHS (N,)
    vector: each system then stops against ITS OWN tolerance inside one
    masked loop.  This is what lets a serving layer coalesce requests
    with different tolerances into a single batch (the tolerance is a
    runtime argument, not a trace-time constant).  A non-scalar ``tol``
    on an unbatched solve is rejected loudly.
    """
    tol = jnp.asarray(tol)
    if tol.ndim > (1 if batched else 0):
        raise ValueError(
            "tol must be a scalar"
            + (" or a per-RHS (N,) vector" if batched else "")
            + f" ({'' if batched else 'batched=False; '}got shape "
            f"{tol.shape})")
    return (tol.astype(bs.dtype) ** 2) * bs


# ---------------------------------------------------------------------------
# Conjugate Gradient (HPD operator)
# ---------------------------------------------------------------------------

def cg_parts(op: Op, b: Array, x0: Array | None = None, *,
             tol: float = 1e-8, maxiter: int = 1000,
             dot=field_dot, norm2=field_norm2,
             update=None, xpay=None, batched: bool = False) -> LoopParts:
    """:func:`cg` decomposed into :class:`LoopParts` (same arguments).

    ``cg(...)`` is exactly ``parts.finish(while_loop(parts.cond,
    parts.body, parts.init))`` over these parts; a segmented runner reuses
    the identical ``body`` (see :class:`LoopParts`)."""
    if batched:
        dot, norm2 = _batched_defaults(dot, norm2)
    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - op(x) if x0 is not None else b
    p = r
    rs = _real(norm2(r))
    bs = _real(norm2(b))
    limit = _stop_limit(tol, bs, batched)

    def cond(carry):
        k, rs = carry[0], carry[4]
        broken = carry[6] if batched else carry[5]
        # a broken-down system cannot progress: drop it from the loop's
        # liveness test so one poisoned RHS never burns maxiter for the
        # batch (classified BREAKDOWN at exit).  NaN rs compares False, so
        # non-finite systems go inactive here with no extra checks.
        alive = jnp.logical_and(rs > limit, jnp.logical_not(broken))
        return jnp.logical_and(k < maxiter, jnp.any(alive))

    def body(carry):
        k, x, r, p, rs = carry[:5]
        if batched:
            it, broken, rs_mark = carry[5:8]
        else:
            broken, rs_mark = carry[5:7]
        # stagnation watermark: snapshot ‖r‖² every STAGNATION_WINDOW
        # iterations; exit-time classification compares against it
        rs_mark = jnp.where(k % STAGNATION_WINDOW == 0, rs, rs_mark)
        ap = op(p)
        pap = _real(dot(p, ap))
        if batched:
            active = jnp.logical_and(rs > limit, jnp.logical_not(broken))
            # alpha = 0 both for frozen systems AND on p·Ap breakdown: a
            # masked batch must skip the update (matching cg_trace's
            # convention), and the breakdown flag both stops the loop for
            # that system and classifies its exit
            safe = jnp.logical_and(active, pap != 0)
            broken = jnp.logical_or(broken,
                                    jnp.logical_and(active, pap == 0))
            alpha = jnp.where(safe, rs / jnp.where(pap == 0, 1.0, pap), 0.0)
        else:
            # guarded division: on p·Ap breakdown the iterate stays finite
            # and the loop exits with verdict=BREAKDOWN instead of flooding
            # x with inf/NaN (bitwise rs/pap whenever pap != 0)
            safe = pap != 0
            broken = jnp.logical_or(broken, pap == 0)
            alpha = jnp.where(safe, rs / jnp.where(safe, pap, 1.0), 0.0)
        if update is None:
            a = (_bcast(alpha, b) if batched else alpha).astype(b.dtype)
            x = x + a * p
            r = r - a * ap
            rs_new = _real(norm2(r))
        else:
            x, r, rs_new = update(alpha, x, r, p, ap)
            if norm2 not in _DEFAULT_NORM2:  # don't bypass an injected reduction
                rs_new = _real(norm2(r))
        beta = rs_new / (jnp.where(rs == 0, 1.0, rs) if batched else rs)
        if xpay is None:
            bb = (_bcast(beta, b) if batched else beta).astype(b.dtype)
            p_new = r + bb * p
            p = jnp.where(_bcast(safe, b), p_new, p) if batched else p_new
        else:
            p = xpay(beta, r, p, safe) if batched else xpay(beta, r, p)
        if batched:
            # per-RHS trip counts: a system still active this step ran it
            it = jnp.where(active, k + 1, it)
            return (k + 1, x, r, p, rs_new, it, broken, rs_mark)
        return (k + 1, x, r, p, rs_new, broken, rs_mark)

    init = (jnp.asarray(0, jnp.int32), x, r, p, rs)
    if batched:
        init = init + (jnp.zeros_like(rs, jnp.int32),)
    init = init + (jnp.zeros(rs.shape, bool), rs)

    # the x0 branch of the prologue applied op once for the initial residual
    init_mv = jnp.asarray(0 if x0 is None else 1, jnp.int32)

    def finish(out):
        k, x, r, p, rs = out[:5]
        broken, rs_mark = out[-2:]
        # exit-time stagnation test: ran past a full window yet ‖r‖² failed
        # to contract by STAGNATION_FACTOR since the last watermark
        stalled = jnp.logical_and(k >= STAGNATION_WINDOW,
                                  rs > STAGNATION_FACTOR * rs_mark)
        stats = SolveStats(iterations=k,
                           outer_iterations=jnp.asarray(1, jnp.int32),
                           residual_norm2=rs, converged=rs <= limit,
                           rhs_iterations=out[5] if batched else None,
                           verdict=classify(rs, limit, broken, stalled),
                           matvecs=jnp.broadcast_to(k + init_mv, rs.shape))
        return x, stats

    return LoopParts(init=init, cond=cond, body=body, finish=finish,
                     counter=lambda c: c[0])


def _run(parts: LoopParts):
    """Run a solver's parts to completion in one ``lax.while_loop``."""
    return parts.finish(jax.lax.while_loop(parts.cond, parts.body,
                                           parts.init))


@phases.scoped(phases.KRYLOV)
def cg(op: Op, b: Array, x0: Array | None = None, *,
       tol: float = 1e-8, maxiter: int = 1000,
       dot=field_dot, norm2=field_norm2,
       update=None, xpay=None, batched: bool = False,
       ) -> tuple[Array, SolveStats]:
    """Standard conjugate gradient for a Hermitian positive-definite ``op``.

    Stops when ``||r||^2 <= tol^2 * ||b||^2`` or at ``maxiter``.

    ``update``/``xpay`` inject the iteration's vector algebra (the fused
    vector engine; see the module docstring).  ``update`` must return the
    residual norm it computed alongside the updated ``x``/``r`` so no
    separate ``norm2`` pass over ``r`` is needed.  When a NON-default
    ``norm2`` is also injected (e.g. a psum-ing distributed reduction),
    the engine's locally-reduced norm cannot be trusted and ``norm2(r)``
    is recomputed instead — a distributed fused engine should fold the
    collective into ``update`` itself and leave ``norm2`` for the
    initial residual only.

    ``batched=True``: ``b`` (and ``op``'s in/out) carry a leading RHS-batch
    axis; each system stops against ITS OWN ``tol² ||b_n||²`` through the
    convergence mask — and ``tol`` itself may be a per-RHS (N,) vector
    (see ``_stop_limit``), so systems with different target tolerances
    share one masked loop — a converged system's ``alpha`` is masked to 0 (so
    ``x_n``/``r_n`` freeze bitwise, even inside an injected engine) and
    its direction update is gated off; the loop runs while ANY system is
    active.  Default ``dot``/``norm2`` swap to their per-RHS versions; an
    injected engine must follow the batched contract (per-RHS ``rs`` from
    ``update``, gate argument on ``xpay``; see DESIGN.md §6).
    """
    return _run(cg_parts(op, b, x0, tol=tol, maxiter=maxiter, dot=dot,
                         norm2=norm2, update=update, xpay=xpay,
                         batched=batched))


def cg_trace(op: Op, b: Array, *, iters: int,
             dot=field_dot, norm2=field_norm2,
             update=None, xpay=None, batched: bool = False,
             tol: float | None = None) -> tuple[Array, Array]:
    """CG for a fixed number of iterations, recording ||r||^2 per iteration.

    Used by convergence benchmarks (paper §2/§3.2 mixed-precision study);
    ``lax.scan`` based so the whole history lowers to one XLA program.
    ``update``/``xpay`` inject the fused vector engine exactly as in
    :func:`cg`.

    ``batched=True`` records a per-RHS history of shape (iters, N); when
    ``tol`` is also given, the convergence mask of :func:`cg` applies and
    a converged system's history entries stay flat at their frozen value —
    the mask-freeze property the batched tests assert on.  ``tol`` is a
    masking knob of the batched mode only (a fixed-iteration single-RHS
    trace has nothing to mask) and is rejected without ``batched=True``.
    """
    if tol is not None and not batched:
        raise ValueError("cg_trace: tol enables the per-RHS convergence "
                         "mask and requires batched=True")
    if batched:
        dot, norm2 = _batched_defaults(dot, norm2)
    x = jnp.zeros_like(b)
    r = b
    p = r
    rs = _real(norm2(r))
    limit = (None if tol is None
             else _stop_limit(tol, _real(norm2(b)), batched))

    def step(carry, _):
        x, r, p, rs = carry
        ap = op(p)
        pap = _real(dot(p, ap))
        safe = pap != 0
        alpha = jnp.where(safe, rs / jnp.where(safe, pap, 1.0), 0.0)
        if batched and limit is not None:
            active = rs > limit
            alpha = jnp.where(active, alpha, 0.0)
        else:
            active = None
        if update is None:
            a = (_bcast(alpha, b) if batched else alpha).astype(b.dtype)
            x = x + a * p
            r = r - a * ap
            rs_new = _real(norm2(r))
        else:
            x, r, rs_new = update(alpha, x, r, p, ap)
            if norm2 not in _DEFAULT_NORM2:  # don't bypass an injected reduction
                rs_new = _real(norm2(r))
        beta = jnp.where(rs > 0, rs_new / jnp.where(rs > 0, rs, 1.0), 0.0)
        if xpay is None:
            bb = (_bcast(beta, b) if batched else beta).astype(b.dtype)
            p_new = r + bb * p
            p = (jnp.where(_bcast(active, b), p_new, p)
                 if active is not None else p_new)
        elif batched:
            gate = active if active is not None else jnp.ones_like(rs, bool)
            p = xpay(beta, r, p, gate)
        else:
            p = xpay(beta, r, p)
        return (x, r, p, rs_new), rs_new

    (x, r, p, rs), hist = jax.lax.scan(step, (x, r, p, rs), None, length=iters)
    return x, hist


# ---------------------------------------------------------------------------
# CGNR — CG on the normal equations (the paper's solver for Dirac-Wilson)
# ---------------------------------------------------------------------------

def cgnr(d_op: Op, d_dag_op: Op, b: Array, **kw) -> tuple[Array, SolveStats]:
    """Solve D x = b for non-Hermitian D via D^dag D x = D^dag b.

    Keyword arguments (including ``update``/``xpay``/``batched``) forward
    to :func:`cg`; for a batched solve the operators must accept the
    leading RHS-batch axis.
    """
    return cg(lambda v: d_dag_op(d_op(v)), d_dag_op(b), **kw)


# ---------------------------------------------------------------------------
# Even-odd (Schur) preconditioned CGNR
# ---------------------------------------------------------------------------
#
# For a parity-blocked operator  D = [[M_ee, D_eo], [D_oe, M_oo]]  (the
# Wilson hopping term only couples opposite parities), eliminating the odd
# block of ``D x = b`` leaves the half-size Schur system
#
#     D_hat x_e = b_hat,    D_hat = M_ee - D_eo M_oo^{-1} D_oe
#                           b_hat = b_e  - D_eo M_oo^{-1} b_o
#
# and the odd solution follows by back-substitution
#
#     x_o = M_oo^{-1} (b_o - D_oe x_e).
#
# ``D_hat`` inherits gamma5-hermiticity from D (see repro.core.wilson), so
# CGNR applies unchanged: CG on ``D_hat^dag D_hat x_e = D_hat^dag b_hat``.
# All vectors are half the full-lattice size and the reduced spectrum is
# better conditioned — empirically ~2x fewer iterations at equal tolerance.
# The solvers below stay operator-agnostic: they take the blocks as
# callables, so the same code runs single-device or inside ``shard_map``
# with psum-ing ``dot``/``norm2`` injected, exactly like ``cg``.


def cgnr_eo(dhat: Op, dhat_dag: Op, d_eo: Op, d_oe: Op, m_inv: Op,
            b_e: Array, b_o: Array, x0: Array | None = None, *,
            tol: float = 1e-8, maxiter: int = 1000, dot=field_dot,
            norm2=field_norm2, update=None, xpay=None,
            batched: bool = False,
            ) -> tuple[tuple[Array, Array], SolveStats]:
    """Even-odd Schur-preconditioned CGNR.

    Args:
      dhat, dhat_dag: the Schur operator D_hat and its adjoint on
        even-parity half fields.
      d_eo, d_oe:     the parity-changing hopping blocks.
      m_inv:          applies M_oo^{-1} (for Wilson: scale by 1/(m+4r)).
      b_e, b_o:       the RHS split by parity; a leading RHS-batch axis on
        both (with ``batched=True`` and batch-capable operator blocks)
        solves all N systems in one masked CG loop.
      x0:             optional even-parity initial guess for the Schur
        normal system (deflation projects the RHS into one; see
        :func:`deflate_x0`).  ``None`` keeps the zero-start fast path.
      update, xpay:   optional fused vector engine, forwarded to :func:`cg`.
    Returns:
      ((x_e, x_o), SolveStats) — merge with ``lattice.merge_eo`` for the
      full-lattice solution.  ``iterations`` counts the half-size CG steps.
    """
    with phases.scope(phases.SCHUR_RHS):
        rhs = dhat_dag(b_e - d_eo(m_inv(b_o)))
    x_e, stats = cg(lambda v: dhat_dag(dhat(v)), rhs, x0,
                    tol=tol, maxiter=maxiter, dot=dot, norm2=norm2,
                    update=update, xpay=xpay, batched=batched)
    with phases.scope(phases.BACK_SUBSTITUTE):
        x_o = m_inv(b_o - d_oe(x_e))
    return (x_e, x_o), stats


def mpcg_eo(a_low: Op, a_high: Op, dhat_dag: Op, d_eo: Op, d_oe: Op,
            m_inv: Op, b_e: Array, b_o: Array, *,
            tol: float = 1e-6, inner_tol: float = 5e-2,
            inner_maxiter: int = 200, max_outer: int = 50,
            low_dtype=jnp.bfloat16, to_low=None, to_high=None,
            dot=field_dot, norm2=field_norm2, update=None, xpay=None,
            batched: bool = False,
            ) -> tuple[tuple[Array, Array], SolveStats]:
    """Even-odd reduction composed with mixed-precision reliable-update CG.

    The paper's two central optimizations finally compose: the half-size
    Schur normal system is solved by ``mpcg`` (bulk iterations through
    ``a_low``, the low-precision D_hat^dag D_hat; true residuals through
    ``a_high``), then the odd sites are back-substituted in high precision.
    ``to_low``/``to_high`` convert iterates between representations (see
    ``mpcg``); complex half fields use the real-pair view helpers in
    :mod:`repro.core.lattice` since complex bf16 does not exist.
    """
    with phases.scope(phases.SCHUR_RHS):
        rhs = dhat_dag(b_e - d_eo(m_inv(b_o)))
    x_e, stats = mpcg(a_low, a_high, rhs, tol=tol,
                      inner_tol=inner_tol, inner_maxiter=inner_maxiter,
                      max_outer=max_outer, low_dtype=low_dtype,
                      to_low=to_low, to_high=to_high, dot=dot, norm2=norm2,
                      update=update, xpay=xpay, batched=batched)
    with phases.scope(phases.BACK_SUBSTITUTE):
        x_o = m_inv(b_o - d_oe(x_e))
    return (x_e, x_o), stats


# ---------------------------------------------------------------------------
# Mixed-precision reliable-update CG  (the paper's Ref. [10] variant)
# ---------------------------------------------------------------------------

def mpcg_parts(op_low: Op, op_high: Op, b: Array, *,
               tol: float = 1e-6, inner_tol: float = 5e-2,
               inner_maxiter: int = 200, max_outer: int = 50,
               low_dtype=jnp.bfloat16, to_low=None, to_high=None,
               dot=field_dot, norm2=field_norm2,
               update=None, xpay=None, batched: bool = False) -> LoopParts:
    """:func:`mpcg` decomposed into :class:`LoopParts` (same arguments).

    The loop is the OUTER reliable-update cycle, so ``counter`` reads the
    accumulated INNER iteration total (carry slot 1): a segmented runner
    snapshots at reliable-update boundaries — exactly where the true
    residual was just recomputed in high precision — and a segment may
    overshoot its ``stop`` by at most one inner solve.

    Each outer cycle solves ``A d = r`` approximately in low precision
    (relative tolerance ``inner_tol``), then updates ``x += d`` and
    recomputes the TRUE residual ``r = b - A x`` in high precision.
    Equivalent to defect correction / iterative refinement with a CG
    inner solver; converges to the high-precision tolerance while doing
    most arithmetic in the cheap type.

    ``to_low``/``to_high`` convert a vector between the high- and
    low-precision REPRESENTATIONS and default to plain dtype casts.
    Inject them when the representations differ structurally — e.g.
    complex64 fields stored as bf16 real pairs (complex bf16 does not
    exist); ``op_low`` then operates on the low representation.

    ``batched=True``: per-RHS outer residuals; the outer loop (and each
    masked inner solve) runs until every RHS meets the tolerance.  A
    converged system enters the next inner solve with a ZEROED low
    residual, so the inner mask deactivates it at iteration 0 (zero RHS
    ⇒ zero limit), its correction comes back exactly 0, and its solution
    stops moving — without this, the RELATIVE ``inner_tol`` would keep
    iterating on a converged system's noise floor every remaining cycle.
    The reliable update itself is not masked: recomputing an
    already-converged true residual is harmless.
    """
    if batched:
        dot, norm2 = _batched_defaults(dot, norm2)
    high = b.dtype
    if to_low is None:
        to_low = lambda v: v.astype(low_dtype)
    if to_high is None:
        to_high = lambda v: v.astype(high)
    bs = _real(norm2(b))
    limit = _stop_limit(tol, bs, batched)

    def cond(carry):
        outer, rs = carry[0], carry[4]
        broken = carry[-2]
        # drop broken-down systems from the liveness test (see cg.cond);
        # a non-finite reliable-update rs compares False and goes inactive
        # here — this IS the "non-finite detection at reliable-update
        # boundaries" point: no checks inside the inner iteration body
        alive = jnp.logical_and(rs > limit, jnp.logical_not(broken))
        return jnp.logical_and(outer < max_outer, jnp.any(alive))

    def body(carry):
        outer, inner_total, x, r, rs = carry[:5]
        broken, rs_mark = carry[-2:]
        rs_mark = rs  # previous outer cycle's true ‖r‖², for stagnation
        rhs = r
        if batched:  # freeze converged systems: zero RHS -> inactive inner CG
            rhs = jnp.where(_bcast(rs > limit, r), r, jnp.zeros_like(r))
        r_low = to_low(rhs)
        d, st = _run(cg_parts(op_low, r_low, tol=inner_tol,
                              maxiter=inner_maxiter, dot=dot, norm2=norm2,
                              update=update, xpay=xpay, batched=batched))
        broken = jnp.logical_or(broken, st.verdict == BREAKDOWN)
        x = x + to_high(d)
        r = b - op_high(x)                     # reliable update (true residual)
        rs = _real(norm2(r))
        out = (outer + 1, inner_total + st.iterations, x, r, rs)
        if batched:  # per-RHS inner-iteration totals across outer cycles
            out = out + (carry[5] + st.rhs_iterations,)
        return out + (broken, rs_mark)

    init = (jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.zeros_like(b), b, bs)
    if batched:
        init = init + (jnp.zeros_like(bs, jnp.int32),)
    init = init + (jnp.zeros(bs.shape, bool), bs)

    def finish(out):
        outer, inner_total, x, r, rs = out[:5]
        broken, rs_mark = out[-2:]
        # outer-cycle stagnation: a reliable update that failed to contract
        # the true residual by STAGNATION_FACTOR over the last cycle
        stalled = jnp.logical_and(outer >= 2,
                                  rs > STAGNATION_FACTOR * rs_mark)
        # each outer cycle: the inner CG's per-iteration op_low applications
        # (= inner_total) plus ONE op_high reliable-update application
        stats = SolveStats(iterations=inner_total, outer_iterations=outer,
                           residual_norm2=rs, converged=rs <= limit,
                           rhs_iterations=out[5] if batched else None,
                           verdict=classify(rs, limit, broken, stalled),
                           matvecs=jnp.broadcast_to(inner_total + outer,
                                                    rs.shape))
        return x, stats

    return LoopParts(init=init, cond=cond, body=body, finish=finish,
                     counter=lambda c: c[1])


@phases.scoped(phases.KRYLOV)
def mpcg(op_low: Op, op_high: Op, b: Array, *,
         tol: float = 1e-6, inner_tol: float = 5e-2,
         inner_maxiter: int = 200, max_outer: int = 50,
         low_dtype=jnp.bfloat16, to_low=None, to_high=None,
         dot=field_dot, norm2=field_norm2,
         update=None, xpay=None,
         batched: bool = False) -> tuple[Array, SolveStats]:
    """Two-precision CG: bulk iterations in ``low_dtype``, corrected by
    high-precision true-residual "reliable updates".

    See :func:`mpcg_parts` for the full algorithm notes; this is exactly
    its parts run to completion in one ``lax.while_loop``.
    """
    return _run(mpcg_parts(op_low, op_high, b, tol=tol, inner_tol=inner_tol,
                           inner_maxiter=inner_maxiter, max_outer=max_outer,
                           low_dtype=low_dtype, to_low=to_low,
                           to_high=to_high, dot=dot, norm2=norm2,
                           update=update, xpay=xpay, batched=batched))


# ---------------------------------------------------------------------------
# Pipelined CG — one fused reduction per iteration (Ghysels–Vanroose)
# ---------------------------------------------------------------------------

def pipecg_parts(op: Op, b: Array, *, tol: float = 1e-8,
                 maxiter: int = 1000, residual_replacement_every: int = 25,
                 dot=field_dot, norm2=field_norm2, fused_dots=None,
                 batched: bool = False) -> LoopParts:
    """:func:`pipecg` decomposed into :class:`LoopParts` (same arguments).

    Pipelined CG: the two inner products of an iteration are fused into a
    single reduction which the scheduler can overlap with the matvec
    ``A w`` — per-iteration collective count drops from 2-3 to 1.

    Pipelined CG's three-term recurrences drift in floating point, so every
    ``residual_replacement_every`` iterations the TRUE residual
    ``r = b - A x`` is recomputed and the recurrences restarted — the same
    reliable-update idea the paper applies across precisions (Ref. [10]),
    applied here across recurrence drift.  Set 0 to disable.

    ``fused_dots(r, w) -> (gamma, delta)`` injects the iteration's single
    reduction (``gamma = (r, r)``, ``delta = (w, r)``).  The default
    composes the injected ``norm2``/``dot``; a distributed implementation
    should stack both local partials and issue ONE ``psum`` — see
    :func:`repro.core.distributed.make_fused_psum_dots` — making this the
    only collective per iteration.

    ``batched=True`` follows the masked multi-RHS contract of :func:`cg`:
    per-RHS ``gamma``/``delta``/``alpha``/``beta`` of shape (N,), a
    converged system's ``alpha`` masked to 0 (x/r/w freeze) and its
    z/q/p recurrences gated off, the loop running until every RHS meets
    its own relative tolerance.  The residual replacement stays global
    (recomputing a converged system's true residual is harmless).
    """
    if batched:
        dot, norm2 = _batched_defaults(dot, norm2)
    x = jnp.zeros_like(b)
    r = b
    w = op(r)
    dt = b.dtype
    rr = int(residual_replacement_every)

    if fused_dots is None:
        # fused reduction: computed together so a distributed implementation
        # can batch both into one collective.
        def fused_dots(r, w):
            return _real(norm2(r)), _real(dot(w, r))

    gamma, delta = fused_dots(r, w)
    bs = _real(norm2(b))
    limit = _stop_limit(tol, bs, batched)

    zero = jnp.zeros_like(b)
    init = (jnp.asarray(0, jnp.int32), x, r, w, zero, zero, zero,
            gamma, delta, jnp.ones_like(gamma),
            jnp.zeros_like(gamma), jnp.asarray(True))
    if batched:
        init = init + (jnp.zeros_like(gamma, jnp.int32),)
    init = init + (jnp.zeros(gamma.shape, bool),)

    def cond(c):
        k, gamma, broken = c[0], c[7], c[-1]
        alive = jnp.logical_and(gamma > limit, jnp.logical_not(broken))
        return jnp.logical_and(k < maxiter, jnp.any(alive))

    def body(c):
        (k, x, r, w, z, q, p, gamma, delta, alpha_prev, gamma_prev,
         restarted) = c[:12]
        broken = c[-1]
        m = op(w)  # ← overlaps the (gamma, delta) reduction
        beta = jnp.where(restarted, 0.0,
                         gamma / jnp.where(gamma_prev == 0, 1.0, gamma_prev))
        denom = delta - beta * gamma / jnp.where(alpha_prev == 0, 1.0,
                                                 alpha_prev)
        alpha = gamma / jnp.where(denom == 0, 1.0, denom)
        if batched:
            active = jnp.logical_and(gamma > limit, jnp.logical_not(broken))
            broken = jnp.logical_or(broken,
                                    jnp.logical_and(active, denom == 0))
            alpha = jnp.where(active, alpha, 0.0)  # freeze x/r/w bitwise
            bb, aa = _bcast(beta, b).astype(dt), _bcast(alpha, b).astype(dt)
            gate = _bcast(active, b)
            # gate the recurrence vectors too: beta -> 1 for a frozen
            # system (its gamma stopped moving), which would keep GROWING
            # p/q/z without this.
            z = jnp.where(gate, m + bb * z, z)
            q = jnp.where(gate, w + bb * q, q)
            p = jnp.where(gate, r + bb * p, p)
        else:
            bb = aa = None
            broken = jnp.logical_or(broken, denom == 0)
            z = m + beta.astype(dt) * z
            q = w + beta.astype(dt) * q
            p = r + beta.astype(dt) * p
        x = x + (aa if batched else alpha.astype(dt)) * p
        r = r - (aa if batched else alpha.astype(dt)) * q
        w = w - (aa if batched else alpha.astype(dt)) * z

        if rr > 0:
            do_replace = (k + 1) % rr == 0

            def replace(x, r, w):
                r_true = b - op(x)
                return r_true, op(r_true)

            r, w = jax.lax.cond(do_replace, replace,
                                lambda x, r, w: (r, w), x, r, w)
        else:
            do_replace = jnp.asarray(False)
        gamma_new, delta_new = fused_dots(r, w)
        out = (k + 1, x, r, w, z, q, p, gamma_new, delta_new, alpha, gamma,
               do_replace)
        if batched:
            out = out + (jnp.where(active, k + 1, c[12]),)
        return out + (broken,)

    def finish(out):
        k, x, gamma, broken = out[0], out[1], out[7], out[-1]
        # prologue w = op(r) is 1; each body iteration applies op once; a
        # residual replacement (every rr iterations) applies it twice more
        mv = k + 1 + (2 * (k // rr) if rr > 0 else 0)
        stats = SolveStats(iterations=k,
                           outer_iterations=jnp.asarray(1, jnp.int32),
                           residual_norm2=gamma, converged=gamma <= limit,
                           rhs_iterations=out[12] if batched else None,
                           verdict=classify(gamma, limit, broken),
                           matvecs=jnp.broadcast_to(mv, gamma.shape))
        return x, stats

    return LoopParts(init=init, cond=cond, body=body, finish=finish,
                     counter=lambda c: c[0])


@phases.scoped(phases.KRYLOV)
def pipecg(op: Op, b: Array, *, tol: float = 1e-8, maxiter: int = 1000,
           residual_replacement_every: int = 25,
           dot=field_dot, norm2=field_norm2, fused_dots=None,
           batched: bool = False) -> tuple[Array, SolveStats]:
    """Pipelined CG — ONE fused reduction per iteration.

    See :func:`pipecg_parts` for the full algorithm notes; this is exactly
    its parts run to completion in one ``lax.while_loop``.
    """
    return _run(pipecg_parts(
        op, b, tol=tol, maxiter=maxiter,
        residual_replacement_every=residual_replacement_every,
        dot=dot, norm2=norm2, fused_dots=fused_dots, batched=batched))


# ---------------------------------------------------------------------------
# BiCGStab — direct non-Hermitian solve (D x = b without normal equations)
# ---------------------------------------------------------------------------

def bicgstab(op: Op, b: Array, *, tol: float = 1e-8, maxiter: int = 1000,
             dot=field_dot, norm2=field_norm2) -> tuple[Array, SolveStats]:
    """BiCGStab for general (non-Hermitian) operators such as D itself.

    ``tol`` goes through :func:`_stop_limit` like every other solver, so a
    per-RHS tolerance VECTOR raises the same loud ``ValueError`` here
    (bicgstab has no batched mode to give it meaning).  The method's
    classic breakdowns — ``(rhat, r) = 0``, ``(rhat, v) = 0`` and a zero
    stabilizer norm ``‖t‖² = 0`` — set the breakdown flag and exit with
    ``verdict=BREAKDOWN`` instead of silently iterating on a guarded-away
    division.
    """
    x = jnp.zeros_like(b)
    r = b
    rhat = r
    dt = b.dtype
    # scalar carries take the dtype of the injected dot (complex for complex b)
    one = dot(b, b) * 0 + 1
    bs = _real(norm2(b))
    limit = _stop_limit(tol, bs, False)

    init = (jnp.asarray(0, jnp.int32), x, r, jnp.zeros_like(b),
            jnp.zeros_like(b), one, one, one, _real(norm2(r)),
            jnp.asarray(False))

    def cond(c):
        k, rs, broken = c[0], c[8], c[9]
        return jnp.logical_and(
            k < maxiter,
            jnp.logical_and(rs > limit, jnp.logical_not(broken)))

    def body(c):
        k, x, r, p, v, rho, alpha, omega, rs, broken = c
        rho_new = dot(rhat, r)
        broken = jnp.logical_or(broken, rho_new == 0)
        beta = (rho_new / jnp.where(rho == 0, 1.0, rho)) * \
               (alpha / jnp.where(omega == 0, 1.0, omega))
        p = r + beta.astype(dt) * (p - omega.astype(dt) * v)
        v = op(p)
        denom = dot(rhat, v)
        broken = jnp.logical_or(broken, denom == 0)
        alpha_new = rho_new / jnp.where(denom == 0, 1.0, denom)
        s = r - alpha_new.astype(dt) * v
        t = op(s)
        tn = _real(norm2(t))
        broken = jnp.logical_or(broken, tn == 0)
        omega_new = dot(t, s) / jnp.where(tn == 0, 1.0, tn)
        x = x + alpha_new.astype(dt) * p + omega_new.astype(dt) * s
        r = s - omega_new.astype(dt) * t
        return (k + 1, x, r, p, v, rho_new, alpha_new, omega_new,
                _real(norm2(r)), broken)

    out = jax.lax.while_loop(cond, body, init)
    k, x, rs, broken = out[0], out[1], out[8], out[9]
    stats = SolveStats(iterations=k, outer_iterations=jnp.asarray(1, jnp.int32),
                       residual_norm2=rs, converged=rs <= limit,
                       verdict=classify(rs, limit, broken),
                       matvecs=2 * k)  # v = op(p) and t = op(s) per iteration
    return x, stats


# ---------------------------------------------------------------------------
# Block CG — one shared Krylov search space for N right-hand sides
# ---------------------------------------------------------------------------
#
# Batched CG (above) shares the MATVEC across N systems but keeps N
# independent Krylov spaces: every RHS burns its own iteration budget.
# Block CG (O'Leary 1980) shares the SEARCH SPACE too — the N scalar
# alpha/beta pairs become small N×N Gram solves, every column's update
# draws on all N directions, and the iteration count drops toward the one
# set by the operator's spectrum divided by the block width.  Per-RHS
# matvecs equal the (smaller) trip count, so the total operator work for
# N systems falls well below N× the single-RHS count (DESIGN.md §12).


def gram(a: Array, b: Array) -> Array:
    """Pairwise inner products ``G[i, j] = ⟨a_i, b_j⟩`` over the leading
    axis (single-device; the site axes are flattened and contracted in one
    einsum).  Real for packed real-pair fields, Hermitian complex for
    natural fields."""
    a2 = a.reshape(a.shape[0], -1)
    b2 = b.reshape(b.shape[0], -1)
    return exact_einsum("if,jf->ij", a2.conj(), b2)


def _mix(fields: Array, coef: Array) -> Array:
    """Column mixing ``out_j = Σ_i fields_i · coef[i, j]`` over the leading
    RHS axis — the block-CG generalization of ``alpha * p``."""
    f2 = fields.reshape(fields.shape[0], -1)
    return exact_einsum("ij,if->jf", coef.astype(f2.dtype),
                        f2).reshape(fields.shape)


def _gram_psolve(g: Array, rhs: Array, rcond: float = 1e-7) -> Array:
    """Hermitian pseudo-solve of the N×N Gram system — the block-CG
    RANK-DEFLATION point.  Eigenvalues below ``rcond·λ_max`` (converged
    columns are zeroed out of P, linearly dependent directions collapse)
    get zero inverse weight, so degenerate directions drop out of the
    update instead of poisoning every column through a singular solve."""
    evals, evecs = jnp.linalg.eigh(g)
    cut = rcond * jnp.maximum(jnp.max(jnp.abs(evals)), 1e-30)
    inv = jnp.where(evals > cut, 1.0 / jnp.where(evals > cut, evals, 1.0),
                    0.0)
    return exact_matmul(evecs, inv[:, None].astype(rhs.dtype)
                        * exact_matmul(evecs.conj().T, rhs))


@phases.scoped(phases.KRYLOV)
def blockcg(op: Op, b: Array, x0: Array | None = None, *,
            tol: float = 1e-8, maxiter: int = 1000,
            norm2=field_norm2_batched) -> tuple[Array, SolveStats]:
    """Block CG for a Hermitian positive-definite ``op`` over a leading
    RHS-batch axis — N systems share ONE Krylov search space.

    Per iteration: one block matvec ``Q = A P`` (the same batched
    operator the masked multi-RHS solvers use — one gauge fetch serves
    all N spinors), then two N×N Gram solves

        alpha = (PᴴAP)⁺ PᴴR          (Galerkin step)
        beta  = −(PᴴAP)⁺ QᴴR₊        (A-orthogonalization)

    with a Hermitian PSEUDO-inverse (:func:`_gram_psolve`): converged
    columns are zeroed out of ``P``/``R`` and linearly dependent search
    directions collapse onto eigenvalues below the cut, so both are
    rank-deflated out of the shared space instead of breaking the solve.
    Columns therefore do NOT freeze bitwise the way the masked batched CG
    freezes them (every update mixes all active directions) — the
    contract degrades gracefully to per-RHS verdicts: per-RHS
    convergence, per-RHS ``rhs_iterations``, per-RHS classification, and
    the §10 true-residual verification gate still applies per RHS.

    ``tol`` may be a per-RHS (N,) vector exactly as in :func:`cg`.
    Single-device only (the Gram einsums contract unsharded site axes).
    """
    if b.ndim < 2:
        raise ValueError("blockcg requires a leading RHS-batch axis")
    _, norm2 = _batched_defaults(field_dot, norm2)  # always per-RHS here
    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - op(x) if x0 is not None else b
    rs = _real(norm2(r))
    bs = _real(norm2(b))
    limit = _stop_limit(tol, bs, True)
    active0 = rs > limit
    # invariant: inactive columns of P are identically zero, so they
    # contribute nothing to the Gram matrices or the shared updates
    p = jnp.where(_bcast(active0, b), r, jnp.zeros_like(b))

    def cond(c):
        k, rs, broken = c[0], c[4], c[6]
        alive = jnp.logical_and(rs > limit, jnp.logical_not(broken))
        return jnp.logical_and(k < maxiter, jnp.any(alive))

    def body(c):
        k, x, r, p, rs, it, broken, rs_mark = c
        rs_mark = jnp.where(k % STAGNATION_WINDOW == 0, rs, rs_mark)
        m = jnp.logical_and(rs > limit, jnp.logical_not(broken))
        q = op(p)
        g = gram(p, q)                       # N×N, PSD (zero inactive slots)
        alpha = _gram_psolve(g, gram(p, r))
        # mask converged/broken columns: their x/r stay untouched
        alpha = alpha * m[None, :].astype(alpha.dtype)
        colbad = jnp.logical_not(jnp.all(jnp.isfinite(alpha), axis=0))
        broken = jnp.logical_or(broken, jnp.logical_and(m, colbad))
        alpha = jnp.where(jnp.isfinite(alpha), alpha, 0.0)
        x = x + _mix(p, alpha)
        r = r - _mix(q, alpha)
        rs_new = _real(norm2(r))
        m_next = jnp.logical_and(rs_new > limit, jnp.logical_not(broken))
        beta = -_gram_psolve(g, gram(q, r))
        beta = beta * m_next[None, :].astype(beta.dtype)
        beta = jnp.where(jnp.isfinite(beta), beta, 0.0)
        p_new = (jnp.where(_bcast(m_next, b), r, jnp.zeros_like(b))
                 + _mix(p, beta))
        it = jnp.where(m, k + 1, it)
        return (k + 1, x, r, p_new, rs_new, it, broken, rs_mark)

    init = (jnp.asarray(0, jnp.int32), x, r, p, rs,
            jnp.zeros_like(rs, jnp.int32), jnp.zeros(rs.shape, bool), rs)
    k, x, r, p, rs, it, broken, rs_mark = jax.lax.while_loop(cond, body,
                                                             init)
    stalled = jnp.logical_and(k >= STAGNATION_WINDOW,
                              rs > STAGNATION_FACTOR * rs_mark)
    init_mv = jnp.asarray(0 if x0 is None else 1, jnp.int32)
    stats = SolveStats(iterations=k,
                       outer_iterations=jnp.asarray(1, jnp.int32),
                       residual_norm2=rs, converged=rs <= limit,
                       rhs_iterations=it,
                       verdict=classify(rs, limit, broken, stalled),
                       matvecs=jnp.broadcast_to(k + init_mv, rs.shape))
    return x, stats


# ---------------------------------------------------------------------------
# EigCG-style deflation — harvest low eigenpairs from early solves, then
# project them out of every later solve on the same gauge field
# ---------------------------------------------------------------------------
#
# CG's alpha/beta coefficients ARE a Lanczos factorization of the Krylov
# operator in the normalized-residual basis: T[k,k] = 1/α_k + β_{k-1}/α_{k-1},
# T[k,k+1] = √β_k / α_k.  Recording the normalized residuals alongside a
# normal solve (``cg_harvest``) therefore yields Ritz pairs of A for free —
# the smallest ones approximate the low modes that dominate the iteration
# count.  A later solve on the same operator projects its RHS against the
# harvested basis (Galerkin: x₀ = W (WᴴAW)⁻¹ Wᴴ b) and init-CGs from that
# x₀ — the low-mode components arrive pre-solved and CG only works on the
# better-conditioned remainder (DESIGN.md §12).


class DeflationBasis(NamedTuple):
    """A harvested low-mode basis for one (gauge, operator) pair.

    ``w``: (nev, *field) approximate low eigenvectors (Ritz vectors) of
    the Krylov operator, in the solver's working layout.  ``gram``: the
    (nev, nev) projected operator ``WᴴAW`` — identity-padded on slots
    beyond the harvested rank, so the Galerkin solve is always
    nonsingular and a padded slot contributes exactly zero correction.
    """

    w: Array
    gram: Array

    @property
    def nev(self) -> int:
        return self.w.shape[0]


@phases.scoped(phases.KRYLOV)
def cg_harvest(op: Op, b: Array, *, tol: float = 1e-8, maxiter: int = 1000,
               m_max: int = 48, dot=field_dot, norm2=field_norm2,
               ) -> tuple[Array, SolveStats, tuple[Array, Array, Array]]:
    """:func:`cg` (single-RHS) that additionally records its Lanczos data.

    Returns ``(x, stats, (v, alphas, betas))``: the solution and stats of
    a normal CG solve, plus the first ``min(iterations, m_max)``
    normalized residuals ``v_k = r_k/‖r_k‖`` (the Lanczos vectors of
    ``op`` in the Krylov space) and the CG coefficients they pair with —
    exactly what :func:`ritz_deflation_basis` turns into a
    :class:`DeflationBasis`.  The hot loop gains one buffer write per
    iteration and no extra reductions or matvecs; the while-loop trip
    count (and the iterate trajectory) is bitwise that of :func:`cg`.
    """
    m_max = int(min(m_max, maxiter))
    x = jnp.zeros_like(b)
    r = b
    p = r
    rs = _real(norm2(r))
    bs = _real(norm2(b))
    limit = _stop_limit(tol, bs, False)

    def cond(c):
        k, rs, broken = c[0], c[4], c[5]
        alive = jnp.logical_and(rs > limit, jnp.logical_not(broken))
        return jnp.logical_and(k < maxiter, alive)

    def body(c):
        k, x, r, p, rs, broken, rs_mark, vbuf, albuf, bebuf = c
        rs_mark = jnp.where(k % STAGNATION_WINDOW == 0, rs, rs_mark)
        # record the k-th Lanczos vector (normalized residual) before the
        # update; writes past m_max re-write the last slot with its own
        # value (a no-op), keeping the loop free of conditionals
        idx = jnp.minimum(k, m_max - 1)
        v = r * jnp.where(rs > 0, jax.lax.rsqrt(rs), 0.0).astype(r.dtype)
        keep = jax.lax.dynamic_index_in_dim(vbuf, idx, 0, keepdims=False)
        vbuf = jax.lax.dynamic_update_index_in_dim(
            vbuf, jnp.where(k < m_max, v, keep), idx, 0)
        ap = op(p)
        pap = _real(dot(p, ap))
        safe = pap != 0
        broken = jnp.logical_or(broken, pap == 0)
        alpha = jnp.where(safe, rs / jnp.where(safe, pap, 1.0), 0.0)
        x = x + alpha.astype(b.dtype) * p
        r = r - alpha.astype(b.dtype) * ap
        rs_new = _real(norm2(r))
        beta = rs_new / rs
        p = r + beta.astype(b.dtype) * p
        keep_al = jax.lax.dynamic_index_in_dim(albuf, idx, 0, False)
        keep_be = jax.lax.dynamic_index_in_dim(bebuf, idx, 0, False)
        albuf = jax.lax.dynamic_update_index_in_dim(
            albuf, jnp.where(k < m_max, alpha, keep_al), idx, 0)
        bebuf = jax.lax.dynamic_update_index_in_dim(
            bebuf, jnp.where(k < m_max, beta, keep_be), idx, 0)
        return (k + 1, x, r, p, rs_new, broken, rs_mark, vbuf, albuf, bebuf)

    init = (jnp.asarray(0, jnp.int32), x, r, p, rs,
            jnp.asarray(False), rs,
            jnp.zeros((m_max,) + b.shape, b.dtype),
            jnp.zeros((m_max,), rs.dtype), jnp.zeros((m_max,), rs.dtype))
    out = jax.lax.while_loop(cond, body, init)
    k, x, r, p, rs, broken, rs_mark, vbuf, albuf, bebuf = out
    stalled = jnp.logical_and(k >= STAGNATION_WINDOW,
                              rs > STAGNATION_FACTOR * rs_mark)
    stats = SolveStats(iterations=k,
                       outer_iterations=jnp.asarray(1, jnp.int32),
                       residual_norm2=rs, converged=rs <= limit,
                       verdict=classify(rs, limit, broken, stalled),
                       matvecs=jnp.broadcast_to(k, rs.shape))
    return x, stats, (vbuf, albuf, bebuf)


def ritz_deflation_basis(op: Op, v: Array, alphas: Array, betas: Array,
                         k, nev: int) -> DeflationBasis:
    """Host-side (eager): turn :func:`cg_harvest` records into a
    :class:`DeflationBasis` of exactly ``nev`` slots.

    Builds the k×k Lanczos tridiagonal from the CG coefficients, takes
    its ``min(nev, k)`` SMALLEST Ritz pairs, combines the recorded
    Lanczos vectors into Ritz vectors ``W = V·Y``, and projects the
    operator once: ``gram = WᴴAW`` (costing ``min(nev, k)`` extra
    matvecs, amortized over every later deflated solve on this gauge
    field).  Slots beyond the harvested rank are zero vectors with
    identity gram rows — inert in the Galerkin solve — so the basis shape
    is static regardless of how early the harvest solve converged.
    """
    import numpy as np
    m = int(min(int(k), v.shape[0]))
    if m < 1:
        raise ValueError("ritz_deflation_basis: empty harvest (k < 1)")
    al = np.asarray(alphas)[:m].astype(np.float64)
    be = np.asarray(betas)[:m].astype(np.float64)
    al = np.where(al == 0, 1.0, al)
    diag = 1.0 / al
    diag[1:] += be[:m - 1] / al[:m - 1]
    off = np.sqrt(np.maximum(be[:m - 1], 0.0)) / al[:m - 1]
    t = np.diag(diag) + np.diag(off, 1) + np.diag(off, -1)
    _, y = np.linalg.eigh(t)          # ascending: low modes first
    n_eff = max(1, min(nev, m))
    # the true Lanczos vectors are q_k = (-1)^k r_k/‖r_k‖; the recorded
    # v_k drop the sign, so fold it into the eigenvector rows (combining
    # unsigned v's with unsigned y's would target the WRONG spectrum end)
    signs = (-1.0) ** np.arange(m)
    yk = jnp.asarray((y[:, :n_eff] * signs[:, None]).astype(np.float32))
    vm = v[:m]
    w = exact_einsum("km,k...->m...", yk.astype(vm.dtype), vm)
    aw = jnp.stack([op(w[i]) for i in range(n_eff)])
    g = gram(w, aw)
    if n_eff < nev:
        pad = jnp.zeros((nev - n_eff,) + w.shape[1:], w.dtype)
        w = jnp.concatenate([w, pad], axis=0)
        g_full = jnp.eye(nev, dtype=g.dtype)
        g = g_full.at[:n_eff, :n_eff].set(g)
    return DeflationBasis(w=w, gram=g)


def deflate_x0(basis: DeflationBasis, rhs: Array) -> Array:
    """Galerkin deflation: ``x₀ = W (WᴴAW)⁻¹ Wᴴ rhs``.

    ``rhs`` may carry a leading RHS-batch axis (same rank as ``basis.w``);
    the projection is per-RHS — no cross-lane mixing, so a poisoned lane's
    NaNs stay in its own x₀ (the §10 blast-radius contract).  A zero rhs
    (serving pad slot) yields exactly zero x₀.
    """
    nev = basis.w.shape[0]
    w2 = basis.w.reshape(nev, -1)
    batched = rhs.ndim == basis.w.ndim
    r2 = rhs.reshape(rhs.shape[0] if batched else 1, -1)
    proj = exact_einsum("kf,nf->kn", w2.conj(), r2)
    c = jnp.linalg.solve(basis.gram, proj)
    x0 = exact_einsum("kn,kf->nf", c, w2.astype(c.dtype))
    return x0.reshape(rhs.shape).astype(rhs.dtype)
