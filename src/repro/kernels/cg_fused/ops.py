"""Jitted wrappers for the fused CG updates on arbitrary field shapes.

Fields are flattened to a (rows, 128) streaming view; a zero pad (which
contributes 0 to the residual reduction and is sliced off afterwards)
handles sizes that are not multiples of 128*block_rows.

Lowering: like the dslash wrappers, ``interpret=False`` on CPU (where
``pallas_call`` cannot compile) routes to the jnp reference triad — for
these pure vector ops the ref IS the compiled-XLA implementation; XLA
fuses the a*x+y chains into the same streaming passes the kernel
hand-codes, so the "xla" lowering loses nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import phases
from repro.kernels.cg_fused.kernel import (LANE, cg_update_batched_pallas,
                                           cg_update_pallas,
                                           cg_xpay_batched_pallas,
                                           cg_xpay_pallas)
from repro.kernels.cg_fused.ref import (cg_update_batched_ref, cg_update_ref,
                                        cg_xpay_batched_ref, cg_xpay_ref)
from repro.kernels.dispatch import resolve_lowering

__all__ = ["cg_update", "cg_xpay", "cg_update_batched", "cg_xpay_batched",
           "cg_pallas", "fused_engine", "fused_engine_batched"]


def _pick_block_rows(rows: int) -> int:
    for cand in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if rows % cand == 0:
            return cand
    return 1


def _to_stream(v: jax.Array):
    n = v.size
    rows = -(-n // LANE)
    pad = rows * LANE - n
    flat = jnp.pad(v.reshape(-1), (0, pad))
    return flat.reshape(rows, LANE), pad


def _to_stream_batched(v: jax.Array):
    """(N, ...) -> (N, rows, 128): each RHS flattened to its own stream."""
    nb = v.shape[0]
    per = v.size // nb
    rows = -(-per // LANE)
    pad = rows * LANE - per
    flat = jnp.pad(v.reshape(nb, -1), ((0, 0), (0, pad)))
    return flat.reshape(nb, rows, LANE), pad


@phases.scoped(phases.CG_UPDATE)
@functools.partial(jax.jit, static_argnames=("interpret", "use_pallas"))
def cg_update(alpha, x, r, p, ap, *, interpret: bool | None = None,
              use_pallas: bool = True):
    """Fused (x + alpha p, r - alpha Ap, ||r_new||^2) for any field shape."""
    if not use_pallas or resolve_lowering(interpret) == "xla":
        return cg_update_ref(alpha, x, r, p, ap)
    shape = x.shape
    xs, _ = _to_stream(x)
    rs_, _ = _to_stream(r)
    ps, _ = _to_stream(p)
    aps, _ = _to_stream(ap)
    br = _pick_block_rows(xs.shape[0])
    xo, ro, rs = cg_update_pallas(alpha, xs, rs_, ps, aps,
                                  block_rows=br, interpret=interpret)
    n = x.size
    return (xo.reshape(-1)[:n].reshape(shape),
            ro.reshape(-1)[:n].reshape(shape), rs)


@phases.scoped(phases.CG_XPAY)
@functools.partial(jax.jit, static_argnames=("interpret", "use_pallas"))
def cg_xpay(beta, r, p, *, interpret: bool | None = None,
            use_pallas: bool = True):
    """p <- r + beta p for any field shape."""
    if not use_pallas or resolve_lowering(interpret) == "xla":
        return cg_xpay_ref(beta, r, p)
    shape = p.shape
    rstream, _ = _to_stream(r)
    pstream, _ = _to_stream(p)
    br = _pick_block_rows(pstream.shape[0])
    po = cg_xpay_pallas(beta, rstream, pstream, block_rows=br,
                        interpret=interpret)
    return po.reshape(-1)[:p.size].reshape(shape)


@phases.scoped(phases.CG_UPDATE)
@functools.partial(jax.jit, static_argnames=("interpret", "use_pallas"))
def cg_update_batched(alpha, x, r, p, ap, *, interpret: bool | None = None,
                      use_pallas: bool = True):
    """Per-RHS fused triad for (N, ...) fields; ``alpha`` is (N,).

    Returns (x', r', rs) with rs the per-RHS ||r'_n||² of shape (N,).
    A frozen RHS (α_n = 0) keeps its x/r slices bitwise unchanged.
    """
    if not use_pallas or resolve_lowering(interpret) == "xla":
        return cg_update_batched_ref(alpha, x, r, p, ap)
    shape = x.shape
    xs, _ = _to_stream_batched(x)
    rs_, _ = _to_stream_batched(r)
    ps, _ = _to_stream_batched(p)
    aps, _ = _to_stream_batched(ap)
    br = _pick_block_rows(xs.shape[1])
    xo, ro, rs = cg_update_batched_pallas(alpha, xs, rs_, ps, aps,
                                          block_rows=br, interpret=interpret)
    nb = shape[0]
    per = x.size // nb
    return (xo.reshape(nb, -1)[:, :per].reshape(shape),
            ro.reshape(nb, -1)[:, :per].reshape(shape), rs)


@phases.scoped(phases.CG_XPAY)
@functools.partial(jax.jit, static_argnames=("interpret", "use_pallas"))
def cg_xpay_batched(beta, r, p, gate, *, interpret: bool | None = None,
                    use_pallas: bool = True):
    """Gated per-RHS direction update for (N, ...) fields.

    ``beta``/``gate`` are (N,): where ``gate`` is set the slice gets
    ``r + beta p``; a cleared gate freezes the slice (p returned as-is) —
    the in-kernel form of the solver's convergence mask.
    """
    if not use_pallas or resolve_lowering(interpret) == "xla":
        return cg_xpay_batched_ref(beta, r, p, gate)
    shape = p.shape
    rstream, _ = _to_stream_batched(r)
    pstream, _ = _to_stream_batched(p)
    br = _pick_block_rows(pstream.shape[1])
    po = cg_xpay_batched_pallas(beta, rstream, pstream, gate,
                                block_rows=br, interpret=interpret)
    nb = shape[0]
    per = p.size // nb
    return po.reshape(nb, -1)[:, :per].reshape(shape)


def fused_engine(*, interpret: bool | None = None, use_pallas: bool = True):
    """(update, xpay) pair for the solvers' injectable vector engine.

    Plug straight into :func:`repro.core.solvers.cg`'s ``update=``/``xpay=``
    hooks: the per-iteration vector algebra then runs through the two fused
    streaming kernels (4 reads + 2 writes for the x/r/||r||² triad, 2 reads
    + 1 write for the direction update) instead of seven separate jnp
    passes.
    """
    update = functools.partial(cg_update, interpret=interpret,
                               use_pallas=use_pallas)
    xpay = functools.partial(cg_xpay, interpret=interpret,
                             use_pallas=use_pallas)
    return update, xpay


def fused_engine_batched(*, interpret: bool | None = None,
                         use_pallas: bool = True):
    """(update, xpay) pair for the solvers' BATCHED vector engine.

    For ``cg(..., batched=True)``: ``update`` takes the per-RHS (N,)
    ``alpha`` (already masked to 0 on converged systems) and returns
    per-RHS residual norms; ``xpay`` additionally takes the solver's
    activity ``gate`` so converged directions freeze inside the kernel.
    See DESIGN.md §6 for the contract.
    """
    update = functools.partial(cg_update_batched, interpret=interpret,
                               use_pallas=use_pallas)
    xpay = functools.partial(cg_xpay_batched, interpret=interpret,
                             use_pallas=use_pallas)
    return update, xpay


def cg_pallas(op, b, *, tol=1e-8, maxiter=1000, interpret: bool | None = None):
    """CG whose vector algebra runs through the fused Pallas kernels.

    The matvec ``op`` is arbitrary (e.g. the wilson_dslash normal op);
    everything else is two fused streaming passes per iteration.
    """
    x = jnp.zeros_like(b)
    r = b
    p = r
    rs = jnp.sum(r.astype(jnp.float32) ** 2)
    bs = rs
    limit = (tol ** 2) * bs

    def cond(c):
        k, x, r, p, rs = c
        return jnp.logical_and(k < maxiter, rs > limit)

    def body(c):
        k, x, r, p, rs = c
        ap = op(p)
        pap = jnp.sum(p.astype(jnp.float32) * ap.astype(jnp.float32))
        alpha = rs / pap
        x, r, rs_new = cg_update(alpha, x, r, p, ap, interpret=interpret)
        beta = rs_new / rs
        p = cg_xpay(beta, r, p, interpret=interpret)
        return (k + 1, x, r, p, rs_new)

    k, x, r, p, rs = jax.lax.while_loop(
        cond, body, (jnp.asarray(0, jnp.int32), x, r, p, rs))
    return x, (k, rs)
