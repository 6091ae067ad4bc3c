"""Pallas kernel fusing the CG vector triad into one HBM pass.

A plain CG iteration does, after the matvec:
    x <- x + alpha p        (read x,p; write x)
    r <- r - alpha Ap       (read r,Ap; write r)
    rs <- ||r||^2           (read r; reduce)
    p <- r + beta p         (read r,p; write p)   [next half-step]

Done naively that is 7 reads + 3 writes of HBM per iteration.  The FPGA
paper hides all vector updates inside the streaming pipeline; the TPU
analogue is fusion — one kernel that streams (x, r, p, Ap) through VMEM
once, writes the updated (x, r) and emits per-block partial sums of
||r_new||^2 (4 reads + 2 writes + negligible partials).  ``cg_fused2``
additionally folds the p-update of the *following* iteration once beta is
known.

Vectors are viewed as (rows, 128) with a (block_rows, 128) grid — layout
matches the packed-field flattening, lane axis innermost.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import resolve_interpret

LANE = 128


def _update_kernel(alpha_ref, x_ref, r_ref, p_ref, ap_ref,
                   xo_ref, ro_ref, rs_ref):
    alpha = alpha_ref[0, 0]
    p = p_ref[...].astype(jnp.float32)
    ap = ap_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32) + alpha * p
    r = r_ref[...].astype(jnp.float32) - alpha * ap
    xo_ref[...] = x.astype(xo_ref.dtype)
    ro_ref[...] = r.astype(ro_ref.dtype)
    rs_ref[...] = jnp.full(rs_ref.shape, jnp.sum(r * r), jnp.float32)


def cg_update_pallas(alpha: jax.Array, x: jax.Array, r: jax.Array,
                     p: jax.Array, ap: jax.Array, *,
                     block_rows: int = 256, interpret: bool | None = None):
    """(x + alpha p, r - alpha Ap, ||r_new||^2) in one fused pass.

    Inputs must be 2D (rows, 128); use ``ops.cg_update`` for arbitrary
    shapes (it handles the reshape/pad).
    """
    rows, lane = x.shape
    assert lane == LANE and rows % block_rows == 0
    nb = rows // block_rows
    vec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    scal = pl.BlockSpec((1, 1), lambda i: (0, 0))
    # each block's partial sum fills one lane-wide row: Mosaic needs a
    # block's trailing two dims (8,128)-aligned or equal to the array's
    xo, ro, rs = pl.pallas_call(
        _update_kernel,
        grid=(nb,),
        in_specs=[scal, vec, vec, vec, vec],
        out_specs=[vec, vec, pl.BlockSpec((1, 1, LANE), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(r.shape, r.dtype),
                   jax.ShapeDtypeStruct((nb, 1, LANE), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(alpha, jnp.float32).reshape(1, 1), x, r, p, ap)
    return xo, ro, jnp.sum(rs[:, 0, 0])


def _xpay_kernel(beta_ref, r_ref, p_ref, po_ref):
    beta = beta_ref[0, 0]
    po_ref[...] = (r_ref[...].astype(jnp.float32)
                   + beta * p_ref[...].astype(jnp.float32)).astype(po_ref.dtype)


def cg_xpay_pallas(beta: jax.Array, r: jax.Array, p: jax.Array, *,
                   block_rows: int = 256, interpret: bool | None = None):
    """p <- r + beta p (the direction update), streaming layout as above."""
    rows, lane = r.shape
    assert lane == LANE and rows % block_rows == 0
    nb = rows // block_rows
    vec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    scal = pl.BlockSpec((1, 1), lambda i: (0, 0))
    return pl.pallas_call(
        _xpay_kernel,
        grid=(nb,),
        in_specs=[scal, vec, vec],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct(p.shape, p.dtype),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(beta, jnp.float32).reshape(1, 1), r, p)


# ---------------------------------------------------------------------------
# Multi-RHS (batched) variants: vectors are (N, rows, 128), scalars are
# per-RHS (N,).  The grid gains a leading batch dimension; per-RHS partial
# sums land in an (N, nb, 1, 128) output so each right-hand side keeps its own
# residual norm — the solver's convergence mask needs them separately.
# ---------------------------------------------------------------------------


def _update_batched_kernel(alpha_ref, x_ref, r_ref, p_ref, ap_ref,
                           xo_ref, ro_ref, rs_ref):
    alpha = alpha_ref[0, 0, 0]
    p = p_ref[...].astype(jnp.float32)
    ap = ap_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32) + alpha * p
    r = r_ref[...].astype(jnp.float32) - alpha * ap
    xo_ref[...] = x.astype(xo_ref.dtype)
    ro_ref[...] = r.astype(ro_ref.dtype)
    rs_ref[...] = jnp.full(rs_ref.shape, jnp.sum(r * r), jnp.float32)


def cg_update_batched_pallas(alpha: jax.Array, x: jax.Array, r: jax.Array,
                             p: jax.Array, ap: jax.Array, *,
                             block_rows: int = 256,
                             interpret: bool | None = None):
    """Per-RHS fused triad: (x + α_n p, r - α_n Ap, ||r'_n||²) in one pass.

    Inputs are (N, rows, 128) with per-RHS ``alpha`` of shape (N,); a
    frozen (converged) RHS rides through with α_n = 0, which leaves its
    x/r slices bitwise untouched.  Returns per-RHS norms of shape (N,).
    """
    n, rows, lane = x.shape
    assert lane == LANE and rows % block_rows == 0
    nb = rows // block_rows
    vec = pl.BlockSpec((1, block_rows, LANE), lambda ni, i: (ni, i, 0))
    scal = pl.BlockSpec((1, 1, 1), lambda ni, i: (ni, 0, 0))
    xo, ro, rs = pl.pallas_call(
        _update_batched_kernel,
        grid=(n, nb),
        in_specs=[scal, vec, vec, vec, vec],
        out_specs=[vec, vec, pl.BlockSpec((1, 1, 1, LANE),
                                          lambda ni, i: (ni, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(r.shape, r.dtype),
                   jax.ShapeDtypeStruct((n, nb, 1, LANE), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(alpha, jnp.float32).reshape(n, 1, 1), x, r, p, ap)
    return xo, ro, jnp.sum(rs[:, :, 0, 0], axis=1)


def _xpay_batched_kernel(beta_ref, gate_ref, r_ref, p_ref, po_ref):
    beta = beta_ref[0, 0, 0]
    gate = gate_ref[0, 0, 0] != 0
    p32 = p_ref[...].astype(jnp.float32)
    r32 = r_ref[...].astype(jnp.float32)
    po_ref[...] = jnp.where(gate, r32 + beta * p32, p32).astype(po_ref.dtype)


def cg_xpay_batched_pallas(beta: jax.Array, r: jax.Array, p: jax.Array,
                           gate: jax.Array, *, block_rows: int = 256,
                           interpret: bool | None = None):
    """Gated per-RHS direction update: p_n <- r_n + β_n p_n where gate_n,
    else p_n unchanged (the frozen lane of the convergence mask)."""
    n, rows, lane = r.shape
    assert lane == LANE and rows % block_rows == 0
    nb = rows // block_rows
    vec = pl.BlockSpec((1, block_rows, LANE), lambda ni, i: (ni, i, 0))
    scal = pl.BlockSpec((1, 1, 1), lambda ni, i: (ni, 0, 0))
    return pl.pallas_call(
        _xpay_batched_kernel,
        grid=(n, nb),
        in_specs=[scal, scal, vec, vec],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct(p.shape, p.dtype),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(beta, jnp.float32).reshape(n, 1, 1),
      jnp.asarray(gate, jnp.float32).reshape(n, 1, 1), r, p)
