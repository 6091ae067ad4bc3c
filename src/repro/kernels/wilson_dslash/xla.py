"""Compiled-XLA lowering of the half-spinor dslash algorithm.

``pallas_call`` cannot compile on the CPU backend ("Only interpret mode
is supported on CPU backend"), so ``interpret=False`` on CPU routes here
(:func:`repro.kernels.dispatch.resolve_lowering`): a whole-field jnp
implementation of the SAME spin-projection algorithm as the Pallas
kernels — project 4-spinors to 2 half spinors (trace-time tables from
:mod:`.kernel`), one batched complex 3x3 einsum per hop with f32
accumulation, reconstruct, γ5 folded into the constant tables.  This is
the honest *compiled* number for this host: measured 1.8–2x the naive
jnp reference (the einsum form; a scalar-FMA transcription of the kernel
body is SLOWER than the reference under XLA-CPU, 0.6–0.9x).

Numerics: same f32 compute precision and the same per-hop -1/2
accumulation as the kernels, but XLA is free to reorder the einsum
reduction — results agree with the interpret-mode kernels and the
reference to f32 roundoff (≤1e-5 relative), NOT bitwise.  Bitwise
contracts (goldens, tile-neutrality) are stated for the Pallas
lowerings only; this path is accuracy-gated in tests instead.

Layout contract is identical to the kernels: packed site fields
(..., T, Z, Y, 24, X) with X innermost, packed gauge (4, T, Z, Y, 18, X);
the parity entry point takes the kernels' packed half fields
(..., T, 24, Z, Y*Xh), computes on the (..., T, Z, Y, 24, Xh) view (one
transpose each way at its boundary) and supports the full fused-epilogue
surface (psi_acc/acc_coeff/hop_coeff/acc_twist/hop_twist), so `schur_normal_op`
lowers to 4 calls of this function with zero extra full-field passes —
the launch accounting matches the Pallas path one-for-one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.lattice import (GAUGE_G, NCOL, NDIRS, NSPIN, SPINOR_S,
                                exact_einsum, half_lanes, half_rows)
from repro.kernels.wilson_dslash.kernel import _TABLES

# Whole-field axes (from the right): site fields unpack to
# (..., T, Z, Y, spin=4, color=3, X); gauge unpacks to
# (4, T, Z, Y, row=3, col=3, X) — the T/Z/Y/X offsets coincide.
_T_AX, _Z_AX, _Y_AX, _X_AX = -6, -5, -4, -1
_AXIS = {0: _T_AX, 1: _Z_AX, 2: _Y_AX, 3: _X_AX}


def _split_spinor(pp):
    """(..., 24, X) packed -> re/im (..., 4, 3, X) f32."""
    x = pp.shape[-1]
    q = pp.reshape(pp.shape[:-2] + (NSPIN, NCOL, 2, x)).astype(jnp.float32)
    return q[..., 0, :], q[..., 1, :]


def _split_gauge(up):
    """(4, ..., 18, X) packed -> re/im (4, ..., 3, 3, X) f32."""
    x = up.shape[-1]
    q = up.reshape(up.shape[:-2] + (NCOL, NCOL, 2, x)).astype(jnp.float32)
    return q[..., 0, :], q[..., 1, :]


def _repack(out_r, out_i, shape, dtype):
    out = jnp.stack([out_r, out_i], axis=-2)
    return out.reshape(shape).astype(dtype)


def _tables(mu: int, sign: str, g5in: bool, g5out: bool):
    """The kernel's halfspinor tables with γ5 folded in (same sign flips
    as ``kernel._hop``)."""
    proj, recon = _TABLES[(mu, sign)]
    if g5in:
        proj = [[(b, -c if b >= 2 else c) for (b, c) in terms]
                for terms in proj]
    if g5out:
        recon = [(src, -ph) for (src, ph) in recon]
    return proj, recon


def _hop_half(ur, ui, pr, pi, mu: int, sign: str, g5in: bool, g5out: bool):
    """One hop on whole fields: -1/2 · recon(U·proj(ψ)) (U† for 'bwd').

    ur/ui: (..., T, Z, Y, 3, 3, X) gauge re/im at the SOURCE of the
    parallel transport (already rolled by the caller for backward hops);
    pr/pi: (..., T, Z, Y, 4, 3, X) neighbour-spinor re/im (already
    rolled).  Returns the (..., T, Z, Y, 4, 3, X) re/im contribution.
    """
    proj, recon = _tables(mu, sign, g5in, g5out)
    # stage 1: project to half spinors, stacked as (..., 2, 3, X)
    hs_r, hs_i = [], []
    for a in range(2):
        accr, acci = None, None
        for (b, coeff) in proj[a]:
            cr, ci = coeff.real, coeff.imag
            tr = cr * pr[..., b, :, :] - ci * pi[..., b, :, :]
            ti = cr * pi[..., b, :, :] + ci * pr[..., b, :, :]
            accr = tr if accr is None else accr + tr
            acci = ti if acci is None else acci + ti
        hs_r.append(accr)
        hs_i.append(acci)
    hr = jnp.stack(hs_r, axis=-3)
    hi = jnp.stack(hs_i, axis=-3)
    # stage 2: SU(3) multiply, one complex einsum per hop.  'bwd' applies
    # U† = conj(U)ᵀ via the transposed subscript + conjugation signs.
    sub = ("tzyabx,...tzyhbx->...tzyhax" if sign == "fwd"
           else "tzybax,...tzyhbx->...tzyhax")
    e = lambda u, h: exact_einsum(sub, u, h,
                                  preferred_element_type=jnp.float32)
    if sign == "fwd":
        gr = e(ur, hr) - e(ui, hi)
        gi = e(ur, hi) + e(ui, hr)
    else:
        gr = e(ur, hr) + e(ui, hi)
        gi = e(ur, hi) - e(ui, hr)
    # stage 3: reconstruct rows 2,3 from the half spinors by a phase
    rows_r = [gr[..., 0, :, :], gr[..., 1, :, :]]
    rows_i = [gi[..., 0, :, :], gi[..., 1, :, :]]
    for idx in range(2):
        src, phase = recon[idx]
        cr, ci = phase.real, phase.imag
        rr = cr * gr[..., src, :, :] - ci * gi[..., src, :, :]
        ri = cr * gi[..., src, :, :] + ci * gr[..., src, :, :]
        rows_r.append(rr)
        rows_i.append(ri)
    out_r = jnp.stack(rows_r, axis=-3)
    out_i = jnp.stack(rows_i, axis=-3)
    return -0.5 * out_r, -0.5 * out_i


def dslash_xla(up: jax.Array, pp: jax.Array, mass: float, *,
               twist: float = 0.0, gamma5_in: bool = False,
               gamma5_out: bool = False) -> jax.Array:
    """Full-lattice γ5out D (γ5in ψ): mass/twist site term + 8 hops.

    Same signature semantics as ``dslash_pallas`` minus the launch-space
    knobs (tiling is XLA's problem here); accepts the optional leading
    RHS-batch axis.
    """
    nd, t, z, y, g, x = up.shape
    assert nd == NDIRS and g == GAUGE_G
    assert pp.shape[-5:] == (t, z, y, SPINOR_S, x)
    pr, pi = _split_spinor(pp)
    ur, ui = _split_gauge(up)

    m4 = float(mass) + 4.0
    m4_lo = -m4 if (gamma5_in != gamma5_out) else m4
    scale = jnp.asarray([m4, m4, m4_lo, m4_lo], jnp.float32
                        ).reshape(NSPIN, 1, 1)
    out_r = scale * pr
    out_i = scale * pi
    if twist != 0.0:
        tw = [float(twist)] * 2 + (
            [-float(twist)] * 2 if gamma5_in == gamma5_out
            else [float(twist)] * 2)
        twv = jnp.asarray(tw, jnp.float32).reshape(NSPIN, 1, 1)
        out_r = out_r - twv * pi
        out_i = out_i + twv * pr

    for mu in range(NDIRS):
        ax = _AXIS[mu]
        fr, fi = _hop_half(ur[mu], ui[mu],
                           jnp.roll(pr, -1, ax), jnp.roll(pi, -1, ax),
                           mu, "fwd", gamma5_in, gamma5_out)
        br, bi = _hop_half(jnp.roll(ur[mu], 1, ax), jnp.roll(ui[mu], 1, ax),
                           jnp.roll(pr, 1, ax), jnp.roll(pi, 1, ax),
                           mu, "bwd", gamma5_in, gamma5_out)
        out_r = out_r + fr + br
        out_i = out_i + fi + bi
    return _repack(out_r, out_i, pp.shape, pp.dtype)


def dslash_parity_xla(u_out: jax.Array, u_nbr: jax.Array, pp: jax.Array, *,
                      xh: int, parity: int, gamma5_in: bool = False,
                      gamma5_out: bool = False,
                      psi_acc: jax.Array | None = None,
                      acc_coeff: float = 0.0, hop_coeff: float = 1.0,
                      acc_twist: float = 0.0,
                      hop_twist: float = 0.0) -> jax.Array:
    """Parity hop block on half fields with the full fused epilogue.

    Mirrors ``_dslash_parity_kernel`` on whole half fields: T/Z/Y
    neighbours are rolls, the parity-compressed X neighbour is a per-row
    select between the field and its rolled copy, with the row's
    output-site offset s_out = (t + z + y + parity) mod 2.
    """
    rows = lambda a: half_rows(a, xh)
    out = _dslash_parity_rows(
        rows(u_out), rows(u_nbr), rows(pp), parity=parity,
        gamma5_in=gamma5_in, gamma5_out=gamma5_out,
        psi_acc=None if psi_acc is None else rows(psi_acc),
        acc_coeff=acc_coeff, hop_coeff=hop_coeff, acc_twist=acc_twist,
        hop_twist=hop_twist)
    return half_lanes(out)


def _dslash_parity_rows(u_out, u_nbr, pp, *, parity, gamma5_in, gamma5_out,
                        psi_acc, acc_coeff, hop_coeff, acc_twist, hop_twist):
    """:func:`dslash_parity_xla` on the (..., T, Z, Y, C, Xh) views."""
    nd, t, z, y, g, x = u_out.shape
    assert nd == NDIRS and g == GAUGE_G and u_nbr.shape == u_out.shape
    assert pp.shape[-5:] == (t, z, y, SPINOR_S, x)
    pr, pi = _split_spinor(pp)
    uor, uoi = _split_gauge(u_out)
    unr, uni = _split_gauge(u_nbr)

    it = jax.lax.broadcasted_iota(jnp.int32, (t, z, y), 0)
    iz = jax.lax.broadcasted_iota(jnp.int32, (t, z, y), 1)
    iy = jax.lax.broadcasted_iota(jnp.int32, (t, z, y), 2)
    # (t, z, y, 1, 1, 1) broadcasts against both the spinor arrays
    # (..., t, z, y, 4, 3, x) and the rank-6 gauge arrays (t, z, y, 3, 3, x)
    sel = ((it + iz + iy + int(parity)) % 2 == 1).reshape(t, z, y, 1, 1, 1)

    hop_r = jnp.zeros_like(pr)
    hop_i = jnp.zeros_like(pi)
    for mu in range(3):  # T, Z, Y: plain rolls on half fields
        ax = _AXIS[mu]
        fr, fi = _hop_half(uor[mu], uoi[mu],
                           jnp.roll(pr, -1, ax), jnp.roll(pi, -1, ax),
                           mu, "fwd", gamma5_in, gamma5_out)
        br, bi = _hop_half(jnp.roll(unr[mu], 1, ax), jnp.roll(uni[mu], 1, ax),
                           jnp.roll(pr, 1, ax), jnp.roll(pi, 1, ax),
                           mu, "bwd", gamma5_in, gamma5_out)
        hop_r = hop_r + fr + br
        hop_i = hop_i + fi + bi
    # X: compressed-lane neighbour j + s_out (fwd) / j - (1 - s_out) (bwd)
    fr, fi = _hop_half(uor[3], uoi[3],
                       jnp.where(sel, jnp.roll(pr, -1, _X_AX), pr),
                       jnp.where(sel, jnp.roll(pi, -1, _X_AX), pi),
                       3, "fwd", gamma5_in, gamma5_out)
    br, bi = _hop_half(jnp.where(sel, unr[3], jnp.roll(unr[3], 1, _X_AX)),
                       jnp.where(sel, uni[3], jnp.roll(uni[3], 1, _X_AX)),
                       jnp.where(sel, pr, jnp.roll(pr, 1, _X_AX)),
                       jnp.where(sel, pi, jnp.roll(pi, 1, _X_AX)),
                       3, "bwd", gamma5_in, gamma5_out)
    hop_r = hop_r + fr + br
    hop_i = hop_i + fi + bi

    # epilogue: out = (acc_coeff + acc_twist·iγ5) ψ_acc
    #               + (hop_coeff + hop_twist·iγ5) hop
    g5 = jnp.asarray([1.0, 1.0, -1.0, -1.0], jnp.float32).reshape(NSPIN, 1, 1)
    h = jnp.float32(hop_coeff)
    out_r = h * hop_r
    out_i = h * hop_i
    if hop_twist != 0.0:
        hg = jnp.float32(hop_twist) * g5
        out_r = out_r - hg * hop_i
        out_i = out_i + hg * hop_r
    if psi_acc is not None:
        assert psi_acc.shape == pp.shape
        ar, ai = _split_spinor(psi_acc)
        a = jnp.float32(acc_coeff)
        out_r = out_r + a * ar
        out_i = out_i + a * ai
        if acc_twist != 0.0:
            ag = jnp.float32(acc_twist) * g5
            out_r = out_r - ag * ai
            out_i = out_i + ag * ar
    return _repack(out_r, out_i, pp.shape, pp.dtype)
