"""The Dirac-Wilson operator.

Two implementations, mutually validated in tests:

* ``dslash``        — natural layout, complex arrays, textbook form.  The
                      correctness oracle for everything else.
* ``dslash_packed`` — packed real layout ``(T,Z,Y,S,X)`` (see
                      :mod:`repro.core.lattice`), real arithmetic with
                      explicit re/im planes.  This is the layout the Pallas
                      TPU kernel uses; it also runs in bf16 (the paper's
                      "low precision data type") with f32 accumulation —
                      the TPU analogue of FPGA narrow datapaths feeding
                      wider accumulators.

Operator convention (r = Wilson parameter, m = bare mass):

    D psi(x) = (m + 4r) psi(x)
             - 1/2 sum_mu [ (r - gamma_mu) U_mu(x)       psi(x+mu)
                          + (r + gamma_mu) U_mu(x-mu)^dag psi(x-mu) ]

Directions are ordered (t, z, y, x) matching the array axes.  Gamma
matrices are in the DeGrand-Rossi basis; ``gamma5 D gamma5 = D^dag`` holds
and is tested, giving the daggered operator and the HPD normal operator
``D^dag D`` used by CGNR.

Even-odd (Schur) decomposition
------------------------------

The hopping term only connects sites of opposite parity, so in the
even/odd site ordering of :mod:`repro.core.lattice` the operator is
2x2 block-structured::

    D = [ M_ee   D_eo ]        M_ee = M_oo = (m + 4r) * 1
        [ D_oe   M_oo ]        D_eo : odd -> even hops, D_oe : even -> odd

Block-eliminating the odd sites from ``D x = b`` gives the Schur
complement system on the EVEN sublattice only::

    D_hat x_e = b_hat,   D_hat = M_ee - D_eo M_oo^{-1} D_oe
                         b_hat = b_e  - D_eo M_oo^{-1} b_o

followed by back-substitution ``x_o = M_oo^{-1} (b_o - D_oe x_e)``.
Because ``gamma5 D_eo gamma5 = D_oe^dag`` (each hop inherits the
gamma5-hermiticity of the full operator) and ``M`` is a real scalar,
``gamma5 D_hat gamma5 = D_hat^dag`` holds on the half lattice too — so
CGNR applies to ``D_hat`` unchanged, on vectors HALF the size and with a
better-conditioned spectrum (empirically ~2x fewer iterations; see
``benchmarks/bench_solvers.py``).  Implemented by ``dslash_eo`` /
``dslash_oe`` / ``schur_op`` below; solver orchestration lives in
:mod:`repro.core.eo`.

Half-lattice fields compress X by 2 (see ``split_eo``): within a row
(t, z, y) the neighbour of compressed index j in the x direction is
j + s (forward) or j - (1 - s) (backward) where s is the output row's
parity offset; t/z/y hops keep j and roll the row axes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lattice import (NCOL, NDIRS, NSPIN, eo_row_offset,
                                 exact_einsum)

# ---------------------------------------------------------------------------
# Gamma matrices, DeGrand-Rossi basis, order (t, z, y, x) = axes (0,1,2,3)
# ---------------------------------------------------------------------------

_i = 1j
GAMMA_T = np.array([[0, 0, 1, 0],
                    [0, 0, 0, 1],
                    [1, 0, 0, 0],
                    [0, 1, 0, 0]], dtype=np.complex64)
GAMMA_X = np.array([[0, 0, 0, _i],
                    [0, 0, _i, 0],
                    [0, -_i, 0, 0],
                    [-_i, 0, 0, 0]], dtype=np.complex64)
GAMMA_Y = np.array([[0, 0, 0, -1],
                    [0, 0, 1, 0],
                    [0, 1, 0, 0],
                    [-1, 0, 0, 0]], dtype=np.complex64)
GAMMA_Z = np.array([[0, 0, _i, 0],
                    [0, 0, 0, -_i],
                    [-_i, 0, 0, 0],
                    [0, _i, 0, 0]], dtype=np.complex64)

# axis order (T, Z, Y, X)
GAMMAS = np.stack([GAMMA_T, GAMMA_Z, GAMMA_Y, GAMMA_X])
GAMMA5 = np.diag([1, 1, -1, -1]).astype(np.complex64)  # g5 = gt gx gy gz

EYE4 = np.eye(4, dtype=np.complex64)


def _projectors(r: float):
    """P-[mu] = r - gamma_mu (forward hop), P+[mu] = r + gamma_mu (backward)."""
    pm = np.stack([r * EYE4 - GAMMAS[mu] for mu in range(NDIRS)])
    pp = np.stack([r * EYE4 + GAMMAS[mu] for mu in range(NDIRS)])
    return pm, pp


# ---------------------------------------------------------------------------
# Natural-layout reference operator (complex)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("r",))
def dslash(u: jax.Array, psi: jax.Array, mass: float | jax.Array,
           r: float = 1.0) -> jax.Array:
    """Dirac-Wilson operator, natural layout.

    Args:
      u:    (4, T, Z, Y, X, 3, 3) complex gauge field.
      psi:  (T, Z, Y, X, 4, 3) complex spinor field.
      mass: bare mass m.
    Returns:
      D psi, same shape/dtype as psi.
    """
    pm, pp = _projectors(r)
    pm = jnp.asarray(pm, dtype=psi.dtype)
    pp = jnp.asarray(pp, dtype=psi.dtype)
    out = (mass + 4.0 * r) * psi
    for mu in range(NDIRS):
        umu = u[mu]
        # forward hop: (r - gamma_mu) U_mu(x) psi(x + mu)
        fwd = jnp.roll(psi, -1, axis=mu)
        hf = exact_einsum("tzyxab,tzyxsb->tzyxsa", umu, fwd)
        hf = exact_einsum("sp,tzyxpa->tzyxsa", pm[mu], hf)
        # backward hop: (r + gamma_mu) U_mu(x - mu)^dag psi(x - mu)
        bwd = jnp.roll(psi, 1, axis=mu)
        ubw = jnp.roll(umu, 1, axis=mu)
        hb = exact_einsum("tzyxba,tzyxsb->tzyxsa", jnp.conj(ubw), bwd)
        hb = exact_einsum("sp,tzyxpa->tzyxsa", pp[mu], hb)
        out = out - 0.5 * (hf + hb)
    return out


def apply_gamma5(psi: jax.Array) -> jax.Array:
    """gamma5 in DeGrand-Rossi = diag(+,+,-,-) on the spin axis (-2)."""
    sign = jnp.asarray([1.0, 1.0, -1.0, -1.0], dtype=psi.dtype)
    return psi * sign[:, None]


@partial(jax.jit, static_argnames=("r",))
def dslash_dagger(u: jax.Array, psi: jax.Array, mass, r: float = 1.0):
    """D^dag psi = gamma5 D gamma5 psi (tested against explicit adjoint)."""
    return apply_gamma5(dslash(u, apply_gamma5(psi), mass, r=r))


@partial(jax.jit, static_argnames=("r",))
def normal_op(u: jax.Array, psi: jax.Array, mass, r: float = 1.0):
    """A = D^dag D — Hermitian positive definite; the CGNR operator."""
    return dslash_dagger(u, dslash(u, psi, mass, r=r), mass, r=r)


# ---------------------------------------------------------------------------
# Even-odd hopping operators and the Schur complement (natural layout)
# ---------------------------------------------------------------------------

def _hop_half(u_out: jax.Array, u_nbr: jax.Array, psi: jax.Array,
              s_out: np.ndarray, r: float) -> jax.Array:
    """Hopping term of D restricted to one parity's output sites.

    Args:
      u_out: (4, T, Z, Y, Xh, 3, 3) links attached to the OUTPUT-parity
             sites (forward hops use U_mu(x) at the output site x).
      u_nbr: links attached to the opposite-parity (neighbour) sites
             (backward hops use U_mu(x - mu)^dag at the neighbour site).
      psi:   (T, Z, Y, Xh, 4, 3) opposite-parity spinor half field.
      s_out: (T, Z, Y) int row offsets of the output parity (see
             ``eo_row_offset``): output sites sit at x = 2*j + s_out.
    Returns:
      (T, Z, Y, Xh, 4, 3) = -1/2 sum_mu [ (r - g_mu) U psi(x+mu)
                                        + (r + g_mu) U^dag psi(x-mu) ].

    For mu in {t, z, y} the neighbour keeps its compressed x index j and
    the row axis rolls.  For mu = x the neighbour index is j + s_out
    (forward) / j - (1 - s_out) (backward) — a row-parity-dependent shift
    implemented as a ``where`` between the field and its rolled copy.
    Periodic wrap in x is exact because the X extent is even.
    """
    t, z, y = psi.shape[:3]
    assert t % 2 == z % 2 == y % 2 == 0, (
        "even-odd operators need even T/Z/Y extents: an odd periodic "
        f"extent breaks bipartiteness, got {(t, z, y)}")
    pm, pp = _projectors(r)
    pm = jnp.asarray(pm, dtype=psi.dtype)
    pp = jnp.asarray(pp, dtype=psi.dtype)
    sel_s = jnp.asarray(s_out == 1).reshape(s_out.shape + (1, 1, 1))
    sel_g = sel_s  # same (T,Z,Y,1,1,1) broadcast works for (T,Z,Y,Xh,3,3)

    out = jnp.zeros_like(psi)
    for mu in range(NDIRS):
        if mu < 3:  # t, z, y: plain rolls on the uncompressed row axes
            fwd = jnp.roll(psi, -1, axis=mu)
            u_fwd = u_out[mu]
            bwd = jnp.roll(psi, 1, axis=mu)
            u_bwd = jnp.roll(u_nbr[mu], 1, axis=mu)
        else:  # x: compressed axis 3, neighbour index depends on s_out
            fwd = jnp.where(sel_s, jnp.roll(psi, -1, axis=3), psi)
            u_fwd = u_out[3]
            bwd = jnp.where(sel_s, psi, jnp.roll(psi, 1, axis=3))
            u_bwd = jnp.where(sel_g, u_nbr[3], jnp.roll(u_nbr[3], 1, axis=3))
        hf = exact_einsum("tzyjab,tzyjsb->tzyjsa", u_fwd, fwd)
        hf = exact_einsum("sp,tzyjpa->tzyjsa", pm[mu], hf)
        hb = exact_einsum("tzyjba,tzyjsb->tzyjsa", jnp.conj(u_bwd), bwd)
        hb = exact_einsum("sp,tzyjpa->tzyjsa", pp[mu], hb)
        out = out - 0.5 * (hf + hb)
    return out


@partial(jax.jit, static_argnames=("r",))
def dslash_eo(u_e: jax.Array, u_o: jax.Array, psi_o: jax.Array,
              r: float = 1.0) -> jax.Array:
    """D_eo: hopping term from an ODD half field onto EVEN output sites.

    ``u_e``/``u_o`` are the per-parity link fields from ``split_eo_gauge``;
    ``psi_o`` is (T, Z, Y, Xh, 4, 3) odd-parity.  Mass term excluded.
    """
    t, z, y = psi_o.shape[:3]
    return _hop_half(u_e, u_o, psi_o, eo_row_offset(t, z, y), r)


@partial(jax.jit, static_argnames=("r",))
def dslash_oe(u_e: jax.Array, u_o: jax.Array, psi_e: jax.Array,
              r: float = 1.0) -> jax.Array:
    """D_oe: hopping term from an EVEN half field onto ODD output sites."""
    t, z, y = psi_e.shape[:3]
    return _hop_half(u_o, u_e, psi_e, 1 - eo_row_offset(t, z, y), r)


@partial(jax.jit, static_argnames=("r",))
def schur_op(u_e: jax.Array, u_o: jax.Array, psi_e: jax.Array,
             mass, r: float = 1.0) -> jax.Array:
    """Schur complement D_hat psi_e = (m+4r) psi_e - D_eo D_oe psi_e / (m+4r).

    Acts on even-parity half fields only; gamma5-hermitian (tested), so
    CGNR on ``D_hat^dag D_hat`` converges exactly as for the full D.
    """
    m = mass + 4.0 * r
    return m * psi_e - dslash_eo(u_e, u_o, dslash_oe(u_e, u_o, psi_e, r=r),
                                 r=r) / m


@partial(jax.jit, static_argnames=("r",))
def schur_dagger(u_e, u_o, psi_e, mass, r: float = 1.0):
    """D_hat^dag = gamma5 D_hat gamma5 (gamma5 acts on spin axis -2)."""
    return apply_gamma5(schur_op(u_e, u_o, apply_gamma5(psi_e), mass, r=r))


@partial(jax.jit, static_argnames=("r",))
def schur_normal_op(u_e, u_o, psi_e, mass, r: float = 1.0):
    """A_hat = D_hat^dag D_hat — HPD on the even sublattice."""
    return schur_dagger(u_e, u_o, schur_op(u_e, u_o, psi_e, mass, r=r),
                        mass, r=r)


# ---------------------------------------------------------------------------
# Packed-layout operator (real arithmetic, TPU layout)
# ---------------------------------------------------------------------------

def _split_packed_spinor(p: jax.Array):
    """(T,Z,Y,24,X) -> re, im each (T,Z,Y,4,3,X)."""
    t, z, y, s, x = p.shape
    q = p.reshape(t, z, y, NSPIN, NCOL, 2, x)
    return q[..., 0, :], q[..., 1, :]


def _merge_packed_spinor(re: jax.Array, im: jax.Array) -> jax.Array:
    t, z, y, s, c, x = re.shape
    q = jnp.stack([re, im], axis=5)  # (T,Z,Y,4,3,2,X)
    return q.reshape(t, z, y, NSPIN * NCOL * 2, x)


def _split_packed_gauge(up: jax.Array):
    """(4,T,Z,Y,18,X) -> re, im each (4,T,Z,Y,3,3,X)."""
    d, t, z, y, g, x = up.shape
    q = up.reshape(d, t, z, y, NCOL, NCOL, 2, x)
    return q[..., 0, :], q[..., 1, :]


# spinor re/im arrays are (T,Z,Y,spin,color,X): roll axes per direction
_SPINOR_ROLL_AXIS = {0: 0, 1: 1, 2: 2, 3: 5}
# per-mu gauge re/im arrays are (T,Z,Y,row,col,X)
_GAUGE_ROLL_AXIS = {0: 0, 1: 1, 2: 2, 3: 5}


def hop_term_packed(u_mu: jax.Array, psi_nbr: jax.Array, mu: int,
                    forward: bool, r: float = 1.0) -> jax.Array:
    """One hop's contribution ``-1/2 (r ∓ gamma_mu) U psi`` on PRE-ALIGNED
    packed fields (no shifts happen here — callers align neighbours).

    Args:
      u_mu:    (T',Z',Y,18,X) — U_mu at the *output* site (forward hop) or
               at the neighbour site (backward hop; daggered internally).
      psi_nbr: (T',Z',Y,24,X) — psi at the neighbour site.
      forward: True -> (r - gamma) U psi ; False -> (r + gamma) U^dag psi.

    Shared by ``dslash_packed`` (with rolled inputs) and the distributed
    halo fix-ups in :mod:`repro.core.distributed` (with exchanged planes) —
    both the full-lattice ones and the parity-compressed even-odd ones:
    for mu in {t, z, y} a half-field hop keeps the compressed x index, so
    the same plane correction applies verbatim to (T', Z', Y, *, Xh)
    boundary planes with the per-parity link fields swapped in.
    """
    acc = jnp.float32 if psi_nbr.dtype in (jnp.bfloat16, jnp.float16,
                                           jnp.float32) else psi_nbr.dtype
    pm_c, pp_c = _projectors(r)
    P = pm_c[mu] if forward else pp_c[mu]

    t, z, y, s, x = psi_nbr.shape
    q = psi_nbr.reshape(t, z, y, NSPIN, NCOL, 2, x)
    pr, pi = q[..., 0, :], q[..., 1, :]
    g = u_mu.reshape(t, z, y, NCOL, NCOL, 2, x)
    ur, ui = g[..., 0, :], g[..., 1, :]

    sub = "tzyabx,tzysbx->tzysax" if forward else "tzybax,tzysbx->tzysax"
    e = partial(exact_einsum, sub, preferred_element_type=acc)
    if forward:
        hr, hi = e(ur, pr) - e(ui, pi), e(ur, pi) + e(ui, pr)
    else:  # U^dag
        hr, hi = e(ur, pr) + e(ui, pi), e(ur, pi) - e(ui, pr)

    mr = jnp.asarray(np.real(P), dtype=hr.dtype)
    mi = jnp.asarray(np.imag(P), dtype=hr.dtype)
    es = partial(exact_einsum, "sp,tzypcx->tzyscx", preferred_element_type=acc)
    outr, outi = es(mr, hr) - es(mi, hi), es(mr, hi) + es(mi, hr)
    out = jnp.stack([outr, outi], axis=5).reshape(t, z, y, s, x)
    return (-0.5 * out).astype(psi_nbr.dtype)


@partial(jax.jit, static_argnames=("r",))
def dslash_packed(up: jax.Array, pp: jax.Array, mass,
                  r: float = 1.0) -> jax.Array:
    """Dirac-Wilson on the packed real layout.

    Args:
      up: (4, T, Z, Y, 18, X) real gauge field.
      pp: (T, Z, Y, 24, X) real spinor field.
    Returns:
      packed D psi, same shape/dtype as ``pp``.

    All contractions accumulate in f32 (``preferred_element_type``) even
    when inputs are bf16 — narrow storage, wide accumulate, as on the
    FPGA's DSP datapath.
    """
    acc = jnp.float32 if pp.dtype in (jnp.bfloat16, jnp.float16,
                                      jnp.float32) else pp.dtype
    pm_c, pp_c = _projectors(r)

    pr, pi = _split_packed_spinor(pp)
    ur, ui = _split_packed_gauge(up)

    outr = ((mass + 4.0 * r) * pr).astype(acc)
    outi = ((mass + 4.0 * r) * pi).astype(acc)

    def cdot_color(ar, ai, br, bi, dag: bool):
        """(U or U^dag) @ psi over color: a=(...,3,3,X), b=(...,4,3,X)."""
        sub = "tzyabx,tzysbx->tzysax" if not dag else "tzybax,tzysbx->tzysax"
        e = partial(exact_einsum, sub, preferred_element_type=acc)
        if not dag:
            return e(ar, br) - e(ai, bi), e(ar, bi) + e(ai, br)
        return e(ar, br) + e(ai, bi), e(ar, bi) - e(ai, br)

    def spin_mul(mat: np.ndarray, hr, hi):
        """4x4 complex constant acting on the spin axis (3)."""
        mr = jnp.asarray(np.real(mat), dtype=hr.dtype)
        mi = jnp.asarray(np.imag(mat), dtype=hr.dtype)
        e = partial(exact_einsum, "sp,tzypcx->tzyscx", preferred_element_type=acc)
        return e(mr, hr) - e(mi, hi), e(mr, hi) + e(mi, hr)

    for mu in range(NDIRS):
        sax = _SPINOR_ROLL_AXIS[mu]
        gax = _GAUGE_ROLL_AXIS[mu]
        urm, uim = ur[mu], ui[mu]
        # forward
        fr = jnp.roll(pr, -1, axis=sax)
        fi = jnp.roll(pi, -1, axis=sax)
        hr, hi = cdot_color(urm, uim, fr, fi, dag=False)
        hr, hi = spin_mul(pm_c[mu], hr, hi)
        outr = outr - 0.5 * hr
        outi = outi - 0.5 * hi
        # backward
        br = jnp.roll(pr, 1, axis=sax)
        bi = jnp.roll(pi, 1, axis=sax)
        ubr = jnp.roll(urm, 1, axis=gax)
        ubi = jnp.roll(uim, 1, axis=gax)
        hr, hi = cdot_color(ubr, ubi, br, bi, dag=True)
        hr, hi = spin_mul(pp_c[mu], hr, hi)
        outr = outr - 0.5 * hr
        outi = outi - 0.5 * hi

    return _merge_packed_spinor(outr.astype(pp.dtype), outi.astype(pp.dtype))


def apply_gamma5_packed(p: jax.Array) -> jax.Array:
    """gamma5 on a packed field's S axis (-2); leading axes pass through."""
    assert p.shape[-2] == NSPIN * NCOL * 2
    sign = jnp.repeat(jnp.asarray([1.0, 1.0, -1.0, -1.0], dtype=p.dtype),
                      NCOL * 2)
    return p * sign[:, None]


@partial(jax.jit, static_argnames=("r",))
def dslash_dagger_packed(up, pp, mass, r: float = 1.0):
    return apply_gamma5_packed(
        dslash_packed(up, apply_gamma5_packed(pp), mass, r=r))


@partial(jax.jit, static_argnames=("r",))
def normal_op_packed(up, pp, mass, r: float = 1.0):
    """A = D^dag D on the packed layout."""
    return dslash_dagger_packed(up, dslash_packed(up, pp, mass, r=r),
                                mass, r=r)


# FLOPs per lattice site for one dslash application (the standard count
# for r=1 Wilson dslash with spin projection; the paper's §5 GFLOP/s
# figures use the same convention).
DSLASH_FLOPS_PER_SITE = 1320


def dslash_flops(volume: int) -> int:
    return DSLASH_FLOPS_PER_SITE * volume
