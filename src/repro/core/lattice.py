"""4D lattice geometry, SU(3) gauge fields and layout packing.

Two layouts are used throughout the package:

* **natural**  — complex arrays in the index order physicists write:
  ``psi[T, Z, Y, X, spin(4), color(3)]`` and
  ``U[mu(4), T, Z, Y, X, color(3), color(3)]``.  This is the layout of the
  pure-jnp reference operator and of all correctness oracles.

* **packed**   — real arrays blocked for the TPU vector unit:
  ``psi[T, Z, Y, S=24, X]`` with ``S = (spin*3 + color)*2 + reim`` and
  ``U[mu(4), T, Z, Y, G=18, X]`` with ``G = (row*3 + col)*2 + reim``.
  ``X`` innermost maps to the 128-wide lane axis, ``S`` to sublanes.
  This is the FPGA paper's "stream one site per cycle" layout re-thought
  for a (8,128)-register machine: one vector op touches 128 lattice sites.

* **packed half** — the even-odd half fields of the parity kernels:
  ``psi[T, S=24, Z, Y*Xh]`` and ``U[mu(4), T, G=18, Z, Y*Xh]`` (see
  "Packed half fields" below): a parity-compressed row of Xh = X/2 sites
  is too short for the lanes, so (Y, Xh) fill them together.

The packing functions below are exact bijections; tests round-trip them.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NDIRS = 4  # t, z, y, x
NSPIN = 4
NCOL = 3
SPINOR_S = NSPIN * NCOL * 2  # 24 packed real components per site
GAUGE_G = NCOL * NCOL * 2    # 18 packed real components per link


@dataclasses.dataclass(frozen=True)
class LatticeShape:
    """Geometry of the 4D lattice. Axis order is (T, Z, Y, X)."""

    t: int
    z: int
    y: int
    x: int

    @property
    def dims(self) -> tuple[int, int, int, int]:
        return (self.t, self.z, self.y, self.x)

    @property
    def volume(self) -> int:
        return self.t * self.z * self.y * self.x

    def __str__(self) -> str:  # e.g. 8x8x8x16
        return f"{self.t}x{self.z}x{self.y}x{self.x}"


# ---------------------------------------------------------------------------
# Random fields
# ---------------------------------------------------------------------------

def random_spinor(key: jax.Array, lat: LatticeShape,
                  dtype=jnp.complex64) -> jax.Array:
    """Gaussian random spinor field, natural layout (T,Z,Y,X,4,3)."""
    kr, ki = jax.random.split(key)
    shape = lat.dims + (NSPIN, NCOL)
    re = jax.random.normal(kr, shape, dtype=jnp.float32)
    im = jax.random.normal(ki, shape, dtype=jnp.float32)
    return (re + 1j * im).astype(dtype)


def _project_su3(m: jax.Array) -> jax.Array:
    """Project a complex 3x3 matrix onto SU(3) via QR + det normalization."""
    q, r = jnp.linalg.qr(m)
    # Make the decomposition unique (positive diagonal of r) so q is Haar-ish.
    d = jnp.diagonal(r, axis1=-2, axis2=-1)
    q = q * (d / jnp.abs(d))[..., None, :]
    det = jnp.linalg.det(q)
    return q / det[..., None, None] ** (1.0 / 3.0)


def random_gauge(key: jax.Array, lat: LatticeShape,
                 dtype=jnp.complex64) -> jax.Array:
    """Random SU(3) gauge field, natural layout (4,T,Z,Y,X,3,3).

    The field is drawn on the host's CPU backend and then placed on the
    default device: an accelerator pads each 3×3 matrix of the batched QR
    to a (4,128) tile — 57× the field, 41 GB of HBM at 24³×48 on TPU
    v5e.  A seed gives the same field on every platform.
    """
    try:
        host = jax.devices("cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "random_gauge draws on the host's CPU backend; add 'cpu' to "
            "JAX_PLATFORMS (e.g. JAX_PLATFORMS=tpu,cpu)") from e
    with jax.default_device(host):
        kr, ki = jax.random.split(jax.device_put(key, host))
        shape = (NDIRS,) + lat.dims + (NCOL, NCOL)
        re = jax.random.normal(kr, shape, dtype=jnp.float32)
        im = jax.random.normal(ki, shape, dtype=jnp.float32)
        u = _project_su3((re + 1j * im).astype(dtype))
    return jax.device_put(np.asarray(u))


def unit_gauge(lat: LatticeShape, dtype=jnp.complex64) -> jax.Array:
    """Free-field (identity links) gauge configuration."""
    eye = jnp.eye(NCOL, dtype=dtype)
    return jnp.broadcast_to(eye, (NDIRS,) + lat.dims + (NCOL, NCOL))


# ---------------------------------------------------------------------------
# Layout packing (natural complex <-> packed real)
# ---------------------------------------------------------------------------

def pack_spinor(psi: jax.Array, dtype=jnp.float32) -> jax.Array:
    """(..., X, 4, 3) complex -> (..., 24, X) real.

    The canonical site axes are (T, Z, Y, X); any leading axes (e.g. an
    RHS-batch axis in front of T) pass through unchanged.
    """
    re = jnp.real(psi).astype(dtype)
    im = jnp.imag(psi).astype(dtype)
    # (..., X, 4, 3, 2)
    p = jnp.stack([re, im], axis=-1)
    p = p.reshape(psi.shape[:-2] + (SPINOR_S,))
    return jnp.moveaxis(p, -2, -1)  # X to innermost


def unpack_spinor(p: jax.Array, dtype=jnp.complex64) -> jax.Array:
    """(..., 24, X) real -> (..., X, 4, 3) complex (leading axes pass through)."""
    s, x = p.shape[-2:]
    assert s == SPINOR_S
    q = jnp.moveaxis(p, -1, -2).reshape(p.shape[:-2] + (x, NSPIN, NCOL, 2))
    return (q[..., 0] + 1j * q[..., 1]).astype(dtype)


def pack_gauge(u: jax.Array, dtype=jnp.float32) -> jax.Array:
    """(4,T,Z,Y,X,3,3) complex -> (4,T,Z,Y,18,X) real."""
    re = jnp.real(u).astype(dtype)
    im = jnp.imag(u).astype(dtype)
    p = jnp.stack([re, im], axis=-1)  # (4,T,Z,Y,X,3,3,2)
    d, t, z, y, x = u.shape[:5]
    p = p.reshape(d, t, z, y, x, GAUGE_G)
    return jnp.moveaxis(p, 4, 5)


def unpack_gauge(p: jax.Array, dtype=jnp.complex64) -> jax.Array:
    """(4,T,Z,Y,18,X) real -> (4,T,Z,Y,X,3,3) complex."""
    d, t, z, y, g, x = p.shape
    assert g == GAUGE_G
    q = jnp.moveaxis(p, 5, 4).reshape(d, t, z, y, x, NCOL, NCOL, 2)
    return (q[..., 0] + 1j * q[..., 1]).astype(dtype)


# ---------------------------------------------------------------------------
# Even-odd (red-black) parity geometry
# ---------------------------------------------------------------------------
#
# A site (t, z, y, x) has parity (t + z + y + x) mod 2; the Wilson hopping
# term only connects sites of OPPOSITE parity, which is what makes the
# Schur reduction in :mod:`repro.core.wilson` possible.  Half-lattice
# fields compress the X axis by 2: within the row (t, z, y) the sites of a
# given parity sit at x = 2*j + s where the row offset s depends only on
# (t + z + y) mod 2.  Compressed fields keep the natural trailing axes, so
# an even-parity spinor is (T, Z, Y, X//2, 4, 3); ``pack_half_spinor`` /
# ``pack_half_gauge`` give the parity kernels' packed half layout.
#
# The split/merge bijections only require an even X extent (asserted) —
# the compression never crosses rows.  The even-odd OPERATORS in
# repro.core.wilson additionally need even T/Z/Y extents: with periodic
# boundaries an odd extent creates an odd cycle, the lattice graph stops
# being bipartite, and the hopping term no longer changes parity across
# the wrap.


def eo_row_offset(t: int, z: int, y: int) -> np.ndarray:
    """x-offset of EVEN-parity sites in each (t, z, y) row, shape (T,Z,Y).

    Even sites of row (t, z, y) are x = 2*j + s with s = (t+z+y) mod 2;
    odd sites are x = 2*j + (1 - s).  Returned as a NumPy int array so it
    folds to a constant under ``jit``.
    """
    tt, zz, yy = np.meshgrid(np.arange(t), np.arange(z), np.arange(y),
                             indexing="ij")
    return ((tt + zz + yy) % 2).astype(np.int32)


def parity_masks(lat: LatticeShape) -> tuple[np.ndarray, np.ndarray]:
    """(even_mask, odd_mask) boolean site masks of shape (T, Z, Y, X)."""
    tt, zz, yy, xx = np.meshgrid(np.arange(lat.t), np.arange(lat.z),
                                 np.arange(lat.y), np.arange(lat.x),
                                 indexing="ij")
    even = (tt + zz + yy + xx) % 2 == 0
    return even, ~even


def _eo_row_sel(t: int, z: int, y: int, n_rest: int) -> jax.Array:
    """Broadcastable bool: True where the even-site row offset is 0."""
    s = eo_row_offset(t, z, y)
    return jnp.asarray(s == 0).reshape((t, z, y, 1) + (1,) * n_rest)


def split_eo(field: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Split a natural-layout site field into (even, odd) half fields.

    Args:
      field: (T, Z, Y, X, *rest) with even X — e.g. a spinor (T,Z,Y,X,4,3).
    Returns:
      (even, odd), each (T, Z, Y, X//2, *rest).  Compressed index j of the
      even field addresses site x = 2*j + (t+z+y)%2, the odd field the
      complementary offset.  Exact bijection with :func:`merge_eo`.
    """
    t, z, y, x = field.shape[:4]
    assert x % 2 == 0, f"even-odd split needs even X extent, got {x}"
    rest = field.shape[4:]
    pair = field.reshape((t, z, y, x // 2, 2) + rest)
    lo, hi = pair[:, :, :, :, 0], pair[:, :, :, :, 1]  # x = 2j and 2j+1
    sel = _eo_row_sel(t, z, y, len(rest))
    even = jnp.where(sel, lo, hi)
    odd = jnp.where(sel, hi, lo)
    return even, odd


def merge_eo(even: jax.Array, odd: jax.Array) -> jax.Array:
    """Inverse of :func:`split_eo`: (T,Z,Y,X//2,*rest) pair -> (T,Z,Y,X,*rest)."""
    t, z, y, xh = even.shape[:4]
    rest = even.shape[4:]
    sel = _eo_row_sel(t, z, y, len(rest))
    lo = jnp.where(sel, even, odd)
    hi = jnp.where(sel, odd, even)
    pair = jnp.stack([lo, hi], axis=4)
    return pair.reshape((t, z, y, 2 * xh) + rest)


def split_eo_gauge(u: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Split a (4, T, Z, Y, X, 3, 3) gauge field into per-parity link fields.

    Returns (u_e, u_o), each (4, T, Z, Y, X//2, 3, 3): ``u_e[mu]`` holds the
    links U_mu(x) attached to EVEN sites x (compressed as in
    :func:`split_eo`), ``u_o[mu]`` those attached to odd sites.
    """
    return jax.vmap(split_eo)(u)


def merge_eo_gauge(u_e: jax.Array, u_o: jax.Array) -> jax.Array:
    """Inverse of :func:`split_eo_gauge`."""
    return jax.vmap(merge_eo)(u_e, u_o)


# ---------------------------------------------------------------------------
# Packed half fields (the parity kernels' layout)
# ---------------------------------------------------------------------------
#
# A parity-compressed row holds only X/2 sites, far fewer than the 128
# lanes of a vector register, so the half fields put the component axis
# OUTSIDE the site axes and flatten (Y, Xh) y-major into the lane axis:
#
#   psi_half[T, S=24, Z, Y*Xh]       U_half[mu(4), T, G=18, Z, Y*Xh]
#
# with S and G packed as in ``pack_spinor``/``pack_gauge``.  Each
# component is one dense (Z, Y*Xh) slab: Z on sublanes, lane l addressing
# y = l // Xh, j = l % Xh.  Leading axes (an RHS batch) pass through.  The
# compressed extent Xh cannot be read back from the flat lane axis, so
# unpacking (and every parity kernel) takes it explicitly; X is never
# sharded, so Xh is the same on every shard.

HALF_S_AXIS = -3  # the component axis of a packed half field


def _pack_half(f: jax.Array, dtype) -> jax.Array:
    """(..., T, Z, Y, Xh, a, b) complex -> (..., T, a*b*2, Z, Y*Xh) real."""
    p = jnp.stack([jnp.real(f).astype(dtype), jnp.imag(f).astype(dtype)],
                  axis=-1)
    *lead, t, z, y, xh = f.shape[:-2]
    p = p.reshape(*lead, t, z, y * xh, -1)
    return jnp.moveaxis(p, -1, HALF_S_AXIS)


def _unpack_half(p: jax.Array, xh: int, inner: tuple[int, int],
                 dtype) -> jax.Array:
    """Inverse of :func:`_pack_half` for ``inner`` = (a, b)."""
    *lead, t, _, z, lanes = p.shape
    assert lanes % xh == 0, f"lane extent {lanes} is not a multiple of {xh}"
    q = jnp.moveaxis(p, HALF_S_AXIS, -1)
    q = q.reshape(*lead, t, z, lanes // xh, xh, *inner, 2)
    return (q[..., 0] + 1j * q[..., 1]).astype(dtype)


def half_rows(p: jax.Array, xh: int) -> jax.Array:
    """Packed half field (..., T, C, Z, Y*Xh) -> its (..., T, Z, Y, C, Xh)
    row view (the site axes in front of the components, Xh innermost):
    what plane-wise code outside the kernels computes on."""
    *lead, t, c, z, lanes = p.shape
    q = p.reshape(*lead, t, c, z, lanes // xh, xh)
    return jnp.moveaxis(q, -4, -2)


def half_lanes(q: jax.Array) -> jax.Array:
    """Inverse of :func:`half_rows`."""
    *lead, t, z, y, c, xh = q.shape
    return jnp.moveaxis(q, -2, -4).reshape(*lead, t, c, z, y * xh)


def pack_half_spinor(psi: jax.Array, dtype=jnp.float32) -> jax.Array:
    """(..., T, Z, Y, Xh, 4, 3) complex half field -> (..., T, 24, Z, Y*Xh)."""
    return _pack_half(psi, dtype)


def unpack_half_spinor(p: jax.Array, xh: int,
                       dtype=jnp.complex64) -> jax.Array:
    """(..., T, 24, Z, Y*Xh) real -> (..., T, Z, Y, Xh, 4, 3) complex."""
    assert p.shape[HALF_S_AXIS] == SPINOR_S
    return _unpack_half(p, xh, (NSPIN, NCOL), dtype)


def pack_half_gauge(u: jax.Array, dtype=jnp.float32) -> jax.Array:
    """(4, T, Z, Y, Xh, 3, 3) per-parity links -> (4, T, 18, Z, Y*Xh)."""
    return _pack_half(u, dtype)


def unpack_half_gauge(p: jax.Array, xh: int,
                      dtype=jnp.complex64) -> jax.Array:
    """(4, T, 18, Z, Y*Xh) real -> (4, T, Z, Y, Xh, 3, 3) complex."""
    assert p.shape[HALF_S_AXIS] == GAUGE_G
    return _unpack_half(p, xh, (NCOL, NCOL), dtype)


# ---------------------------------------------------------------------------
# Complex <-> real-pair views (for low-precision storage of complex fields)
# ---------------------------------------------------------------------------

def complex_to_real_pair(v: jax.Array, dtype=jnp.float32) -> jax.Array:
    """(..., ) complex -> (..., 2) real, castable to bf16 for narrow storage."""
    return jnp.stack([jnp.real(v), jnp.imag(v)], axis=-1).astype(dtype)


def real_pair_to_complex(w: jax.Array, dtype=jnp.complex64) -> jax.Array:
    """Inverse of :func:`complex_to_real_pair` (widens before recombining)."""
    wf = w.astype(jnp.float32 if dtype == jnp.complex64 else jnp.float64)
    return (wf[..., 0] + 1j * wf[..., 1]).astype(dtype)


# ---------------------------------------------------------------------------
# Contractions at full precision
# ---------------------------------------------------------------------------

# A TPU contracts f32 operands in a single bf16 pass unless told otherwise,
# which leaves ~1e-3 relative error in every SU(3)/spin product — far above
# a 1e-6 solver tolerance.  Every jnp contraction on lattice fields asks for
# HIGHEST; on the CPU the result is the same bits either way.
exact_einsum = partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
exact_matmul = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# Inner products on fields (any layout — they are just arrays)
# ---------------------------------------------------------------------------

def field_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """<a, b> with complex conjugation if complex; f32/f64 accumulation."""
    if jnp.iscomplexobj(a):
        acc = jnp.complex128 if a.dtype == jnp.complex128 else jnp.complex64
        return jnp.sum(jnp.conj(a) * b, dtype=acc)
    acc = jnp.float64 if a.dtype == jnp.float64 else jnp.float32
    return jnp.sum(a.astype(acc) * b.astype(acc))


def field_norm2(a: jax.Array) -> jax.Array:
    if jnp.iscomplexobj(a):
        acc = jnp.float64 if a.dtype == jnp.complex128 else jnp.float32
        return jnp.sum((jnp.real(a) ** 2 + jnp.imag(a) ** 2).astype(acc))
    acc = jnp.float64 if a.dtype == jnp.float64 else jnp.float32
    return jnp.sum(a.astype(acc) ** 2)


# Batched (multi-RHS) reductions: leading axis is the RHS batch, each RHS
# reduced independently to a per-RHS scalar.  Implemented as vmaps of the
# single-RHS reductions so a batched solve accumulates each slice in the
# SAME order as N independent solves — the batched-vs-looped equivalence
# tests rely on this being bitwise.

def field_dot_batched(a: jax.Array, b: jax.Array) -> jax.Array:
    """Per-RHS <a_n, b_n> over all non-batch axes; returns shape (N,)."""
    return jax.vmap(field_dot)(a, b)


def field_norm2_batched(a: jax.Array) -> jax.Array:
    """Per-RHS ||a_n||^2 over all non-batch axes; returns shape (N,)."""
    return jax.vmap(field_norm2)(a)
