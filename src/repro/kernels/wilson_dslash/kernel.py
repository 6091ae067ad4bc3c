"""Pallas TPU kernels for the Dirac-Wilson stencil (packed layouts).

This is the TPU re-think of the paper's FPGA compute kernel (Fig. 1) and
cyclic-buffer transport (its Ref. [11]):

* **grid = (T, Z/BZ[, Y/BY][, N])** (full lattice; the parity kernels
  stream whole t-planes, grid (T[, N])) — the kernel streams (t, z-block)
  lattice *planes* (optionally further tiled along Y, optionally with the
  RHS batch as the trailing grid axis); Pallas's software pipeline
  double-buffers the next planes' HBM->VMEM DMA behind the current
  plane's compute — the cyclic-buffer / II=1 analogue.
* **neighbour planes as extra BlockSpecs** — ψ(t±1), ψ(z-block boundary),
  ψ(y-block boundary when Y is tiled) and the backward links U_t(t-1),
  U_z(z-1), U_y(y-1) arrive through their own index-maps (periodic wrap
  via modular index arithmetic), so the kernel body never touches HBM
  addresses — exactly the paper's separation of "transport mechanism"
  from "stencil evaluation".
* **Y/X hops stay inside the block** — when the block spans full Y those
  neighbours are register/VMEM rolls (X is the 128-lane axis); a tiled Y
  switches to the same boundary-splice scheme as Z, bitwise identically.
* **spin-projection trick** — each hop projects 4-spinors to 2 half
  spinors before the SU(3) multiply (stage 2 of the paper's Fig. 1
  pipeline), halving the matvec work: 8 hops × 2 matvecs = the standard
  1320 flop/site dslash.
* **γ5 folding** — ``gamma5_in``/``gamma5_out`` fold γ5 = diag(+,+,-,-)
  into the trace-time projection/reconstruction tables (a sign flip on
  constant coefficients), so D†ψ = γ5 D γ5 ψ and the CGNR normal operator
  cost ZERO extra full-field HBM passes versus plain D.

**Launch space (DESIGN.md §13).**  Tile parameters of the full-lattice
kernel — z-block ``bz``,
y-block ``by``, RHS-batch placement ``batch`` ("block" keeps the whole
batch inside every block; "grid" makes it the trailing, fastest-varying
grid axis so consecutive steps revisit one gauge block), and gauge
streaming mode ``stream`` ("blockspec" = the implicit Pallas pipeline;
"db" = explicit double-buffering of the center gauge planes through a
2-slot VMEM scratch with async copies, so the next (t, z-block) plane's
DMA overlaps the current plane's compute) — are all **bitwise-neutral**:
they change HBM->VMEM data movement only, never the per-site FMA order.
When none is given explicitly the wrappers consult the autotuner's
checked-in ``kernels/tuning_cache.json`` (:func:`repro.kernels.dispatch.
pick_tile`).  On a cold or disabled cache a launch takes the largest
tile that fits the scoped VMEM budget, starting from the historical
default (bz ≤ 4, full Y), which stands wherever it fits.  The parity
kernels have one knob, ``bz``, the Z rows of one body chunk, and consult
no cache; their RHS batch always rides the grid.

**Lowerings.**  ``interpret=None`` interprets on CPU and compiles
(Mosaic) on GPU/TPU; ``interpret=False`` on CPU routes to the
compiled-XLA half-spinor implementation in
:mod:`repro.kernels.wilson_dslash.xla` — ``pallas_call`` cannot compile
on the CPU backend, and the XLA path is this host's honest compiled
number (see :func:`repro.kernels.dispatch.resolve_lowering`).

Two kernel families share the per-site machinery (``_hop``, the tables):

* ``dslash_pallas``      — the full-lattice operator (mass term + 8 hops)
  on the (T, Z, Y, 24, X) packed layout, with the launch space above;
* ``dslash_eo_pallas`` / ``dslash_oe_pallas`` — the even-odd parity hop
  blocks D_eo / D_oe on packed HALF fields (T, 24, Z, Y*Xh) (see
  :mod:`repro.core.lattice`): the component axis sits outside the site
  axes and (Y, Xh) fill the lanes, so every component is a dense
  (Z, Y*Xh) slab.  A grid step is one t-plane; Z hops are sublane rolls,
  Y hops lane rolls by Xh, and the parity-compressed X hop is a lane roll
  by 1 under two selects (the row's output-site offset, and the wrap at
  the row ends).  The parity kernels also take an optional accumulator
  operand (``psi_acc``/``acc_coeff``/``hop_coeff``) so the Schur
  complement m·ψ - D_eo D_oe ψ / m is TWO kernel launches with the axpy
  folded into the second epilogue — no separate full-field scale/add
  passes.

Both families are **multi-RHS batched**: a spinor field may carry a leading
RHS-batch axis.  The gauge BlockSpecs never carry it: with the whole batch
in each spinor block (batch block index pinned to 0), or with the batch on
the innermost grid axis (always so in the parity kernels, where
consecutive steps ask for the same gauge blocks), one HBM fetch of a gauge plane
(8 links × 18 reals = 144 reals/site) feeds all N spinor planes (24 in +
24 out reals/site each), shrinking per-RHS traffic from 144+48 to
144/N+48 reals/site: an up-to (144+48)/48 ≈ 4x arithmetic-intensity gain
before the compute roof (see DESIGN.md §6).  The kernel bodies broadcast
over the batch, so batching adds ZERO trace-time unrolling — compile time
is independent of N.

The kernels compute in f32 registers regardless of the (bf16/f32) storage
dtype — narrow storage, wide accumulate, like the FPGA DSP datapath.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lattice import GAUGE_G, NCOL, NDIRS, NSPIN, SPINOR_S
from repro.core.wilson import _projectors
from repro.kernels.dispatch import (DEFAULT_TILE, pick_tile,
                                    resolve_lowering)

# ---------------------------------------------------------------------------
# Trace-time tables for the spin-projection trick.
#
# For r=1 every hop matrix P = (1 ∓ γ_mu) has rank 2: rows 2,3 are a complex
# phase times row 0 or 1.  We precompute, per (mu, sign):
#   proj[alpha in {0,1}]   -> list of (beta, coeff) with coeff = P[alpha,beta]
#   recon[alpha in {2,3}]  -> (src_halfspinor_row, phase)
# ---------------------------------------------------------------------------


def _halfspinor_tables():
    pm, pp = _projectors(1.0)
    tables = {}
    for mu in range(NDIRS):
        for sign, P in (("fwd", pm[mu]), ("bwd", pp[mu])):
            proj = []
            for a in range(2):
                terms = [(b, complex(P[a, b])) for b in range(NSPIN)
                         if abs(P[a, b]) > 1e-12]
                proj.append(terms)
            recon = []
            for a in (2, 3):
                row = P[a]
                hit = None
                for src in range(2):
                    ref = P[src]
                    nz = np.nonzero(np.abs(ref) > 1e-12)[0]
                    if np.all((np.abs(row) > 1e-12) == (np.abs(ref) > 1e-12)):
                        phase = row[nz[0]] / ref[nz[0]]
                        if np.allclose(row, phase * ref, atol=1e-12):
                            hit = (src, complex(phase))
                            break
                if hit is None:  # zero row (can happen only for r != 1)
                    raise ValueError("projector is not rank-2; need r=1")
                recon.append(hit)
            tables[(mu, sign)] = (proj, recon)
    return tables


_TABLES = _halfspinor_tables()


def _cmul_phase(gr, gi, phase: complex):
    """(gr + i gi) * phase with trace-time constant folding."""
    cr, ci = phase.real, phase.imag
    outr = 0.0
    outi = 0.0
    if cr != 0.0:
        outr = cr * gr
        outi = cr * gi
    if ci != 0.0:
        outr = outr - ci * gi if cr != 0.0 else -ci * gi
        outi = outi + ci * gr if cr != 0.0 else ci * gr
    return outr, outi


def _hop(out_r, out_i, psi_r, psi_i, u_r, u_i, mu: int, sign: str,
         g5in: bool = False, g5out: bool = False):
    """Accumulate -1/2 * G5out P (U G5in psi) for one hop into out_{r,i}.

    psi_{r,i}: [spin][color] -> (..., X) arrays  (the neighbour spinor)
    u_{r,i}:   [row][col]    -> (..., X) arrays  (U or, for 'bwd', U^dag is
               realized by index transposition + conjugation here)

    γ5 = diag(+,+,-,-) folds into the constant tables: ``g5in`` negates the
    projection coefficients of source spins 2,3 (P -> P γ5), ``g5out``
    negates the reconstruction phases of output spins 2,3 (P -> γ5 P) —
    both are trace-time sign flips, zero runtime cost.
    """
    proj, recon = _TABLES[(mu, sign)]
    if g5in:  # P γ5: columns 2,3 change sign
        proj = [[(b, -coeff if b >= 2 else coeff) for (b, coeff) in terms]
                for terms in proj]
    if g5out:  # γ5 P: rows 2,3 change sign (rows 0,1 untouched)
        recon = [(src, -phase) for (src, phase) in recon]
    dag = sign == "bwd"
    # stage 2a: project to half spinors  h[alpha][c]
    h_r = [[None] * NCOL for _ in range(2)]
    h_i = [[None] * NCOL for _ in range(2)]
    for a in range(2):
        for c in range(NCOL):
            accr, acci = 0.0, 0.0
            for (b, coeff) in proj[a]:
                tr, ti = _cmul_phase(psi_r[b][c], psi_i[b][c], coeff)
                accr = accr + tr
                acci = acci + ti
            h_r[a][c] = accr
            h_i[a][c] = acci
    # stage 2b: SU(3) multiply g[alpha] = U h[alpha]  (or U^dag h)
    g_r = [[None] * NCOL for _ in range(2)]
    g_i = [[None] * NCOL for _ in range(2)]
    for a in range(2):
        for row in range(NCOL):
            accr, acci = 0.0, 0.0
            for col in range(NCOL):
                if not dag:
                    ur, ui = u_r[row][col], u_i[row][col]
                else:  # (U^dag)[row,col] = conj(U[col,row])
                    ur, ui = u_r[col][row], -u_i[col][row]
                hr, hi = h_r[a][col], h_i[a][col]
                accr = accr + ur * hr - ui * hi
                acci = acci + ur * hi + ui * hr
            g_r[a][row] = accr
            g_i[a][row] = acci
    # stage 3: reconstruct 4-spinor rows and accumulate with -1/2
    for c in range(NCOL):
        for a in range(2):
            out_r[a][c] = out_r[a][c] - 0.5 * g_r[a][c]
            out_i[a][c] = out_i[a][c] - 0.5 * g_i[a][c]
        for idx, a in enumerate((2, 3)):
            src, phase = recon[idx]
            pr, pi = _cmul_phase(g_r[src][c], g_i[src][c], phase)
            out_r[a][c] = out_r[a][c] - 0.5 * pr
            out_i[a][c] = out_i[a][c] - 0.5 * pi


def _split_spinor_block(blk):
    """(..., Y, S=24, X) -> [spin][color] re/im lists of (..., Y, X) f32.

    Per-element axis order is (..., BZ, Y, X) — leading axes (e.g. the
    RHS-batch axis of the batched kernels) pass through unchanged.
    """
    x = blk.shape[-1]
    q = blk.reshape(blk.shape[:-2] + (NSPIN, NCOL, 2, x)).astype(jnp.float32)
    re = [[q[..., s_, c_, 0, :] for c_ in range(NCOL)] for s_ in range(NSPIN)]
    im = [[q[..., s_, c_, 1, :] for c_ in range(NCOL)] for s_ in range(NSPIN)]
    return re, im


def _split_gauge_block(blk):
    """(..., Y, G=18, X) -> [row][col] re/im lists of (..., Y, X) f32."""
    x = blk.shape[-1]
    q = blk.reshape(blk.shape[:-2] + (NCOL, NCOL, 2, x)).astype(jnp.float32)
    re = [[q[..., a, b, 0, :] for b in range(NCOL)] for a in range(NCOL)]
    im = [[q[..., a, b, 1, :] for b in range(NCOL)] for a in range(NCOL)]
    return re, im


def _repack_spinor_block(out_r, out_i, dtype):
    """[spin][color] re/im lists of (..., Y, X) -> (..., Y, 24, X)."""
    flat = []
    for s in range(NSPIN):
        for c in range(NCOL):
            flat.append(out_r[s][c])
            flat.append(out_i[s][c])
    return jnp.stack(flat, axis=-2).astype(dtype)


# Within a block element (..., BZ, BY, X): Y rolls on axis -2, X (lane) rolls
# on axis -1, the z-shift splices along axis -3 — negative so the same
# kernel body serves the plain blocks and the batched (NB leading) blocks.
_Y_AXIS, _X_AXIS, _Z_AXIS = -2, -1, -3


def _roll_sc(lists, shift, axis):
    return [[jnp.roll(e, shift, axis=axis) for e in row] for row in lists]


def _shift(lists, boundary, forward: bool, axis: int):
    """Shift [..][..] lists of (..., BZ, BY, X) along ``axis``, splicing
    the boundary plane (extent 1 on that axis) in at the open end.

    Bitwise-equivalent to ``jnp.roll`` when the block spans the full
    extent — the Y-tiled launch switches rolls to shifts without changing
    any per-site value or FMA order.  A block of extent 1 shifts to the
    boundary plane itself (Mosaic has no zero-width vector for the empty
    body slice).
    """
    out = []
    for r, row in enumerate(lists):
        orow = []
        for c, e in enumerate(row):
            b = boundary[r][c]
            n = e.shape[axis]
            if n == 1:     # the whole block is the plane that leaves
                orow.append(b)
            elif forward:  # value at +1: drop plane 0, append boundary
                body = jax.lax.slice_in_dim(e, 1, n, axis=axis)
                orow.append(jnp.concatenate([body, b], axis=axis))
            else:        # value at -1: prepend boundary, drop last
                body = jax.lax.slice_in_dim(e, 0, n - 1, axis=axis)
                orow.append(jnp.concatenate([b, body], axis=axis))
        out.append(orow)
    return out


# ---------------------------------------------------------------------------
# Shared plane-streaming BlockSpecs (full-lattice AND parity kernels)
# ---------------------------------------------------------------------------


def _divisors(n: int) -> list[int]:
    return [c for c in range(1, n + 1) if n % c == 0]


def _pick_bz(z: int, bz: int | None) -> int:
    """Validate/default the z-block size. Default: largest divisor ≤ 4."""
    if bz is None:
        return max(c for c in (1, 2, 3, 4) if z % c == 0)
    bz = int(bz)
    if bz < 1 or z % bz != 0:
        raise ValueError(
            f"bz={bz} does not tile the Z extent {z}: the z-block size "
            f"must be a positive divisor of Z; legal bz values for Z={z}: "
            f"{_divisors(z)}")
    return bz


def _pick_by(y: int, by: int | None) -> int:
    """Validate/default the y-block size. Default: the full Y extent."""
    if by is None:
        return y
    by = int(by)
    if by < 1 or y % by != 0:
        raise ValueError(
            f"by={by} does not tile the Y extent {y}: the y-block size "
            f"must be a positive divisor of Y; legal by values for Y={y}: "
            f"{_divisors(y)}")
    return by


def _site_spec(zblk: int, yblk: int, s: int, x: int, tmap, zmap, ymap,
               nb: int | None, grid_batch: bool, y_tiled: bool):
    """BlockSpec for one (t, z-block[, y-block]) plane of a site field.

    ``nb`` is the RHS-batch extent: None produces the plain 5D layout;
    with a batch the placement decides the block shape — "block"
    (``grid_batch=False``) prepends a FULL batch axis whose block index
    is pinned to 0 (every grid step sees all N spinor planes while the
    gauge specs deliver each link plane exactly once: the
    gauge-amortization contract), "grid" (``grid_batch=True``) prepends a
    size-1 batch axis indexed by the TRAILING grid dimension, so
    consecutive grid steps revisit the same gauge block with an N-times
    smaller spinor working set.
    """
    def site_idx(ids):
        ti, zi = ids[0], ids[1]
        yi = ids[2] if y_tiled else 0
        return (tmap(ti), zmap(zi), ymap(yi), 0, 0)
    if nb is None:
        return pl.BlockSpec((1, zblk, yblk, s, x),
                            lambda *ids: site_idx(ids))
    if grid_batch:
        return pl.BlockSpec((1, 1, zblk, yblk, s, x),
                            lambda *ids: (ids[-1],) + site_idx(ids))
    return pl.BlockSpec((nb, 1, zblk, yblk, s, x),
                        lambda *ids: (0,) + site_idx(ids))


def _spinor_specs(t: int, z: int, bz: int, y: int, by: int, x: int,
                  nb: int | None = None, grid_batch: bool = False):
    """center, t±1, z-boundary (and, when Y is tiled, y-boundary) specs.

    Returns a list of 5 specs (full-Y blocks) or 7 (Y-tiled: +ym, +yp).
    """
    s = SPINOR_S
    y_tiled = by < y
    idf = lambda i: i
    mk = functools.partial(_site_spec, nb=nb, grid_batch=grid_batch,
                           y_tiled=y_tiled)
    c = mk(bz, by, s, x, idf, idf, idf)
    tm = mk(bz, by, s, x, lambda ti: (ti - 1 + t) % t, idf, idf)
    tp = mk(bz, by, s, x, lambda ti: (ti + 1) % t, idf, idf)
    # single boundary planes (block size 1 -> block index = plane idx)
    zm = mk(1, by, s, x, idf, lambda zi: (zi * bz - 1 + z) % z, idf)
    zp = mk(1, by, s, x, idf, lambda zi: (zi * bz + bz) % z, idf)
    specs = [c, tm, tp, zm, zp]
    if y_tiled:
        ym = mk(bz, 1, s, x, idf, idf, lambda yi: (yi * by - 1 + y) % y)
        yp = mk(bz, 1, s, x, idf, idf, lambda yi: (yi * by + by) % y)
        specs += [ym, yp]
    return specs


def _gauge_specs(t: int, z: int, bz: int, y: int, by: int, x: int,
                 grid_batch: bool = False):
    """center (all 4 dirs), U_t(t-1), the U_z(z-1) boundary plane and,
    when Y is tiled, the U_y(y-1) boundary plane.

    Gauge fields never carry a batch axis; with the batch on the grid the
    index maps simply ignore the trailing grid id — consecutive steps
    then ask for the SAME gauge block, which the pipeline need not
    refetch.
    """
    g = GAUGE_G
    y_tiled = by < y

    def gmap(dmap, tfn, zfn, yfn):
        def imap(*ids):
            ti, zi = ids[0], ids[1]
            yi = ids[2] if y_tiled else 0
            return (dmap, tfn(ti), zfn(zi), yfn(yi), 0, 0)
        return imap

    idf = lambda i: i
    c = pl.BlockSpec((NDIRS, 1, bz, by, g, x), gmap(0, idf, idf, idf))
    tm = pl.BlockSpec((1, 1, bz, by, g, x),
                      gmap(0, lambda ti: (ti - 1 + t) % t, idf, idf))
    zm = pl.BlockSpec((1, 1, 1, by, g, x),
                      gmap(1, idf, lambda zi: (zi * bz - 1 + z) % z, idf))
    specs = [c, tm, zm]
    if y_tiled:
        ym = pl.BlockSpec((1, 1, bz, 1, g, x),
                          gmap(2, idf, idf, lambda yi: (yi * by - 1 + y) % y))
        specs.append(ym)
    return specs


# Scoped VMEM of one full-lattice launch.  Mosaic's default scoped limit
# is 16 MiB on v5e.  The Pallas pipeline double-buffers every block, and
# a VMEM tile pads a block's last two dims to (8·4/itemsize, 128).  The
# unrolled kernel body keeps about 1,200 f32 (…, X) vectors live per
# (z, y) row of the block, each padded to 128 lanes (Mosaic's
# scoped-VMEM reports at 24³×48); _BODY_ROWS adds a margin to that.
SCOPED_VMEM_BYTES = 16 * 2**20
VMEM_BUDGET_BYTES = SCOPED_VMEM_BYTES * 7 // 8
_BODY_ROWS = 1400


def _padded_bytes(shape: tuple[int, ...], dtype) -> int:
    """VMEM bytes of one block: the last two dims padded to the tiling."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // item
    *lead, rows, lanes = shape
    return (int(np.prod(lead, dtype=np.int64)) * (-(-rows // sub) * sub)
            * (-(-lanes // 128) * 128) * item)


def launch_vmem_bytes(bz: int, by: int, y: int, x: int, nbb: int, dtype,
                      gauge_dtype) -> int:
    """Scoped VMEM bytes of one full-lattice launch: its double-buffered
    blocks plus the kernel body's f32 working set.

    ``nbb`` RHS ride in every spinor block ("block" placement; 1 for
    "grid" or no batch).  Spinor blocks: center, t±1 and the output.
    """
    sp = lambda zb, yb: _padded_bytes((nbb, zb, yb, SPINOR_S, x), dtype)
    ga = lambda d, zb, yb: _padded_bytes((d, zb, yb, GAUGE_G, x),
                                         gauge_dtype)
    total = (4 * sp(bz, by) + 2 * sp(1, by) + ga(NDIRS, bz, by)
             + ga(1, bz, by) + ga(1, 1, by))
    if by < y:
        total += 2 * sp(bz, 1) + ga(1, bz, 1)
    lane_row = 4 * (-(-x // 128) * 128)   # one f32 (…, X) row in VMEM
    return 2 * total + _BODY_ROWS * nbb * bz * by * lane_row


def _fit_tile(z: int, y: int, x: int, nb: int | None, dtype, gauge_dtype):
    """The default tile: the largest block that fits the VMEM budget.

    Candidates run from the historical default (bz = largest divisor of
    Z ≤ 4, full Y) down through smaller bz, then smaller by, first with
    the whole RHS batch in every block and then with the batch on the
    grid.  The historical default stands wherever it fits.
    """
    bz0 = _pick_bz(z, None)
    bzs = [c for c in reversed(_divisors(z)) if c <= bz0]
    placements = ("block", "grid") if nb is not None else ("block",)
    for batch in placements:
        nbb = nb if (nb is not None and batch == "block") else 1
        for by in reversed(_divisors(y)):
            for bz in bzs:
                if launch_vmem_bytes(bz, by, y, x, nbb, dtype,
                                     gauge_dtype) <= VMEM_BUDGET_BYTES:
                    return bz, by, batch
    need = launch_vmem_bytes(1, 1, y, x, 1, dtype, gauge_dtype)
    raise ValueError(
        f"no dslash tile fits VMEM for a (Z, Y, X) = {(z, y, x)} plane of "
        f"{jnp.dtype(dtype).name} spinors (nrhs={nb or 1}): the smallest "
        f"tile bz=1, by=1 needs {need} bytes against a budget of "
        f"{VMEM_BUDGET_BYTES}")


def _resolve_tile(bz, by, batch, stream, t, z, y, x, nb, dtype, *,
                  gauge_dtype):
    """Explicit args > tuning cache > defaults.

    Any explicitly-passed knob disables the cache for the whole launch
    (tests and the autotuner stay deterministic); all-None consults
    :func:`repro.kernels.dispatch.pick_tile`, whose miss path takes the
    largest tile that fits VMEM (:func:`_fit_tile`).
    """
    if bz is None and by is None and batch is None and stream is None:
        tile = pick_tile((t, z, y, x), nb or 1, dtype)
        if tile == DEFAULT_TILE:
            bz, by, batch = _fit_tile(z, y, x, nb, dtype, gauge_dtype)
        else:
            bz, by, batch, stream = tile.bz, tile.by, tile.batch, tile.stream
    batch = batch or "block"
    stream = stream or "blockspec"
    bz = _pick_bz(z, bz)
    by = _pick_by(y, by)
    y_tiled = by < y
    # an unbatched field has no batch axis to place — "grid" degenerates
    # to the plain layout
    grid_batch = batch == "grid" and nb is not None
    if stream == "db" and (y_tiled or grid_batch):
        raise ValueError(
            "gauge stream 'db' double-buffers whole (t, z-block) gauge "
            "planes and supports only the untiled-Y, batch='block' "
            f"layout; got by={by} (Y={y}), batch={batch!r}")
    return bz, by, batch, stream, y_tiled, grid_batch


def _launch_grid(t, z, bz, y, by, nb, y_tiled, grid_batch):
    grid = (t, z // bz)
    if y_tiled:
        grid += (y // by,)
    if grid_batch:
        grid += (nb,)
    return grid


# ---------------------------------------------------------------------------
# Double-buffered gauge streaming (stream="db")
#
# The center gauge operand (4 dirs × 18 reals = 144 reals/site — the
# dominant stream; boundary fix-up planes stay on the implicit pipeline)
# lives in ANY memory and is copied (t, z-block)-plane by plane into a
# 2-slot VMEM scratch: at grid step i the kernel STARTS the DMA for step
# i+1 into slot (i+1)%2, then WAITS on slot i%2 and computes from it —
# the copy of the next plane overlaps the current plane's compute.  All
# grid/program ids are hoisted OUT of the pl.when closures (a program_id
# primitive inside a cond branch cannot lower on the interpret path).
# ---------------------------------------------------------------------------


def _db_gauge_plane(u_any, u_vmem, sem, bz: int):
    """Prefetch-next / wait-current on one gauge stream; returns the
    current step's (NDIRS, bz, Y, G, X) VMEM plane."""
    ti, zi = pl.program_id(0), pl.program_id(1)
    nzb = pl.num_programs(1)
    total = pl.num_programs(0) * nzb
    step = ti * nzb + zi
    slot = jax.lax.rem(step, 2)
    nxt = step + 1
    nslot = jax.lax.rem(nxt, 2)
    ti_n, zi_n = nxt // nzb, jax.lax.rem(nxt, nzb)

    def start(s, t_, z_):
        pltpu.make_async_copy(
            u_any.at[:, t_, pl.ds(z_ * bz, bz)],
            u_vmem.at[s], sem.at[s]).start()

    @pl.when(step == 0)
    def _prologue():
        start(slot, ti, zi)

    @pl.when(nxt < total)
    def _prefetch():
        start(nslot, ti_n, zi_n)

    pltpu.make_async_copy(
        u_any.at[:, ti, pl.ds(zi * bz, bz)],
        u_vmem.at[slot], sem.at[slot]).wait()
    return u_vmem[slot]


def _db_scratch(bz: int, y: int, x: int, dtype, streams: int):
    """Scratch shapes for ``streams`` double-buffered gauge streams."""
    shapes = []
    for _ in range(streams):
        shapes.append(pltpu.VMEM((2, NDIRS, bz, y, GAUGE_G, x), dtype))
    for _ in range(streams):
        shapes.append(pltpu.SemaphoreType.DMA((2,)))
    return shapes


# ---------------------------------------------------------------------------
# Full-lattice kernel
# ---------------------------------------------------------------------------


def _take_plane(ref, batched: bool):
    """Drop the size-1 T-block axis: axis 0 plain, axis 1 when an RHS-batch
    axis leads the block."""
    return ref[:, 0] if batched else ref[0]


def _dslash_kernel(*refs, mass: float, twist: float = 0.0, g5in: bool,
                   g5out: bool, batched: bool = False, y_tiled: bool = False,
                   stream_db: bool = False, bz_sz: int = 0):
    f32 = jnp.float32
    psi_ym = psi_yp = u_ym = None
    if stream_db:
        (psi_c, psi_tm, psi_tp, psi_zm, psi_zp, u_any, u_tm, u_zm,
         out_ref, u_vmem, sem) = refs
    elif y_tiled:
        (psi_c, psi_tm, psi_tp, psi_zm, psi_zp, psi_ym, psi_yp,
         u_c, u_tm, u_zm, u_ym, out_ref) = refs
    else:
        (psi_c, psi_tm, psi_tp, psi_zm, psi_zp,
         u_c, u_tm, u_zm, out_ref) = refs

    # ---- stage 1: load & unpack (all data now in VMEM) ----
    pc_r, pc_i = _split_spinor_block(_take_plane(psi_c, batched))
    ptm_r, ptm_i = _split_spinor_block(_take_plane(psi_tm, batched))
    ptp_r, ptp_i = _split_spinor_block(_take_plane(psi_tp, batched))
    pzm_r, pzm_i = _split_spinor_block(_take_plane(psi_zm, batched))
    pzp_r, pzp_i = _split_spinor_block(_take_plane(psi_zp, batched))
    if stream_db:
        uv = _db_gauge_plane(u_any, u_vmem, sem, bz_sz)
        u = [_split_gauge_block(uv[mu]) for mu in range(NDIRS)]
    else:
        u = [_split_gauge_block(u_c[mu, 0]) for mu in range(NDIRS)]
    utm_r, utm_i = _split_gauge_block(u_tm[0, 0])
    uzm_r, uzm_i = _split_gauge_block(u_zm[0, 0])

    # mass term m4 * γ5out γ5in ψ: identity when the flags agree (γ5² = 1),
    # γ5 itself (spins 2,3 negated) when exactly one flag is set.
    m4 = f32(mass + 4.0)
    m4_lo = -m4 if (g5in != g5out) else m4
    out_r = [[(m4 if s < 2 else m4_lo) * pc_r[s][c] for c in range(NCOL)]
             for s in range(NSPIN)]
    out_i = [[(m4 if s < 2 else m4_lo) * pc_i[s][c] for c in range(NCOL)]
             for s in range(NSPIN)]

    # site-term twist (operator registry): + γ5out (twist·iγ5) γ5in ψ.
    # γ5 commutes through, so the wrap collapses to i·twist·γ5 ψ when the
    # flags agree (γ5² = 1) and to i·twist·ψ when exactly one is set —
    # per-spin trace-time constants; twist = 0 (Wilson) emits nothing.
    if twist != 0.0:
        for s in range(NSPIN):
            tw = f32(-twist if (g5in == g5out and s >= 2) else twist)
            for c in range(NCOL):
                out_r[s][c] = out_r[s][c] - tw * pc_i[s][c]
                out_i[s][c] = out_i[s][c] + tw * pc_r[s][c]

    hop = functools.partial(_hop, g5in=g5in, g5out=g5out)

    # ---- T direction (mu=0): neighbour planes come from extra refs ----
    hop(out_r, out_i, ptp_r, ptp_i, u[0][0], u[0][1], 0, "fwd")
    hop(out_r, out_i, ptm_r, ptm_i, utm_r, utm_i, 0, "bwd")

    # ---- Z direction (mu=1): in-block shift + boundary planes ----
    fz_r = _shift(pc_r, pzp_r, forward=True, axis=_Z_AXIS)
    fz_i = _shift(pc_i, pzp_i, forward=True, axis=_Z_AXIS)
    hop(out_r, out_i, fz_r, fz_i, u[1][0], u[1][1], 1, "fwd")
    bz_r = _shift(pc_r, pzm_r, forward=False, axis=_Z_AXIS)
    bz_i = _shift(pc_i, pzm_i, forward=False, axis=_Z_AXIS)
    ubz_r = _shift(u[1][0], uzm_r, forward=False, axis=_Z_AXIS)
    ubz_i = _shift(u[1][1], uzm_i, forward=False, axis=_Z_AXIS)
    hop(out_r, out_i, bz_r, bz_i, ubz_r, ubz_i, 1, "bwd")

    # ---- Y direction (mu=2): in-block rolls when the block spans full Y,
    # the Z-style boundary-splice when Y is tiled (bitwise identical) ----
    if y_tiled:
        pym_r, pym_i = _split_spinor_block(_take_plane(psi_ym, batched))
        pyp_r, pyp_i = _split_spinor_block(_take_plane(psi_yp, batched))
        uym_r, uym_i = _split_gauge_block(u_ym[0, 0])
        fy_r = _shift(pc_r, pyp_r, forward=True, axis=_Y_AXIS)
        fy_i = _shift(pc_i, pyp_i, forward=True, axis=_Y_AXIS)
        hop(out_r, out_i, fy_r, fy_i, u[2][0], u[2][1], 2, "fwd")
        by_r = _shift(pc_r, pym_r, forward=False, axis=_Y_AXIS)
        by_i = _shift(pc_i, pym_i, forward=False, axis=_Y_AXIS)
        uby_r = _shift(u[2][0], uym_r, forward=False, axis=_Y_AXIS)
        uby_i = _shift(u[2][1], uym_i, forward=False, axis=_Y_AXIS)
        hop(out_r, out_i, by_r, by_i, uby_r, uby_i, 2, "bwd")
    else:
        hop(out_r, out_i, _roll_sc(pc_r, -1, _Y_AXIS),
            _roll_sc(pc_i, -1, _Y_AXIS), u[2][0], u[2][1], 2, "fwd")
        hop(out_r, out_i, _roll_sc(pc_r, 1, _Y_AXIS),
            _roll_sc(pc_i, 1, _Y_AXIS),
            _roll_sc(u[2][0], 1, _Y_AXIS), _roll_sc(u[2][1], 1, _Y_AXIS),
            2, "bwd")

    # ---- X direction (mu=3): lane rolls ----
    hop(out_r, out_i, _roll_sc(pc_r, -1, _X_AXIS), _roll_sc(pc_i, -1, _X_AXIS),
        u[3][0], u[3][1], 3, "fwd")
    hop(out_r, out_i, _roll_sc(pc_r, 1, _X_AXIS), _roll_sc(pc_i, 1, _X_AXIS),
        _roll_sc(u[3][0], 1, _X_AXIS), _roll_sc(u[3][1], 1, _X_AXIS),
        3, "bwd")

    # ---- stage 4: repack & store ----
    packed = _repack_spinor_block(out_r, out_i, out_ref.dtype)
    if batched:
        out_ref[:, 0] = packed
    else:
        out_ref[0] = packed


def dslash_pallas(up: jax.Array, pp: jax.Array, mass: float, *,
                  bz: int | None = None, by: int | None = None,
                  batch: str | None = None, stream: str | None = None,
                  interpret: bool | None = None,
                  twist: float = 0.0, gamma5_in: bool = False,
                  gamma5_out: bool = False) -> jax.Array:
    """Dirac-Wilson dslash via the Pallas plane-streaming kernel.

    Args:
      up:   (4, T, Z, Y, 18, X) packed gauge field.
      pp:   (T, Z, Y, 24, X) packed spinor field, or (N, T, Z, Y, 24, X)
        for an N-RHS batch: the gauge BlockSpecs carry no batch axis, so
        each link plane is fetched ONCE per grid step and streams all N
        spinor planes through the stencil (multi-RHS gauge amortization).
      mass: bare mass (trace-time constant, like the paper's #define).
      twist: site-term twist (operator registry): adds ``i·twist·γ5 ψ`` to
        the mass term inside the kernel (twisted-mass Wilson); 0 = Wilson.
      bz:   z-planes per block (VMEM working-set knob); must divide Z.
      by:   y-extent per block; must divide Y (default: full Y).
      batch: RHS-batch placement, "block" or "grid" (see DESIGN.md §13).
      stream: gauge streaming, "blockspec" or "db" (double-buffered).
        When bz/by/batch/stream are ALL None the tuning cache decides
        (:func:`repro.kernels.dispatch.pick_tile`); every choice is
        bitwise-neutral.
      interpret: None = interpret only on CPU; True forces the
        interpreter; False forces compiled execution (Mosaic on GPU/TPU,
        the XLA half-spinor lowering on CPU).
      gamma5_in/gamma5_out: compute γ5out D (γ5in ψ) with γ5 folded into the
        constant hop tables — both True gives D† for free.
    Returns:
      packed D psi (or its γ5-conjugations) with the shape/dtype of ``pp``.
    """
    nd, t, z, y, g, x = up.shape
    assert nd == NDIRS and g == GAUGE_G
    assert pp.ndim in (5, 6), f"spinor rank must be 5 or 6, got {pp.ndim}"
    nb = pp.shape[0] if pp.ndim == 6 else None
    tt, zz, yy, s, xx = pp.shape[-5:]
    assert (tt, zz, yy, xx) == (t, z, y, x) and s == SPINOR_S

    lowering = resolve_lowering(interpret)
    if lowering == "xla":
        from repro.kernels.wilson_dslash import xla as _xla
        return _xla.dslash_xla(up, pp, mass, twist=twist,
                               gamma5_in=gamma5_in, gamma5_out=gamma5_out)

    bz, by, batch, stream, y_tiled, grid_batch = _resolve_tile(
        bz, by, batch, stream, t, z, y, x, nb, pp.dtype, gauge_dtype=up.dtype)
    stream_db = stream == "db"

    psi_specs = _spinor_specs(t, z, bz, y, by, x, nb, grid_batch)
    gauge_specs = _gauge_specs(t, z, bz, y, by, x, grid_batch)
    if stream_db:
        gauge_specs[0] = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)

    kernel = functools.partial(_dslash_kernel, mass=float(mass),
                               twist=float(twist), g5in=bool(gamma5_in),
                               g5out=bool(gamma5_out),
                               batched=nb is not None, y_tiled=y_tiled,
                               stream_db=stream_db, bz_sz=bz)
    n_psi = len(psi_specs)
    return pl.pallas_call(
        kernel,
        grid=_launch_grid(t, z, bz, y, by, nb, y_tiled, grid_batch),
        in_specs=psi_specs + gauge_specs,
        out_specs=psi_specs[0],
        out_shape=jax.ShapeDtypeStruct(pp.shape, pp.dtype),
        scratch_shapes=(_db_scratch(bz, y, x, up.dtype, streams=1)
                        if stream_db else ()),
        interpret=lowering == "interpret",
    )(*([pp] * n_psi), *([up] * len(gauge_specs)))


# ---------------------------------------------------------------------------
# Parity (even-odd) hop kernels on packed half fields
#
# Layout (repro.core.lattice): psi[T, 24, Z, Y*Xh], U[mu, T, 18, Z, Y*Xh].
# Each grid step is one t-plane; its blocks hold whole (component, Z,
# Y*Xh) planes, so every hop but T's stays inside the block:
#
# * T: the t±1 planes (and U_t(t-1)) through their own BlockSpecs;
# * Z: a sublane roll of the block's rows, with the row that crosses the
#   chunk edge read from the plane itself;
# * Y: a lane roll by ±Xh — y-major lanes make it periodic in Y as is;
# * X: a lane roll by ±1 with two selects: the row offset s_out (which
#   sites of the row are the output parity) and the wrap at j = 0 / Xh-1.
#
# The body computes on chunks of ``bz`` Z-rows at a time (the sublane
# block): each operand's chunk is loaded straight from its ref as a
# (component, bz, Y*Xh) stack, every component a dense slab.
# ---------------------------------------------------------------------------

# A parity launch's blocks are whole planes, so it asks Mosaic for more
# scoped VMEM than the 16 MiB default (v5e has 128 MiB).  Beyond its
# double-buffered blocks the body keeps 150-250 f32 (bz, Y*Xh) slabs a
# right-hand side (Mosaic's scoped-VMEM needs at 24³×48 and at 16³×32
# with N = 8); _PARITY_BODY_SLABS adds a margin to that.
PARITY_VMEM_LIMIT_BYTES = 96 * 2**20
PARITY_VMEM_BUDGET_BYTES = PARITY_VMEM_LIMIT_BYTES * 7 // 8
_PARITY_BODY_SLABS = 300


def parity_vmem_bytes(bz: int, z: int, lanes: int, dtype, gauge_dtype,
                      has_acc: bool) -> int:
    """Scoped VMEM bytes of one parity launch with one right-hand side a
    grid step: its double-buffered plane blocks plus the body's working
    set on a ``bz``-row chunk.

    Spinor blocks: center, t±1 and the output, plus the accumulator;
    gauge blocks: the output-parity links (4 directions) and the
    neighbour parity's z/y/x links at t and t-links at t-1.
    """
    sp = _padded_bytes((SPINOR_S, z, lanes), dtype)
    ga = _padded_bytes((GAUGE_G, z, lanes), gauge_dtype)
    blocks = (4 + bool(has_acc)) * sp + (NDIRS + 4) * ga
    body = _PARITY_BODY_SLABS * _padded_bytes((bz, lanes), jnp.float32)
    return 2 * blocks + body


def _parity_chunks(z: int, dtype, gauge_dtype) -> list[int]:
    """The legal chunks of a Z extent, the default's order first.

    Mosaic loads a run of rows at a traced offset only when the offset is
    a multiple of the (8, 128) tile, and the single edge row a chunk's Z
    hop reads only from 32-bit operands.  So 32-bit fields take whole
    sublane tiles (smallest first), the whole Z or one row; narrower
    storage (bf16) takes the whole Z in one chunk, which has no traced
    offset at all.
    """
    if min(jnp.dtype(dtype).itemsize, jnp.dtype(gauge_dtype).itemsize) < 4:
        return [z]
    tiles = [c for c in _divisors(z) if c % 8 == 0]
    return list(dict.fromkeys(tiles + [z, 1]))


def _resolve_parity_tile(bz, dims, dtype, *, gauge_dtype,
                         has_acc: bool) -> int:
    """The Z rows of one body chunk: ``bz`` if given, else the first legal
    chunk (:func:`_parity_chunks`) that fits VMEM with one right-hand side
    a grid step — one sublane tile (bz = 8 for f32) at 24³×48, 16³×32 and
    32³×64."""
    t, z, y, xh = dims
    lanes = y * xh
    chunks = _parity_chunks(z, dtype, gauge_dtype)
    if bz is not None:
        bz = _pick_bz(z, bz)
        if bz not in chunks:
            raise ValueError(
                f"bz={bz} is not a parity chunk of the Z extent {z} for "
                f"{jnp.dtype(dtype).name} spinors and "
                f"{jnp.dtype(gauge_dtype).name} links: f32 chunks are one "
                f"row, the whole Z or whole (8, 128) tiles, narrower ones "
                f"the whole Z; legal bz values: {sorted(chunks)}")
        return bz
    for bz in chunks:
        if parity_vmem_bytes(bz, z, lanes, dtype, gauge_dtype,
                             has_acc) <= PARITY_VMEM_BUDGET_BYTES:
            return bz
    small = min(chunks)
    need = parity_vmem_bytes(small, z, lanes, dtype, gauge_dtype, has_acc)
    raise ValueError(
        f"no parity tile fits VMEM for a (Z, Y*Xh) = {(z, lanes)} plane of "
        f"{jnp.dtype(dtype).name} spinors: the smallest chunk bz={small} "
        f"needs {need} bytes against a budget of {PARITY_VMEM_BUDGET_BYTES}")


def _roll(v, shift: int, axis: int):
    """``jnp.roll(v, shift, axis)`` through the TPU's native rotate."""
    axis %= v.ndim
    shift %= v.shape[axis]
    return v if shift == 0 else pltpu.roll(v, shift, axis)


def _sc(v):
    """[spin][color] re/im lists of a (…, 24, bz, L) component stack."""
    c = lambda k: v[..., k, :, :]
    re = [[c((s * NCOL + col) * 2) for col in range(NCOL)]
          for s in range(NSPIN)]
    im = [[c((s * NCOL + col) * 2 + 1) for col in range(NCOL)]
          for s in range(NSPIN)]
    return re, im


def _links(v):
    """[row][col] re/im lists of an (18, bz, L) link-component stack."""
    c = lambda k: v[..., k, :, :]
    re = [[c((a * NCOL + b) * 2) for b in range(NCOL)] for a in range(NCOL)]
    im = [[c((a * NCOL + b) * 2 + 1) for b in range(NCOL)]
          for a in range(NCOL)]
    return re, im


def _z_neighbour(v, load, z0, bz: int, z: int, forward: bool):
    """The chunk ``v`` (rows z0..z0+bz-1 of a plane), each row replaced by
    its Z neighbour (z+1 forward, z-1 backward, periodic).
    ``load(start, n)`` reads n rows of the plane."""
    if bz == z:
        return _roll(v, -1 if forward else 1, -2)
    edge = load((z0 + bz) % z if forward else (z0 - 1 + z) % z, 1)
    if bz == 1:  # the whole chunk is the row that crosses the edge
        return edge
    body = _roll(v, -1 if forward else 1, -2)
    row = jax.lax.broadcasted_iota(jnp.int32, (bz, 1), 0)
    return jnp.where(row == (bz - 1 if forward else 0), edge, body)


def _dslash_parity_kernel(*refs, parity: int, hop_coeff: float,
                          acc_coeff: float, has_acc: bool,
                          hop_twist: float = 0.0, acc_twist: float = 0.0,
                          g5in: bool, g5out: bool, batched: bool = False,
                          xh: int, bz: int):
    """Half-lattice hopping block: hop_coeff · γ5out Hop(γ5in ψ) [+ acc].

    ``u_oc`` holds the links attached to the OUTPUT-parity sites (forward
    hops use U_mu(x) at the output site x); ``u_nz``/``u_ny``/``u_nx``/
    ``u_ntm`` the links attached to the neighbour parity (backward hops
    use U_mu(x-mu)† at the neighbour site), z/y/x at t and t at t-1.
    ``parity`` selects which parity the output sites are: output sites of
    row (t, z, y) sit at x = 2j + s_out, s_out = (t + z + y + parity) mod 2.

    ``batched``: the spinor blocks carry a leading (size-1) RHS-batch
    axis; the gauge blocks never do — a batch rides the grid, and one
    gauge fetch feeds all N half-spinor planes of a t.
    """
    (psi_c, psi_tm, psi_tp, u_oc, u_nz, u_ny, u_nx, u_ntm, *rest) = refs
    out_ref = rest[-1]
    acc_ref = rest[0] if has_acc else None
    f32 = jnp.float32
    z, lanes = psi_c.shape[-2:]
    lead = (slice(None), 0) if batched else (0,)

    def rows(ref, idx):
        return lambda start, n: ref[idx + (pl.ds(start, n), slice(None))
                                    ].astype(f32)

    # an operand's rows start..start+n-1, all components: (…, C, n, L)
    spins = lambda ref: rows(ref, lead + (slice(None),))
    links = lambda ref, d: rows(ref, (d, 0, slice(None)))

    # lane geometry of the (Y*Xh) axis, shape (1, lanes): y parity and the
    # row ends j = 0 / j = Xh - 1 of the compressed x index
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    yy = lane // xh
    jj = lane - yy * xh
    y_odd, j_first, j_last = yy & 1, jj == 0, jj == xh - 1
    t = pl.program_id(0)

    def x_fwd(v, sel):  # value at compressed j + s_out in the row
        return jnp.where(sel, jnp.where(j_last, _roll(v, xh - 1, -1),
                                        _roll(v, -1, -1)), v)

    def x_bwd(v, sel):  # value at compressed j - (1 - s_out) in the row
        return jnp.where(sel, v, jnp.where(j_first, _roll(v, 1 - xh, -1),
                                           _roll(v, 1, -1)))

    def chunk(z0):
        # Row parity selector: True where the output site offset s_out == 1
        # (output sites at x = 2j + 1 within the row; lattice.eo_row_offset)
        row = jax.lax.broadcasted_iota(jnp.int32, (bz, 1), 0)
        sel = ((row + y_odd + (t + z0 + parity)) & 1) == 1
        here = lambda ld: ld(z0, bz)
        zero = jnp.zeros(psi_c.shape[:-4] + (bz, lanes) if batched
                         else (bz, lanes), f32)
        out_r = [[zero for _ in range(NCOL)] for _ in range(NSPIN)]
        out_i = [[zero for _ in range(NCOL)] for _ in range(NSPIN)]
        hop = functools.partial(_hop, out_r, out_i, g5in=g5in, g5out=g5out)
        pc = here(spins(psi_c))
        zn = lambda v, ld, fwd: _z_neighbour(v, ld, z0, bz, z, fwd)

        # ---- T direction (mu=0): neighbour planes come from extra refs ----
        hop(*_sc(here(spins(psi_tp))), *_links(here(links(u_oc, 0))),
            0, "fwd")
        hop(*_sc(here(spins(psi_tm))), *_links(here(links(u_ntm, 0))),
            0, "bwd")
        # ---- Z direction (mu=1): row shifts within the plane ----
        hop(*_sc(zn(pc, spins(psi_c), True)),
            *_links(here(links(u_oc, 1))), 1, "fwd")
        u_nz_rows = links(u_nz, 0)
        hop(*_sc(zn(pc, spins(psi_c), False)),
            *_links(zn(here(u_nz_rows), u_nz_rows, False)), 1, "bwd")
        # ---- Y direction (mu=2): lane rolls by Xh ----
        hop(*_sc(_roll(pc, -xh, -1)), *_links(here(links(u_oc, 2))),
            2, "fwd")
        hop(*_sc(_roll(pc, xh, -1)),
            *_links(_roll(here(links(u_ny, 0)), xh, -1)), 2, "bwd")
        # ---- X direction (mu=3): parity-compressed lanes ----
        hop(*_sc(x_fwd(pc, sel)), *_links(here(links(u_oc, 3))), 3, "fwd")
        hop(*_sc(x_bwd(pc, sel)),
            *_links(x_bwd(here(links(u_nx, 0)), sel)), 3, "bwd")

        # ---- epilogue: site-term maps on the hop and the accumulator ----
        #   out = (acc_coeff + acc_twist·iγ5)(ψ_acc)
        #       + (hop_coeff + hop_twist·iγ5)(γ5out Hop(γ5in ψ))
        # A zero-twist epilogue (Wilson) takes the plain branch (the
        # bitwise-identity contract of the operator registry).  A twisted
        # scalar mixes each component's re/im planes with a per-spin-block
        # sign — trace-time constants, zero extra memory traffic.
        h = f32(hop_coeff)
        if has_acc:
            a = f32(acc_coeff)
            ac_r, ac_i = _sc(here(spins(acc_ref)))
        if hop_twist == 0.0 and acc_twist == 0.0:
            if has_acc:
                out_r = [[h * out_r[s][c] + a * ac_r[s][c]
                          for c in range(NCOL)] for s in range(NSPIN)]
                out_i = [[h * out_i[s][c] + a * ac_i[s][c]
                          for c in range(NCOL)] for s in range(NSPIN)]
            elif hop_coeff != 1.0:
                out_r = [[h * e for e in row_] for row_ in out_r]
                out_i = [[h * e for e in row_] for row_ in out_i]
        else:
            new_r = [[None] * NCOL for _ in range(NSPIN)]
            new_i = [[None] * NCOL for _ in range(NSPIN)]
            for sp in range(NSPIN):
                g = 1.0 if sp < 2 else -1.0  # γ5 sign of this spin block
                for c in range(NCOL):
                    nr, ni = h * out_r[sp][c], h * out_i[sp][c]
                    if hop_twist != 0.0:
                        hg = f32(hop_twist * g)
                        nr = nr - hg * out_i[sp][c]
                        ni = ni + hg * out_r[sp][c]
                    if has_acc:
                        nr = nr + a * ac_r[sp][c]
                        ni = ni + a * ac_i[sp][c]
                        if acc_twist != 0.0:
                            ag = f32(acc_twist * g)
                            nr = nr - ag * ac_i[sp][c]
                            ni = ni + ag * ac_r[sp][c]
                    new_r[sp][c], new_i[sp][c] = nr, ni
            out_r, out_i = new_r, new_i
        for s in range(NSPIN):
            for c in range(NCOL):
                for reim, val in enumerate((out_r[s][c], out_i[s][c])):
                    k = (s * NCOL + c) * 2 + reim
                    out_ref[lead + (k, pl.ds(z0, bz), slice(None))] = (
                        val.astype(out_ref.dtype))

    if bz == z:
        chunk(0)
    else:
        def body(i, carry):
            chunk(pl.multiple_of(i * bz, bz))
            return carry
        jax.lax.fori_loop(0, z // bz, body, 0)


def _parity_specs(t: int, z: int, lanes: int, nb: int | None):
    """(spinor center, t-1, t+1), gauge (U_out, U_nbr z/y/x, U_nbr t at
    t-1) BlockSpecs: one whole t-plane per grid step.

    With a batch, the batch is the trailing grid axis: a step holds one
    right-hand side's planes, and consecutive steps ask for the same
    gauge blocks, which the pipeline does not refetch.
    """
    plane = (1, SPINOR_S, z, lanes)

    def spinor(tmap):
        if nb is None:
            return pl.BlockSpec(plane, lambda ti: (tmap(ti), 0, 0, 0))
        return pl.BlockSpec((1,) + plane,
                            lambda ti, n: (n, tmap(ti), 0, 0, 0))

    def gauge(ndir, d, tmap):
        return pl.BlockSpec((ndir, 1, GAUGE_G, z, lanes),
                            lambda ti, *_: (d, tmap(ti), 0, 0, 0))

    here = lambda ti: ti
    psi = [spinor(here), spinor(lambda ti: (ti - 1 + t) % t),
           spinor(lambda ti: (ti + 1) % t)]
    u = [gauge(NDIRS, 0, here), gauge(1, 1, here), gauge(1, 2, here),
         gauge(1, 3, here), gauge(1, 0, lambda ti: (ti - 1 + t) % t)]
    return psi, u


def _dslash_parity_pallas(u_out: jax.Array, u_nbr: jax.Array, pp: jax.Array,
                          *, xh: int, parity: int, bz: int | None,
                          interpret: bool | None, gamma5_in: bool,
                          gamma5_out: bool, psi_acc: jax.Array | None,
                          acc_coeff: float, hop_coeff: float,
                          acc_twist: float = 0.0,
                          hop_twist: float = 0.0) -> jax.Array:
    nd, t, g, z, lanes = u_out.shape
    assert nd == NDIRS and g == GAUGE_G
    assert u_nbr.shape == u_out.shape
    assert pp.ndim in (4, 5), f"spinor rank must be 4 or 5, got {pp.ndim}"
    assert pp.shape[-4:] == (t, SPINOR_S, z, lanes), (pp.shape, u_out.shape)
    assert lanes % xh == 0, f"lane extent {lanes} is not Y * Xh for Xh={xh}"
    y = lanes // xh
    assert t % 2 == z % 2 == y % 2 == 0, (
        "even-odd kernels need even T/Z/Y extents: an odd periodic extent "
        f"breaks bipartiteness, got {(t, z, y)}")
    nb = pp.shape[0] if pp.ndim == 5 else None

    lowering = resolve_lowering(interpret)
    if lowering == "xla":
        from repro.kernels.wilson_dslash import xla as _xla
        return _xla.dslash_parity_xla(
            u_out, u_nbr, pp, xh=xh, parity=int(parity) % 2,
            gamma5_in=gamma5_in, gamma5_out=gamma5_out, psi_acc=psi_acc,
            acc_coeff=acc_coeff, hop_coeff=hop_coeff,
            acc_twist=acc_twist, hop_twist=hop_twist)

    bz = _resolve_parity_tile(
        bz, (t, z, y, xh), pp.dtype, gauge_dtype=u_out.dtype,
        has_acc=psi_acc is not None)
    psi_specs, u_specs = _parity_specs(t, z, lanes, nb)
    in_specs = psi_specs + u_specs
    operands = [pp] * 3 + [u_out] + [u_nbr] * 4
    if psi_acc is not None:
        assert psi_acc.shape == pp.shape
        in_specs.append(psi_specs[0])
        operands.append(psi_acc)

    kernel = functools.partial(
        _dslash_parity_kernel, parity=int(parity) % 2,
        hop_coeff=float(hop_coeff), acc_coeff=float(acc_coeff),
        hop_twist=float(hop_twist), acc_twist=float(acc_twist),
        has_acc=psi_acc is not None, g5in=bool(gamma5_in),
        g5out=bool(gamma5_out), batched=nb is not None, xh=int(xh), bz=bz)
    return pl.pallas_call(
        kernel,
        grid=(t,) if nb is None else (t, nb),
        in_specs=in_specs,
        out_specs=psi_specs[0],
        out_shape=jax.ShapeDtypeStruct(pp.shape, pp.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=PARITY_VMEM_LIMIT_BYTES),
        interpret=lowering == "interpret",
    )(*operands)


def dslash_eo_pallas(u_e: jax.Array, u_o: jax.Array, pp_o: jax.Array, *,
                     xh: int, bz: int | None = None,
                     interpret: bool | None = None,
                     gamma5_in: bool = False, gamma5_out: bool = False,
                     psi_acc: jax.Array | None = None,
                     acc_coeff: float = 0.0, hop_coeff: float = 1.0,
                     acc_twist: float = 0.0,
                     hop_twist: float = 0.0) -> jax.Array:
    """D_eo: odd -> even hopping block on packed half fields.

    Args:
      u_e, u_o: (4, T, 18, Z, Y*Xh) packed per-parity link fields
                (``pack_half_gauge`` of ``split_eo_gauge``'s halves).
      pp_o:     (T, 24, Z, Y*Xh) packed ODD-parity spinor half field, or
        (N, T, 24, Z, Y*Xh) for an N-RHS batch — the batch rides the
        grid inside each t, so each gauge plane is fetched once for all N
        spinor planes (multi-RHS gauge amortization).
      xh:       the parity-compressed X extent X/2 (the lane axis holds
        Y*Xh sites y-major).
      psi_acc/acc_coeff/hop_coeff: optional fused epilogue
        ``out = acc_coeff * psi_acc + hop_coeff * hop`` (psi_acc is an
        EVEN-parity half field, batched iff ``pp_o`` is) — lets the Schur
        complement avoid separate scale/add HBM passes.
      acc_twist/hop_twist: the site-term hook of the operator registry —
        each epilogue scalar generalizes to ``coeff + twist·iγ5``
        (trace-time constants; zero extra passes), which is exactly what
        a site-diagonal ``i·μ·γ5`` term (twisted mass) needs to fold its
        Schur blocks into the same two launches as Wilson.
      bz: Z rows per body chunk (DESIGN.md §13): one row, the whole Z or
        whole sublane tiles; None takes the largest default that fits
        VMEM.  Every legal choice is bitwise-neutral.
      gamma5_in/gamma5_out: fold γ5 around the hop (tables only, free).
    Returns:
      packed even-parity half field(s), shape/dtype of ``pp_o``.
    """
    return _dslash_parity_pallas(
        u_e, u_o, pp_o, xh=xh, parity=0, bz=bz,
        interpret=interpret,
        gamma5_in=gamma5_in, gamma5_out=gamma5_out, psi_acc=psi_acc,
        acc_coeff=acc_coeff, hop_coeff=hop_coeff,
        acc_twist=acc_twist, hop_twist=hop_twist)


def dslash_oe_pallas(u_e: jax.Array, u_o: jax.Array, pp_e: jax.Array, *,
                     xh: int, bz: int | None = None,
                     interpret: bool | None = None,
                     gamma5_in: bool = False, gamma5_out: bool = False,
                     psi_acc: jax.Array | None = None,
                     acc_coeff: float = 0.0, hop_coeff: float = 1.0,
                     acc_twist: float = 0.0,
                     hop_twist: float = 0.0) -> jax.Array:
    """D_oe: even -> odd hopping block on packed half fields (see above)."""
    return _dslash_parity_pallas(
        u_o, u_e, pp_e, xh=xh, parity=1, bz=bz,
        interpret=interpret,
        gamma5_in=gamma5_in, gamma5_out=gamma5_out, psi_acc=psi_acc,
        acc_coeff=acc_coeff, hop_coeff=hop_coeff,
        acc_twist=acc_twist, hop_twist=hop_twist)
