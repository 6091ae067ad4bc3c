"""Distributed Dirac-Wilson solver: 4D domain decomposition over the device
mesh with halo exchange and communication/compute overlap.

This is the scale-out layer the paper motivates via HPCG ("boundary values
have to be frequently exchanged between the neighbours as well as global
communications ... to establish total error estimates"):

* The lattice is block-decomposed over mesh axes (default: T over ``data``,
  Z over ``model``, and — multi-pod — Y over ``pod``).  Each device owns a
  contiguous 4D sub-volume; X (the lane axis) is never sharded.

* ``dslash_halo`` evaluates the *bulk* stencil entirely locally (periodic
  rolls) and then **corrects only the boundary planes** with
  `collective_permute`d halo planes.  The bulk compute does not depend on
  the halos, so XLA's latency-hiding scheduler overlaps the ppermutes with
  the bulk — the inter-chip version of the paper's streaming overlap (T4).
  The price is one extra plane of hop evaluations per sharded direction —
  O(1/T_local) redundant compute traded for full overlap, the same trade
  the FPGA paper makes with its redundant cyclic-buffer reloads.

* Global reductions inside CG go through an injected ``dot``/``norm2``
  performing a single fused ``psum`` over all mesh axes; with ``pipecg``
  this is ONE collective per iteration.
"""

from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import phases
from repro.core.lattice import half_lanes, half_rows
from repro.core.operators import apply_igamma5_packed, schur_launch_coeffs
from repro.core.wilson import (apply_gamma5_packed, dslash_packed,
                               hop_term_packed)

# lattice axis index -> name, for error messages
_LAT_AXIS_NAMES = {0: "T", 1: "Z", 2: "Y"}


def _take(arr: jax.Array, axis: int, idx: int) -> jax.Array:
    """Single plane at static index ``idx`` (0 or -1), keeping the dim."""
    sl = [slice(None)] * arr.ndim
    sl[axis] = slice(idx, idx + 1) if idx >= 0 else slice(idx, None)
    return arr[tuple(sl)]


def _add_at(arr: jax.Array, axis: int, idx: int, delta: jax.Array):
    sl = [slice(None)] * arr.ndim
    sl[axis] = slice(idx, idx + 1) if idx >= 0 else slice(idx, None)
    return arr.at[tuple(sl)].add(delta.astype(arr.dtype))


def dslash_halo(up: jax.Array, pp: jax.Array, mass,
                sharded: Mapping[int, tuple[str, int]],
                r: float = 1.0, use_pallas: bool = False,
                twist: float = 0.0) -> jax.Array:
    """Full-lattice dslash on a LOCAL shard; call inside ``shard_map``.

    Args:
      up:      local (4, Tl, Zl, Yl, 18, X) gauge shard.
      pp:      local (Tl, Zl, Yl, 24, X) spinor shard.
      sharded: {lattice_axis (0=T,1=Z,2=Y): (mesh_axis_name, axis_size)}.
      use_pallas: run the bulk stencil through the Pallas plane-streaming
        kernel (the TPU deployment path; r=1 only) instead of the jnp op.
      twist: operator-registry site-term twist (0 = Wilson).  Site-LOCAL
        by construction, so it rides the bulk stencil and the halo
        corrections (hop-only) are untouched — the registry's transport
        contract.
    """
    # 1) bulk: local periodic stencil (independent of any communication)
    if use_pallas:
        from repro.kernels.wilson_dslash.kernel import dslash_pallas
        out = dslash_pallas(up, pp, mass, twist=twist)
    else:
        out = dslash_packed(up, pp, mass, r=r)
        if twist != 0.0:
            out = (out + twist * apply_igamma5_packed(pp)).astype(out.dtype)

    # 2) halo exchange + boundary-plane corrections per sharded direction
    for mu, (ax, n) in sorted(sharded.items()):
        if n == 1:
            continue
        fwd = [(i, (i + 1) % n) for i in range(n)]  # recv from prev rank
        bwd = [(i, (i - 1) % n) for i in range(n)]  # recv from next rank
        first = _take(pp, mu, 0)
        last = _take(pp, mu, -1)
        u_mu = up[mu]
        u_last = _take(u_mu, mu, -1)

        psi_prev = lax.ppermute(last, ax, fwd)    # psi at my (axis)-1 edge
        u_prev = lax.ppermute(u_last, ax, fwd)    # U_mu at that edge
        psi_next = lax.ppermute(first, ax, bwd)   # psi at my (axis)+1 edge

        # backward hop into plane 0: bulk used local wrap (last plane)
        wrong_b = hop_term_packed(u_last, last, mu, forward=False, r=r)
        right_b = hop_term_packed(u_prev, psi_prev, mu, forward=False, r=r)
        out = _add_at(out, mu, 0, right_b - wrong_b)

        # forward hop into plane -1: U is local (output site), psi was wrapped
        wrong_f = hop_term_packed(u_last, first, mu, forward=True, r=r)
        right_f = hop_term_packed(u_last, psi_next, mu, forward=True, r=r)
        out = _add_at(out, mu, -1, right_f - wrong_f)
    return out


def dslash_dagger_halo(up, pp, mass, sharded, r: float = 1.0,
                       use_pallas: bool = False, twist: float = 0.0):
    """D^dag = gamma5 D(-twist) gamma5 on a local shard."""
    return apply_gamma5_packed(
        dslash_halo(up, apply_gamma5_packed(pp), mass, sharded, r=r,
                    use_pallas=use_pallas, twist=-twist))


def normal_op_halo(up, pp, mass, sharded, r: float = 1.0,
                   use_pallas: bool = False, twist: float = 0.0):
    return dslash_dagger_halo(up, dslash_halo(up, pp, mass, sharded, r=r,
                                              use_pallas=use_pallas,
                                              twist=twist),
                              mass, sharded, r=r, use_pallas=use_pallas,
                              twist=twist)


# ---------------------------------------------------------------------------
# Parity-compressed halo exchange: the even-odd Schur fast path, sharded
# ---------------------------------------------------------------------------
#
# The parity hop blocks D_eo / D_oe only roll the UNCOMPRESSED axes
# (T, Z, Y) — the x-direction hops stay inside a row (never sharded) — so
# their halo structure is identical to the full-lattice stencil above:
# evaluate the bulk with local periodic wrap, then correct the two
# boundary planes of every sharded direction with `collective_permute`d
# neighbour planes.  The correction hop for a t/z/y direction on a
# parity-compressed half field is the SAME ``hop_term_packed`` used by the
# full-lattice fix-ups: this is the paper's layering argument made
# concrete — the data-transport layer is untouched while the operator
# underneath swapped from full to parity.
#
# Packed half fields are (…, T, 24, Z, Y*Xh) (repro.core.lattice): a T
# or Z boundary plane is one index of its own axis, a Y boundary plane the
# first or last Xh lanes.  Each plane is taken in the row view
# ``half_rows`` ((…, T, Z, Y, 24, Xh), the layout ``hop_term_packed``
# computes on) and its correction goes back through ``half_lanes``:
# plane-sized transposes only.
#
# Requirement: every sharded LOCAL extent must be even.  Shard origins
# are then even too, so each device's local row parity equals the global
# row parity and the (local) bulk kernels compute the right projections.
#
# A batched RHS axis (N, T, 24, Z, Y*Xh) rides in front and is never
# sharded: the spinor boundary planes carry the batch, but the GAUGE
# boundary planes don't — each direction's link halo is exchanged once
# per plane regardless of N.


def _g5(p: jax.Array) -> jax.Array:
    """gamma5 on a (possibly batched) row-view plane of a half field."""
    return apply_gamma5_packed(p)


def _edge_index(arr: jax.Array, mu: int, idx: int, xh: int):
    """Index of the boundary plane ``idx`` (0 or -1) of lattice axis
    ``mu`` in a packed half field (…, T, C, Z, Y*Xh)."""
    sl = [slice(None)] * arr.ndim
    if mu == 2:    # Y: the first or last row of Xh lanes
        sl[-1] = slice(0, xh) if idx == 0 else slice(-xh, None)
    else:          # T (axis -4) or Z (axis -2)
        sl[-4 if mu == 0 else -2] = (slice(0, 1) if idx == 0
                                     else slice(-1, None))
    return tuple(sl)


def _edge(arr: jax.Array, mu: int, idx: int, xh: int) -> jax.Array:
    """A boundary plane of a packed half field, in the row view."""
    return half_rows(arr[_edge_index(arr, mu, idx, xh)], xh)


def _add_edge(arr: jax.Array, mu: int, idx: int, delta: jax.Array,
              xh: int) -> jax.Array:
    """Add a row-view correction to a boundary plane of a half field."""
    return arr.at[_edge_index(arr, mu, idx, xh)].add(
        half_lanes(delta).astype(arr.dtype))


def _hop_plane(u_plane: jax.Array, psi_plane: jax.Array, mu: int,
               forward: bool) -> jax.Array:
    """``hop_term_packed`` on one (possibly RHS-batched) boundary plane."""
    if psi_plane.ndim == 6:
        return jax.vmap(
            lambda q: hop_term_packed(u_plane, q, mu, forward=forward))(
                psi_plane)
    return hop_term_packed(u_plane, psi_plane, mu, forward=forward)


def parity_hop_halo(which: str, u_e: jax.Array, u_o: jax.Array,
                    pp: jax.Array, sharded: Mapping[int, tuple[str, int]], *,
                    xh: int, use_pallas: bool = False,
                    gamma5_in: bool = False,
                    gamma5_out: bool = False, psi_acc: jax.Array | None = None,
                    acc_coeff: float = 0.0, hop_coeff: float = 1.0,
                    acc_twist: float = 0.0, hop_twist: float = 0.0,
                    bz: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Parity hop block on a LOCAL shard; call inside ``shard_map``.

    Computes ``(acc_coeff + acc_twist·iγ5) psi_acc + (hop_coeff +
    hop_twist·iγ5) γ5out Hop(γ5in ψ)`` where Hop is D_eo (``which="eo"``:
    odd ψ in, even out) or D_oe: the bulk via the local-block kernel entry
    (:func:`repro.kernels.wilson_dslash.ops.hop_block`, Pallas or
    reference), the boundary planes of every sharded direction corrected
    with exchanged halos.  γ5 factors — and the operator registry's
    site-term twists — are applied to the correction PLANES only
    (plane-sized work), mirroring the kernels' trace-time folding: no
    standalone full-field γ5/twist pass exists on this path for any
    operator family.  ``xh`` is the compressed X extent X/2.
    """
    # local import: repro.core is imported by the kernels package, so a
    # module-level import here would be circular.
    from repro.kernels.wilson_dslash import ops as wops

    with phases.scope(phases.HOP_EO if which == "eo" else phases.HOP_OE):
        out = wops.hop_block(u_e, u_o, pp, which=which, xh=xh,
                             gamma5_in=gamma5_in,
                             gamma5_out=gamma5_out, psi_acc=psi_acc,
                             acc_coeff=acc_coeff, hop_coeff=hop_coeff,
                             acc_twist=acc_twist, hop_twist=hop_twist,
                             use_pallas=use_pallas, bz=bz,
                             interpret=interpret)
    u_out, u_nbr = (u_e, u_o) if which == "eo" else (u_o, u_e)
    hc = jnp.asarray(hop_coeff, jnp.float32)
    for mu, (ax, n) in sorted(sharded.items()):
        if n == 1:
            continue
        fwd = [(i, (i + 1) % n) for i in range(n)]  # recv from prev rank
        bwd = [(i, (i - 1) % n) for i in range(n)]  # recv from next rank
        first = _edge(pp, mu, 0, xh)
        last = _edge(pp, mu, -1, xh)
        if gamma5_in:  # fold γ5 into the plane, exactly like the kernels
            first, last = _g5(first), _g5(last)
        u_out_last = _edge(u_out[mu], mu, -1, xh)
        u_nbr_last = _edge(u_nbr[mu], mu, -1, xh)

        psi_prev = lax.ppermute(last, ax, fwd)    # ψ at my (axis)-1 edge
        u_prev = lax.ppermute(u_nbr_last, ax, fwd)  # U_mu at that edge
        psi_next = lax.ppermute(first, ax, bwd)   # ψ at my (axis)+1 edge

        # backward hop into plane 0: bulk used the local wrap (last plane)
        wrong_b = _hop_plane(u_nbr_last, last, mu, forward=False)
        right_b = _hop_plane(u_prev, psi_prev, mu, forward=False)
        # forward hop into plane -1: U is local (output site), ψ was wrapped
        wrong_f = _hop_plane(u_out_last, first, mu, forward=True)
        right_f = _hop_plane(u_out_last, psi_next, mu, forward=True)

        delta_b, delta_f = right_b - wrong_b, right_f - wrong_f
        if gamma5_out:
            delta_b, delta_f = _g5(delta_b), _g5(delta_f)
        if hop_twist != 0.0:
            # the same (hop_coeff + hop_twist·iγ5) epilogue the bulk kernel
            # folded, applied plane-sized to the corrections
            ht = jnp.asarray(hop_twist, jnp.float32)
            delta_b = hc * delta_b + ht * apply_igamma5_packed(delta_b)
            delta_f = hc * delta_f + ht * apply_igamma5_packed(delta_f)
        else:
            delta_b, delta_f = hc * delta_b, hc * delta_f
        out = _add_edge(out, mu, 0, delta_b, xh)
        out = _add_edge(out, mu, -1, delta_f, xh)
    return out


def schur_op_halo(u_e, u_o, pp_e, mass, sharded, *, xh: int,
                  use_pallas: bool = False, twist: float = 0.0,
                  dagger: bool = False, bz: int | None = None,
                  interpret: bool | None = None):
    """Sharded Schur complement D_hat ψ = S ψ - D_eo S^-1 D_oe ψ with the
    registry site term S = (mass+4) + i·twist·γ5 (Wilson: twist = 0).

    Two local hop blocks with the γ5 (``dagger``), the site-term axpy and
    the twist folded exactly as in the single-device kernel path — the
    only extra work versus one device is the boundary-plane corrections
    and their ppermutes, which XLA overlaps with the bulk stencils.
    """
    m = float(mass) + 4.0
    kw = dict(xh=xh, use_pallas=use_pallas, bz=bz, interpret=interpret)
    if twist == 0.0:
        tmp_o = parity_hop_halo("oe", u_e, u_o, pp_e, sharded,
                                gamma5_in=dagger, **kw)
        return parity_hop_halo("eo", u_e, u_o, tmp_o, sharded,
                               gamma5_out=dagger, psi_acc=pp_e, acc_coeff=m,
                               hop_coeff=-1.0 / m, **kw)
    # twisted: the same two-launch split as the single-device kernels —
    # the sign algebra has ONE home, operators.schur_launch_coeffs
    # (S(∓tw)^-1 into the first block's epilogue, S(±tw) into the
    # second block's accumulator; dagger = γ5 D_hat(-tw) γ5)
    h1c, h1t, acc, acct = schur_launch_coeffs(m, twist, dagger)
    tmp_o = parity_hop_halo("oe", u_e, u_o, pp_e, sharded, gamma5_in=dagger,
                            hop_coeff=h1c, hop_twist=h1t, **kw)
    return parity_hop_halo("eo", u_e, u_o, tmp_o, sharded, gamma5_out=dagger,
                           psi_acc=pp_e, acc_coeff=acc, acc_twist=acct,
                           hop_coeff=-1.0, **kw)


def schur_normal_op_halo(u_e, u_o, pp_e, mass, sharded, *, xh: int,
                         use_pallas: bool = False, twist: float = 0.0,
                         bz: int | None = None,
                         interpret: bool | None = None):
    """A_hat = D_hat^dag D_hat on local shards — four hop blocks, zero
    standalone full-field γ5/axpy/twist passes, halo corrections per
    block, for every registered operator family."""
    kw = dict(xh=xh, use_pallas=use_pallas, twist=twist, bz=bz,
              interpret=interpret)
    w = schur_op_halo(u_e, u_o, pp_e, mass, sharded, **kw)
    return schur_op_halo(u_e, u_o, w, mass, sharded, dagger=True, **kw)


# ---------------------------------------------------------------------------
# Mesh plumbing
# ---------------------------------------------------------------------------

def lattice_specs(mesh: Mesh, axis_map: Mapping[int, str] | None = None):
    """(psi_spec, gauge_spec, sharded) for decomposing (T,Z,Y) over ``mesh``.

    Default axis map: T->data, Z->model, and Y->pod when present.
    """
    if axis_map is None:
        axis_map = {0: "data", 1: "model"}
        if "pod" in mesh.axis_names:
            axis_map[2] = "pod"
    sharded = {mu: (name, mesh.shape[name]) for mu, name in axis_map.items()}
    spin = [None] * 5
    for mu, name in axis_map.items():
        spin[mu] = name
    psi_spec = P(*spin)
    gauge_spec = P(None, *spin)
    return psi_spec, gauge_spec, sharded


def half_lattice_specs(mesh: Mesh, axis_map: Mapping[int, str] | None = None):
    """(psi_spec, gauge_spec, sharded) for packed HALF fields.

    Half fields are (T, 24, Z, Y*Xh) and per-parity links
    (4, T, 18, Z, Y*Xh): T and Z shard on their own axes, and Y on the
    lanes, whose y-major order gives each shard a contiguous run of rows.
    """
    _, _, sharded = lattice_specs(mesh, axis_map)
    spin = [None] * 4
    for mu, (name, _) in sharded.items():
        spin[(0, 2, 3)[mu]] = name
    return P(*spin), P(None, *spin), sharded


def make_psum_dots(mesh: Mesh, batched: bool = False):
    """Local-shard inner products with one psum per reduction across the mesh.

    ``batched=True``: operands carry a leading RHS-batch axis and the
    reductions return per-RHS ``(N,)`` scalars — the N local partial sums
    still travel in a SINGLE ``psum`` (one collective for the whole batch),
    never N per-RHS collectives.
    """
    axes = tuple(mesh.axis_names)
    lead = 1 if batched else 0

    @phases.scoped(phases.PSUM)
    def dot(a, b):
        red = tuple(range(lead, a.ndim))
        local = jnp.sum(a.astype(jnp.float32) * b.astype(jnp.float32),
                        axis=red)
        return lax.psum(local, axes)

    @phases.scoped(phases.PSUM)
    def norm2(a):
        a32 = a.astype(jnp.float32)
        return lax.psum(jnp.sum(a32 * a32, axis=tuple(range(lead, a.ndim))),
                        axes)

    return dot, norm2


def make_fused_psum_dots(mesh: Mesh, batched: bool = False):
    """The pipelined-CG reduction: gamma = (r, r) and delta = (w, r) — for
    EVERY right-hand side — fused into ONE ``psum`` per iteration.

    The local partial sums are stacked into a single (2,) or (2, N) array
    before the collective, so the sharded pipelined CGNR pays exactly one
    all-reduce per iteration regardless of batch size (jaxpr-asserted in
    tests/test_distributed.py) — the cluster-scale version of the paper's
    "global communications ... to establish total error estimates" being
    batched into one transfer.
    """
    axes = tuple(mesh.axis_names)
    lead = 1 if batched else 0

    @phases.scoped(phases.PSUM)
    def fused_dots(r, w):
        red = tuple(range(lead, r.ndim))
        r32, w32 = r.astype(jnp.float32), w.astype(jnp.float32)
        local = jnp.stack([jnp.sum(r32 * r32, axis=red),
                           jnp.sum(w32 * r32, axis=red)])
        both = lax.psum(local, axes)      # the iteration's ONLY collective
        return both[0], both[1]

    return fused_dots


# (solver name) -> (plan.solver, plan.precision) for the legacy entry point
_LEGACY_SOLVERS = {"cg": ("cgnr", "single"), "pipecg": ("pipecg", "single"),
                   "mpcg": ("cgnr", "mixed"), "cg16": ("cgnr", "low")}


def solve_wilson(mesh: Mesh, up: jax.Array, b: jax.Array, mass, *,
                 solver: str = "cg", tol: float = 1e-6, maxiter: int = 1000,
                 inner_tol: float = 5e-2, low_dtype=jnp.bfloat16,
                 axis_map: Mapping[int, str] | None = None, r: float = 1.0,
                 residual_replacement_every: int = 25):
    """Solve D x = b (via the HPD normal equations) on a device mesh.

    ``solver``: "cg" | "pipecg" | "mpcg" | "cg16".  Returns (x,
    SolveStats), both with the same sharding as the inputs / replicated
    scalars.  Thin forwarder: builds the equivalent full-operator
    :class:`repro.core.plan.SolverPlan` (packed-layout contract) and
    executes it.
    """
    if solver not in _LEGACY_SOLVERS:
        raise ValueError(f"unknown solver {solver!r}")
    from repro.core import plan as plan_mod  # forwarder; avoid import cycle
    sv, precision = _LEGACY_SOLVERS[solver]
    p = plan_mod.SolverPlan(operator="full", solver=sv, precision=precision,
                            low=low_dtype, mesh=mesh, axis_map=axis_map, r=r)
    return plan_mod.solve(
        p, up, b, mass, tol=tol, maxiter=maxiter, inner_tol=inner_tol,
        inner_maxiter=maxiter,
        residual_replacement_every=residual_replacement_every,
        layout="packed")


def shard_lattice_fields(mesh: Mesh, up: jax.Array, pp: jax.Array,
                         axis_map: Mapping[int, str] | None = None):
    """device_put global packed fields with the lattice decomposition."""
    psi_spec, gauge_spec, _ = lattice_specs(mesh, axis_map)
    return (jax.device_put(up, NamedSharding(mesh, gauge_spec)),
            jax.device_put(pp, NamedSharding(mesh, psi_spec)))
