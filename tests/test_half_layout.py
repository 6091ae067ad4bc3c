"""The packed half-field layout of the parity kernels, (T, 24, Z, Y*Xh).

Packing is an exact bijection with ``split_eo``/``merge_eo`` and the
natural layout, and the interpret-mode parity kernels match the
natural-layout oracle, whatever the lane extent Y*Xh: fewer than the 128
lanes of a vector register, exactly 128, and more without being a
multiple of 128.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LatticeShape, merge_eo, merge_eo_gauge,
                        pack_half_gauge, pack_half_spinor, random_gauge,
                        random_spinor, split_eo, split_eo_gauge,
                        unpack_half_gauge, unpack_half_spinor)
from repro.core.lattice import HALF_S_AXIS, half_lanes, half_rows
from repro.core.operators import apply_igamma5_packed
from repro.kernels.wilson_dslash.kernel import (dslash_eo_pallas,
                                                dslash_oe_pallas)
from repro.kernels.wilson_dslash.ref import dslash_eo_ref, dslash_oe_ref

LANE_SHAPES = [LatticeShape(2, 4, 4, 4), LatticeShape(2, 2, 16, 16),
               LatticeShape(2, 2, 12, 24)]
_lanes = lambda lat: f"{lat}-lanes{lat.y * lat.x // 2}"


@pytest.mark.parametrize("lat", LANE_SHAPES, ids=_lanes)
def test_half_pack_is_a_bijection_with_split_and_merge(lat):
    """natural -> split_eo -> pack_half -> unpack_half -> merge_eo is the
    identity, bit for bit, for spinors (with an RHS batch) and links, and
    each packed element sits where the layout says."""
    ku, kp = jax.random.split(jax.random.PRNGKey(lat.volume))
    xh = lat.x // 2
    psi = jnp.stack([random_spinor(jax.random.fold_in(kp, i), lat)
                     for i in range(2)])
    p_e, p_o = jax.vmap(split_eo)(psi)
    pk_e, pk_o = pack_half_spinor(p_e), pack_half_spinor(p_o)
    assert pk_e.shape == (2, lat.t, 24, lat.z, lat.y * xh)
    back = jax.vmap(merge_eo)(unpack_half_spinor(pk_e, xh),
                              unpack_half_spinor(pk_o, xh))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(psi))

    u = random_gauge(ku, lat)
    u_e, u_o = split_eo_gauge(u)
    g_e = pack_half_gauge(u_e)
    assert g_e.shape == (4, lat.t, 18, lat.z, lat.y * xh)
    back = merge_eo_gauge(unpack_half_gauge(g_e, xh),
                          unpack_half_gauge(pack_half_gauge(u_o), xh))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(u))

    # addressing: component (spin s, color c, re/im) of half-site
    # (t, z, y, j) is element (t, (3 s + c) 2 + reim, z, y Xh + j)
    t, z, y, j, s, c = 1, lat.z - 1, lat.y - 1, xh - 1, 3, 2
    site = p_e[1, t, z, y, j, s, c]
    got = pk_e[1, t, (3 * s + c) * 2:(3 * s + c) * 2 + 2, z, y * xh + j]
    assert float(got[0]) == float(site.real)
    assert float(got[1]) == float(site.imag)
    # the row view is the (T, Z, Y, 24, Xh) order, and back
    rows = half_rows(pk_e, xh)
    assert rows.shape == (2, lat.t, lat.z, lat.y, 24, xh)
    np.testing.assert_array_equal(np.asarray(half_lanes(rows)),
                                  np.asarray(pk_e))


def _eo_packed(lat, key):
    ku, kp = jax.random.split(jax.random.fold_in(key, lat.volume))
    u_e, u_o = split_eo_gauge(random_gauge(ku, lat))
    p_e, p_o = split_eo(random_spinor(kp, lat))
    return (pack_half_gauge(u_e), pack_half_gauge(u_o),
            pack_half_spinor(p_e), pack_half_spinor(p_o))


LANE_CASES = [("eo", {}), ("oe", {}),
              ("eo", dict(gamma5_in=True, gamma5_out=True)),
              ("oe", dict(hop_coeff=-0.3, hop_twist=0.25, acc_coeff=4.1,
                          acc_twist=-0.25)),
              ("eo", dict(batch=3))]


@pytest.mark.parametrize("lat", LANE_SHAPES, ids=_lanes)
@pytest.mark.parametrize("which,kw", LANE_CASES,
                         ids=["eo", "oe", "dagger", "twisted", "batched"])
def test_parity_kernels_match_ref_across_lane_widths(lat, which, kw):
    """D_eo/D_oe, γ5-folded (the dagger's launch), the twisted fused
    epilogue with an accumulator, and an RHS batch, against the
    natural-layout oracle."""
    kw = dict(kw)
    upe, upo, ppe, ppo = _eo_packed(lat, jax.random.PRNGKey(29))
    xh = lat.x // 2
    src = ppo if which == "eo" else ppe
    n = kw.pop("batch", None)
    if n:
        src = jnp.stack([src * (i + 1) for i in range(n)])
    acc = None
    if "acc_coeff" in kw:
        acc = ppe if which == "eo" else ppo
    kern = dslash_eo_pallas if which == "eo" else dslash_oe_pallas
    out = kern(upe, upo, src, xh=xh, psi_acc=acc, **kw)
    ref_hop = dslash_eo_ref if which == "eo" else dslash_oe_ref
    ref = ref_hop(upe, upo, src, xh=xh,
                  gamma5_in=kw.get("gamma5_in", False),
                  gamma5_out=kw.get("gamma5_out", False))
    if acc is not None:
        ig5 = lambda v: apply_igamma5_packed(v, HALF_S_AXIS)
        ref = (kw["hop_coeff"] * ref + kw["hop_twist"] * ig5(ref)
               + kw["acc_coeff"] * acc + kw["acc_twist"] * ig5(acc))
    assert out.shape == src.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
