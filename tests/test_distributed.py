"""Distributed lattice solver + sharded train step, on 8 fake CPU devices.

Runs in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count=8
(the main pytest process must keep the default single device)."""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.core import *
from repro.core import distributed as dist
from repro.core.wilson import dslash_packed

out = {}
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
lat = LatticeShape(4, 4, 4, 8)
ku, kp = jax.random.split(jax.random.PRNGKey(3))
U = random_gauge(ku, lat); psi = random_spinor(kp, lat); m = 0.3
up, pp = pack_gauge(U), pack_spinor(psi)
upd, ppd = dist.shard_lattice_fields(mesh, up, pp)

psi_spec, gauge_spec, sharded = dist.lattice_specs(mesh)
f = jax.jit(jax.shard_map(lambda u, p: dist.dslash_halo(u, p, m, sharded),
                      mesh=mesh, in_specs=(gauge_spec, psi_spec),
                      out_specs=psi_spec))
err = float(jnp.max(jnp.abs(f(upd, ppd) - dslash_packed(up, pp, m))))
out["halo_dslash_err"] = err

# the TPU path: Pallas plane-streaming kernel as the bulk stencil
fk = jax.jit(jax.shard_map(
    lambda u, p: dist.dslash_halo(u, p, m, sharded, use_pallas=True),
    mesh=mesh, in_specs=(gauge_spec, psi_spec), out_specs=psi_spec,
    check_vma=False))
out["halo_pallas_err"] = float(
    jnp.max(jnp.abs(fk(upd, ppd) - dslash_packed(up, pp, m))))

for sv in ("cg", "pipecg", "mpcg"):
    x, st = dist.solve_wilson(mesh, upd, ppd, m, solver=sv, tol=1e-6,
                              maxiter=500)
    res = dslash_packed(up, jax.device_get(x), m) - pp
    rel = float(jnp.linalg.norm(res.ravel()) / jnp.linalg.norm(pp.ravel()))
    out[sv] = {"iters": int(st.iterations), "rel_res": rel,
               "converged": bool(st.converged)}

# sharded LM train step on a debug mesh
from repro import configs
from repro.models import steps as S
from repro.optim import AdamWConfig
from repro.data import SyntheticLM
from jax.sharding import NamedSharding, PartitionSpec as P
# the LM steps shard by GSPMD propagation: Auto axes
mesh2 = jax.make_mesh((2, 2), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = configs.get_smoke("glm4-9b")
opt = AdamWConfig(lr=1e-3)
state = S.init_train_state(cfg, jax.random.PRNGKey(0), opt)
specs = S.state_specs(cfg, jax.eval_shape(lambda: state))
shardings = jax.tree.map(lambda sp: NamedSharding(mesh2, sp), specs,
                         is_leaf=lambda x: isinstance(x, P))
state = jax.device_put(state, shardings)
fn = jax.jit(S.make_train_step(cfg, opt, mesh=mesh2,
                               compute_dtype=jnp.float32),
             in_shardings=(shardings, None),
             out_shardings=(shardings, None))
data = SyntheticLM(cfg, batch=4, seq_len=32)
losses = []
for i in range(8):
    state, metr = fn(state, data.batch_at(i))
    losses.append(float(metr["loss"]))
out["sharded_train"] = {"first": losses[0], "last": losses[-1]}

# --- sharded even-odd Schur fast path (plan-driven) --------------------
from repro.core import plan as plan_mod
from repro.core import solvers
from repro.core.lattice import split_eo, split_eo_gauge
from repro.kernels.wilson_dslash import ops as wops
from repro.testing import while_body_psum_counts

N = 2
bb = jnp.stack([random_spinor(jax.random.fold_in(kp, i), lat)
                for i in range(N)])
pl_eo = plan_mod.SolverPlan(operator="eo-schur", backend="reference",
                            solver="pipecg", nrhs=N, mesh=mesh)
xsh, stsh = plan_mod.solve(pl_eo, U, bb, m, tol=1e-6, maxiter=500)
# gather to the host: the checks below apply the global-view operator
xsh = jax.device_get(xsh)
xs1, sts1 = solve_wilson_eo_batched(U, bb, m, tol=1e-6, maxiter=500,
                                    use_pallas=False)
res = jax.vmap(lambda xx, bv: dslash(U, xx, m) - bv)(xsh, bb)
rels = (jnp.linalg.norm(res.reshape(N, -1), axis=1)
        / jnp.linalg.norm(bb.reshape(N, -1), axis=1))
out["eo_sharded"] = {
    "iters": int(stsh.iterations),
    "rhs_iters": [int(v) for v in stsh.rhs_iterations],
    "all_converged": bool(jnp.all(stsh.converged)),
    "max_rel_res": float(jnp.max(rels)),
    "max_dev_vs_single_device": float(jnp.max(jnp.abs(xsh - xs1))),
}

# the Pallas parity kernels as the sharded bulk stencil: one halo matvec
# against the global single-device operator
xh = lat.x // 2
u_e, u_o = split_eo_gauge(U)
upe, upo = pack_half_gauge(u_e), pack_half_gauge(u_o)
pe = pack_half_spinor(split_eo(psi)[0])
psi_spec2, gauge_spec2, sharded2 = dist.half_lattice_specs(mesh)
upe_d = jax.device_put(upe, NamedSharding(mesh, gauge_spec2))
upo_d = jax.device_put(upo, NamedSharding(mesh, gauge_spec2))
pe_d = jax.device_put(pe, NamedSharding(mesh, psi_spec2))
fk = jax.jit(jax.shard_map(
    lambda ue, uo, p: dist.schur_normal_op_halo(ue, uo, p, m, sharded2,
                                                xh=xh, use_pallas=True),
    mesh=mesh, in_specs=(gauge_spec2, gauge_spec2, psi_spec2),
    out_specs=psi_spec2, check_vma=False))
ref = wops.schur_normal_op(upe, upo, pe, m, xh=xh, use_pallas=False)
out["eo_halo_pallas_err"] = float(jnp.max(jnp.abs(
    fk(upe_d, upo_d, pe_d) - ref)))

# the fused-reduction contract: the pipelined sharded CGNR's while body
# holds EXACTLY ONE psum, for the whole batch (jaxpr-level, no execution)
bspec = P(None, *psi_spec2)
pbe = pack_half_spinor(jax.vmap(split_eo)(bb)[0])
pbo = pack_half_spinor(jax.vmap(split_eo)(bb)[1])
kkw = dict(sharded=sharded2, xh=xh, use_pallas=False)
pdot, pnorm2 = dist.make_psum_dots(mesh, batched=True)
fused = dist.make_fused_psum_dots(mesh, batched=True)

def local_pipecg(ue, uo, be, bo):
    a_hat = lambda v: dist.schur_normal_op_halo(ue, uo, v, m, **kkw)
    d_eo = lambda v: dist.parity_hop_halo("eo", ue, uo, v, **kkw)
    ddag = lambda v: dist.schur_op_halo(ue, uo, v, m, dagger=True, **kkw)
    rhs = ddag(be - d_eo(bo / (m + 4.0)))
    x_e, _ = solvers.pipecg(a_hat, rhs, tol=1e-6, maxiter=500,
                            dot=pdot, norm2=pnorm2, batched=True,
                            fused_dots=fused)
    return x_e

jx = jax.make_jaxpr(jax.shard_map(
    local_pipecg, mesh=mesh,
    in_specs=(gauge_spec2, gauge_spec2, bspec, bspec),
    out_specs=bspec, check_vma=False))(upe, upo, pbe, pbo)
out["pipecg_psums_per_iteration"] = while_body_psum_counts(jx)

print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def test_halo_dslash_matches_global(results):
    assert results["halo_dslash_err"] < 1e-5


def test_halo_pallas_kernel_matches_global(results):
    assert results["halo_pallas_err"] < 1e-4


@pytest.mark.parametrize("solver", ["cg", "pipecg", "mpcg"])
def test_distributed_solvers_converge(results, solver):
    r = results[solver]
    assert r["converged"] and r["rel_res"] < 1e-4, r


def test_sharded_train_step_learns(results):
    r = results["sharded_train"]
    assert r["last"] < r["first"]


def test_sharded_eo_schur_matches_single_device(results):
    """A plan-driven sharded batched EO Schur solve converges per RHS and
    matches the single-device solve_wilson_eo_batched iterates to <=1e-5
    (float reassociation across the psum tree is the only difference)."""
    r = results["eo_sharded"]
    assert r["all_converged"], r
    assert r["max_rel_res"] < 1e-4, r
    assert r["max_dev_vs_single_device"] <= 1e-5, r
    assert all(n <= r["iters"] for n in r["rhs_iters"])
    assert max(r["rhs_iters"]) == r["iters"]


def test_sharded_eo_pallas_bulk_kernel_matches_global(results):
    """schur_normal_op_halo with the Pallas parity kernels as the bulk
    stencil reproduces the global single-device Schur normal operator."""
    assert results["eo_halo_pallas_err"] < 1e-4


def test_sharded_pipecg_is_one_psum_per_iteration(results):
    """The fused-reduction contract (DESIGN.md §7): the sharded pipelined
    CGNR's while-loop body contains EXACTLY ONE psum — gamma and delta
    for every RHS of the batch travel in a single stacked collective."""
    assert results["pipecg_psums_per_iteration"] == [1]
