"""Solver comparison table: iterations + collectives per iteration.

The paper motivates minimizing "global communications ... for total error
estimates"; ``pipecg`` restructures CG to ONE fused reduction per
iteration.  This bench counts all-reduces in the lowered HLO of one
iteration body per solver (8 fake devices, subprocess), plus CPU
convergence behaviour.

It also compares plain CGNR against the even-odd (Schur) preconditioned
``cgnr_eo`` on the same lattice — iterations and wall-clock µs — and the
``mpcg``-composed even-odd variant (bf16 inner solve, f32 reliable
updates): the paper's two central optimizations running together.

Beyond the CSV rows, ``run()`` writes **BENCH_solvers.json** — the
machine-readable perf trajectory (iterations, wall-clock, sites/s, and the
fused CG engine's per-iteration kernel/traffic shape).  CI uploads it and
``check_solver_regression.py`` guards the 4⁴ smoke-lattice iteration count
against ``benchmarks/BENCH_solvers_baseline.json``.

The ``batch_sweep`` section records the multi-RHS batched Schur solve for
N ∈ {1, 4, 8, 16} right-hand sides on the Pallas parity-dslash path —
sites·RHS/s per batch size, demonstrating the gauge-amortization win (one
gauge read feeds N spinors), with per-N iteration counts regression-guarded
by the same baseline file.

The ``eo_sharded`` section records the plan-driven sharded batched EO
Schur solve (8 fake host devices, pipelined CGNR with its single fused
psum per iteration) — its trip count is guarded too, pinning the
distributed fast path's Krylov math to the committed baseline.

The ``eo_smoke_tm`` section runs the SAME smoke problem through the
operator registry's second family (twisted-mass, site term
(m+4) + i·mu·gamma5) on both backends — guarding that the site-term
epilogue hook keeps the transport stack's Krylov math stable for a
non-Wilson operator.

Every timed entry is tagged with its ``backend`` (reference/pallas) and
``interpret`` mode, and reports the warm steady-state call (``us_warm``)
separately from the first, compile-inclusive call (``us_first``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks import bench_config

# Kept in sync with tests/test_eo.py's module fixture so the committed
# baseline guards the same solve the tier-1 suite runs.
SMOKE_DIMS = (4, 4, 4, 4)
SMOKE_SEED = 7
SMOKE_MASS = 0.1
SMOKE_TOL = 1e-6

# twisted-mass smoke row: same problem, second operator family (the
# registry's proof that the transport stack is operator-agnostic)
SMOKE_TM_MU = 0.25

# RHS-batch sizes for the gauge-amortization sweep (ISSUE 3 acceptance:
# sites·RHS/s must grow monotonically from N=1 to N>=8 on the Pallas path).
BATCH_SIZES = (1, 4, 8, 16)

# Iteration-cutting rows (blockcg_16rhs / eo_deflation) run the SAME 4⁴
# lattice and seed at a NEAR-CRITICAL mass: the 14-iteration smoke
# operator at mass 0.1 has no low-mode structure worth sharing or
# deflating, so the demonstration regime is where the Krylov space is
# deep (~120 iterations) and the paper's iteration budget actually hurts.
DEFL_MASS = -1.7
DEFL_TOL = 1e-6
DEFL_NEV = 32          # deflation-basis slots harvested
DEFL_M_MAX = 160       # Lanczos vectors recorded by the harvest solve
DEFL_HARVEST_TOL = 1e-8  # harvest solves past serving tol: deeper basis
BLOCK_NRHS = 16        # the ROADMAP item-2 headline batch


def _timed(fn):
    """((result, ...), first-call µs, warm µs) of fn().

    The FIRST call includes compilation (trace + lower + compile); the
    second call hits the jit cache and measures steady-state execution.
    Both are reported so the JSON separates compile cost from the warm
    throughput the paper's §5 tables are about.  ``fn`` must return a
    tuple whose first element is the jax output to drain
    (block_until_ready) — the shared timing protocol of every solve
    section below.
    """
    import jax

    t0 = time.time()
    jax.block_until_ready(fn()[0])  # compile-inclusive first call
    us_first = (time.time() - t0) * 1e6
    t0 = time.time()
    out = fn()
    jax.block_until_ready(out[0])
    return out, us_first, (time.time() - t0) * 1e6

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.core import LatticeShape
from repro.core import distributed as dist
from repro.data import lattice_problem
from repro.core.wilson import dslash_packed
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
lat = LatticeShape(4, 4, 4, 8)
up, pp = lattice_problem(lat, mass=0.3)
upd, ppd = dist.shard_lattice_fields(mesh, up, pp)

out = {}
for sv in ("cg", "pipecg", "mpcg"):
    x, st = dist.solve_wilson(mesh, upd, ppd, 0.3, solver=sv, tol=1e-6,
                              maxiter=500)
    res = dslash_packed(up, jax.device_get(x), 0.3) - pp
    rel = float(jnp.linalg.norm(res.ravel()) / jnp.linalg.norm(pp.ravel()))
    # count reductions in the whole compiled solve (while-body counted once
    # == per-iteration collective count for the loop)
    import functools
    f = functools.partial(dist.solve_wilson, mesh, solver=sv, tol=1e-6,
                          maxiter=500)
    txt = jax.jit(lambda u, b: dist.solve_wilson(mesh, u, b, 0.3, solver=sv,
                                                 tol=1e-6, maxiter=500)
                  ).lower(upd, ppd).compile().as_text()
    out[sv] = {"iters": int(st.iterations), "rel_res": rel,
               "all_reduce_in_body": txt.count(" all-reduce(")}
print("RESULT" + json.dumps(out))
"""


def _run_eo_comparison() -> list[tuple[str, float, str]]:
    """Plain CGNR vs even-odd Schur CGNR vs even-odd mpcg, same lattice."""
    import jax
    import jax.numpy as jnp
    from repro.core import (LatticeShape, cgnr, dslash, dslash_dagger,
                            random_gauge, random_spinor, solve_wilson_eo,
                            solve_wilson_eo_mp)

    lat = LatticeShape(4, 4, 4, 8)
    mass, tol = 0.1, 1e-6
    key = jax.random.PRNGKey(7)
    ku, kb = jax.random.split(key)
    u, b = random_gauge(ku, lat), random_spinor(kb, lat)

    def rel(x):
        r = dslash(u, x, mass) - b
        return float(jnp.linalg.norm(r.ravel()) / jnp.linalg.norm(b.ravel()))

    (x_f, st_f), _, us_f = _timed(lambda: cgnr(
        lambda v: dslash(u, v, mass), lambda v: dslash_dagger(u, v, mass),
        b, tol=tol, maxiter=1000))
    (x_e, st_e), _, us_e = _timed(lambda: solve_wilson_eo(
        u, b, mass, tol=tol, maxiter=1000))
    (x_m, st_m), _, us_m = _timed(lambda: solve_wilson_eo_mp(
        u, b, mass, tol=tol, inner_maxiter=100, max_outer=40))

    it_f, it_e = int(st_f.iterations), int(st_e.iterations)
    return [
        ("cgnr_full", us_f, f"iters={it_f};rel_res={rel(x_f):.2e}"),
        ("cgnr_eo", us_e,
         f"iters={it_e};rel_res={rel(x_e):.2e};"
         f"iter_ratio={it_e / max(it_f, 1):.2f};"
         f"speedup={us_f / max(us_e, 1e-9):.2f}x"),
        ("cgnr_eo_mpcg", us_m,
         f"inner={int(st_m.iterations)};outer={int(st_m.outer_iterations)};"
         f"rel_res={rel(x_m):.2e}"),
    ]


def _run_eo_smoke() -> dict:
    """Reference vs Pallas-fast-path Schur solve on the 4⁴ smoke lattice.

    This is the guarded trajectory entry: cgnr_eo iteration counts here
    feed ``BENCH_solvers.json`` and must not regress versus the committed
    baseline (see check_solver_regression.py).
    """
    import jax
    import jax.numpy as jnp
    from repro.core import (LatticeShape, random_gauge, random_spinor,
                            solve_wilson_eo)
    from repro.core.wilson import dslash

    lat = LatticeShape(*SMOKE_DIMS)
    key = jax.random.PRNGKey(SMOKE_SEED)
    ku, kb = jax.random.split(key)
    u, b = random_gauge(ku, lat), random_spinor(kb, lat)

    def rel(x):
        r = dslash(u, x, SMOKE_MASS) - b
        return float(jnp.linalg.norm(r.ravel()) / jnp.linalg.norm(b.ravel()))

    (x_ref, st_ref), us_ref_first, us_ref = _timed(lambda: solve_wilson_eo(
        u, b, SMOKE_MASS, tol=SMOKE_TOL, maxiter=1000))
    (x_pal, st_pal), us_pal_first, us_pal = _timed(lambda: solve_wilson_eo(
        u, b, SMOKE_MASS, tol=SMOKE_TOL, maxiter=1000,
        use_pallas=True, interpret=True))

    def sites_per_s(st, us):
        return lat.volume * int(st.iterations) / max(us / 1e6, 1e-12)

    # compiled-lowering row (launch_bench.sh / --compiled): the SAME solve
    # through the kernels' compiled path — on CPU the XLA half-spinor
    # lowering, on device Mosaic.  Not iteration-guarded (compiled
    # reductions may reorder; counts can differ by roundoff), the perf
    # trajectory consumes its warm timing.
    compiled_entries = []
    if bench_config.is_compiled():
        (x_cmp, st_cmp), us_cmp_first, us_cmp = _timed(
            lambda: solve_wilson_eo(
                u, b, SMOKE_MASS, tol=SMOKE_TOL, maxiter=1000,
                use_pallas=True, interpret=False))
        compiled_entries.append({
            "name": "cgnr_eo_pallas_compiled", "backend": "pallas",
            "interpret": False, "iters": int(st_cmp.iterations),
            "matvecs": int(st_cmp.matvecs), "us_first": us_cmp_first,
            "us_warm": us_cmp, "rel_res": rel(x_cmp),
            "sites_per_s": sites_per_s(st_cmp, us_cmp)})

    return {
        "lattice": str(lat), "mass": SMOKE_MASS, "tol": SMOKE_TOL,
        "seed": SMOKE_SEED,
        "cgnr_eo_iters": int(st_ref.iterations),
        "cgnr_eo_pallas_iters": int(st_pal.iterations),
        "cgnr_eo_matvecs": int(st_ref.matvecs),
        "cgnr_eo_pallas_matvecs": int(st_pal.matvecs),
        "cgnr_eo_us": us_ref, "cgnr_eo_pallas_us": us_pal,
        "rel_res_ref": rel(x_ref), "rel_res_pallas": rel(x_pal),
        "sites_per_s_ref": sites_per_s(st_ref, us_ref),
        "sites_per_s_pallas": sites_per_s(st_pal, us_pal),
        "pallas_interpret_mode": True,
        # per-backend tagged entries: warm steady-state timing separated
        # from the first (compile-inclusive) call
        "entries": [
            {"name": "cgnr_eo", "backend": "reference", "interpret": None,
             "iters": int(st_ref.iterations),
             "matvecs": int(st_ref.matvecs), "us_first": us_ref_first,
             "us_warm": us_ref},
            {"name": "cgnr_eo_pallas", "backend": "pallas",
             "interpret": True, "iters": int(st_pal.iterations),
             "matvecs": int(st_pal.matvecs), "us_first": us_pal_first,
             "us_warm": us_pal},
        ] + compiled_entries,
    }


def _run_eo_smoke_tm() -> dict:
    """Twisted-mass EO Schur smoke: the registry's second operator family.

    Same lattice/seed/tolerance as ``eo_smoke``, site term
    (m+4) + i·mu·gamma5 — the iteration counts are the guarded signal
    that the operator-registry indirection (site-term epilogues folded
    into the SAME four hop-kernel launches) keeps the Krylov math stable
    on both backends.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import (LatticeShape, SolverPlan, random_gauge,
                            random_spinor, solve_plan)
    from repro.core.operators import dslash_g

    lat = LatticeShape(*SMOKE_DIMS)
    key = jax.random.PRNGKey(SMOKE_SEED)
    ku, kb = jax.random.split(key)
    u, b = random_gauge(ku, lat), random_spinor(kb, lat)

    def rel(x):
        r = dslash_g(u, x, SMOKE_MASS, twist=SMOKE_TM_MU) - b
        return float(jnp.linalg.norm(r.ravel()) / jnp.linalg.norm(b.ravel()))

    def plan(backend):
        return SolverPlan(operator="eo-schur",
                          operator_family="twisted-mass", mu=SMOKE_TM_MU,
                          backend=backend,
                          interpret=True if backend == "pallas" else None)

    (x_ref, st_ref), us_ref_first, us_ref = _timed(lambda: solve_plan(
        plan("reference"), u, b, SMOKE_MASS, tol=SMOKE_TOL, maxiter=1000))
    (x_pal, st_pal), us_pal_first, us_pal = _timed(lambda: solve_plan(
        plan("pallas"), u, b, SMOKE_MASS, tol=SMOKE_TOL, maxiter=1000))

    return {
        "lattice": str(lat), "mass": SMOKE_MASS, "mu": SMOKE_TM_MU,
        "tol": SMOKE_TOL, "seed": SMOKE_SEED, "operator": "twisted-mass",
        "cgnr_eo_tm_iters": int(st_ref.iterations),
        "cgnr_eo_tm_pallas_iters": int(st_pal.iterations),
        "cgnr_eo_tm_matvecs": int(st_ref.matvecs),
        "cgnr_eo_tm_pallas_matvecs": int(st_pal.matvecs),
        "rel_res_ref": rel(x_ref), "rel_res_pallas": rel(x_pal),
        "pallas_interpret_mode": True,
        "entries": [
            {"name": "cgnr_eo_tm", "backend": "reference",
             "interpret": None, "iters": int(st_ref.iterations),
             "matvecs": int(st_ref.matvecs),
             "us_first": us_ref_first, "us_warm": us_ref},
            {"name": "cgnr_eo_tm_pallas", "backend": "pallas",
             "interpret": True, "iters": int(st_pal.iterations),
             "matvecs": int(st_pal.matvecs),
             "us_first": us_pal_first, "us_warm": us_pal},
        ],
    }


def _run_batch_sweep() -> dict:
    """Multi-RHS batched Schur solve: throughput vs batch size N.

    One gauge field, N random right-hand sides, one masked CG loop on the
    Pallas parity-dslash path: every matvec reads each gauge plane once
    and streams all N spinor planes through it, so the per-RHS cost of
    the launch/transport overhead falls like 1/N — sites·RHS/s should
    rise monotonically with N until compute dominates.  Per-N iteration
    counts feed the committed baseline (deterministic seed), wall-clock
    is informational.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import (LatticeShape, random_gauge, random_spinor,
                            solve_wilson_eo_batched)
    from repro.core.wilson import dslash

    lat = LatticeShape(*SMOKE_DIMS)
    key = jax.random.PRNGKey(SMOKE_SEED)
    ku, kb = jax.random.split(key)
    u = random_gauge(ku, lat)
    n_max = max(BATCH_SIZES)
    b_all = jnp.stack([random_spinor(jax.random.fold_in(kb, i), lat)
                       for i in range(n_max)])

    entries = []
    for n in BATCH_SIZES:
        b_n = b_all[:n]
        (x, st), us_first, us = _timed(lambda b=b_n: solve_wilson_eo_batched(
            u, b, SMOKE_MASS, tol=SMOKE_TOL, maxiter=1000,
            use_pallas=True, interpret=True))
        res = jax.vmap(lambda xx, bb: dslash(u, xx, SMOKE_MASS) - bb)(x, b_n)
        rel = float(jnp.max(
            jnp.linalg.norm(res.reshape(n, -1), axis=1)
            / jnp.linalg.norm(b_n.reshape(n, -1), axis=1)))
        iters = int(st.iterations)
        mv = jax.device_get(st.matvecs)
        entries.append({
            "n_rhs": n, "iters": iters, "us_warm": us, "us_first": us_first,
            "backend": "pallas", "interpret": True,
            # per-RHS operator applications: max over lanes matches the
            # "iters" convention; the SUM is the gauge-amortization ledger
            "matvecs": int(mv.max()), "matvecs_total": int(mv.sum()),
            "max_rel_res": rel, "all_converged": bool(jnp.all(st.converged)),
            "sites_rhs_per_s": lat.volume * n * iters / max(us / 1e6, 1e-12),
        })
    return {
        "lattice": str(lat), "mass": SMOKE_MASS, "tol": SMOKE_TOL,
        "seed": SMOKE_SEED, "pallas_interpret_mode": True,
        "backend": "pallas", "interpret": True,
        "entries": entries,
    }


def _run_blockcg() -> dict:
    """Block CGNR vs 16 independent solves: the shared-Krylov-space win.

    Same 4⁴ lattice/seed as the smoke rows, near-critical mass (see
    DEFL_MASS).  The guarded headline (ROADMAP item 2): TOTAL matvecs for
    16 RHS through one block solve must come in well under 16× the
    single-RHS count — the block search space lets every lane ride the
    others' directions, so the block iteration count (= each lane's
    matvec count) drops far below the single-RHS iteration count.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import (LatticeShape, random_gauge, random_spinor)
    from repro.core import plan as plan_mod
    from repro.core.wilson import dslash

    lat = LatticeShape(*SMOKE_DIMS)
    key = jax.random.PRNGKey(SMOKE_SEED)
    ku, kb = jax.random.split(key)
    u = random_gauge(ku, lat)
    b_all = jnp.stack([random_spinor(jax.random.fold_in(kb, i), lat)
                       for i in range(BLOCK_NRHS)])

    single = plan_mod.SolverPlan(operator="eo-schur", backend="reference")
    (x_s, st_s), _, us_s = _timed(lambda: plan_mod.solve(
        single, u, b_all[0], DEFL_MASS, tol=DEFL_TOL, maxiter=500))

    block = plan_mod.SolverPlan(operator="eo-schur", backend="reference",
                                solver="blockcg", nrhs=BLOCK_NRHS)
    (x_b, st_b), us_b_first, us_b = _timed(lambda: plan_mod.solve(
        block, u, b_all, DEFL_MASS, tol=DEFL_TOL, maxiter=500))

    res = jax.vmap(lambda xx, bb: dslash(u, xx, DEFL_MASS) - bb)(x_b, b_all)
    rel = float(jnp.max(
        jnp.linalg.norm(res.reshape(BLOCK_NRHS, -1), axis=1)
        / jnp.linalg.norm(b_all.reshape(BLOCK_NRHS, -1), axis=1)))
    mv = jax.device_get(st_b.matvecs)
    total = int(mv.sum())
    total_single16 = BLOCK_NRHS * int(st_s.matvecs)
    return {
        "lattice": str(lat), "mass": DEFL_MASS, "tol": DEFL_TOL,
        "seed": SMOKE_SEED, "n_rhs": BLOCK_NRHS, "backend": "reference",
        "single_iters": int(st_s.iterations),
        "single_matvecs": int(st_s.matvecs),
        "blockcg_iters": int(st_b.iterations),
        "blockcg_matvecs": int(mv.max()),
        "total_matvecs": total,
        "total_matvecs_single16": total_single16,
        "matvec_ratio": total / max(total_single16, 1),
        "max_rel_res": rel,
        "all_converged": bool(jnp.all(st_b.converged)),
        "all_verified": bool(jnp.all(st_b.verified)),
        "us_warm": us_b, "us_first": us_b_first, "us_single_warm": us_s,
    }


def _run_eo_deflation() -> dict:
    """EigCG deflation: harvest on the first solve, deflate the second.

    The harvest solve runs past serving tolerance (DEFL_HARVEST_TOL) to
    record a deep Krylov space, condenses it into DEFL_NEV approximate
    low modes, and every LATER solve on this gauge field starts from the
    Galerkin projection — the guarded signal is the strict iteration drop
    of the deflated solve versus the identical undeflated one, and the
    deflated solve still passing true-residual verification against the
    ORIGINAL system.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import (LatticeShape, random_gauge, random_spinor)
    from repro.core import plan as plan_mod

    lat = LatticeShape(*SMOKE_DIMS)
    key = jax.random.PRNGKey(SMOKE_SEED)
    ku, kb = jax.random.split(key)
    u = random_gauge(ku, lat)
    b0 = random_spinor(jax.random.fold_in(kb, 0), lat)
    b1 = random_spinor(jax.random.fold_in(kb, 1), lat)

    plan = plan_mod.SolverPlan(operator="eo-schur", backend="reference")
    _, st_h, basis = plan_mod.harvest_deflation(
        plan, u, b0, DEFL_MASS, tol=DEFL_HARVEST_TOL, maxiter=500,
        nev=DEFL_NEV, m_max=DEFL_M_MAX, verify_tol=DEFL_TOL)

    (x_u, st_u), _, us_u = _timed(lambda: plan_mod.solve(
        plan, u, b1, DEFL_MASS, tol=DEFL_TOL, maxiter=500))
    (x_d, st_d), us_d_first, us_d = _timed(lambda: plan_mod.solve(
        plan, u, b1, DEFL_MASS, tol=DEFL_TOL, maxiter=500,
        deflation=basis))

    return {
        "lattice": str(lat), "mass": DEFL_MASS, "tol": DEFL_TOL,
        "seed": SMOKE_SEED, "backend": "reference",
        "nev": DEFL_NEV, "m_max": DEFL_M_MAX,
        "harvest_tol": DEFL_HARVEST_TOL,
        "harvest_iters": int(st_h.iterations),
        "harvest_matvecs": int(st_h.matvecs),
        "harvest_verified": bool(st_h.verified),
        "undeflated_iters": int(st_u.iterations),
        "undeflated_matvecs": int(st_u.matvecs),
        "deflated_iters": int(st_d.iterations),
        "deflated_matvecs": int(st_d.matvecs),
        "iteration_drop": int(st_u.iterations) - int(st_d.iterations),
        "deflated_converged": bool(st_d.converged),
        "deflated_verified": bool(st_d.verified),
        "us_undeflated_warm": us_u, "us_deflated_warm": us_d,
        "us_deflated_first": us_d_first,
    }


_SHARDED_EO_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import jax, jax.numpy as jnp
from repro.core import LatticeShape, random_gauge, random_spinor
from repro.core import plan as plan_mod
from repro.core.wilson import dslash

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
lat = LatticeShape(%(t)d, %(z)d, %(y)d, 8)
mass, tol, seed, n = %(mass)r, %(tol)r, %(seed)d, %(n)d
ku, kb = jax.random.split(jax.random.PRNGKey(seed))
u = random_gauge(ku, lat)
b = jnp.stack([random_spinor(jax.random.fold_in(kb, i), lat)
               for i in range(n)])
p = plan_mod.SolverPlan(operator="eo-schur", backend="reference",
                        solver="pipecg", nrhs=n, mesh=mesh)
t0 = time.time()
x, st = plan_mod.solve(p, u, b, mass, tol=tol, maxiter=500)
jax.block_until_ready(x)             # compile-inclusive first call
us_first = (time.time() - t0) * 1e6
t0 = time.time()
x, st = plan_mod.solve(p, u, b, mass, tol=tol, maxiter=500)
jax.block_until_ready(x)
us = (time.time() - t0) * 1e6
# gather to the host: dslash is the global-view operator
res = jax.vmap(lambda xx, bb: dslash(u, xx, mass) - bb)(jax.device_get(x), b)
rel = float(jnp.max(jnp.linalg.norm(res.reshape(n, -1), axis=1)
                    / jnp.linalg.norm(b.reshape(n, -1), axis=1)))
out = {"lattice": str(lat), "mass": mass, "tol": tol, "seed": seed,
       "n_rhs": n, "mesh": "2x2x2", "solver": "pipecg",
       "backend": "reference", "interpret": None,
       "iters": int(st.iterations),
       "rhs_iters": [int(v) for v in st.rhs_iterations],
       "matvecs": int(jnp.max(st.matvecs)),
       "matvecs_total": int(jnp.sum(st.matvecs)),
       "max_rel_res": rel, "all_converged": bool(jnp.all(st.converged)),
       "us_warm": us, "us_first": us_first,
       "sites_rhs_per_s": lat.volume * n * int(st.iterations)
                          / max(us / 1e6, 1e-12)}
print("RESULT" + json.dumps(out))
"""


def _run_eo_sharded() -> dict:
    """Sharded batched EO Schur pipelined CGNR on an 8-way host mesh.

    The iteration count is the guarded trajectory signal (deterministic
    seed; the fused single-psum reduction must not change the Krylov
    math); wall-clock on 8 fake CPU devices is informational only.
    Subprocess because the host-device count must be set before jax
    initializes.
    """
    script = _SHARDED_EO_SCRIPT % dict(
        t=SMOKE_DIMS[0], z=SMOKE_DIMS[1], y=SMOKE_DIMS[2],
        mass=SMOKE_MASS, tol=SMOKE_TOL, seed=SMOKE_SEED, n=2)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=560)
    if r.returncode != 0:
        raise RuntimeError("sharded eo bench failed: " + r.stderr[-500:])
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def _run_ckpt_overhead() -> dict:
    """Segmented (checkpointed) vs one-shot smoke solve (DESIGN.md §11).

    The guarded signal is algorithmic, like every other row: the
    segmented solve must run the SAME number of iterations and produce a
    BITWISE-identical iterate — segmenting only augments the while-loop's
    stopping condition, never its body.  The wall-clock cost per snapshot
    (host sync + npz write + prune) is recorded for trend context but not
    gated; CI runner I/O is noise.
    """
    import tempfile

    import jax
    import numpy as np
    from repro.core import LatticeShape, random_gauge, random_spinor
    from repro.core import plan as plan_mod

    lat = LatticeShape(*SMOKE_DIMS)
    key = jax.random.PRNGKey(SMOKE_SEED)
    ku, kb = jax.random.split(key)
    u, b = random_gauge(ku, lat), random_spinor(kb, lat)
    plan = plan_mod.SolverPlan(operator="eo-schur")
    every = 5

    (x_ref, st_ref), _, us_ref = _timed(lambda: plan_mod.solve(
        plan, u, b, SMOKE_MASS, tol=SMOKE_TOL, maxiter=1000))
    with tempfile.TemporaryDirectory() as d:
        policy = plan_mod.CheckpointPolicy(dir=d, every_iters=every)
        (x_seg, st_seg), _, us_seg = _timed(lambda: plan_mod.solve(
            plan, u, b, SMOKE_MASS, tol=SMOKE_TOL, maxiter=1000,
            checkpoint=policy))
    iters = int(st_ref.iterations)
    segments = -(-iters // every)
    return {
        "lattice": str(lat), "mass": SMOKE_MASS, "tol": SMOKE_TOL,
        "seed": SMOKE_SEED, "every_iters": every,
        "iters": iters,
        "matvecs": int(st_ref.matvecs),
        "iters_checkpointed": int(st_seg.iterations),
        "matvecs_checkpointed": int(st_seg.matvecs),
        "bitwise_equal": bool(np.array_equal(np.asarray(x_seg),
                                             np.asarray(x_ref))),
        "segments": segments,
        "us_oneshot": us_ref, "us_checkpointed": us_seg,
        "overhead_us_per_snapshot": (max(us_seg - us_ref, 0.0)
                                     / max(segments, 1)),
    }


def _fused_engine_shape() -> dict:
    """Per-iteration kernel count and HBM traffic shape of the fused CG.

    Inspects the jaxpr of ONE fused iteration body: the vector algebra
    must be exactly two pallas_call launches — the x/r/||r||² triad
    (4 vector reads, 2 vector writes + negligible partials) and the
    direction xpay (2 reads, 1 write) — versus 7 reads + 3 writes for the
    naive jnp expression chain.
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels.cg_fused import fused_engine
    from repro.testing import pallas_call_eqns

    n = (256, 128)
    update, xpay = fused_engine(interpret=True)

    def body(x, r, p, ap, rs):
        alpha = rs / jnp.sum(p * ap)
        x, r, rs_new = update(alpha, x, r, p, ap)
        p = xpay(rs_new / rs, r, p)
        return x, r, p, rs_new

    args = [jnp.zeros(n, jnp.float32)] * 4 + [jnp.float32(1.0)]
    calls = pallas_call_eqns(jax.make_jaxpr(body)(*args))
    size = n[0] * n[1]

    def shape_of(eqn):
        reads = sum(1 for v in eqn.invars
                    if getattr(v.aval, "size", 0) == size)
        writes = sum(1 for v in eqn.outvars
                     if getattr(v.aval, "size", 0) == size)
        return reads, writes

    shapes = sorted((shape_of(e) for e in calls), reverse=True)
    out = {"pallas_calls_per_iteration": len(calls),
           "backend": "pallas", "interpret": True,
           "naive_traffic": "7R+3W",
           "kernel_traffic": "+".join(f"{r}R{w}W" for r, w in shapes)}
    if len(shapes) == 2:
        (out["update_reads"], out["update_writes"]) = shapes[0]
        (out["xpay_reads"], out["xpay_writes"]) = shapes[1]
    return out


def run() -> list[tuple[str, float, str]]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560)
    if r.returncode != 0:
        rows = [("solver_comparison", -1.0, "FAILED:" + r.stderr[-200:])]
    else:
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT")][-1]
        d = json.loads(line[len("RESULT"):])
        rows = []
        for sv, v in d.items():
            rows.append((f"solver_{sv}", float(v["iters"]),
                         f"rel_res={v['rel_res']:.2e};"
                         f"all_reduces={v['all_reduce_in_body']}"))
    try:
        rows.extend(_run_eo_comparison())
    except Exception as e:  # keep the subprocess rows; degrade like above
        rows.append(("eo_comparison", -1.0, f"FAILED:{e!r:.200}"))

    report = {"schema": 1, "bench": "solvers",
              "generated_by": "benchmarks/bench_solvers.py"}
    try:
        smoke = _run_eo_smoke()
        report["eo_smoke"] = smoke
        rows.append(("cgnr_eo_pallas_4x4x4x4", smoke["cgnr_eo_pallas_us"],
                     f"iters={smoke['cgnr_eo_pallas_iters']};"
                     f"rel_res={smoke['rel_res_pallas']:.2e};"
                     f"sites_per_s={smoke['sites_per_s_pallas']:.0f}"))
    except Exception as e:
        rows.append(("eo_smoke", -1.0, f"FAILED:{e!r:.200}"))
    try:
        tm = _run_eo_smoke_tm()
        report["eo_smoke_tm"] = tm
        for e in tm["entries"]:
            rows.append((e["name"] + "_4x4x4x4", e["us_warm"],
                         f"iters={e['iters']};backend={e['backend']};"
                         f"us_first={e['us_first']:.0f}"))
    except Exception as e:
        rows.append(("eo_smoke_tm", -1.0, f"FAILED:{e!r:.200}"))
    try:
        sweep = _run_batch_sweep()
        report["batch_sweep"] = sweep
        for e in sweep["entries"]:
            rows.append((f"cgnr_eo_batched_n{e['n_rhs']}", e["us_warm"],
                         f"iters={e['iters']};"
                         f"max_rel_res={e['max_rel_res']:.2e};"
                         f"sites_rhs_per_s={e['sites_rhs_per_s']:.0f}"))
    except Exception as e:
        rows.append(("batch_sweep", -1.0, f"FAILED:{e!r:.200}"))
    try:
        blk = _run_blockcg()
        report["blockcg_16rhs"] = blk
        rows.append((f"blockcg_n{blk['n_rhs']}", blk["us_warm"],
                     f"iters={blk['blockcg_iters']};"
                     f"total_matvecs={blk['total_matvecs']};"
                     f"vs_16x_single={blk['matvec_ratio']:.2f}x"))
    except Exception as e:
        rows.append(("blockcg_16rhs", -1.0, f"FAILED:{e!r:.200}"))
    try:
        dfl = _run_eo_deflation()
        report["eo_deflation"] = dfl
        rows.append(("eo_deflation", dfl["us_deflated_warm"],
                     f"iters={dfl['deflated_iters']}"
                     f"(undeflated={dfl['undeflated_iters']});"
                     f"harvest={dfl['harvest_iters']};"
                     f"nev={dfl['nev']}"))
    except Exception as e:
        rows.append(("eo_deflation", -1.0, f"FAILED:{e!r:.200}"))
    try:
        sh = _run_eo_sharded()
        report["eo_sharded"] = sh
        rows.append((f"cgnr_eo_sharded_n{sh['n_rhs']}", sh["us_warm"],
                     f"iters={sh['iters']};mesh={sh['mesh']};"
                     f"max_rel_res={sh['max_rel_res']:.2e};"
                     f"sites_rhs_per_s={sh['sites_rhs_per_s']:.0f}"))
    except Exception as e:
        rows.append(("eo_sharded", -1.0, f"FAILED:{e!r:.200}"))
    try:
        ck = _run_ckpt_overhead()
        report["ckpt_overhead"] = ck
        rows.append(("cgnr_eo_checkpointed_4x4x4x4", ck["us_checkpointed"],
                     f"iters={ck['iters_checkpointed']};"
                     f"bitwise_equal={ck['bitwise_equal']};"
                     f"segments={ck['segments']};"
                     f"us_per_snapshot="
                     f"{ck['overhead_us_per_snapshot']:.0f}"))
    except Exception as e:
        rows.append(("ckpt_overhead", -1.0, f"FAILED:{e!r:.200}"))
    try:
        shape = _fused_engine_shape()
        report["fused_engine"] = shape
        rows.append(("cg_fused_engine", float(
            shape["pallas_calls_per_iteration"]),
            f"traffic={shape['kernel_traffic']};"
            f"naive={shape['naive_traffic']}"))
    except Exception as e:
        rows.append(("fused_engine_shape", -1.0, f"FAILED:{e!r:.200}"))
    report["rows"] = [list(row) for row in rows]

    # Uniform labels + achieved-vs-roofline bandwidth on every tagged
    # entry (ISSUE 10).  The traffic model: one Schur matvec streams
    # ~one full-lattice dslash's §6 traffic ((144/N + 48)·4 bytes/site
    # over the two half-lattice hop passes), a LOWER bound that ignores
    # the CG vector engine's 48 reals/site — so bw_fraction here is
    # conservative.  Entries keep their own interpret/backend tags (a
    # row that deliberately ran the other lowering says so).
    from benchmarks.roofline import dslash_intensity
    smoke_volume = 1
    for d in SMOKE_DIMS:
        smoke_volume *= d

    def _annotate(e):
        n = int(e.get("n_rhs", 1))
        mv = e.get("matvecs")
        if mv and e.get("us_warm"):
            model = dslash_intensity(n_rhs=n, dtype_bytes=4)
            total = model["bytes_per_site"] * smoke_volume * n * mv
            bw = total / (e["us_warm"] / 1e6) / 1e9
            e = {**e, "model_bw_gbs": bw,
                 "bw_fraction": bench_config.bw_fraction(bw)}
        return bench_config.label_entry(e)

    for sec_name in ("eo_smoke", "eo_smoke_tm", "batch_sweep"):
        sec = report.get(sec_name)
        if sec and "entries" in sec:
            sec["entries"] = [_annotate(e) for e in sec["entries"]]
    report["labels"] = bench_config.labels()
    report["launch"] = bench_config.launch_env()
    try:
        report["peak_bw_gbs"] = bench_config.peak_bandwidth_gbs()
    except Exception:
        pass

    path = os.environ.get("BENCH_SOLVERS_JSON", "BENCH_solvers.json")
    try:
        with open(path, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError as e:
        rows.append(("bench_solvers_json", -1.0, f"FAILED:{e!r:.120}"))
    return rows
