"""Lattice solver launcher — the paper's workload end-to-end, plan-driven.

Every invocation builds ONE :class:`repro.core.plan.SolverPlan` and
executes it — the CLI axes map 1:1 onto plan fields, including the
operator registry (:mod:`repro.core.operators`):

    python -m repro.launch.solve --lattice 4x4x4x8 --solver mpcg
    python -m repro.launch.solve --parity eo --backend pallas
    python -m repro.launch.solve --parity eo --backend pallas --nrhs 8
    python -m repro.launch.solve --parity eo --operator twisted-mass \
        --mu 0.25                # second operator, same transport stack
    python -m repro.launch.solve --parity eo --nrhs 4 --mesh debug \
        --solver pipecg          # sharded batched Schur, 1 psum/iteration

Builds a random SU(3) gauge configuration, solves D x = b (for one RHS or
an ``--nrhs`` batch) via the planned CG variant, and reports iterations —
per right-hand side for batched solves — plus residuals and derived FLOP
rates using the paper's 1320 flop/site dslash convention (§5).

The compound legacy solver names (``cg-pallas``, ``cgnr_eo``, ...) are
gone: their axes are orthogonal plan fields now (``--parity``,
``--backend``, ``--nrhs``), so ``--solver`` names ONLY the Krylov loop /
precision policy.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import LatticeShape, dslash_flops, random_spinor
from repro.core import plan as plan_mod
from repro.core import solvers
from repro.core.operators import dslash_g, get_operator, operator_names
from repro.data import lattice_problem
from repro.kernels.dispatch import resolve_lowering
from repro.launch.mesh import make_debug_mesh

# solver name -> (Krylov loop, precision policy); parity/backend/operator
# are independent CLI axes
_SOLVERS = {
    "cgnr": ("cgnr", "single"),
    "pipecg": ("pipecg", "single"),
    "blockcg": ("blockcg", "single"),
    "mpcg": ("cgnr", "mixed"),
    "cg16": ("cgnr", "low"),
}


def build_plan(args) -> plan_mod.SolverPlan:
    """Resolve the CLI axes to a SolverPlan (pure; unit-tested)."""
    loop, precision = _SOLVERS[args.solver]
    mesh = None
    if args.mesh == "debug":
        mesh = make_debug_mesh((2, 2), ("data", "model")) \
            if len(jax.devices()) >= 4 else None
        if mesh is None:
            raise SystemExit(
                "[solve] <4 devices; run under "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return plan_mod.SolverPlan(
        operator="eo-schur" if args.parity == "eo" else "full",
        operator_family=args.operator, mu=args.mu,
        backend=args.backend, solver=loop, precision=precision,
        nrhs=args.nrhs, mesh=mesh)


def _device_label(plan: plan_mod.SolverPlan) -> str:
    """Where the rate above was measured: platform, device, lowering."""
    dev = jax.devices()[0]
    lowering = (resolve_lowering(plan.interpret) if plan.backend == "pallas"
                else "jnp reference")
    return f"{dev.platform}, {dev.device_kind}, {lowering}"


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--lattice", default="4x4x4x8",
                   help="TxZxYxX extents")
    p.add_argument("--mass", type=float, default=0.2)
    p.add_argument("--solver", default="mpcg", choices=sorted(_SOLVERS),
                   help="Krylov loop / precision policy (blockcg shares "
                        "one search space across an --nrhs batch)")
    p.add_argument("--parity", choices=["full", "eo"], default="full",
                   help="operator shape: full lattice or even-odd Schur")
    p.add_argument("--operator", default="wilson",
                   choices=sorted(operator_names()),
                   help="operator family from the registry: "
                        + "; ".join(f"{n}: {get_operator(n).description}"
                                    for n in operator_names()))
    p.add_argument("--mu", type=float, default=0.0,
                   help="twisted-mass site parameter (i*mu*gamma5 term; "
                        "families that declare 'mu' only)")
    p.add_argument("--backend", choices=["reference", "pallas"],
                   default="reference")
    p.add_argument("--nrhs", type=int, default=None,
                   help="solve N right-hand sides in one masked batched CG "
                        "loop (gauge reads amortized across the batch)")
    p.add_argument("--tol", type=float, default=1e-6)
    p.add_argument("--maxiter", type=int, default=2000)
    p.add_argument("--mesh", default="none", choices=["none", "debug"])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--checkpoint-dir", default=None,
                   help="segment the solve and snapshot (x, iteration, "
                        "verdict, rhs_mask) here every --checkpoint-every "
                        "iterations (DESIGN.md §11)")
    p.add_argument("--checkpoint-every", type=int, default=50,
                   help="segment length in iterations between snapshots")
    p.add_argument("--resume", action="store_true",
                   help="restore the latest valid checkpoint from "
                        "--checkpoint-dir and defect-correct from the "
                        "saved iterate (fresh checkpointed solve when the "
                        "directory has no checkpoint yet)")
    p.add_argument("--deflate", type=int, default=0, metavar="NEV",
                   help="harvest an NEV-vector EigCG deflation basis from "
                        "a warmup solve on a separate RHS (same gauge/"
                        "mass), then warm-start this solve with it — "
                        "demonstrates the DESIGN.md §12 iteration cut "
                        "(eo parity, cgnr/blockcg, single precision only)")
    p.add_argument("--deflate-harvest-tol", type=float, default=1e-8,
                   help="recursive-residual tolerance the harvest solve "
                        "iterates to (deeper than --tol mines more "
                        "spectrum)")
    args = p.parse_args(argv)
    enable_compile_cache()
    if args.resume and args.checkpoint_dir is None:
        p.error("--resume requires --checkpoint-dir")
    if args.deflate > 0 and (args.resume or args.checkpoint_dir):
        p.error("--deflate does not compose with checkpointed solves")

    t, z, y, x = (int(v) for v in args.lattice.split("x"))
    shape = LatticeShape(t, z, y, x)
    u, b = lattice_problem(shape, mass=args.mass, seed=args.seed,
                           packed=False)
    if args.nrhs is not None:
        key = jax.random.fold_in(jax.random.PRNGKey(args.seed), 1)
        b = jnp.stack([random_spinor(jax.random.fold_in(key, i), shape)
                       for i in range(args.nrhs)])
    m = args.mass

    try:
        plan = build_plan(args)
    except (ValueError, NotImplementedError) as e:
        print(f"[solve] invalid plan: {e}")
        return 1
    print(f"[solve] plan: operator={plan.operator} "
          f"family={plan.operator_family} mu={plan.mu} "
          f"backend={plan.backend} solver={plan.solver} "
          f"precision={plan.precision} nrhs={plan.nrhs} mesh="
          f"{dict(plan.mesh.shape) if plan.mesh is not None else None}")

    deflation = None
    if args.deflate > 0:
        import dataclasses
        try:
            hplan = dataclasses.replace(plan, solver="cgnr", nrhs=None)
            hkey = jax.random.fold_in(jax.random.PRNGKey(args.seed), 2)
            b_h = random_spinor(hkey, shape)
            th = time.time()
            _, hst, deflation = plan_mod.harvest_deflation(
                hplan, u, b_h, m, tol=args.deflate_harvest_tol,
                maxiter=args.maxiter, nev=args.deflate,
                m_max=max(4 * args.deflate, 48), verify_tol=args.tol)
        except (ValueError, NotImplementedError) as e:
            print(f"[solve] invalid plan: {e}")
            return 1
        print(f"[solve] deflation harvest: nev={deflation.nev} "
              f"iters={int(hst.iterations)} matvecs={int(hst.matvecs)} "
              f"verified={bool(jnp.atleast_1d(hst.verified)[0])} "
              f"time={time.time() - th:.2f}s", flush=True)

    t0 = time.time()
    try:
        if args.resume:
            from repro.core import resilience
            xsol, st, record = resilience.resume_solve(
                plan, u, b, m, checkpoint_dir=args.checkpoint_dir,
                tol=args.tol, maxiter=args.maxiter, missing_ok=True)
            if record.resumed_from_step is None:
                print("[solve] no checkpoint found; fresh checkpointed "
                      "solve", flush=True)
            else:
                print(f"[solve] resumed from step "
                      f"{record.resumed_from_step} "
                      f"({record.checkpoint_iterations} iterations banked, "
                      f"checkpoint verdict "
                      f"{record.checkpoint_verdict})", flush=True)
        elif args.checkpoint_dir is not None:
            policy = plan_mod.CheckpointPolicy(
                dir=args.checkpoint_dir, every_iters=args.checkpoint_every)
            print(f"[solve] checkpointing to {policy.dir} every "
                  f"{policy.every_iters} iterations", flush=True)
            xsol, st = plan_mod.solve(plan, u, b, m, tol=args.tol,
                                      maxiter=args.maxiter,
                                      checkpoint=policy)
        else:
            xsol, st = plan_mod.solve(plan, u, b, m, tol=args.tol,
                                      maxiter=args.maxiter,
                                      deflation=deflation)
    except (ValueError, NotImplementedError) as e:
        # dispatch-time rejections (e.g. full + mesh + nrhs) — same
        # friendly failure as a plan that fails to construct
        print(f"[solve] invalid plan: {e}")
        return 1
    jax.block_until_ready(xsol)
    dt = time.time() - t0
    iters = int(st.iterations)

    # true residual against the FAMILY's full operator (registry oracle)
    twist = plan.twist
    op = lambda v: dslash_g(u, v, m, twist=twist)
    verdicts = jnp.atleast_1d(st.verdict) if st.verdict is not None else None
    verified = jnp.atleast_1d(st.verified) if st.verified is not None else None
    if plan.nrhs is not None:
        res = jax.vmap(lambda xx, bb: op(xx) - bb)(xsol, b)
        rels = (jnp.linalg.norm(res.reshape(plan.nrhs, -1), axis=1)
                / jnp.linalg.norm(b.reshape(plan.nrhs, -1), axis=1))
        rel = float(jnp.max(rels))
        per_rhs = [int(v) for v in st.rhs_iterations]
        print("[solve] per-RHS iterations: " + " ".join(
            f"rhs{i}={n}" for i, n in enumerate(per_rhs)))
        print("[solve] per-RHS matvecs:    " + " ".join(
            f"rhs{i}={int(v)}" for i, v in enumerate(
                jnp.atleast_1d(st.matvecs))))
        print("[solve] per-RHS rel_res:   " + " ".join(
            f"rhs{i}={float(r):.2e}" for i, r in enumerate(rels)))
        if verdicts is not None:
            print("[solve] per-RHS verdict:   " + " ".join(
                f"rhs{i}={solvers.verdict_name(v)}"
                + ("" if bool(verified[i]) else "(UNVERIFIED)")
                for i, v in enumerate(verdicts)))
        n_systems = plan.nrhs
    else:
        res = op(xsol) - b
        rel = float(jnp.linalg.norm(res.ravel())
                    / jnp.linalg.norm(b.ravel()))
        if verdicts is not None:
            print(f"[solve] verdict: {solvers.verdict_name(verdicts[0])} "
                  f"verified={bool(verified[0])}")
        n_systems = 1

    # a solve SUCCEEDS only when every RHS both converged by the taxonomy
    # and passed the true-residual verification matvec (DESIGN.md §10)
    ok = rel < 10 * args.tol
    if verdicts is not None:
        ok = ok and all(
            int(v) == solvers.CONVERGED and bool(verified[i])
            for i, v in enumerate(verdicts))
        if not ok:
            bad = [(i, solvers.verdict_name(v)) for i, v in enumerate(verdicts)
                   if int(v) != solvers.CONVERGED or not bool(verified[i])]
            print("[solve] FAIL: " + " ".join(
                f"rhs{i}:{name}" for i, name in bad))

    # each CGNR iteration applies D and D^dag (2 dslash) + vector algebra;
    # the even-odd Schur matvec does the same work on half-size fields.
    volume = shape.volume // 2 if plan.operator == "eo-schur" else shape.volume
    flops = 2 * dslash_flops(volume) * max(iters, 1) * 2 * n_systems
    mv = jnp.atleast_1d(st.matvecs)
    print(f"[solve] lattice={shape} solver={args.solver} iters={iters} "
          f"matvecs={int(jnp.max(mv))} "
          f"(total {int(jnp.sum(mv))} across {n_systems} RHS) "
          f"max_rel_res={rel:.2e} time={dt:.2f}s "
          f"~{flops/dt/1e9:.2f} GFLOP/s ({_device_label(plan)})")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
