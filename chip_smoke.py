#!/usr/bin/env python3
"""Chip smoke test: the even-odd Pallas solve and the solver server on TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # sharded solve on a 2x2 mesh

One chip, at 24³×48 (T=48, Z=Y=X=24, f32), all in this one process:

* **solve** — ``plan.solve`` with ``SolverPlan(operator="eo-schur",
  backend="pallas", solver="cgnr")`` (the path behind ``python -m
  repro.launch.solve --parity eo --backend pallas --solver cgnr``) for
  Wilson and for twisted mass (μ=0.25).  Each must converge and verify,
  meet ‖D x - b‖ ≤ 10·tol·‖b‖ against the plain ``dslash_g`` reference,
  and run the Mosaic kernels (a ``tpu_custom_call`` in its HLO);
* **serve** — a ``SolverServer`` with ladder (1,) on one registered gauge
  field: warm-up, then 4 requests, each served, converged and verified,
  with no batch failure, lane retry or harvest failure in ``metrics()``.

``--four-chips`` runs only the sharded eo-schur Wilson CGNR solve of the
same system on a 2x2 mesh of 4 chips and the same system on one chip:
both converge and verify, agree to 1e-5 relative, differ by at most 2
iterations, and each chip holds a quarter of the solution.

Each phase prints its result on its own line; the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or without the ``src/repro`` package beside this file, the
script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
LATTICE = (48, 24, 24, 24)   # (T, Z, Y, X)
MASS = 0.1
TOL = 1e-6
MU = 0.25
MAXITER = 1000
N_REQUESTS = 4


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def solve_phase(lattice, family: str, mu: float, seed: int, *,
                expect_lowering: str = "mosaic"):
    """One eo-schur Pallas CGNR solve, checked against ``dslash_g``."""
    import jax
    import jax.numpy as jnp

    from repro.core import LatticeShape, solvers
    from repro.core import plan as plan_mod
    from repro.core.operators import dslash_g
    from repro.data import lattice_problem
    from repro.kernels.dispatch import resolve_lowering

    plan = plan_mod.SolverPlan(operator="eo-schur", backend="pallas",
                               solver="cgnr", operator_family=family, mu=mu)
    lowering = resolve_lowering(plan.interpret)
    check(lowering == expect_lowering,
          f"{family}: lowering {lowering!r}, expected {expect_lowering!r}")
    u, b = lattice_problem(LatticeShape(*lattice), mass=MASS, seed=seed,
                           packed=False)
    fn = jax.jit(lambda u, b: plan_mod.solve(plan, u, b, MASS, tol=TOL,
                                             maxiter=MAXITER))
    t0 = time.perf_counter()
    compiled = fn.lower(u, b).compile()
    compile_s = time.perf_counter() - t0
    if expect_lowering == "mosaic":
        check("tpu_custom_call" in compiled.as_text(),
              f"{family}: no tpu_custom_call in the compiled solve")
    t0 = time.perf_counter()
    x, st = compiled(u, b)
    jax.block_until_ready(x)
    solve_s = time.perf_counter() - t0
    residual = jax.jit(lambda u, x, b: jnp.linalg.norm(
        (dslash_g(u, x, MASS, twist=plan.twist) - b).ravel())
        / jnp.linalg.norm(b.ravel()))
    rel = float(residual(u, x, b))
    verdict = solvers.verdict_name(st.verdict)
    verified = bool(st.verified)
    print(f"[smoke] solve {family} mu={mu} lattice={lattice}: "
          f"lowering={lowering} iters={int(st.iterations)} "
          f"verdict={verdict} verified={verified} true_rel_res={rel!r} "
          f"compile_s={compile_s!r} solve_s={solve_s!r}", flush=True)
    check(verdict == "converged", f"{family}: verdict {verdict}")
    check(verified, f"{family}: verification failed")
    check(rel <= 10 * TOL, f"{family}: true residual {rel} > {10 * TOL}")
    return x, st


def serve_phase(lattice, seed: int):
    """A one-rung SolverServer: warm-up, then N_REQUESTS served solves."""
    import jax

    from repro.core import LatticeShape, random_spinor
    from repro.data import lattice_problem
    from repro.serve.server import SolveRequest, SolverServer

    shape = LatticeShape(*lattice)
    u, _ = lattice_problem(shape, mass=MASS, seed=seed, packed=False)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 7)
    rhs = [random_spinor(jax.random.fold_in(key, i), shape)
           for i in range(N_REQUESTS)]

    async def run():
        async with SolverServer(mass=MASS, backend="pallas", ladder=(1,),
                                maxiter=MAXITER) as server:
            server.register_gauge("g0", u)
            t0 = time.perf_counter()
            compiled = await server.warmup()
            warm_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            results = await asyncio.gather(*(
                server.submit(SolveRequest(operator_family="wilson",
                                           gauge_id="g0", rhs=r, tol=TOL))
                for r in rhs))
            return compiled, warm_s, time.perf_counter() - t0, results, \
                server.metrics()

    compiled, warm_s, serve_s, results, metrics = asyncio.run(run())
    stats = [r.stats for r in results]
    contain = metrics["containment"]
    print(f"[smoke] serve lattice={lattice}: warmup_programs={compiled} "
          f"warmup_s={warm_s!r} served={len(results)} serve_s={serve_s!r} "
          f"iters={[s.iterations for s in stats]} "
          f"verdicts={[s.verdict for s in stats]} "
          f"verified={[s.verified for s in stats]} "
          f"batch_failures={contain['batch_failures']} "
          f"lane_retries={contain['lane_retries']} "
          f"harvest_failures={metrics['deflation']['harvest_failures']}",
          flush=True)
    check(len(results) == N_REQUESTS, f"served {len(results)} requests")
    for i, s in enumerate(stats):
        check(s.converged and s.verdict == "converged" and s.verified,
              f"request {i}: verdict={s.verdict} verified={s.verified}")
    check(contain["batch_failures"] == 0, "batch failures")
    check(contain["lane_retries"] == 0, "lane retries")
    check(contain["failed_requests"] == 0, "failed requests")
    check(metrics["deflation"]["harvest_failures"] == 0, "harvest failures")


def four_chip_phase(lattice, seed: int):
    """The sharded eo-schur solve on a 2x2 mesh against one chip."""
    import jax
    import numpy as np

    from repro.core import LatticeShape, solvers
    from repro.core import plan as plan_mod
    from repro.data import lattice_problem

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, "
                             f"found {len(devices)}")
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    u, b = lattice_problem(LatticeShape(*lattice), mass=MASS, seed=seed,
                           packed=False)
    base = dict(operator="eo-schur", backend="pallas", solver="cgnr")
    sharded = plan_mod.SolverPlan(**base, mesh=mesh)
    single = plan_mod.SolverPlan(**base)
    runs = {}
    for name, solve in (
            ("mesh2x2", lambda u, b: plan_mod.solve(
                sharded, u, b, MASS, tol=TOL, maxiter=MAXITER)),
            ("one_chip", jax.jit(lambda u, b: plan_mod.solve(
                single, u, b, MASS, tol=TOL, maxiter=MAXITER)))):
        t0 = time.perf_counter()
        x, st = solve(u, b)
        jax.block_until_ready(x)
        wall_s = time.perf_counter() - t0
        verdict = solvers.verdict_name(st.verdict)
        print(f"[smoke] {name} solve lattice={lattice}: "
              f"iters={int(st.iterations)} verdict={verdict} "
              f"verified={bool(st.verified)} "
              f"true_res2={float(st.true_residual_norm2)!r} "
              f"wall_s_with_compile={wall_s!r}", flush=True)
        check(verdict == "converged" and bool(st.verified),
              f"{name}: verdict={verdict} verified={bool(st.verified)}")
        runs[name] = (x, st)
    x4, st4 = runs["mesh2x2"]
    x1, st1 = runs["one_chip"]
    shards = [(str(s.device), tuple(s.data.shape))
              for s in x4.addressable_shards]
    a4, a1 = np.asarray(x4), np.asarray(x1)
    rel = float(np.linalg.norm(a4 - a1) / np.linalg.norm(a1))
    d_iters = abs(int(st4.iterations) - int(st1.iterations))
    print(f"[smoke] mesh2x2 vs one_chip: rel_diff={rel!r} "
          f"iters_diff={d_iters} shards={shards}", flush=True)
    check(rel <= 1e-5, f"sharded and one-chip solutions differ by {rel}")
    check(d_iters <= 2, f"iteration counts differ by {d_iters}")
    t, z = lattice[0], lattice[1]
    quarter = (t // 2, z // 2) + tuple(lattice[2:]) + (4, 3)
    check(len({d for d, _ in shards}) == 4
          and all(shape == quarter for _, shape in shards),
          f"solution shards {shards}, expected 4 devices × {quarter}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run only the sharded 2x2-mesh solve and its "
                             "one-chip comparison")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"[smoke] FAIL: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.kernels.dispatch import resolve_lowering

    cache_dir = enable_compile_cache()
    platforms = jax.config.jax_platforms
    if platforms and "cpu" not in platforms.split(","):
        # lattice_problem draws the gauge field on the host's CPU backend
        jax.config.update("jax_platforms", platforms + ",cpu")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[smoke] FAIL: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    print(f"[smoke] jax {jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind} devices={len(jax.devices())} "
          f"lowering={resolve_lowering(None)} compile_cache={cache_dir}",
          flush=True)
    try:
        if args.four_chips:
            four_chip_phase(LATTICE, args.seed)
        else:
            solve_phase(LATTICE, "wilson", 0.0, args.seed)
            solve_phase(LATTICE, "twisted-mass", MU, args.seed)
            serve_phase(LATTICE, args.seed)
    except Exception as e:
        traceback.print_exc()
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
