#!/usr/bin/env python3
"""The control of ``correct``: the reference, in bfloat16, in the program's place.

    python chipbench/control.py --workload wilson-24c48.light \
        --seeds 11,12,13 --seconds 20

The configurations state float32.  The control solves the same systems
with a plain CGNR on the reference operator whose inputs are rounded to
bfloat16 (the precision a later change might be tempted to use), and
hands its answers to the harness exactly where the program's answers go:
in place of the compiled ``plan.solve`` in a closed-loop cell, in place of
the server's compiled batch solve in a served cell.  The harness's
comparison must then read ``correct`` false.  Each seed's worst residual
is printed; the smallest over the seeds is the upper reading of the
limit (see PERF.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import NamedTuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class Stats(NamedTuple):
    """The fields of a solve's statistics that the harness and the server
    read, filled so that they accept every answer."""

    iterations: object
    converged: object
    verified: object
    rhs_iterations: object = None
    residual_norm2: object = None
    verdict: object = None
    true_residual_norm2: object = None


def from_natural_gauge(u):
    import jax.numpy as jnp

    t, z, y, x = u.shape[1:5]
    return jnp.transpose(u, (0, 5, 6, 1, 2, 3, 4)).reshape(
        4, 3, 3, t, z, y * x)


def low_cgnr(ref, u, b, mass, mu, yx, *, tol, maxiter):
    """CGNR on D^dag D with the operator's inputs rounded to bfloat16.

    D^dag = gamma5 D(-mu) gamma5.  Vectors and sums stay float32; only
    what enters the operator is rounded.  Runs to ``tol`` or ``maxiter``.
    """
    import jax
    import jax.numpy as jnp

    g5 = jnp.asarray(ref.GAMMA5).reshape(4, 1, 1, 1, 1)

    def rnd_real(v):
        # round to the nearest bfloat16 (ties to even) in the bits, so
        # that no compiler may keep the excess float32 precision
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & 1)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)

    def rnd(v):
        return jax.lax.complex(rnd_real(jnp.real(v)), rnd_real(jnp.imag(v)))

    ul = rnd(u)

    def d(v, m):
        return ref.dslash(ul, rnd(v), mass, m, yx=yx)

    def a(v):
        return g5 * d(g5 * d(v, mu), -mu)

    def dot(p, q):
        return jnp.real(jnp.sum(jnp.conj(p) * q))

    rhs = g5 * d(g5 * b, -mu)
    limit = tol * tol * dot(rhs, rhs)
    x = jnp.zeros_like(b)

    def cond(c):
        _, r, _, rr, k = c
        return jnp.logical_and(rr > limit, k < maxiter)

    def body(c):
        x, r, p, rr, k = c
        ap = a(p)
        alpha = rr / dot(p, ap)
        x, r = x + alpha * p, r - alpha * ap
        rr_new = dot(r, r)
        return x, r, r + (rr_new / rr) * p, rr_new, k + 1

    x, _, _, rr, k = jax.lax.while_loop(
        cond, body, (x, rhs, rhs, dot(rhs, rhs), jnp.int32(0)))
    return x, k


def control_hooks(jax, cell, *, maxiter=None):
    """Harness hooks that put the low-precision reference in the program's
    place: ``wrap_solve`` for closed loops, ``plan_cache`` for servers."""
    import jax.numpy as jnp

    from chipbench import data, harness

    ref = harness.load_module("references", cell.config["reference"] + ".py")
    maxiter = int(maxiter or cell.traffic.get("control_maxiter", 300))
    tol = float(cell.config["tol"])
    dims = cell.dims
    yx = dims[2:]

    def solve_one(u_nat, b_nat, mass):
        x, k = low_cgnr(ref, from_natural_gauge(u_nat),
                        data.from_natural_spinor(b_nat), mass, cell.mu, yx,
                        tol=tol, maxiter=maxiter)
        return data.to_natural_spinor(x, dims), k

    def wrap_solve(_compiled):
        fn = jax.jit(lambda u, b: solve_one(u, b, cell.mass))

        def solve(u, b):
            x, k = fn(u, b)
            return x, Stats(k, jnp.bool_(True), jnp.bool_(True))

        return solve

    controls = {}

    def plan_cache():
        from repro.serve.plan_cache import PlanCache

        class ControlCache(PlanCache):
            # an empty cache has len 0; the server must still take it
            def __bool__(self):
                return True

            def get(self, plan, mass, maxiter_):
                _, hit = super().get(plan, mass, maxiter_)
                key = self.key(plan, mass, maxiter_)
                if key not in controls:
                    controls[key] = jax.jit(jax.vmap(
                        lambda u, b: solve_one(u, b, float(mass)),
                        in_axes=(None, 0)))
                batched = controls[key]

                def run(u, b, tol_):
                    x, k = batched(u, b)
                    ones = jnp.ones(k.shape, bool)
                    zeros = jnp.zeros(k.shape, jnp.float32)
                    return x, Stats(jnp.max(k), ones, ones, k, zeros,
                                    jnp.zeros(k.shape, jnp.int32), zeros)

                return run, hit

        return ControlCache()

    return {"wrap_solve": wrap_solve, "plan_cache": plan_cache}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    if sys.path and os.path.abspath(sys.path[0]) == BENCH_DIR:
        sys.path.pop(0)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from chipbench import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = harness.read_cell(bench, args.workload)
    hooks = control_hooks(jax, cell)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(bench, args.workload, seed, args.seconds,
                               False, t_start=time.perf_counter(),
                               hooks=hooks)
        row = {"seed": seed, "correct": res["correct"],
               "checks": res["checks"]}
        rows.append(row)
        harness.say("control " + json.dumps(row))
    print(json.dumps({"workload": args.workload, "control": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
