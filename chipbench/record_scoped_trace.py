#!/usr/bin/env python3
"""Record a profiler trace of one closed-loop step on the chip, with the
compiled programs' text, for the phase split's tests.

    python chipbench/record_scoped_trace.py --out chipbench/testdata/scoped_trace

At 8x8x8x16 (T,Z,Y,X = 16,8,8,8), as ``record_trace.py``, it draws one
right-hand side with the benchmark's generator and solves it through the
same jitted ``plan.solve`` (eo-schur Pallas CGNR) the benchmark times,
under ``jax.profiler``.  It writes ``trace.xplane.pb``, the optimized HLO
of both programs (``solve.hlo.txt.gz``, ``rhs.hlo.txt.gz``, from the very
executables that ran) and ``record.json`` (lattice, iterations), then
prints the phase split (``chipbench/phases.py``) of the trace.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--dims", default="16,8,8,8", help="T,Z,Y,X")
    args = ap.parse_args(argv)
    if sys.path and os.path.abspath(sys.path[0]) == BENCH_DIR:
        sys.path.pop(0)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from chipbench import data, harness, phases, trace
    from repro.core import plan as plan_mod

    dims = tuple(int(v) for v in args.dims.split(","))
    u = jax.jit(lambda k: data.to_natural_gauge(
        data.gauge_planes(k, dims), dims))(data.seed_key(5, 0))
    key = data.seed_key(5, 1)
    gen = harness.rhs_maker(jax, dims).lower(key).compile()
    plan = plan_mod.SolverPlan(operator="eo-schur", backend="pallas",
                               solver="cgnr")
    fn = jax.jit(lambda u, b: plan_mod.solve(plan, u, b, 0.1, tol=1e-6,
                                             maxiter=1000))
    solve = fn.lower(u, gen(key)).compile()
    jax.block_until_ready(solve(u, gen(key)))
    tmp = tempfile.mkdtemp(prefix="chipbench-record-")
    jax.profiler.start_trace(tmp)
    with harness.span("dispatch"):
        x, st = solve(u, gen(key))
    with harness.span("wait"):
        jax.block_until_ready((x, st))
    jax.profiler.stop_trace()

    os.makedirs(args.out, exist_ok=True)
    dst = os.path.join(args.out, "trace.xplane.pb")
    shutil.copyfile(trace.find_xplane(tmp), dst)
    shutil.rmtree(tmp, ignore_errors=True)
    for name, compiled in (("solve", solve), ("rhs", gen)):
        with gzip.open(os.path.join(args.out, name + ".hlo.txt.gz"),
                       "wt") as f:
            f.write(compiled.as_text())
    record = {"dims": dims, "mass": 0.1, "iterations": int(st.iterations),
              "ok": bool(st.converged) and bool(st.verified)}
    with open(os.path.join(args.out, "record.json"), "w") as f:
        json.dump(record, f)
    print(f"[record] {record} -> {args.out}")

    tr = trace.load(dst)
    phase_of = phases.instruction_phases(solve.as_text())
    seconds = phases.split(tr, phase_of,
                           phases.instruction_names(gen.as_text()))
    busy = trace.mean_busy_s(tr)
    for name, s in sorted(seconds.items(), key=lambda kv: -kv[1]):
        print(f"[record] phase {name} {s!r} s ({100 * s / busy:.3f}% of "
              "busy)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
