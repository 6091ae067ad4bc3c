#!/usr/bin/env python3
"""Record a small profiler trace of the even-odd Pallas solve on the chip.

    python chipbench/record_trace.py --out chipbench/testdata/small_trace

Solves one Wilson system at 8x8x8x16 through the same ``plan.solve`` path
the benchmark times, under ``jax.profiler``, and copies the ``.xplane.pb``
to ``<out>/trace.xplane.pb``.  Prints every plane and line of the trace
with its event count and the device ops that took most time, so the
event names of the kernels can be read off.  The tests of
``chipbench/trace.py`` and of the kernel metrics read this file.
"""

from __future__ import annotations

import argparse
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

    from chipbench import data, trace
    from repro.core import plan as plan_mod

    dims = tuple(int(v) for v in args.dims.split(","))
    u = jax.jit(lambda k: data.to_natural_gauge(
        data.gauge_planes(k, dims), dims))(data.seed_key(5, 0))
    b = jax.jit(lambda k: data.to_natural_spinor(
        data.spinor_planes(k, dims), dims))(data.seed_key(5, 1))
    plan = plan_mod.SolverPlan(operator="eo-schur", backend="pallas",
                               solver="cgnr")
    fn = jax.jit(lambda u, b: plan_mod.solve(plan, u, b, 0.1, tol=1e-6,
                                             maxiter=1000))
    jax.block_until_ready(fn(u, b))
    tmp = tempfile.mkdtemp(prefix="chipbench-record-")
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("chipbench.solve"):
        x, st = fn(u, b)
        jax.block_until_ready((x, st))
    jax.profiler.stop_trace()
    src = trace.find_xplane(tmp)
    os.makedirs(args.out, exist_ok=True)
    dst = os.path.join(args.out, "trace.xplane.pb")
    shutil.copyfile(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"[record] dims={dims} iterations={int(st.iterations)} "
          f"bytes={os.path.getsize(dst)} -> {dst}")

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(dst)
    for plane in pd.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print(f"[record] plane {plane.name!r} lines={lines}")
    tr = trace.load(dst)
    for name, events in tr.devices.items():
        print(f"[record] {name}: ops={len(events)} "
              f"busy_s={trace.busy_ns(events) / 1e9!r}")
    for name, s in trace.top_ops(tr, 40):
        print(f"[record] op {name!r} {s!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
