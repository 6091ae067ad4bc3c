"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  bench_dslash           — paper §5 sustained-GFLOP/s table
  bench_mixed_precision  — paper §2/§3.2 two-precision CG (Ref. [10])
  bench_overlap          — paper Fig. 2 transfer/compute overlap
  bench_solvers          — collectives-per-iteration (pipelined CG)
  roofline               — §Roofline aggregation from the dry-run JSONs
  bench_serve            — serving-lane latency smoke (``--with-serve``
                           only; the CI serve-smoke job runs it directly)

``--backend`` pins the JAX platform and ``--compiled`` switches EVERY
module to the compiled (non-interpret) lowering coherently through
:mod:`benchmarks.bench_config` — no module decides ``interpret`` on its
own, and every JSON entry carries the same
platform/device_kind/compiled/interpret/lowering label block.
``benchmarks/launch_bench.sh`` wraps this entry point with the pinned
XLA environment for reproducible compiled numbers.
"""

from __future__ import annotations

import argparse
import os
import sys
import traceback

# Make `python benchmarks/run.py` work from anywhere: the interpreter puts
# benchmarks/ (not the repo root) on sys.path for direct script runs.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODULE_NAMES = ["dslash", "mixed_precision", "overlap", "solvers",
                "roofline"]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description="benchmark CSV sweep")
    parser.add_argument("--backend", choices=["cpu", "gpu", "tpu"],
                        default=None,
                        help="pin the JAX platform (default: jax's own "
                             "backend selection)")
    parser.add_argument("--compiled", action="store_true",
                        help="run kernels through the compiled lowering "
                             "(Mosaic on gpu/tpu, the XLA half-spinor "
                             "path on cpu) instead of the historical "
                             "interpret-on-CPU default")
    parser.add_argument("--only", nargs="+", choices=MODULE_NAMES,
                        default=None,
                        help="run only these modules (default: all)")
    parser.add_argument("--with-serve", action="store_true",
                        help="append the serving-lane smoke (slower; it "
                             "spins up the batching server)")
    args = parser.parse_args(argv)

    # configure BEFORE the bench modules import jax and read the mode
    from benchmarks import bench_config
    bench_config.configure(backend=args.backend, compiled=args.compiled)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_dslash, bench_mixed_precision,
                            bench_overlap, bench_solvers, roofline)
    by_name = {"dslash": bench_dslash,
               "mixed_precision": bench_mixed_precision,
               "overlap": bench_overlap, "solvers": bench_solvers,
               "roofline": roofline}
    names = args.only or MODULE_NAMES
    modules = [(n, by_name[n]) for n in names]
    if args.with_serve:
        from benchmarks import bench_serve
        modules.append(("serve", bench_serve))
    print("name,us_per_call,derived")
    failed = 0
    for name, mod in modules:
        try:
            for row in mod.run():
                n, us, derived = row
                print(f"{n},{us:.1f},{derived}")
        except Exception:
            failed += 1
            traceback.print_exc()
            print(f"{name},-1,ERROR")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
