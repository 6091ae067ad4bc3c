#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout: ``BENCHMARK.json`` there names the cell,
and the program under test is the ``src/repro`` package beside it.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window.  Without a TPU (or with fewer chips than the cell asks for), or
without the program, the run exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    bench_file = os.path.join(ROOT, "BENCHMARK.json")
    src = os.path.join(ROOT, "src")
    for need in (bench_file, os.path.join(src, "repro")):
        if not os.path.exists(need):
            print(f"[chipbench] FAIL: {need} is missing", file=sys.stderr)
            return 2
    # the benchmark's modules import as the package ``chipbench``, never as
    # top-level names that could shadow the standard library's
    if sys.path and os.path.abspath(sys.path[0]) == BENCH_DIR:
        sys.path.pop(0)
    sys.path[:0] = [ROOT, src]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    with open(bench_file) as f:
        bench = json.load(f)

    from chipbench import harness

    try:
        result = harness.run_cell(bench, args.workload, args.seed,
                                  args.seconds, bool(args.trace),
                                  t_start=T_START)
    except harness.NoChip as e:
        print(f"[chipbench] FAIL: {e}", file=sys.stderr)
        return 1
    except Exception as e:
        traceback.print_exc()
        print(f"[chipbench] FAIL: {e!r}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
