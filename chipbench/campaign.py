#!/usr/bin/env python3
"""Run several benchmark runs one after another, each in its own process.

    python chipbench/campaign.py --out "$TMPDIR/sets" \
        wilson-24c48.light:101:45:0 wilson-24c48.light:102:45:0 ...

Each argument is ``workload:seed:seconds:trace``.  The runs go one at a
time (one process holds the chip), each through ``chipbench/run.py`` as the
benchmark's own command.  Their standard output and error are kept under
``--out``; one summary line per run is printed, and a JSON list of every
run's result line is written to ``<out>/results.json``.  This process
never imports JAX, so it never holds the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--timeout", type=float, default=1300.0)
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    results = []
    for k, spec in enumerate(args.runs):
        workload, seed, seconds, trace = spec.split(":")
        cmd = [sys.executable, os.path.join(BENCH_DIR, "run.py"),
               "--workload", workload, "--seed", seed, "--seconds", seconds,
               "--trace", trace]
        t = time.perf_counter()
        try:
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                               timeout=args.timeout)
            rc, out, err = p.returncode, p.stdout, p.stderr
        except subprocess.TimeoutExpired as e:
            rc, out, err = 124, e.stdout or "", e.stderr or ""
            out = out.decode() if isinstance(out, bytes) else out
            err = err.decode() if isinstance(err, bytes) else err
        wall = time.perf_counter() - t
        tag = f"{k:02d}_{workload}_{seed}_{trace}"
        for ext, text in (("out", out), ("err", err)):
            with open(os.path.join(args.out, f"{tag}.{ext}"), "w") as f:
                f.write(text)
        last = out.strip().splitlines()[-1] if out.strip() else ""
        try:
            res = json.loads(last)
        except ValueError:
            res = None
        results.append({"spec": spec, "rc": rc, "wall_s": wall,
                        "result": res})
        info = [ln for ln in out.splitlines() if ln.startswith("[chipbench]")]
        print(f"=== {spec} rc={rc} wall_s={wall:.1f}", flush=True)
        for ln in info:
            print("   ", ln[:600], flush=True)
        if res is None:
            print("    stderr tail:", err[-1500:], flush=True)
        else:
            print("    correct=", res.get("correct"), "metrics=",
                  json.dumps(res.get("metrics")), "device=",
                  json.dumps(res.get("device")), "checks=",
                  json.dumps(res.get("checks")), flush=True)
            if "breakdown" in res:
                print("    breakdown=", json.dumps(res["breakdown"])[:3000],
                      flush=True)
        with open(os.path.join(args.out, "results.json"), "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
