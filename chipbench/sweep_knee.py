#!/usr/bin/env python3
"""Find the knee of a served cell: the highest offered rate it keeps up with.

    python chipbench/sweep_knee.py --workload tm-16c32.serve \
        --rates 2,3,4,5,6,8 --seconds 30 --seed 7

One process, one warmed ``SolverServer``: for each rate, one open-loop
window of ``--seconds`` through the same generator the benchmark uses,
then a drain.  A rate is kept up with when the requests still out at the
window's close are fewer than one top-rung batch.  The knee is the highest
such rate below the first that is not.  The result is printed, one line a
rate, and as a JSON line at the end.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    if sys.path and os.path.abspath(sys.path[0]) == BENCH_DIR:
        sys.path.pop(0)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = harness.read_cell(bench, args.workload)
    driver = harness.load_module("drivers", "open_loop.py")
    jax, _ = harness.init_jax(cell.chips, require_tpu=True)
    _, gauges = harness.make_gauges(jax, cell, args.seed)
    top = max(cell.config["serve"]["ladder"])
    rates = [float(r) for r in args.rates.split(",")]

    async def sweep():
        setup = {}
        server, pool = await driver.serve_setup(jax, cell, gauges,
                                                args.seed, setup, {})
        harness.say(f"setup {setup}")
        rows = []
        async with server:
            for k, rate in enumerate(rates):
                w = await driver.serve_window(server, cell, pool,
                                              args.seed + k, rate,
                                              args.seconds)
                done_in = sum(1 for r in w.records if r["done"]
                              and r["done"] <= w.t_close)
                lat = sorted(r["done"] - r["due"] for r in w.records
                             if r["done"])
                row = {"rate_per_s": rate, "offered": len(w.records),
                       "done_in_window": done_in,
                       "out_at_close": w.extra["out_at_close"],
                       "failed": w.failed,
                       "served_per_s": done_in / args.seconds,
                       "p50_s": driver.quantile(lat, 0.5),
                       "p90_s": driver.quantile(lat, 0.9),
                       "batch_hist": w.server_metrics["batch_hist"],
                       "kept_up": w.extra["out_at_close"] < top}
                rows.append(row)
                harness.say("sweep " + json.dumps(row))
                time.sleep(1.0)
        return rows

    rows = asyncio.run(sweep())
    knee = None
    for row in rows:
        if not row["kept_up"]:
            break
        knee = row["rate_per_s"]
    print(json.dumps({"knee_per_s": knee, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
