"""Closed loop: one caller solves a fresh right-hand side after the other.

Each solve goes through one jitted ``plan.solve`` on the deployment's first
gauge field, until ``--seconds`` have passed; the last solve is finished,
not cut.  Traffic keys:

* ``mass`` -- the quark mass (else the configuration's);
* ``queued`` -- solves kept queued behind the running one, as a campaign
  enqueues its next source (default 1); 0 steps from the host, each solve
  dispatched only once the one before has returned;
* ``check_sample`` -- answers kept for the comparison, a reservoir drawn
  from the seed (default 4).

Besides the solve intervals it prints, per solve, the host's time to
dispatch it and the gap from the previous solve's return to that dispatch.
"""

from __future__ import annotations

import collections
import json
import time

import numpy as np

from chipbench import data, harness


def run(jax, cell, seed, seconds, ctx, setup, hooks):
    dims = cell.dims
    u = ctx["gauges"][0]
    gen = harness.rhs_maker(jax, dims)
    t = time.perf_counter()
    zero = jax.numpy.zeros(dims + (4, 3), jax.numpy.complex64)
    solve = harness.program_solve(jax, cell, u, zero)
    if "wrap_solve" in hooks:
        solve = hooks["wrap_solve"](solve)
    setup["compile_s"] = time.perf_counter() - t

    t = time.perf_counter()
    # warm-up: a zero RHS runs the whole program with no Krylov step
    jax.block_until_ready(solve(u, zero))
    jax.block_until_ready(gen(data.seed_key(seed, 1, 0)))
    del zero
    setup["warmup_s"] = time.perf_counter() - t

    depth = int(cell.traffic.get("queued", 1))
    keep = int(cell.traffic.get("check_sample", 4))
    rng = np.random.default_rng([int(seed) % (1 << 64), 1])
    records, answers, queued = [], [], collections.deque()
    dispatch_s, host_gap_s = [], []

    def launch(i):
        key = data.seed_key(seed, 1, i)
        t = time.perf_counter()
        if records and not queued:
            host_gap_s.append(t - records[-1]["t"])
        with harness.span("dispatch"):
            queued.append((i, key) + tuple(solve(u, gen(key))))
        dispatch_s.append(time.perf_counter() - t)

    ctx["start_window"]()
    t0 = time.perf_counter()
    launch(0)
    while queued:
        while (len(queued) <= depth
               and time.perf_counter() - t0 < seconds):
            launch(len(records) + len(queued))
        i, key, x, st = queued.popleft()
        with harness.span("wait"):
            jax.block_until_ready((x, st))
        t = time.perf_counter()
        ok = bool(st.converged) and bool(st.verified)
        records.append({"t": t, "iterations": int(st.iterations), "ok": ok})
        # reservoir sample, drawn from the seed, of the answers to check
        if i < keep:
            answers.append((0, key, x))
        else:
            j = int(rng.integers(0, i + 1))
            if j < keep:
                answers[j] = (0, key, x)
        del x, st
        if not queued and time.perf_counter() - t0 < seconds:
            launch(len(records))
    ctx["stop_window"]()
    ends = [t0] + [r["t"] for r in records]

    def rounded(values):
        return json.dumps([round(v, 4) for v in values])

    harness.say(f"closed loop: queued={depth} solve_intervals_s="
                + rounded([b - a for a, b in zip(ends, ends[1:])])
                + " dispatch_s=" + rounded(dispatch_s)
                + " host_gap_s=" + rounded(host_gap_s))
    failed = sum(not r["ok"] for r in records)
    return harness.Window(records, answers, t0, t0 + seconds,
                          records[-1]["t"], failed=failed)
