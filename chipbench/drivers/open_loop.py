"""Open loop: requests arrive on a schedule drawn from the seed and go to
``SolverServer.submit``; latency runs from each request's *due* time.

Traffic keys:

* ``rate_per_s`` -- mean requests a second;
* ``burst`` -- requests that arrive together at each arrival (default 1);
  arrivals then come at ``rate_per_s / burst``;
* ``gauge_pick`` -- ``"round_robin"`` over the deployment's gauge fields
  (default), or ``"zipf"``: field ``g`` (from 0) asked for in proportion to
  ``(g + 1) ** -zipf_s``;
* ``rhs_pool`` -- right-hand sides drawn from the seed, picked at random
  (default 16);
* ``check_sample`` -- requests whose answers are compared (default 24).

Every seed offers the same work: the same gaps, bursts and per-field
counts, in an order of its own.
"""

from __future__ import annotations

import asyncio
import json
import math
import time

import numpy as np

from chipbench import data, harness


def arrival_schedule(rate: float, seconds: float, seed: int,
                     burst: int = 1) -> np.ndarray:
    """Arrival offsets in ``[0, seconds)``, ``burst`` requests an arrival.

    The gaps between arrivals are the exponential's quantiles, scaled to
    fill the window, in one fixed order; the seed rotates that cycle of
    gaps.  So every seed offers the same arrivals in another order, with
    the same bursts: at 0.8 of the knee, freely shuffled gaps made the tail
    a property of the seed (p90 from 1.16 to 1.77 s over six seeds on one
    TPU v5e chip).
    """
    burst = max(1, int(burst))
    n = max(1, int(round(rate * seconds / burst)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    np.random.default_rng(0x5EED).shuffle(gaps)
    gaps = np.roll(gaps, int(seed) % n)
    return np.repeat(np.concatenate([[0.0], np.cumsum(gaps)[:-1]]), burst)


def gauge_picks(n: int, fields: int, traffic: dict, seed: int) -> np.ndarray:
    """The gauge field of each of ``n`` requests."""
    kind = traffic.get("gauge_pick", "round_robin")
    if kind == "round_robin":
        return np.arange(n) % fields
    if kind == "zipf":
        w = (np.arange(fields) + 1.0) ** -float(traffic["zipf_s"])
        share = n * w / w.sum()
        counts = np.floor(share).astype(int)
        # largest remainders take the requests left over
        counts[np.argsort(counts - share)[:n - counts.sum()]] += 1
        picks = np.repeat(np.arange(fields), counts)
        np.random.default_rng([int(seed) % (1 << 64), 4]).shuffle(picks)
        return picks
    raise ValueError(f"unknown gauge_pick {kind!r}")


def run(jax, cell, seed, seconds, ctx, setup, hooks):
    async def main():
        server, pool = await serve_setup(jax, cell, ctx["gauges"], seed,
                                         setup, hooks)
        async with server:
            return await serve_window(
                server, cell, pool, seed, float(cell.traffic["rate_per_s"]),
                seconds, ctx["start_window"], ctx["stop_window"])

    return asyncio.run(main())


def make_server(cell, hooks: dict):
    from repro.serve.server import SolverServer

    serve = cell.config["serve"]
    kw = {}
    if "plan_cache" in hooks:
        kw["plan_cache"] = hooks["plan_cache"]()
    return SolverServer(mass=cell.mass, backend=cell.config["plan"]["backend"],
                        ladder=tuple(serve["ladder"]),
                        maxiter=int(cell.config["maxiter"]), **kw)


def _request(cell, gauge_id: str, rhs):
    from repro.serve.server import SolveRequest

    return SolveRequest(operator_family=cell.config["operator_family"],
                        gauge_id=gauge_id, rhs=rhs,
                        tol=float(cell.config["tol"]), mu=cell.mu)


async def serve_setup(jax, cell, gauges, seed: int, setup: dict,
                      hooks: dict):
    """A warmed ``SolverServer`` with the cell's gauge fields registered,
    and the pool of right-hand sides, as ``(keys, fields)``."""
    import jax.numpy as jnp

    pool_n = int(cell.traffic.get("rhs_pool", 16))
    gen = harness.rhs_maker(jax, cell.dims)
    keys = [data.seed_key(seed, 2, j) for j in range(pool_n)]
    fields = [gen(k) for k in keys]
    jax.block_until_ready(fields)

    server = make_server(cell, hooks)
    for g, u in enumerate(gauges):
        server.register_gauge(f"g{g}", u)
    t = time.perf_counter()
    await server.warmup(families=((cell.config["operator_family"],
                                   cell.mu),))
    setup["compile_s"] = time.perf_counter() - t
    t = time.perf_counter()
    # every batch size of the ladder once, through submit: padding,
    # stacking and un-padding compile here and not in the window
    zero = jnp.zeros(cell.dims + (4, 3), jnp.complex64)
    for k in range(1, max(cell.config["serve"]["ladder"]) + 1):
        await asyncio.gather(*(server.submit(_request(cell, "g0", zero))
                               for _ in range(k)))
    await server.submit(_request(cell, "g0", fields[0]))
    setup["warmup_s"] = time.perf_counter() - t
    return server, (keys, fields)


async def serve_window(server, cell, pool, seed: int, rate: float,
                       seconds: float, start=None, stop=None,
                       drain_s: float = harness.DRAIN_S):
    """One open-loop window at ``rate`` requests a second.

    Arrivals follow :func:`arrival_schedule`, gauge fields
    :func:`gauge_picks`; each request takes a right-hand side from the
    pool.  After the window closes, the requests still out are awaited up
    to ``drain_s`` seconds past the close.
    """
    keys, fields = pool
    n_g = len(server.gauge_ids())
    offsets = arrival_schedule(rate, seconds, seed,
                               cell.traffic.get("burst", 1))
    n = len(offsets)
    gauge_of = gauge_picks(n, n_g, cell.traffic, seed)
    rng = np.random.default_rng([int(seed) % (1 << 64), 3])
    pick = rng.integers(0, len(fields), size=n)
    sample = set(rng.choice(n, size=min(n, int(
        cell.traffic.get("check_sample", 24))), replace=False).tolist())
    base = server.metrics()
    records = [None] * n
    answers = {}

    async def client(i, due, fired):
        rec = {"due": due, "fired": fired, "done": None, "ok": False}
        records[i] = rec
        g = int(gauge_of[i])
        try:
            with harness.span("submit"):
                res = await server.submit(_request(
                    cell, f"g{g}", fields[pick[i]]))
        except Exception as e:  # a refused or failed request
            rec["error"] = repr(e)
            return
        rec["done"] = time.perf_counter()
        st = res.stats
        rec.update(ok=bool(st.converged and st.verified),
                   queue_s=st.queue_s, solve_s=st.solve_s,
                   batch_size=st.batch_size, padded_to=st.padded_to,
                   iterations=st.iterations)
        if i in sample:
            answers[i] = (g, keys[pick[i]], res.x)

    if start:
        start()
    t0 = time.perf_counter()
    tasks = []
    for i, off in enumerate(offsets):
        due = t0 + off
        delay = due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.create_task(
            client(i, due, time.perf_counter())))
    close = t0 + seconds
    if close > time.perf_counter():
        await asyncio.sleep(close - time.perf_counter())
    if stop:
        stop()
    out_at_close = sum(not t.done() for t in tasks)
    _, pending = await asyncio.wait(
        tasks, timeout=max(0.0, close + drain_s - time.perf_counter()))
    for t in pending:
        t.cancel()
    if pending:
        await asyncio.wait(pending)
    t_end = time.perf_counter()
    metrics = server.metrics()
    failed = sum(1 for r in records if not r["ok"])
    window_metrics = {k: _delta(metrics[k], base[k])
                      for k in ("requests", "batches", "batch_hist",
                                "rung_hist", "padded_slots")}
    lateness = sorted(r["fired"] - r["due"] for r in records)
    by_rung = {}
    for r in records:
        if "solve_s" in r:
            by_rung.setdefault(r["padded_to"], []).append(r["solve_s"])
    solve_s = {k: sum(v) / len(v) for k, v in sorted(by_rung.items())}
    harness.say(
        f"open loop: offered={n} rate_per_s={rate!r} "
        f"mean_batch_solve_s_by_rung={json.dumps(solve_s)} "
        f"out_at_close={out_at_close} never_done={len(pending)} "
        f"generator_late_p50_s={float(quantile(lateness, 0.5))!r} "
        f"generator_late_max_s={float(lateness[-1])!r} "
        f"window={json.dumps(window_metrics)}")
    return harness.Window(records, [answers[i] for i in sorted(answers)], t0,
                          close, t_end, failed=failed,
                          server_metrics=window_metrics,
                          extra={"out_at_close": out_at_close})


def _delta(now, base):
    if isinstance(now, dict):
        return {k: now[k] - base.get(k, 0) for k in now
                if now[k] - base.get(k, 0)}
    return now - base


def quantile(values, q):
    """Nearest-rank quantile of a sorted list."""
    if not values:
        return 0.0
    return values[max(0, math.ceil(q * len(values)) - 1)]
