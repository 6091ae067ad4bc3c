#!/usr/bin/env python
"""CI guard: solver iteration counts must not regress on the smoke lattice.

Compares a freshly generated ``BENCH_solvers.json`` against the committed
``benchmarks/BENCH_solvers_baseline.json``:

* ``eo_smoke``    — single-RHS Schur CGNR, reference + Pallas backends;
* ``eo_smoke_tm`` — the same solve through the operator registry's
  twisted-mass family (site-term epilogues folded into the same kernels);
* ``batch_sweep`` — per-N iteration counts of the multi-RHS batched solve;
* ``eo_sharded``  — the 8-way sharded pipelined Schur solve's trip count;
* ``blockcg_16rhs`` — block CGNR over 16 RHS at near-critical mass: the
  iteration/matvec counts AND the headline inequality (total matvecs
  <= 0.7 x 16 x the single-RHS count, ROADMAP item 2);
* ``eo_deflation`` — EigCG harvest + deflated re-solve: exact counts and
  the strict deflated < undeflated iteration drop, verified against the
  original system.

Iteration count is an ALGORITHMIC property (deterministic seed, fixed
tolerance), so it is the cheap, noise-free regression signal — wall-clock
on shared CI runners is not.  A small slack absorbs cross-platform float
reduction differences.

EVERY guarded entry is checked and the full expected-vs-actual table is
printed — a failure never hides the state of the other entries behind the
first mismatch.

Usage:  check_solver_regression.py [BENCH_solvers.json] [baseline.json]
        check_solver_regression.py --generate [baseline.json]
        check_solver_regression.py --serve [BENCH_serve.json] [baseline.json]
        check_solver_regression.py --chaos [BENCH_serve.json] [baseline.json]
        check_solver_regression.py --resume [BENCH_resume.json] [baseline.json]

``--generate`` runs the smoke solves itself (no full benchmark harness
needed) and guards the result — the BLOCKING ``bench-guard`` CI job and
the standalone/dev mode.  ``--serve`` guards a serving-lane report
(benchmarks/bench_serve.py --verify) against the baseline's ``serve``
section: request volume, direct-solve verification, plan-cache hit rate
after warmup, that coalescing reached a multi-RHS rung, convergence, and
the iteration-count ceiling.  ``--chaos`` guards a fault-injection report
(bench_serve.py --chaos) against the baseline's ``chaos`` section: every
poisoned request failed classified, zero healthy casualties (blast radius
exactly 1), and both fault surfaces actually exercised.  ``--resume``
guards a crash-resume lane report (benchmarks/bench_resume.py): SIGKILLed
solves resumed from their latest checkpoint (including across mesh
shapes) and a killed server's journal replayed to zero incomplete
entries.  The artifact-comparing default mode stays in the non-blocking
smoke-bench job for timing context.
Exit 0 on pass, 1 on regression or missing/invalid inputs.
"""

from __future__ import annotations

import json
import os
import sys

SLACK_ITERS = 2  # float-reduction jitter across platforms, not a budget

# section -> guarded iteration-count keys inside it
GUARDED_SECTIONS = {
    "eo_smoke": ("cgnr_eo_iters", "cgnr_eo_pallas_iters"),
    "eo_smoke_tm": ("cgnr_eo_tm_iters", "cgnr_eo_tm_pallas_iters"),
}

# section -> extra problem keys beyond PROBLEM_KEYS that must match
EXTRA_PROBLEM_KEYS = {"eo_smoke_tm": ("mu", "operator")}

# the guarded solve is only comparable if its parameters match the baseline
PROBLEM_KEYS = ("lattice", "mass", "tol", "seed")


class _Table:
    """Collects every comparison; prints one expected-vs-actual table."""

    def __init__(self):
        self.rows: list[tuple[str, str, object, object, object, str]] = []

    def add(self, section, metric, baseline, actual, limit, verdict):
        self.rows.append((section, metric, baseline, actual, limit, verdict))

    def mismatch(self, section, metric, baseline, actual):
        self.add(section, metric, baseline, actual, "-", "MISMATCH")

    def missing(self, section, metric, baseline):
        self.add(section, metric, baseline, "-", "-", "MISSING")

    def iters(self, section, metric, baseline, actual):
        limit = int(baseline) + SLACK_ITERS
        verdict = "OK" if int(actual) <= limit else "REGRESSION"
        self.add(section, metric, int(baseline), int(actual), limit, verdict)

    @property
    def failed(self) -> bool:
        return any(r[-1] != "OK" for r in self.rows)

    def print(self):
        header = ("section", "metric", "baseline", "actual", "limit",
                  "verdict")
        rows = [header] + [tuple(str(v) for v in r) for r in self.rows]
        widths = [max(len(r[i]) for r in rows) for i in range(len(header))]
        for i, row in enumerate(rows):
            print("  " + "  ".join(v.ljust(w) for v, w in zip(row, widths)))
            if i == 0:
                print("  " + "  ".join("-" * w for w in widths))


def _problem_match(table, name, cur, base, extra=()) -> bool:
    """Record (and fail on) any problem-parameter drift; True if usable."""
    ok = True
    for key in PROBLEM_KEYS + tuple(extra):
        if cur.get(key) != base.get(key):
            table.mismatch(name, key, base.get(key), cur.get(key))
            ok = False
    return ok


def _check_section(table, name, cur, base):
    """Guard the flat iteration-count keys of one smoke section."""
    keys = GUARDED_SECTIONS[name]
    base_s = base.get(name)
    if not base_s:
        return  # baseline predates this section: nothing to guard
    cur_s = cur.get(name)
    if not cur_s:
        table.missing(name, "(section)", "present")
        return
    if not _problem_match(table, name, cur_s, base_s,
                          extra=EXTRA_PROBLEM_KEYS.get(name, ())):
        return
    for key in keys:
        got, ref = cur_s.get(key), base_s.get(key)
        if got is None or ref is None:
            table.missing(name, key, ref)
            continue
        table.iters(name, key, ref, got)


def _check_batch_sweep(table, cur, base):
    """Guard the per-N iteration counts of the multi-RHS batched smoke."""
    base_bs = base.get("batch_sweep")
    if not base_bs:
        return
    cur_bs = cur.get("batch_sweep")
    if not cur_bs:
        table.missing("batch_sweep", "(section)", "present")
        return
    if not _problem_match(table, "batch_sweep", cur_bs, base_bs):
        return
    cur_by_n = {e.get("n_rhs"): e for e in cur_bs.get("entries", [])}
    for ref in base_bs.get("entries", []):
        n = ref.get("n_rhs")
        got = cur_by_n.get(n)
        if got is None:
            table.missing("batch_sweep", f"n_rhs={n} iters", ref.get("iters"))
            continue
        table.iters("batch_sweep", f"n_rhs={n} iters", ref["iters"],
                    got["iters"])


def _check_eo_sharded(table, cur, base):
    """Guard the sharded batched EO Schur solve's iteration count."""
    base_s = base.get("eo_sharded")
    if not base_s:
        return
    cur_s = cur.get("eo_sharded")
    if not cur_s:
        table.missing("eo_sharded", "(section)", "present")
        return
    if not _problem_match(table, "eo_sharded", cur_s, base_s,
                          extra=("n_rhs", "mesh", "solver")):
        return
    table.iters("eo_sharded", "iters", base_s["iters"], cur_s["iters"])


def _check_blockcg(table, cur, base):
    """Guard the block-CG row: exact counts + the 0.7x matvec headline.

    Iteration and matvec counts hold the usual baseline+slack ceiling;
    additionally the ISSUE-9 acceptance inequality is recomputed from the
    CURRENT run — total matvecs for the 16-RHS block solve must stay
    <= max_matvec_ratio x (16 x the single-RHS matvec count) — so the
    win is guarded as a property, not just pinned as a number.
    """
    base_s = base.get("blockcg_16rhs")
    if not base_s:
        return  # baseline predates block CG: nothing to guard
    cur_s = cur.get("blockcg_16rhs")
    if not cur_s:
        table.missing("blockcg_16rhs", "(section)", "present")
        return
    if not _problem_match(table, "blockcg_16rhs", cur_s, base_s,
                          extra=("n_rhs", "backend")):
        return
    for key in ("single_iters", "single_matvecs", "blockcg_iters",
                "blockcg_matvecs"):
        table.iters("blockcg_16rhs", key, base_s[key], cur_s[key])
    for key in ("all_converged", "all_verified"):
        ok = bool(cur_s.get(key, False))
        table.add("blockcg_16rhs", key, True, ok, "-",
                  "OK" if ok else "REGRESSION")
    ratio_cap = float(base_s.get("max_matvec_ratio", 0.7))
    total = int(cur_s.get("total_matvecs", 0))
    cap = ratio_cap * int(cur_s.get("total_matvecs_single16", 0))
    table.add("blockcg_16rhs", "total_matvecs", f"<={cap:.0f}", total,
              f"{ratio_cap}x16xsingle",
              "OK" if total and total <= cap else "REGRESSION")


def _check_eo_deflation(table, cur, base):
    """Guard the EigCG row: exact counts + the strict iteration drop.

    The deflated solve must take STRICTLY fewer iterations than the
    identical undeflated solve (the warm-gauge-field product the serving
    cache sells), and still pass true-residual verification against the
    ORIGINAL system.
    """
    base_s = base.get("eo_deflation")
    if not base_s:
        return  # baseline predates deflation: nothing to guard
    cur_s = cur.get("eo_deflation")
    if not cur_s:
        table.missing("eo_deflation", "(section)", "present")
        return
    if not _problem_match(table, "eo_deflation", cur_s, base_s,
                          extra=("nev", "m_max", "harvest_tol", "backend")):
        return
    for key in ("harvest_iters", "harvest_matvecs", "undeflated_iters",
                "undeflated_matvecs", "deflated_iters", "deflated_matvecs"):
        table.iters("eo_deflation", key, base_s[key], cur_s[key])
    drop = (int(cur_s.get("deflated_iters", 1 << 30))
            < int(cur_s.get("undeflated_iters", 0)))
    table.add("eo_deflation", "deflated<undeflated",
              True, drop, "-", "OK" if drop else "REGRESSION")
    for key in ("harvest_verified", "deflated_converged",
                "deflated_verified"):
        ok = bool(cur_s.get(key, False))
        table.add("eo_deflation", key, True, ok, "-",
                  "OK" if ok else "REGRESSION")


def _check_ckpt_overhead(table, cur, base):
    """Guard the segmented (checkpointed) smoke solve.

    Three properties, all algorithmic: the one-shot iteration count holds
    the usual baseline+slack ceiling, the SEGMENTED solve takes exactly
    as many iterations as the one-shot solve it mirrors, and the two
    iterates are bitwise equal — segmenting may cost snapshot I/O, never
    Krylov math.  Wall-clock overhead stays unguarded.
    """
    base_s = base.get("ckpt_overhead")
    if not base_s:
        return  # baseline predates durable solves: nothing to guard
    cur_s = cur.get("ckpt_overhead")
    if not cur_s:
        table.missing("ckpt_overhead", "(section)", "present")
        return
    if not _problem_match(table, "ckpt_overhead", cur_s, base_s,
                          extra=("every_iters",)):
        return
    table.iters("ckpt_overhead", "iters", base_s["iters"], cur_s["iters"])
    same = (int(cur_s.get("iters_checkpointed", -1))
            == int(cur_s.get("iters", -2)))
    table.add("ckpt_overhead", "iters_checkpointed", cur_s.get("iters"),
              cur_s.get("iters_checkpointed"), "-",
              "OK" if same else "REGRESSION")
    bw = bool(cur_s.get("bitwise_equal", False))
    table.add("ckpt_overhead", "bitwise_equal", True, bw, "-",
              "OK" if bw else "REGRESSION")


def _check_resume(table, cur, base):
    """Guard a crash-resume lane report (benchmarks/bench_resume.py).

    The lane SIGKILLs real subprocesses and the report records what
    recovery achieved; the gate demands each experiment actually ran to
    its kill (``killed`` — an early-exiting child proves nothing) and
    that recovery met the durability contract (DESIGN.md §11):

    * solver: resumed from a checkpoint step >= 1 and the resumed solve
      passed true-residual verification;
    * elastic: a solve checkpointed on a mesh resumed VERIFIED without
      the mesh (smaller-world restart);
    * journal: the killed server left >= min_incomplete journaled
      requests unfinished and recovery replayed EVERY one of them.
    """
    base_r = base.get("resume")
    if not base_r:
        table.missing("resume", "(baseline section)", "present")
        return
    for lane in ("solver", "elastic"):
        s = cur.get(lane)
        if not s:
            table.missing(lane, "(report section)", "present")
            continue
        table.add(lane, "killed", True, s.get("killed"), "-",
                  "OK" if s.get("killed") else "REGRESSION")
        step = s.get("resumed_from_step")
        table.add(lane, "resumed_from_step", ">=1", step, 1,
                  "OK" if isinstance(step, int) and step >= 1
                  else "REGRESSION")
        table.add(lane, "resume_ok", True, s.get("resume_ok"), "-",
                  "OK" if s.get("resume_ok") else "REGRESSION")
    j = cur.get("journal")
    if not j:
        table.missing("journal", "(report section)", "present")
        return
    table.add("journal", "killed", True, j.get("killed"), "-",
              "OK" if j.get("killed") else "REGRESSION")
    found = int(j.get("incomplete_found", 0))
    need = int(base_r.get("min_incomplete", 1))
    table.add("journal", "incomplete_found", f">={need}", found, need,
              "OK" if found >= need else "REGRESSION")
    recovered = int(j.get("recovered", -1))
    table.add("journal", "recovered", found, recovered, found,
              "OK" if recovered == found else "REGRESSION")
    left = int(j.get("incomplete_after_recovery", -1))
    table.add("journal", "incomplete_after_recovery", 0, left, 0,
              "OK" if left == 0 else "REGRESSION")


def _check_serve(table, cur, base):
    """Guard a serving-lane report against the baseline ``serve`` section.

    The serving lane's algorithmic signal is the same as the solver
    smoke's (iteration counts, deterministic seed) plus the serving
    invariants: every response verified against a direct solve, the
    compiled-plan cache effective after warmup, and request coalescing
    actually reaching a multi-RHS ladder rung.  Throughput/latency stay
    unguarded — wall-clock on shared runners is noise.
    """
    base_s = base.get("serve")
    if not base_s:
        table.missing("serve", "(baseline section)", "present")
        return
    if not _problem_match(table, "serve", cur, base_s, extra=("backend",)):
        return
    n = int(cur.get("requests", 0))
    need = int(base_s.get("min_requests", 0))
    table.add("serve", "requests", f">={need}", n, need,
              "OK" if n >= need else "REGRESSION")
    conv = bool(cur.get("all_converged", False))
    table.add("serve", "all_converged", True, conv, "-",
              "OK" if conv else "REGRESSION")
    v = cur.get("verify")
    if not v:
        # the lane must run with --verify; a report without the section
        # never passed the direct-solve comparison
        table.missing("serve", "verify", "passed")
    else:
        table.add("serve", "verify.max_abs_err", f"<={v.get('tol')}",
                  v.get("max_abs_err"), v.get("tol"),
                  "OK" if v.get("passed") else "REGRESSION")
    rate = float(cur.get("request_cache_hit_rate", 0.0))
    min_rate = float(base_s.get("min_hit_rate", 0.9))
    table.add("serve", "request_cache_hit_rate", f">={min_rate}",
              round(rate, 3), min_rate,
              "OK" if rate >= min_rate else "REGRESSION")
    min_rung = int(base_s.get("min_coalesced_rung", 4))
    rungs = {int(k): int(c) for k, c in cur.get("rung_hist", {}).items()}
    coalesced = any(r >= min_rung and c > 0 for r, c in rungs.items())
    table.add("serve", "coalesced_rung", f">={min_rung}",
              sorted(rungs) if rungs else "-", min_rung,
              "OK" if coalesced else "REGRESSION")
    iters_max = cur.get("iters", {}).get("max")
    if iters_max is None:
        table.missing("serve", "iters.max", base_s.get("max_iters"))
    else:
        table.iters("serve", "iters.max", base_s["max_iters"], iters_max)
    _check_deflation_serve(table, cur, base)


def _check_deflation_serve(table, cur, base):
    """Guard the warm-gauge deflation lane embedded in the serve report.

    bench_serve.py runs a second, light-mass workload with the deflation
    cache ON and embeds its report under ``deflation_serve``.  The gate
    is the ISSUE-9 serving acceptance: enough requests were served off a
    deflation-cache hit (``min_hits``), every hit converged in STRICTLY
    fewer iterations than the cold solve on its coalesce key, everything
    converged+verified, and the direct-oracle comparison (re-solved with
    the SAME basis) passed.
    """
    base_d = base.get("deflation_serve")
    if not base_d:
        return  # baseline predates the deflation lane: nothing to guard
    d = cur.get("deflation_serve")
    if not d:
        table.missing("deflation_serve", "(report section)", "present")
        return
    if not _problem_match(table, "deflation_serve", d, base_d,
                          extra=("backend",)):
        return
    conv = bool(d.get("all_converged", False))
    table.add("deflation_serve", "all_converged", True, conv, "-",
              "OK" if conv else "REGRESSION")
    drop = d.get("deflation_drop", {})
    hits = int(drop.get("hit_requests", 0))
    need = int(base_d.get("min_hits", 1))
    table.add("deflation_serve", "hit_requests", f">={need}", hits, need,
              "OK" if hits >= need else "REGRESSION")
    dropped = bool(drop.get("all_hits_dropped", False))
    table.add("deflation_serve", "all_hits_dropped", True, dropped, "-",
              "OK" if dropped else "REGRESSION")
    harvests = int(d.get("deflation", {}).get("harvests", 0))
    need_h = int(base_d.get("min_harvests", 1))
    table.add("deflation_serve", "harvests", f">={need_h}", harvests,
              need_h, "OK" if harvests >= need_h else "REGRESSION")
    v = d.get("verify")
    if not v:
        table.missing("deflation_serve", "verify", "passed")
    else:
        table.add("deflation_serve", "verify.max_abs_err",
                  f"<={v.get('tol')}", v.get("max_abs_err"), v.get("tol"),
                  "OK" if v.get("passed") else "REGRESSION")


def _check_chaos(table, cur, base):
    """Guard a chaos-lane report against the baseline ``chaos`` section.

    The chaos lane (bench_serve.py --chaos --chaos-fault-every N) poisons
    a fraction of the RHS stream and injects transient gauge faults into
    primary batch dispatches.  The containment contract (DESIGN.md §10):

    * every poisoned request fails WITH A CLASSIFIED VERDICT — none is
      silently served;
    * blast radius is exactly 1: zero healthy requests fail or come back
      unverified, however many shared a batch with a poison or a fault;
    * the lane actually exercised both fault surfaces (min_poisoned
      poisons admitted to the stream, transient injection enabled).
    """
    base_c = base.get("chaos")
    if not base_c:
        table.missing("chaos", "(baseline section)", "present")
        return
    c = cur.get("chaos")
    if not c:
        # the report was not produced with --chaos: nothing was injected,
        # so the containment properties were never exercised
        table.missing("chaos", "(report section)", "present")
        return
    poisoned = int(c.get("poisoned", 0))
    need_poison = int(base_c.get("min_poisoned", 1))
    table.add("chaos", "poisoned", f">={need_poison}", poisoned, need_poison,
              "OK" if poisoned >= need_poison else "REGRESSION")
    failed = int(c.get("poisoned_failed", 0))
    table.add("chaos", "poisoned_failed", poisoned, failed, poisoned,
              "OK" if failed == poisoned else "REGRESSION")
    served = int(c.get("poisoned_served", -1))
    table.add("chaos", "poisoned_served", 0, served, 0,
              "OK" if served == 0 else "REGRESSION")
    for metric in ("healthy_failed", "healthy_unverified"):
        got = int(c.get(metric, -1))
        table.add("chaos", metric, 0, got, 0,
                  "OK" if got == 0 else "REGRESSION")
    fault_every = int(c.get("fault_every", 0))
    need_fault = bool(base_c.get("require_fault_injection", True))
    if need_fault:
        table.add("chaos", "fault_every", ">=1", fault_every, 1,
                  "OK" if fault_every >= 1 else "REGRESSION")
    ok = bool(c.get("containment_ok", False))
    table.add("chaos", "containment_ok", True, ok, "-",
              "OK" if ok else "REGRESSION")
    v = cur.get("verify")
    if v is not None:
        # when the chaos lane also re-solves served responses directly,
        # the comparison must still pass — containment may not trade
        # correctness of the healthy stream for isolation
        table.add("chaos", "verify.max_abs_err", f"<={v.get('tol')}",
                  v.get("max_abs_err"), v.get("tol"),
                  "OK" if v.get("passed") else "REGRESSION")


def _load(path: str, what: str) -> dict | None:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"solver-regression guard: cannot load {what} {path}: {e}")
        return None


def main(argv: list[str]) -> int:
    base_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_solvers_baseline.json")
    if len(argv) > 1 and argv[1] in ("--serve", "--chaos", "--resume"):
        mode = argv[1].lstrip("-")
        default_report = ("BENCH_resume.json" if mode == "resume"
                          else "BENCH_serve.json")
        cur_path = argv[2] if len(argv) > 2 else default_report
        if len(argv) > 3:
            base_path = argv[3]
        cur = _load(cur_path, f"{mode} report")
        base = _load(base_path, "baseline")
        if cur is None or base is None:
            return 1
        table = _Table()
        if mode == "serve":
            _check_serve(table, cur, base)
        elif mode == "resume":
            _check_resume(table, cur, base)
        else:
            _check_chaos(table, cur, base)
        table.print()
        if table.failed:
            print(f"{mode} guard: FAILED — see the non-OK rows above")
            return 1
        print(f"{mode} guard: passed")
        return 0
    if len(argv) > 1 and argv[1] == "--generate":
        if len(argv) > 2:
            base_path = argv[2]
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from benchmarks import bench_solvers
        cur = {"eo_smoke": bench_solvers._run_eo_smoke(),
               "eo_smoke_tm": bench_solvers._run_eo_smoke_tm(),
               "batch_sweep": bench_solvers._run_batch_sweep(),
               "blockcg_16rhs": bench_solvers._run_blockcg(),
               "eo_deflation": bench_solvers._run_eo_deflation(),
               "eo_sharded": bench_solvers._run_eo_sharded(),
               "ckpt_overhead": bench_solvers._run_ckpt_overhead()}
    else:
        cur_path = argv[1] if len(argv) > 1 else "BENCH_solvers.json"
        if len(argv) > 2:
            base_path = argv[2]
        try:
            with open(cur_path) as f:
                cur = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"solver-regression guard: cannot load {cur_path}: {e}")
            return 1

    try:
        with open(base_path) as f:
            base = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"solver-regression guard: cannot load {base_path}: {e}")
        return 1

    table = _Table()
    for name in GUARDED_SECTIONS:
        _check_section(table, name, cur, base)
    _check_batch_sweep(table, cur, base)
    _check_blockcg(table, cur, base)
    _check_eo_deflation(table, cur, base)
    _check_eo_sharded(table, cur, base)
    _check_ckpt_overhead(table, cur, base)
    if not table.rows:
        print("solver-regression guard: nothing to compare (baseline has "
              "no guarded sections)")
        return 1
    table.print()
    if table.failed:
        print("solver-regression guard: FAILED — see the non-OK rows above "
              "(MISMATCH = regenerate benchmarks/BENCH_solvers_baseline."
              "json, MISSING = a guarded entry disappeared, REGRESSION = "
              "an iteration count exceeded baseline + slack)")
        return 1
    print("solver-regression guard: passed")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
