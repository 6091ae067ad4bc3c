"""`correct` on the CPU at a small size: true for the program, false for the
control and for each fault that a one-chip cell can have.

Each case drives a whole run of the harness (set-up, window, comparison)
without its look for a chip, with the timed path broken underneath: the
compiled ``plan.solve`` of a closed-loop cell, or the server's compiled
batch solve of a served cell.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import control, harness

SMALL = {"lattice": {"T": 8, "Z": 4, "Y": 4, "X": 4}}
SERVED = dict(SMALL, serve={"ladder": [1, 4]}, gauge_fields=1,
              **{"traffic.rate_per_s": 30.0, "traffic.check_sample": 1000,
                 "traffic.rhs_pool": 4})


def run(bench, workload, overrides, hooks=None, seconds=2.0):
    return harness.run_cell(bench, workload, 2**31 + 11, seconds, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            overrides=overrides, hooks=hooks)


# -- faults, planted under the harness -------------------------------------

def unchanged(compiled):
    """The solve returns its starting state: x = 0."""
    def solve(u, b):
        x, st = compiled(u, b)
        return jnp.zeros_like(x), st
    return solve


def altered(compiled):
    """One component of the answer altered where it is produced."""
    def solve(u, b):
        x, st = compiled(u, b)
        return x.at[(0,) * x.ndim].add(1.0), st
    return solve


def served(fault):
    """A plan cache whose compiled batch solves carry ``fault``."""
    def factory():
        from repro.serve.plan_cache import PlanCache

        class Faulty(PlanCache):
            # an empty cache has len 0; the server must still take it
            def __bool__(self):
                return True

            def get(self, plan, mass, maxiter):
                fn, hit = super().get(plan, mass, maxiter)
                return (lambda u, b, tol: fault(*fn(u, b, tol))), hit

        return Faulty()
    return factory


def half_left_out(x, st):
    """Every second lane of the batch is left out (returned as zero)."""
    keep = (jnp.arange(x.shape[0]) % 2 == 0).reshape(
        (-1,) + (1,) * (x.ndim - 1))
    return jnp.where(keep, x, 0), st


def lane_altered(x, st):
    return x.at[(0,) * x.ndim].add(1.0), st


CLOSED = "wilson-24c48.light"
OPEN = "tm-16c32.serve"


def test_program_reads_correct_closed_loop(bench):
    res = run(bench, CLOSED, SMALL)
    assert res["correct"] is True
    assert res["checks"]["max_rel_residual"]["value"] < 1e-5
    assert res["metrics"]["s_per_rhs"]["value"] > 0


def test_host_stepped_closed_loop_reads_correct(bench):
    res = run(bench, CLOSED, dict(SMALL, **{"traffic.queued": 0}))
    assert res["correct"] is True
    assert res["attempted"] >= 2


def test_program_reads_correct_served(bench):
    res = run(bench, OPEN, SERVED)
    assert res["correct"] is True
    assert res["failed"] == 0
    assert res["metrics"]["serve_p90_s"]["value"] > 0


@pytest.mark.parametrize("fault", [unchanged, altered])
def test_closed_loop_fault_reads_incorrect(bench, fault):
    res = run(bench, CLOSED, SMALL, {"wrap_solve": fault})
    assert res["correct"] is False
    assert res["checks"]["max_rel_residual"]["value"] > 1e-5


@pytest.mark.parametrize("fault", [half_left_out, lane_altered])
def test_served_fault_reads_incorrect(bench, fault):
    res = run(bench, OPEN, SERVED, {"plan_cache": served(fault)})
    assert res["correct"] is False
    assert res["checks"]["max_rel_residual"]["value"] > 1e-5


@pytest.mark.parametrize("workload,overrides", [(CLOSED, SMALL),
                                                (OPEN, SERVED)])
def test_bfloat16_control_reads_incorrect(bench, workload, overrides):
    cell = harness.read_cell(bench, workload, overrides)
    hooks = control.control_hooks(jax, cell)
    res = run(bench, workload, overrides, hooks)
    assert res["correct"] is False
    assert res["checks"]["max_rel_residual"]["value"] > 1e-4
