"""Device time a solve spends outside its Krylov loop, in ms.

The self time of the device ops under every ``solve.*`` scope but
``solve.krylov`` (gauge and right-hand-side layout, the Schur right-hand
side, back-substitution, merge and verification; ``chipbench/phases.py``),
over the solves that finished in the traced window.  With
``krylov_ms_per_iter`` it should make up the solve:
``krylov_ms_per_iter * iterations + fixed_ms_per_solve`` is about
``1000 * s_per_rhs`` where the device is never idle.
"""

from chipbench import phases


def read(view):
    seconds = phases.window_split(view) or {}
    fixed = [p for p in seconds if p.startswith("solve.")
             and p != phases.KRYLOV]
    return phases.ms_per_solve(view, fixed)
