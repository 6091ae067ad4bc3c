"""Device time a solve spends verifying its answer, in ms.

The self time under ``solve.verify``: the true residual ``||b - D x||``
with the natural-layout operator, over the solves that finished in the
traced window (``chipbench/phases.py``).
"""

from chipbench import phases


def read(view):
    return phases.ms_per_solve(view, (phases.VERIFY,))
