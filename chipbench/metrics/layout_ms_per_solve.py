"""Device time a solve spends relaying its fields out, in ms.

The self time under ``solve.gauge_layout`` (the parity split and packing
of the gauge field, which runs on every solve), ``solve.rhs_layout``
(the same for the right-hand side) and ``solve.merge`` (unpacking and
merging the answer), over the solves that finished in the traced window
(``chipbench/phases.py``).
"""

from chipbench import phases


def read(view):
    return phases.ms_per_solve(view, phases.LAYOUT)
