"""Device time of the Krylov loop per CG iteration, in ms.

The self time of every device op that the compiled solve runs under the
program's ``solve.krylov`` scope (``chipbench/phases.py``), in the traced
window, over the CG iterations of the solves that finished in it.  The
loop's per-iteration cost, with the fixed work of a solve left out.
"""

from chipbench import phases


def read(view):
    seconds = phases.window_split(view)
    _, its = phases.solved(view)
    if not seconds or its == 0:
        return None
    return 1000.0 * seconds.get(phases.KRYLOV, 0.0) / its
