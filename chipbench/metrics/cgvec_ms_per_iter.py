"""Device time of the fused CG vector kernels per CG iteration, in ms.

The triad (x += a p, r -= a Ap, and |r|^2) and the xpay (p = r + b p) are
the custom calls ``cg_update`` and ``cg_xpay`` on a TPU v5e trace; their
summed device time in the traced window, over the CG iterations of the
solves that finished in it.  A time and not a roofline share: at 24^3x48
XLA keeps some of their lane-dense operands in VMEM (layout ``S(1)``), so
a share of the HBM roofline read 184% and bounds nothing.
"""

from chipbench import trace

PATTERNS = ("cg_update*", "cg_xpay*")


def read(view):
    if view.trace is None:
        return None
    n, seconds = trace.kernel_time(view.trace, PATTERNS)
    its = sum(r["iterations"] for r in view.window.records
              if r and r.get("ok"))
    if n == 0 or its == 0:
        return None
    return 1000.0 * seconds / its
