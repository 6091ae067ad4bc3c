"""The whole solve's algorithmic HBM traffic at the chip's peak, over its
wall time, in %.

Counts the work of the even-odd CGNR algorithm, not what a kernel moves,
so it reads the same whatever implements it.  Per even site, with one
half-lattice hop at (144 + 48) * 4 B (eight links, one spinor in and out)
and a half-spinor stream at 96 B:

* each iteration: 4 hops (D-hat and its adjoint) and 9 streams (the CG
  triad's 6 and the xpay's 3), 3,936 B;
* each solve, once: 3 hops to prepare the right-hand side, 1 to
  back-substitute the odd sites, 2 for the full-lattice verification, and
  24 streams of vector work around them.
"""

HOP_B = (144 + 48) * 4
STREAM_B = 24 * 4
ITER_B = 4 * HOP_B + 9 * STREAM_B
FIXED_B = 6 * HOP_B + 24 * STREAM_B


def solve_bytes(volume: int, iterations: int) -> float:
    return (volume // 2) * (iterations * ITER_B + FIXED_B)


def read(view):
    recs = [r for r in view.window.records if r and r.get("ok")]
    if not recs or view.peak is None:
        return None
    total = sum(solve_bytes(view.cell.volume, r["iterations"]) for r in recs)
    wall = view.window.t_end - view.window.t0
    return 100.0 * total / view.peak["hbm_bytes_per_s"] / wall
