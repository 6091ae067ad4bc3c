"""The parity Wilson-dslash kernels' share of their roofline, in %.

Each parity launch applies the eight-direction hop to the sites of one
parity: per output site (144 + 48) * 4 B (eight links, one spinor in and
out) and 1,320 flop.  The time is the summed device time of those
launches in the trace.  The kernels carry no ``name=`` of their own yet;
on a TPU v5e trace the parity launches are the custom calls named after
the functions that issue them: ``schur_op`` (the two launches of each
D-hat or D-hat-dagger application), ``dslash_eo`` (the right-hand side's
preparation) and ``dslash_oe`` (the back-substitution).
"""

from chipbench import trace

PATTERNS = ("schur_op*", "dslash_eo*", "dslash_oe*")
SITE_B = (144 + 48) * 4
SITE_FLOP = 1320


def launch_cost(volume: int, nrhs: int = 1) -> tuple[float, float]:
    """(bytes, flop) of one parity launch over ``volume / 2`` sites."""
    sites = volume // 2
    return (sites * (144 / nrhs + 48) * 4 * nrhs, sites * SITE_FLOP * nrhs)


def read(view):
    if view.trace is None or view.peak is None:
        return None
    n, seconds = trace.kernel_time(view.trace, PATTERNS)
    if n == 0 or seconds <= 0:
        return None
    nbytes, flop = launch_cost(view.cell.volume)
    least = max(n * nbytes / view.peak["hbm_bytes_per_s"],
                n * flop / view.peak["bf16_flops_per_s"])
    return 100.0 * least / seconds
